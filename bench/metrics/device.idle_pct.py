"""device.idle_pct: share of the traced window in which no operation ran
on the device (%), averaged over the chips.

Busy is the union of the ``XLA Ops`` intervals clipped to the window, so
overlapping operations count once; the window is the benchmark's
``bench.window`` host span.
"""
import trace_reduce


def read(run):
    if run.trace is None or run.hi <= run.lo:
        return None
    ops = run.trace.ops(run.lo, run.hi)
    if not ops:
        return None
    busy = sum(trace_reduce.busy_ns(evs) for evs in ops.values()) / len(ops)
    return 100.0 * (1.0 - busy / (run.hi - run.lo))
