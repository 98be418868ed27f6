"""verify.kernel_ms: device time of the verify kernel per batch (ms/batch).

The sum of the device durations of every ``block_mips`` custom call in the
traced window (both verify rounds, every call of a chained walk), averaged
over the chips, over the batches the window answered. The kernel's events
are named by their HLO text, ``%block_mips.<n> = ...``.
"""
import trace_reduce


def read(run):
    ns = trace_reduce.kernel_ns(run)
    if ns is None or not run.window.batches:
        return None
    return ns / 1e6 / run.window.batches
