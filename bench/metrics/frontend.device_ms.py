"""frontend.device_ms: device time of the selection frontend per batch
(ms/batch).

The host-driven search runs the frontend (``select_frontend`` with
``quick_probe_batch``) as the program ``jit__frontend`` and the
compensation masks as ``jit__round2``; this sums their module events in the
traced window, averaged over the chips, over the batches answered. A driver
that folds them into another program leaves the metric silent.
"""
MODULES = ("jit__frontend(", "jit__round2(")


def read(run):
    if run.trace is None or not run.window.batches:
        return None
    per_chip = run.trace.module_time_ns(run.lo, run.hi, MODULES)
    if not per_chip:
        return None
    ns = sum(per_chip.values()) / len(per_chip)
    if not ns:
        return None
    return ns / 1e6 / run.window.batches
