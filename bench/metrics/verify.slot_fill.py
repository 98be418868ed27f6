"""verify.slot_fill: share of the verify kernel's tile slots that the batch
selected.

Σ ``union`` / Σ ``slots`` over the program's ``verify_round1/2`` spans of
the window's batches (those its span ring still holds, the newest): ``slots``
is the tile a round walks (the pow2 bucket over its union, or every block
when dense), ``union`` the distinct blocks the batch selected (at most
``slots`` counted where a tile cap truncated it). 1.0 means no padded slot
was walked. Read where the traced window shows the verify kernel's walk, as
``verify.kernel_ms`` is.
"""
import trace_layers
import trace_reduce


def read(run):
    if trace_reduce.kernel_ns(run) is None or not run.window.batches:
        return None
    return trace_layers.slot_fill(trace_layers.program_spans(),
                                  run.window.batches)
