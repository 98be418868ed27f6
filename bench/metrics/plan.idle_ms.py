"""plan.idle_ms: device idle time per batch while the host plans a verify
tile (ms/batch).

Idle time of the device in the traced window under the program's ``plan``
spans (``plan_tile_round1/2``: the host numpy work that sizes a round's
tile), where no deeper layered span is open, averaged over the chips, over
the batches answered (`trace_layers.idle_by_layer`).
"""
import trace_layers


def read(run):
    return trace_layers.idle_ms(run, ("plan",))
