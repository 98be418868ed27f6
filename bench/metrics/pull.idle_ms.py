"""pull.idle_ms: device idle time per batch while the host waits on a
device -> host copy (ms/batch).

Idle time of the device in the traced window under the program's ``pull``
spans (``pull_mask_round1/2``, ``pull_priority``, ``pull_answers``: each
wraps one pull and its wait for the device), averaged over the chips, over
the batches answered (`trace_layers.idle_by_layer`).
"""
import trace_layers


def read(run):
    return trace_layers.idle_ms(run, ("pull",))
