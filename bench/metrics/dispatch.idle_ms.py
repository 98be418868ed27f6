"""dispatch.idle_ms: device idle time per batch while the host dispatches
device calls (ms/batch).

Idle time of the device in the traced window under the program's
``dispatch`` spans (``search``, ``select_frontend``, ``verify_round*``,
``compensation``, ``rescore``, ...) or its ``api`` span (``api_search``,
the facade's own work), where no deeper layered span is open, averaged over
the chips, over the batches answered (`trace_layers.idle_by_layer`).
"""
import trace_layers


def read(run):
    return trace_layers.idle_ms(run, ("dispatch", "api"))
