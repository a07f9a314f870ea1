"""Device time under the window's scopes (`window.<type>`, with `ring_emit` and
`ring_update` inside) of the partitioned per-batch step, vmapped over the
slots: per send. Device trace."""

import part_scopes


def read(trace, spans, counters, cell):
    return part_scopes.device_ms_per_send(trace, spans, cell, "window.")
