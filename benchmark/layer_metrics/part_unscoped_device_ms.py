"""Device time of the partitioned per-batch step under none of its stages'
scopes, per send: the loop's own time and the operations the compiler made
inside it (a ring's scatter and the relayouts round it, the sort of a
scatter's indices), which carry the loop's `tf_op` or none. With
`part_keys_device_ms`, `part_route_device_ms`, `window_device_ms.part` and
the selector's few microseconds it adds up to the step. Device trace."""

import part_scopes


def read(trace, spans, counters, cell):
    return part_scopes.device_ms_per_send(trace, spans, cell)
