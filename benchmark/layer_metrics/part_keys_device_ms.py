"""Device time of the partition key table's probe (scope `partition.keys`: the
key expression and `assign_slots`' dense [B, P] compare) per send. Device
trace."""

import part_scopes


def read(trace, spans, counters, cell):
    return part_scopes.device_ms_per_send(trace, spans, cell, "partition.keys")
