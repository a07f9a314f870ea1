"""Device time of routing a batch's rows to their partition's sub-batch and
of merging the partitions' emissions back into arrival order (scopes
`partition.route` and `partition.merge`) per send. Device trace."""

import part_scopes


def read(trace, spans, counters, cell):
    return part_scopes.device_ms_per_send(
        trace, spans, cell, "partition.route", "partition.merge")
