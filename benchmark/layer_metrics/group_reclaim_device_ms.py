"""Device time of the key table's slot bookkeeping (scope `group.reclaim`
inside `selector`: unused slots handed to new keys, the slots of the groups a
step emptied taken back) per micro-batch of the chunk program; part of
`group_device_ms`. Device trace."""

import group_scopes


def read(trace, spans, counters, cell):
    return group_scopes.device_ms_per_microbatch(
        trace, counters, cell, "group.reclaim")
