"""Device time of the group-by's key probe (scope `group.probe` inside
`selector`: finding each row's slot in the key table) per micro-batch of the
chunk program; part of `group_device_ms`. Device trace."""

import group_scopes


def read(trace, spans, counters, cell):
    return group_scopes.device_ms_per_microbatch(
        trace, counters, cell, "group.probe")
