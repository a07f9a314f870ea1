"""Device time of an `every` state arming its tokens (scope `pattern.arm`: the rows that pass move to the front and into the lanes behind the table's head; the table's compaction when the tail has no room) per micro-batch of the chunk program. Device trace."""

import pattern_scopes


def read(trace, spans, counters, cell):
    return pattern_scopes.device_ms_per_microbatch(
        trace, counters, cell, "pattern.arm")
