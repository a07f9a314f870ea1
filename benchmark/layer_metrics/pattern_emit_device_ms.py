"""Device time of ordering and writing the completions (scope `pattern.emit`: the done tokens to the front, a sort on the completing row, the emission buffer) per micro-batch of the chunk program. Device trace."""

import pattern_scopes


def read(trace, spans, counters, cell):
    return pattern_scopes.device_ms_per_microbatch(
        trace, counters, cell, "pattern.emit")
