"""Device time of finding each token's first matching row (scope `pattern.match`: the key sort, the passes over a key's rows, the residual, the capture) per micro-batch of the chunk program. Device trace."""

import pattern_scopes


def read(trace, spans, counters, cell):
    return pattern_scopes.device_ms_per_microbatch(
        trace, counters, cell, "pattern.match")
