"""Device time of the selector (group-by, aggregators, projection; scope
`selector`) per micro-batch of the chunk program. Device trace."""

import program_spans


def read(trace, spans, counters, cell):
    return program_spans.device_scope_ms(trace, spans, counters, cell, "selector")
