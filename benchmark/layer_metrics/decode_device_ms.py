"""Device time to decode the wire into a batch (scope `wire_decode`) per
micro-batch of the chunk program. Device trace."""

import program_spans


def read(trace, spans, counters, cell):
    return program_spans.device_scope_ms(trace, spans, counters, cell, "wire_decode")
