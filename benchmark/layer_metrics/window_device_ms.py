"""Device time under the window's scopes (`window.<type>`, with `ring_emit` and
`ring_update` inside), exclusive of nothing it contains: per micro-batch of the
chunk program, or per send of the per-batch step. Device trace."""

import program_spans


def read(trace, spans, counters, cell):
    return program_spans.device_scope_ms(trace, spans, counters, cell, "window.")
