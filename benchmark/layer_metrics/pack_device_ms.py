"""Device time to mask and pack the deliverable rows (scopes `deliver_mask` and
`deliver_pack`) per micro-batch of the chunk program. Device trace."""

import program_spans


def read(trace, spans, counters, cell):
    return program_spans.device_scope_ms(trace, spans, counters, cell, "deliver_mask", "deliver_pack")
