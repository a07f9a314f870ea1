"""Device time of the chunk program under no scope of the engine (the `while`'s
own time, state copies, flag reductions) per micro-batch. Device trace."""

import program_spans


def read(trace, spans, counters, cell):
    return program_spans.device_scope_ms(trace, spans, counters, cell, program_spans.UNSCOPED)
