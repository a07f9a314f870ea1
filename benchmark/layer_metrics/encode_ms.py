"""Host time to encode one chunk into its wire buffer and start its transfer:
`siddhi:encode` + `siddhi:h2d`, mean per chunk of the traced window. Program
spans in the device trace."""

import program_spans


def read(trace, spans, counters, cell):
    ps = program_spans.of(cell, trace)
    if ps is None:
        return None
    return ps.per_chunk_ms("encode", "h2d")
