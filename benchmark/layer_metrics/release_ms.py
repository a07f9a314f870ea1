"""Dropping a chunk's decoded rows once its callbacks returned
(`siddhi:release`: what no callback kept of a million `Event`s is freed
there), mean per chunk. Program spans in the device trace."""

import program_spans


def read(trace, spans, counters, cell):
    ps = program_spans.of(cell, trace)
    if ps is None:
        return None
    return ps.per_chunk_ms("release")
