"""The drain's blocking reads of a chunk's packed output: the first, which
waits for the chunk program (`siddhi:readback_wait`), and the top-ups
(`siddhi:readback`), mean per chunk. Program spans in the device trace."""

import program_spans


def read(trace, spans, counters, cell):
    ps = program_spans.of(cell, trace)
    if ps is None:
        return None
    return ps.per_chunk_ms("readback_wait", "readback")
