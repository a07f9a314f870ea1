"""Hand-off to the drain worker: a chunk's submit -> the start of its
`siddhi:drain` (the span's `queued_us`, since no span crosses threads), mean
per chunk. Program spans in the device trace."""

import numpy as np

import program_spans


def read(trace, spans, counters, cell):
    ps = program_spans.of(cell, trace)
    if ps is None:
        return None
    queued = [s["queued_us"] for s in ps.spans("drain") if "queued_us" in s]
    return float(np.mean(queued)) / 1e3 if queued else None
