"""Executions of the chunk program per send of the traced window: 2.0 where
every send is whole chunks (64 micro-batches = 2 x 32); behind the reorder
stage a release that passes 64 micro-batches by a few rows adds a tail chunk
of the K = 2 variant. Device trace."""

import readers


def read(trace, spans, counters, cell):
    runs, sends = len(readers.chunk_executions(trace)), len(spans["sends"])
    return runs / sends if runs and sends else None
