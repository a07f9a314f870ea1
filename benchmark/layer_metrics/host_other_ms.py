"""Per-batch path: what a send spends outside every stage the engine names:
`siddhi:send` less the part its child spans cover, mean per send. Program
spans in the device trace."""

import numpy as np

import program_spans


def read(trace, spans, counters, cell):
    ps = program_spans.of(cell, trace)
    if ps is None:
        return None
    own = ps.self_ms("send")
    return float(np.mean(own)) if own else None
