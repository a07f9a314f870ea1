"""Time the sender waits for the pipeline and not for its own work: a wire slot
still read by the device (`siddhi:slot_wait`), the bounded drain queue
(`siddhi:submit_wait`) and the delivery barrier that ends a send
(`siddhi:barrier`), summed over the window, per chunk. Program spans in the
device trace."""

import program_spans


def read(trace, spans, counters, cell):
    ps = program_spans.of(cell, trace)
    if ps is None:
        return None
    if not ps.chunks():
        return None
    return (ps.total_ms("slot_wait", "submit_wait", "barrier") or 0.0) / ps.chunks()
