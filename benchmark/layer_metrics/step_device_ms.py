"""Device-busy time per send on the per-batch path: the seconds in which
any operation ran, over the window's sends. Device trace."""

import trace_reduce


def read(trace, spans, counters, cell):
    if trace is None or not len(spans["sends"]):
        return None
    return trace_reduce.busy_seconds(trace) * 1e3 / len(spans["sends"])
