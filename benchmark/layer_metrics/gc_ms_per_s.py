"""Python's collector: ms inside `siddhi:gc` spans, on any thread, per second of
traced window. Program spans in the device trace."""

import program_spans


def read(trace, spans, counters, cell):
    ps = program_spans.of(cell, trace)
    if ps is None:
        return None
    if not ps.threads or ps.window is None:
        return None
    return (ps.total_ms("gc") or 0.0) / ((ps.window[1] - ps.window[0]) / 1e9)
