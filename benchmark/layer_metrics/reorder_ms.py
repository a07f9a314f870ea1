"""Host time of the `@app:watermark` reorder stage on the sender's thread,
ahead of the send it hands on: `siddhi:reorder` (the late mask, the held
rows joined to the call's, the stable sort on event time, the gather of
every column and the cut at the watermark; the inner `send_columns` is
outside the span), mean per call of the traced window. Program spans in the
device trace."""

import program_spans


def read(trace, spans, counters, cell):
    ps = program_spans.of(cell, trace)
    if ps is None:
        return None
    calls = len(ps.spans("reorder"))
    return ps.total_ms("reorder") / calls if calls else None
