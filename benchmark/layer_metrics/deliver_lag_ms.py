"""End of a chunk program's execution on the device -> entry of the harness
callback that receives its first rows: readback, decode to `Event` objects
and the drain thread's queueing. Device trace against the harness clock."""

import readers


def read(trace, spans, counters, cell):
    return readers.deliver_lag_ms(trace, spans, counters, cell)
