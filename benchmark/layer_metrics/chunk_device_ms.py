"""Device time of one execution of the fused chunk program, mean over the
traced window. Device trace."""

import readers


def read(trace, spans, counters, cell):
    return readers.chunk_device_ms(trace)
