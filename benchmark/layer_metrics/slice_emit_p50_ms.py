"""Send start -> entry of the callback that delivers that send's last row,
median over the window's sends. Harness clock."""

import numpy as np

import readers


def read(trace, spans, counters, cell):
    lag = readers.emission_of_sends(spans) - spans["sends"][:, 0]
    lag = lag[np.isfinite(lag)]
    return float(np.percentile(lag, 50)) * 1e3 if len(lag) else None
