"""Programs compiled or loaded inside the measured window; has to read 0."""

import readers


def read(trace, spans, counters, cell):
    return readers.compiles_in_window(counters)
