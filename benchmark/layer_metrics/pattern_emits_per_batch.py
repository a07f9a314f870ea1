"""Matches emitted per micro-batch, over the whole run (fill and warm-up
included: the status holds no reading from before the window), as the engine
counts them (`snapshot_status()["queries"][<query>]["pattern"]["completed"]`)
over the micro-batches sent since the stream's first row. Program counter."""

import pattern_scopes


def read(trace, spans, counters, cell):
    completed = pattern_scopes.counter(counters, cell, "completed")
    sends = spans["sends"]
    if completed is None or not len(sends):
        return None
    return completed / (sends[-1, 3] / cell["sizes"]["batch"])
