"""Slots the key table took back per micro-batch, over the whole run (fill
and warm-up included: the status holds no reading from before the window),
as the engine counts them (`snapshot_status()["queries"][<query>]["group"]
["freed"]`) over the micro-batches sent since the stream's first row. In a
full window as many groups empty as appear. Program counter."""

import group_scopes


def read(trace, spans, counters, cell):
    freed = group_scopes.counter(counters, cell, "freed")
    sends = spans["sends"]
    if freed is None or not len(sends):
        return None
    return freed / (sends[-1, 3] / cell["sizes"]["batch"])
