"""Groups the key table holds after the window: the keys with a row in the
query's window (`snapshot_status()["queries"][<query>]["group"]["used"]`).
Against it: the reference's live keys. Program counter."""

import group_scopes


def read(trace, spans, counters, cell):
    return group_scopes.counter(counters, cell, "used")
