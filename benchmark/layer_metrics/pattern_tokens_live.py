"""Partial matches the token table holds after the window: tokens that hold
a first event (`snapshot_status()["queries"][<query>]["pattern"]["tokens"]`).
Against it: the reference's held first events. Program counter."""

import pattern_scopes


def read(trace, spans, counters, cell):
    return pattern_scopes.counter(counters, cell, "tokens")
