"""The most matches one row has completed since deploy
(`snapshot_status()["queries"][<query>]["pattern"]["max_emits_per_row"]`):
how many pending matches of one key a single event let go at once.
Program counter."""

import pattern_scopes


def read(trace, spans, counters, cell):
    return pattern_scopes.counter(counters, cell, "max_emits_per_row")
