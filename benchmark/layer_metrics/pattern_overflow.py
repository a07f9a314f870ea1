"""Arms refused for want of a token lane plus emissions refused for want of
room in the emission buffer, since deploy
(`snapshot_status()["queries"][<query>]["pattern"]["overflow"]`): 0 is part
of the cell's `ready` and of its guarantees. Program counter."""

import pattern_scopes


def read(trace, spans, counters, cell):
    return pattern_scopes.counter(counters, cell, "overflow")
