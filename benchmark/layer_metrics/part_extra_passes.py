"""Passes beyond a step's first that the partitioned step took since the app
started, because a partition was sent more rows in one batch than its
sub-batch holds (`snapshot_status()["queries"][<query>]["partition"]
["extra_passes"]`). Has to read 0 on a stream whose keys arrive evenly.
Program counter."""

import part_scopes


def read(trace, spans, counters, cell):
    return part_scopes.counter(counters, cell, "extra_passes")
