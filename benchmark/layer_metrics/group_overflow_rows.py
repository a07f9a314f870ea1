"""Rows that found no slot in the key table since the app started
(`snapshot_status()["queries"][<query>]["group"]["overflow_rows"]`): they
lose their group's carried values. Has to read 0. Program counter."""

import group_scopes


def read(trace, spans, counters, cell):
    return group_scopes.counter(counters, cell, "overflow_rows")
