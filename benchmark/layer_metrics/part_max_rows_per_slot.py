"""The most rows one partition was sent in one batch since the app started
(`snapshot_status()["queries"][<query>]["partition"]["max_rows_per_slot"]`),
to set beside `sub_batch`, the rows one pass takes. Program counter."""

import part_scopes


def read(trace, spans, counters, cell):
    return part_scopes.counter(counters, cell, "max_rows_per_slot")
