"""Rows the time window expired early for want of capacity since the app
started (`snapshot_status()["queries"][<query>]["window"]["early_expired"]`).
Has to read 0: the configuration's capacity is the most rows its window's
time can hold. Program counter."""


def read(trace, spans, counters, cell):
    window = (counters["status"].get("queries") or {}).get(
        cell["config"]["query"], {}).get("window") or {}
    return window.get("early_expired")
