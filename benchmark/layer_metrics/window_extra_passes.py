"""Passes beyond a step's first that the time window took since the app
started, because more rows were due than one flow holds
(`snapshot_status()["queries"][<query>]["window"]["extra_passes"]`). Has to
read 0 on a stream without silences longer than a flow's rows. Program
counter."""


def read(trace, spans, counters, cell):
    window = (counters["status"].get("queries") or {}).get(
        cell["config"]["query"], {}).get("window") or {}
    return window.get("extra_passes")
