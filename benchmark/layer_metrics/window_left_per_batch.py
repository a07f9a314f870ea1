"""Rows the window let go of per micro-batch, over the whole run (fill and
warm-up included: the status holds no reading from before the window), as
the engine counts them (`snapshot_status()["queries"][<query>]["window"]
["expired_rows"]`) over the micro-batches sent since the stream's first row.
Against it: the rows that entered per micro-batch, the batch times the
stream's kept share. Program counter."""


def read(trace, spans, counters, cell):
    window = (counters["status"].get("queries") or {}).get(
        cell["config"]["query"], {}).get("window") or {}
    sends = spans["sends"]
    if "expired_rows" not in window or not len(sends):
        return None
    return window["expired_rows"] / (sends[-1, 3] / cell["sizes"]["batch"])
