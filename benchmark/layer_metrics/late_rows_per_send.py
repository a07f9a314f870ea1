"""Rows that arrived behind the watermark per `send_columns` call, over the
whole run (fill and warm-up included: the status holds no reading from
before the window): `late_total` over `offers` of the stream's reorder stage
(`snapshot_status()["watermark"]["streams"][<stream>]`). Against it: the
reference's late count. Program counter."""


def read(trace, spans, counters, cell):
    stage = ((counters["status"].get("watermark") or {}).get("streams")
             or {}).get(cell["config"]["stream"]) or {}
    if not stage.get("offers"):
        return None
    return stage["late_total"] / stage["offers"]
