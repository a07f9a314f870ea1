"""The most rows the reorder stage has held at once since deploy, between a
call's arrival and the release at the watermark
(`snapshot_status()["watermark"]["streams"][<stream>]["peak_buffered"]`): a
call's rows plus what the call before left behind. Program counter."""


def read(trace, spans, counters, cell):
    stage = ((counters["status"].get("watermark") or {}).get("streams")
             or {}).get(cell["config"]["stream"]) or {}
    return stage.get("peak_buffered")
