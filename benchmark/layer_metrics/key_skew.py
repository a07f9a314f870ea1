"""Fullest device's group keys over the mean of the keys mesh, as the engine
reports it for the configuration's query (`snapshot_status()["shard"]
["keyshard"][<query>]["skew"]`): a count, it repeats exactly. 1.0 is an even
spread. Program counter."""


def read(trace, spans, counters, cell):
    placed = ((counters["status"].get("shard") or {}).get("keyshard") or {}).get(
        cell["config"]["query"]) or {}
    return placed.get("skew")
