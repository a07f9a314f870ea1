"""XLA backend compiles (builds or cache loads) between the first send's start
and the last send's end, from the engine's own process-wide ring
(`snapshot_status()["compile_events"]`): needs no `@app:statistics` and sees
eager programs. Has to read 0. Program counter."""


def read(trace, spans, counters, cell):
    ring = counters["status"].get("compile_events")
    sends = spans["sends"]
    if ring is None or not len(sends):
        return None
    t0, t1 = sends[0, 0], sends[-1, 1]
    return float(sum(t0 <= e["t"] <= t1 for e in ring["recent"]))
