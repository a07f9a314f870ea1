"""Closed loop, one caller: back-to-back `send_columns` calls of
`send_batches` micro-batches each. Every send that starts inside the window
runs to its end, and the rate is taken over the completed sends up to the
last one's return, so it does not step with where the window happens to end."""

import time


def run(dep, params: dict, seconds: float) -> dict:
    rows = params["send_batches"] * dep.cell["sizes"]["batch"]
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while time.perf_counter() < deadline:
        dep.send(dep.prepare(rows))
    return {"t0": t0}


def end_to_end(dep, params: dict, win: dict) -> dict:
    done = [s for s in win["sends"] if s[4]]
    if not done:
        return {}
    events = sum(hi - lo for _, _, lo, hi, _ in done)
    return {"events_per_s": events / (done[-1][1] - win["info"]["t0"])}


def describe(dep, params: dict, win: dict) -> str:
    walls = [round(t1 - t0, 4) for t0, t1, *_ in win["sends"]]
    return f"bulk: {len(walls)} sends; walls s: {walls[:40]}"
