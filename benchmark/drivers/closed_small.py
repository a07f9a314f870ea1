"""Closed loop, one caller, small sends: a source thread that forwards what
it polled, `send_rows` rows at a time, and is blocked while each call runs."""

import time

import numpy as np


def run(dep, params: dict, seconds: float) -> dict:
    rows = params["send_rows"]
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while time.perf_counter() < deadline:
        dep.send(dep.prepare(rows))
    return {"t0": t0}


def end_to_end(dep, params: dict, win: dict) -> dict:
    walls = [t1 - t0 for t0, t1, _, _, ok in win["sends"] if ok]
    if not walls:
        return {}
    return {"send_p50_ms": float(np.percentile(walls, 50)) * 1e3}


def describe(dep, params: dict, win: dict) -> str:
    walls = np.array([t1 - t0 for t0, t1, *_ in win["sends"]]) * 1e3
    return (f"closed_small: {len(walls)} sends; wall ms p5 "
            f"{np.percentile(walls, 5):.3f} p50 {np.percentile(walls, 50):.3f} "
            f"p95 {np.percentile(walls, 95):.3f} max {walls.max():.3f}")
