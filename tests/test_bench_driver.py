"""Bench driver resilience: the final JSON line must print on EVERY exit
path (ROADMAP: round 5 shipped rc=124 with no JSON at all when the
harness's outer `timeout -k` killed the driver).

These tests run `bench.py` as a real subprocess — the same shape the
harness uses — and assert the one-line contract:

* deadline path: a too-small `--deadline` skips every leg and still emits;
* SIGTERM path: the outer-timeout analog (`timeout -k` sends TERM first)
  emits the final line from the signal handler via a direct fd-1 write,
  BEFORE attempting any cleanup that could block.

Also covers the p99 leg's new keys offline (no accelerator required): the
leg function itself runs in-process on CPU in the slow marker-free suite
would be too costly, so the key contract is asserted on the driver level.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "bench.py")


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["SIDDHI_TPU_AUX_DRAIN_S"] = "0"
    return env


def _last_json_line(text: str) -> dict:
    lines = [ln for ln in text.strip().splitlines() if ln.strip()]
    assert lines, f"no output at all: {text!r}"
    return json.loads(lines[-1])


class TestBenchDriverExitPaths:
    def test_deadline_skips_all_legs_and_emits_final_json(self):
        """--deadline smaller than the 60 s per-leg floor: every leg is
        skipped, the final line is valid JSON with the skip reasons
        recorded, and the driver exits non-zero (a skipped leg is not a
        completed run)."""
        proc = subprocess.run(
            [sys.executable, BENCH, "--deadline", "5"],
            capture_output=True, text=True, timeout=120, env=_env(),
        )
        assert proc.returncode == 1, proc.stderr[-2000:]
        got = _last_json_line(proc.stdout)
        assert got["metric"] == "engine_throughput_geomean"
        failed = got["detail"].get("failed_legs", [])
        assert failed and all(
            f["error"] == "skipped(deadline)" for f in failed
        ), failed

    def test_bench_budget_env_trims_and_emits_final_json(self):
        """SIDDHI_TPU_BENCH_BUDGET=<seconds> (no --deadline flag at all —
        the harness shape): a tiny budget caps the overall deadline AND the
        per-leg subprocess timeouts; every leg is skip-recorded and the
        final line is parseable JSON."""
        env = _env()
        env["SIDDHI_TPU_BENCH_BUDGET"] = "12"
        proc = subprocess.run(
            [sys.executable, BENCH],
            capture_output=True, text=True, timeout=120, env=env,
        )
        assert proc.returncode == 1, proc.stderr[-2000:]
        got = _last_json_line(proc.stdout)
        assert got["metric"] == "engine_throughput_geomean"
        failed = got["detail"].get("failed_legs", [])
        assert failed and all(
            f["error"] == "skipped(deadline)" for f in failed
        ), failed
        # the snapshot-line tail contract under the budget knob (the
        # harness shape that shipped BENCH_r05 rc=124 with an EMPTY tail):
        # every line on stdout — per-leg snapshots AND the final line —
        # must parse, so a SIGKILL at any point leaves a consumable tail
        lines = [
            ln for ln in proc.stdout.strip().splitlines() if ln.strip()
        ]
        assert len(lines) >= 2, lines
        for ln in lines[:-1]:
            snap = json.loads(ln)
            assert snap["detail"].get("partial_through_leg"), snap
        assert "partial_through_leg" not in got["detail"]

    def test_per_leg_snapshot_lines_are_parseable(self):
        """Every completed leg prints a snapshot JSON line (the SIGKILL
        defense: a hard kill mid-suite still leaves a parseable tail).
        With a sub-floor deadline no legs run, but each skip still updates
        detail — assert every non-final line parses and carries the
        partial marker."""
        proc = subprocess.run(
            [sys.executable, BENCH, "--deadline", "5"],
            capture_output=True, text=True, timeout=120, env=_env(),
        )
        assert proc.returncode == 1, proc.stderr[-2000:]
        lines = [
            ln for ln in proc.stdout.strip().splitlines() if ln.strip()
        ]
        assert len(lines) >= 2  # snapshots + the final line
        for ln in lines[:-1]:
            snap = json.loads(ln)
            assert snap["metric"] == "engine_throughput_geomean"
            assert snap["detail"].get("partial_through_leg")
        assert "partial_through_leg" not in json.loads(lines[-1])["detail"]

    def test_sigterm_mid_leg_emits_final_json(self):
        """SIGTERM while a leg subprocess is running (what `timeout -k`
        sends first): the handler must emit the final JSON line before the
        kill grace window can expire."""
        proc = subprocess.Popen(
            [sys.executable, BENCH, "--deadline", "0"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=_env(),
        )
        try:
            # give the driver time to spawn its first leg subprocess (the
            # leg imports jax; the driver itself is up within a second)
            time.sleep(6.0)
            proc.send_signal(signal.SIGTERM)
            out, _err = proc.communicate(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.communicate()
        got = _last_json_line(out)
        assert proc.returncode != 0  # an interrupted run is not a pass
        assert got["metric"] == "engine_throughput_geomean"
        # the interrupted leg is recorded, not silently dropped
        failed = got["detail"].get("failed_legs", [])
        assert any(
            f["error"] == f"signal{int(signal.SIGTERM)}" for f in failed
        ), failed
