"""chip_smoke.py's contract off the chip, and the compile-cache location.

The smoke itself only proves something on a TPU (run it through the chip
tool); what can be held here is that it REFUSES to pass anywhere else, and
that its explicit dry-run size goes through every stage on the CPU.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(args, cwd=ROOT, script=SMOKE, timeout=120):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=timeout,
    )


def _json_line(stdout: str, which: int):
    """stdout line `which` (-1 = last) as an object, or None if it is not one."""
    lines = [ln for ln in stdout.strip().splitlines() if ln.strip()]
    try:
        got = json.loads(lines[which]) if len(lines) >= -which else None
    except ValueError:
        return None
    return got if isinstance(got, dict) else None


def _result_line(stdout: str):
    """The last stdout line as a result object, or None if it is not one."""
    got = _json_line(stdout, -1)
    return got if got is not None and "ok" in got else None


def test_default_run_without_a_tpu_fails_before_building_anything():
    proc = _run([])
    assert proc.returncode != 0
    assert "platform=cpu" in proc.stdout  # the device is named first
    assert "not 'tpu'" in proc.stderr
    assert "compile cache" not in proc.stdout  # stopped before any set-up
    assert _result_line(proc.stdout) is None


def test_alone_in_a_directory_it_fails_and_prints_no_result(tmp_path):
    alone = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    # --dry-run gets it past the platform check, to the missing package
    proc = _run(["--dry-run"], cwd=str(tmp_path), script=str(alone))
    assert proc.returncode != 0
    assert "siddhi_tpu" in proc.stderr
    assert _result_line(proc.stdout) is None


@pytest.mark.slow
def test_dry_run_passes_every_stage_on_cpu():
    proc = _run(["--dry-run"], timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "DRY RUN" in proc.stdout
    # the result line holds exactly these keys; what else the run has to
    # say is in the summary line before it
    result = _result_line(proc.stdout)
    assert result is not None and set(result) == {"ok", "device"}
    assert result["ok"] is True
    assert set(result["device"]) == {"platform", "kind", "count"}
    assert result["device"]["platform"] == "cpu"
    assert isinstance(result["device"]["kind"], str)
    assert type(result["device"]["count"]) is int
    got = _json_line(proc.stdout, -2)
    assert got is not None and got["dry_run"] is True
    assert list(got)[-1] == "claim" and got["claim"] is None
    a = got["stages"]["A"]
    assert a["rows_delivered"] > 0  # the NumPy comparison ran over these
    assert a["avg_max_err_vs_tol"][0] <= a["avg_max_err_vs_tol"][1]
    assert set(got["stages"]["B"]) == {
        "filter_window_avg", "tumbling_groupby", "sliding_join",
        "pattern_2state", "count_sequence",
    }
    assert got["stages"]["D"]["native_ring"] is True
    g = got["stages"]["G"]  # the periodic aux-flag drain ran off the main thread
    assert g["async"]["drain_thread_is_main"] is False
    assert g["fused"]["drain_thread_is_main"] is False
    assert g["fused"]["flushes_during_send"] >= 1


class TestCompileCacheLocation:
    def test_env_set_means_no_directory_set_in_code(self, monkeypatch, tmp_path):
        import jax

        from siddhi_tpu.utils.backend import configure_compile_cache

        elsewhere = str(tmp_path / "cache_from_env")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", elsewhere)
        before = jax.config.jax_compilation_cache_dir
        assert before != elsewhere
        assert configure_compile_cache() == elsewhere
        assert jax.config.jax_compilation_cache_dir == before
        assert not os.path.exists(elsewhere)  # JAX's to create, not ours

    def test_env_unset_means_checkout_jax_cache(self, monkeypatch):
        import jax

        from siddhi_tpu.utils.backend import configure_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            first = configure_compile_cache()
            assert first == os.path.join(ROOT, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == first
            assert configure_compile_cache() == first
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
