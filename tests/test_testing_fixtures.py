"""The shared fixtures (`siddhi_tpu/testing/apps.py`, `verify_cases.py`) may
be imported by any test, tool or smoke without changing the engine under
test: no JAX of their own, nothing set in the environment."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from siddhi_tpu.testing.verify_cases import diff_cases, rows_match

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_HYGIENE = """
import importlib.util, json, os, sys
env = dict(os.environ)
# each file alone, outside the package (whose __init__ imports the engine):
# what the module ITSELF pulls in at import
for name in ("apps", "verify_cases"):
    spec = importlib.util.spec_from_file_location(
        "standalone_" + name,
        os.path.join("siddhi_tpu", "testing", name + ".py"),
    )
    spec.loader.exec_module(importlib.util.module_from_spec(spec))
pulled_in = sorted(m for m in ("jax", "siddhi_tpu") if m in sys.modules)
env_alone = dict(os.environ) == env
import siddhi_tpu  # the engine's own import may set what it likes
env = dict(os.environ)
import siddhi_tpu.testing.apps, siddhi_tpu.testing.verify_cases
print(json.dumps({
    "pulled_in": pulled_in, "env_alone": env_alone,
    "env_in_package": dict(os.environ) == env,
}))
"""


def test_importing_the_fixtures_sets_no_env_and_imports_no_jax():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", _HYGIENE], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got == {"pulled_in": [], "env_alone": True, "env_in_package": True}


@pytest.mark.parametrize(
    "a, b, same",
    [
        ([("+", "IBM", 1.0)], [("+", "IBM", 1.0 + 1e-5)], True),
        ([("+", "IBM", 1.0)], [("+", "IBM", 1.01)], False),
        ([("+", "IBM", 0.0)], [("+", "IBM", 1e-5)], True),
        ([("+", "IBM", 7)], [("+", "IBM", 8)], False),
        ([("+", "IBM", 7)], [("+", "IBM", 7), ("+", "IBM", 7)], False),
        ({"q": [(1,)], "q2": []}, {"q": [(1,)], "q2": []}, True),
        ({"q": [(1,)]}, {"q2": [(1,)]}, False),
        ([(1,)], "ERROR: boom", False),
    ],
)
def test_rows_match(a, b, same):
    assert rows_match(a, b) is same


def test_diff_cases_fails_an_error_on_either_side():
    a = {"cases": {"x": [[1, 2.0]], "y": "ERROR: boom", "z": [[3]]}}
    b = {"cases": {"x": [[1, 2.0]], "y": "ERROR: boom", "w": [[3]]}}
    assert diff_cases(a, b) == {
        "w": "FAIL", "x": "pass", "y": "FAIL", "z": "FAIL",
    }
