"""Test configuration: force an 8-device virtual CPU platform BEFORE jax initializes.

Multi-chip hardware is not available in CI; sharding tests run against a virtual
8-device CPU mesh per the build spec. Must run before any jax import.
"""

import os
import sys

# Force, don't default: tests must run on the virtual 8-device CPU platform
# even on a machine that holds an accelerator. If something imported jax
# before this file ran, the env var alone was read too early — update the jax
# config explicitly as well.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# persistent compilation cache: pattern/window programs take O(minutes) to
# compile on CPU; cached across test runs they load in milliseconds
from siddhi_tpu.utils.backend import configure_compile_cache  # noqa: E402

configure_compile_cache()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long wall-clock tests excluded from tier-1 (-m 'not slow')"
    )


# ---------------------------------------------------------------------------
# analyzer sweep: every app that successfully builds a runtime anywhere in the
# suite must also analyze clean (zero errors, no SA000 internal faults) — the
# whole test corpus doubles as the analyzer's false-positive regression net.
# Disable with SIDDHI_ANALYSIS_SWEEP=0.
# ---------------------------------------------------------------------------

if os.environ.get("SIDDHI_ANALYSIS_SWEEP", "1") != "0":
    from siddhi_tpu.core.manager import SiddhiManager as _SM

    _orig_create = _SM.create_siddhi_app_runtime

    def _checked_create(self, app, strict=False):
        runtime = _orig_create(self, app, strict=strict)
        # only sweep apps that construct successfully: tests asserting
        # creation errors must keep seeing the original exception
        try:
            from siddhi_tpu.analysis import analyze

            result = analyze(runtime.app)
        except Exception as exc:  # analyzer crash = sweep failure
            raise AssertionError(f"analyzer crashed on a valid app: {exc!r}")
        problems = result.errors + [
            d for d in result.warnings if d.code == "SA000"
        ]
        if problems:
            msgs = "\n".join(d.format() for d in problems)
            raise AssertionError(
                "analyzer flagged a valid app (false positive):\n" + msgs
            )
        return runtime

    _SM.create_siddhi_app_runtime = _checked_create
    _SM.create_runtime = _checked_create
