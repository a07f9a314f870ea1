"""Backend capability probes and the compile-cache location.

A PJRT backend can report a standard platform name and still reject host
send/recv callbacks at execution time — a name check cannot detect that, so
the capability is probed once by actually running a trivial callback.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

_CB_SUPPORT: Optional[bool] = None

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: fixed and inside the checkout (git-ignored), so
# every process started from this tree — chip_smoke.py, benchmark/run.py,
# the tests — finds what an earlier one compiled
_CHECKOUT_CACHE = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and this
    sets no directory in code. Otherwise the cache is `<checkout>/.jax_cache`.
    The size and compile-time thresholds are dropped either way, so every
    program is kept: a fresh machine pays each compile at most once, and a
    small program that many runtimes rebuild is compiled once even within a
    cold run (tier-1 from an empty cache: 672 s and 677 s, against 855 s and
    776 s with the former 0.3 s compile-time threshold; CPU, PR 21).
    Call before the first compile; calling again gives the same path.
    """
    import jax

    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env_dir = os.environ.get(CACHE_ENV)
    if env_dir:
        return env_dir
    os.makedirs(_CHECKOUT_CACHE, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE)
    return _CHECKOUT_CACHE


def host_callbacks_supported() -> bool:
    """True when jax io/debug callbacks execute on the default backend."""
    global _CB_SUPPORT
    if _CB_SUPPORT is None:
        import numpy as _np

        import jax
        import jax.numpy as jnp
        from jax.experimental import io_callback

        def probe(x):
            return io_callback(
                lambda v: v, jax.ShapeDtypeStruct((), jnp.int32), x
            )

        try:
            # the readback (not just block) forces real completion, so a
            # backend that accepts the launch but fails the callback at
            # execution time is still detected
            _np.asarray(jax.jit(probe)(jnp.int32(0)))
            _CB_SUPPORT = True
        except Exception:
            _CB_SUPPORT = False
    return _CB_SUPPORT
