"""`harness.prebuild_flag_drain` mirrors the shapes of the engine's periodic
overflow-flag drain. If the engine changes how it stacks its flags, the
pre-build misses in silence and programs are built inside a measured
window; this test makes that drift loud instead."""

import logging

import jax.numpy as jnp
import pytest

import harness
from run import CompileLog


class FakeQuery:
    def _check_aux_flags(self, flags):
        self.flags = flags


@pytest.mark.parametrize("backlog", [1, 7, 33, 64])
def test_engine_drain_builds_nothing_after_the_prebuild(backlog, monkeypatch):
    from siddhi_tpu.core.query_runtime import _AuxWarnPool

    monkeypatch.setenv("SIDDHI_TPU_AUX_DRAIN_S", "0")  # no drain on submit
    seen = CompileLog()
    log = logging.getLogger("jax._src.dispatch")
    monkeypatch.setattr(log, "level", logging.DEBUG)
    log.addHandler(seen)
    try:
        harness.prebuild_flag_drain()
        flag = jnp.zeros((), dtype=bool)
        flag.block_until_ready()
        pool, query = _AuxWarnPool(), FakeQuery()
        for _ in range(backlog):
            pool.submit(query, {"group_overflow": flag})
        built = len(seen.names)
        pool.flush()
        assert query.flags == {"group_overflow": False}
        assert seen.names[built:] == []
    finally:
        log.removeHandler(seen)
