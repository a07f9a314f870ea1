"""Multi-chip correctness: a partitioned app must produce IDENTICAL outputs
with its [P] partition axis sharded over an 8-device mesh and unsharded.

VERDICT r2 item 3: liveness (the dryrun) is not a correctness contract; this
runs 60+ steps with more keys than devices and key churn (keys appearing,
disappearing, and crossing shard boundaries as slots allocate) and compares
every emitted row. Reference contract: the per-key isolated query graphs of
PartitionRuntime.java:256-315 — outputs may not depend on WHERE a key's
partition lives."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager

QL = """@app:batch(size='64')
@app:partitionCapacity(size='32')
define stream S (symbol string, price float, volume long);
partition with (symbol of S)
begin
    @info(name='q')
    from S[price > 0]#window.length(8)
    select symbol, sum(volume) as total, avg(price) as ap
    insert into Out;
end;
"""


def _build():
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(QL)
    rt.start()
    return mgr, rt, rt.queries["q"]


def _batches(n_steps=60, bsz=64, seed=11):
    """Key churn: early steps use keys 1..6, middle steps rotate through
    1..20 (over the 8 'devices'), late steps revisit early keys."""
    rng = np.random.default_rng(seed)
    out = []
    for s in range(n_steps):
        if s < 15:
            pool = np.arange(1, 7)
        elif s < 40:
            pool = np.arange(1 + (s % 5) * 4, 1 + (s % 5) * 4 + 8)
        else:
            pool = np.arange(1, 21)
        ts = np.arange(bsz, dtype=np.int64) + 1_700_000_000_000 + s * bsz
        cols = {
            "symbol": rng.choice(pool, size=bsz).astype(np.int32),
            "price": rng.uniform(1.0, 100.0, size=bsz).astype(np.float32),
            "volume": rng.integers(1, 100, size=bsz).astype(np.int64),
        }
        out.append((ts, cols))
    return out


def _run(qr, mgr, sharded: bool, feed):
    from siddhi_tpu.core.event import EventBatch

    schema = qr.in_schema
    if sharded:
        from jax.sharding import Mesh

        from siddhi_tpu.parallel.mesh import shard_partitioned_query

        mesh = Mesh(np.asarray(jax.devices()[:8]), ("part",))
        sq = shard_partitioned_query(qr, mesh)
        step = sq.step
        psums = []
    else:
        import jax.numpy as jnp

        fn = jax.jit(qr._pstep_outer_impl)
        state = qr._fresh(qr.init_state())
        ptable = {
            "keys": jnp.zeros((qr.p,), jnp.int64),
            "used": jnp.zeros((qr.p,), jnp.bool_),
            "n": jnp.zeros((), jnp.int32),
        }
        counters = {"extra_passes": jnp.zeros((), jnp.int64),
                    "max_rows": jnp.zeros((), jnp.int32)}

        def step(batch, now, _box=[ptable, state, counters]):
            # the routed step: its output is the merged flat batch
            _box[0], _box[1], _box[2], outs, _slot, aux = fn(
                _box[0], _box[1], _box[2], batch, np.int64(now))
            return outs, aux

    rows = []
    for ts, cols in feed:
        batch = schema.to_batch_cols(ts, cols, mgr.interner, capacity=64)
        outs, _aux = step(batch, int(ts[-1]))
        v = np.asarray(outs.valid)
        if sharded:
            psums.append((sq.total_emitted(outs), int(v.sum())))
        ts_a = np.asarray(outs.ts)
        cols_a = {c: np.asarray(a) for c, a in outs.cols.items()}
        step_rows = sorted(
            (int(ts_a[i]), *(cols_a[c][i].item() for c in cols_a))
            for i in map(tuple, np.argwhere(v))
        )
        rows.append(step_rows)
    return (rows, psums) if sharded else rows


@pytest.fixture(scope="module")
def runs():
    feed = _batches()
    mgr1, rt1, qr1 = _build()
    unsharded = _run(qr1, mgr1, sharded=False, feed=feed)
    rt1.shutdown()
    mgr1.shutdown()

    mgr2, rt2, qr2 = _build()
    sharded, psums = _run(qr2, mgr2, sharded=True, feed=feed)
    rt2.shutdown()
    mgr2.shutdown()
    return feed, unsharded, sharded, psums


def test_sharded_matches_unsharded_over_key_churn(runs):
    feed, unsharded, sharded, _psums = runs
    assert len(unsharded) == len(sharded) == len(feed)
    n_rows = sum(len(r) for r in unsharded)
    assert n_rows > 1000, f"feed produced too few outputs ({n_rows}) to be meaningful"
    for i, (a, b) in enumerate(zip(unsharded, sharded)):
        assert a == b, f"step {i}: sharded output diverged"


def test_total_emitted_psum_counts_every_valid_row(runs):
    """`ShardedPartitionedQuery.total_emitted` (the explicit psum that
    `__graft_entry__.dryrun_multichip` calls each step) agrees with a host
    count of the valid lane on every step."""
    _feed, _unsharded, _sharded, psums = runs
    assert len(psums) == 60
    assert sum(got for got, _ in psums) > 1000
    assert all(got == want for got, want in psums), psums
