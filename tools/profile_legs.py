"""Per-leg time budget profiler: measures the FUSED ingest program itself.

For each headline workload this stages real wire chunks on host, then times
(a) host wire encode, (b) h2d transfer of the wire, (c) the fused device
scan (states donated, one truth-sync read at the end), so the terms provably
bound the end-to-end leg number and name its binding wall.

Usage: python tools/profile_legs.py [leg ...]
"""

from __future__ import annotations

import sys
import time

import numpy as np

sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.abspath(__file__)) + "/..")

import bench as B  # noqa: E402


def profile_leg(name: str, batch=32768, reps=4):
    import jax

    ql, stream, mult, batch_override = B.WORKLOADS[name]
    bsz = batch_override or batch
    ql = f"@app:batch(size='{bsz}')\n" + ql
    from siddhi_tpu import SiddhiManager

    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(ql)
    B._prime_interner(mgr, B._make_stock_data(8)["names"])
    rt.start()
    j = rt.junctions[stream]
    fi = j.fused_ingest
    if fi is None or not fi.eligible():
        print(f"{name}: fused path NOT eligible")
        return
    K = fi.K
    data = B._make_stock_data(bsz * K)  # sized from the engine's real K
    cols = {k: v for k, v in data.items() if k not in ("ts", "names")}
    encode, wire_bytes = fi.staged_codec(
        data["ts"][:bsz], {k: v[:bsz] for k, v in cols.items()})

    # ---- host encode of one K-batch chunk
    t0 = time.perf_counter()
    bufs, counts, bases = [], np.full((K,), bsz, np.int32), np.zeros((K,), np.int64)
    for k in range(K):
        lo = k * bsz
        buf, base = encode(data["ts"][lo:lo + bsz], {kk: v[lo:lo + bsz] for kk, v in cols.items()}, bsz)
        bufs.append(buf)
        bases[k] = base
    wire = np.stack(bufs)
    t_encode = time.perf_counter() - t0

    ev_per_chunk = K * bsz

    # warm up: compile, and complete one real readback
    def run_once(w):
        states = []
        for ep in fi.endpoints:
            if ep.qr.state is None:
                ep.qr.state = ep.qr._fresh(ep.init_state(0))
            states.append(ep.qr.state)
        tstates = {}
        for ep in fi.endpoints:
            tstates.update(ep.qr._collect_table_states())
        ns, tst, _aux, _lin, _packs = fi._fused(tuple(states), tstates, w, counts, bases, np.int64(1_700_000_000_000))
        for ep, st in zip(fi.endpoints, ns):
            ep.qr.state = st
        return ns

    ns = run_once(wire)
    # truth sync
    leaf = jax.tree_util.tree_leaves(ns)[0]
    np.asarray(leaf.ravel()[:1])

    # ---- h2d: transfer the wire alone (median of 5)
    h2ds = []
    for _ in range(5):
        t0 = time.perf_counter()
        dev = jax.device_put(wire)
        np.asarray(dev.ravel()[:1])
        h2ds.append(time.perf_counter() - t0)
    h2ds.sort()
    t_h2d = h2ds[len(h2ds) // 2]

    # ---- fused device scan on a PRE-STAGED device wire: pure device cost
    dev_wire = jax.device_put(wire)
    np.asarray(dev_wire.ravel()[:1])
    t0 = time.perf_counter()
    for _ in range(reps):
        ns = run_once(dev_wire)
    leaf = jax.tree_util.tree_leaves(ns)[0]
    np.asarray(leaf.ravel()[:1])
    t_dev = (time.perf_counter() - t0) / reps

    # ---- end-to-end chunk (host wire: h2d + scan as the engine runs it)
    t0 = time.perf_counter()
    for _ in range(reps):
        ns = run_once(wire)
    leaf = jax.tree_util.tree_leaves(ns)[0]
    np.asarray(leaf.ravel()[:1])
    t_scan = (time.perf_counter() - t0) / reps

    print(f"{name}: B={bsz} K={K} wire={wire.nbytes/1e6:.1f}MB "
          f"encode={t_encode*1e3:.1f}ms ({ev_per_chunk/t_encode/1e6:.2f}Mev/s) "
          f"h2d={t_h2d*1e3:.1f}ms ({wire.nbytes/t_h2d/1e6:.0f}MB/s) "
          f"device={t_dev*1e3:.1f}ms ({ev_per_chunk/t_dev/1e6:.2f}Mev/s) "
          f"e2e={t_scan*1e3:.1f}ms ({ev_per_chunk/t_scan/1e6:.2f}Mev/s)")
    rt.shutdown()
    mgr.shutdown()


if __name__ == "__main__":
    legs = sys.argv[1:] or list(B.WORKLOADS)
    for leg in legs:
        profile_leg(leg)
