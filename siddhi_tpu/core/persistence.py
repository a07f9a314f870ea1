"""Snapshot / persistence: checkpoint and restore of all carried state.

Reference: util/snapshot/SnapshotService.java:45-520 — walks every registered
`Snapshotable` (window queues, NFA token lists, tables, aggregator buckets,
rate limiters) under the ThreadBarrier, Java-serializes a nested map;
util/persistence/{InMemory,FileSystem,IncrementalFileSystem}PersistenceStore
keep revisions named `<timestamp>_<appName>`; restore paths
SiddhiAppRuntime.restore/restoreRevision/restoreLastRevision (:560-600).

Here every stateful component's carried state is a device pytree; a snapshot
is the pytree forest pulled to host numpy plus the host-side bits (intern
table, rate-limiter buffers), pickled. Incremental snapshots store only the
leaves that changed since the previous full snapshot (the analog of the
reference's base/delta split over table operation logs).
"""

from __future__ import annotations

import io
import os
import pickle
import re
import threading
import time
from typing import Optional

import jax
import numpy as np

from siddhi_tpu.ops.scatter import U32Pair, is_pair, join_pairs


# ---------------------------------------------------------------------------
# persistence stores
# ---------------------------------------------------------------------------


class InMemoryPersistenceStore:
    """reference: util/persistence/InMemoryPersistenceStore.java:30."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._data: dict[str, dict[str, bytes]] = {}

    def save(self, app_name: str, revision: str, snapshot: bytes) -> None:
        with self._lock:
            self._data.setdefault(app_name, {})[revision] = snapshot

    def load(self, app_name: str, revision: str) -> Optional[bytes]:
        with self._lock:
            return self._data.get(app_name, {}).get(revision)

    def get_last_revision(self, app_name: str) -> Optional[str]:
        with self._lock:
            revs = self._data.get(app_name)
            if not revs:
                return None
            return max(revs, key=lambda r: int(r.split("_", 1)[0]))

    def list_revisions(self, app_name: str) -> list[str]:
        with self._lock:
            return sorted(
                self._data.get(app_name, {}), key=lambda r: int(r.split("_", 1)[0])
            )

    def clear_all_revisions(self, app_name: str) -> None:
        with self._lock:
            self._data.pop(app_name, None)

    def delete_revision(self, app_name: str, revision: str) -> None:
        """Drop one revision (auto-checkpoint retention pruning — see
        core/supervision.prune_revisions)."""
        with self._lock:
            self._data.get(app_name, {}).pop(revision, None)


class FileSystemPersistenceStore:
    """reference: util/persistence/FileSystemPersistenceStore.java:32."""

    def __init__(self, base_path: str) -> None:
        self.base_path = base_path

    def _dir(self, app_name: str) -> str:
        return os.path.join(self.base_path, app_name)

    def save(self, app_name: str, revision: str, snapshot: bytes) -> None:
        d = self._dir(app_name)
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, revision), "wb") as f:
            f.write(snapshot)

    def load(self, app_name: str, revision: str) -> Optional[bytes]:
        p = os.path.join(self._dir(app_name), revision)
        if not os.path.exists(p):
            return None
        with open(p, "rb") as f:
            return f.read()

    def get_last_revision(self, app_name: str) -> Optional[str]:
        d = self._dir(app_name)
        if not os.path.isdir(d):
            return None
        revs = [f for f in os.listdir(d) if re.match(r"^\d+_", f)]
        if not revs:
            return None
        return max(revs, key=lambda r: int(r.split("_", 1)[0]))

    def list_revisions(self, app_name: str) -> list[str]:
        d = self._dir(app_name)
        if not os.path.isdir(d):
            return []
        return sorted(
            (f for f in os.listdir(d) if re.match(r"^\d+_", f)),
            key=lambda r: int(r.split("_", 1)[0]),
        )

    def clear_all_revisions(self, app_name: str) -> None:
        d = self._dir(app_name)
        if os.path.isdir(d):
            for f in os.listdir(d):
                os.unlink(os.path.join(d, f))

    def delete_revision(self, app_name: str, revision: str) -> None:
        """Drop one revision (auto-checkpoint retention pruning — see
        core/supervision.prune_revisions)."""
        p = os.path.join(self._dir(app_name), revision)
        if os.path.exists(p):
            os.unlink(p)


class IncrementalFileSystemPersistenceStore(FileSystemPersistenceStore):
    """Marker subclass: SnapshotService stores base + delta revisions here
    (reference: IncrementalFileSystemPersistenceStore)."""

    incremental = True


# ---------------------------------------------------------------------------
# snapshot service
# ---------------------------------------------------------------------------


def _to_host(tree):
    # OWNING copies, never views: np.asarray over a jax array can be
    # zero-copy on CPU backends, leaving the snapshot (and the incremental
    # delta base kept in `_last_full`) viewing the live XLA buffer — which
    # the next DONATED dispatch frees out from under it (flaky reads, then
    # a crash when the view outlives the backend).
    # A snapshot holds LOGICAL lanes: a 64-bit lane the live state keeps as
    # a U32Pair (a sliding window's ring) is joined on the host, under the
    # path it has always had, so snapshots do not depend on the layout
    return join_pairs(
        jax.tree_util.tree_map(
            lambda x: np.array(x, copy=True), without_indexes(tree))
    )


def without_indexes(tree):
    """`tree` without the bucket index of any group-by key table in it: the
    table's `keys` and `used` are the truth and a snapshot holds them alone;
    restore lays the index out again (`_upgrade`)."""
    if isinstance(tree, dict):
        return {
            k: without_indexes(v) for k, v in tree.items()
            if not (k == "index" and "used" in tree)
        }
    if isinstance(tree, (list, tuple)):
        return type(tree)(without_indexes(v) for v in tree)
    return tree


def _to_device(tree, like=None):
    """The snapshot tree on the device; where `like`, the component's live
    or freshly initialized state, holds a U32Pair, the snapshot's 64-bit
    lane at that path is split into one (on the host)."""
    import jax.numpy as jnp

    wide = set()
    if like is not None:
        tree = _upgrade(tree, like)
        wide = {
            jax.tree_util.keystr(path)
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                like, is_leaf=is_pair
            )[0]
            if is_pair(leaf)
        }

    def put(path, x):
        # copy=True: jnp.asarray may alias the unpickled host buffer on CPU,
        # and the restored state's first donated dispatch would then free
        # memory numpy still owns (the restore-then-fused-send hazard)
        if jax.tree_util.keystr(path) in wide:
            return jax.tree_util.tree_map(
                jnp.array, U32Pair.split(np.asarray(x))
            )
        return jnp.array(x, copy=True)

    return jax.tree_util.tree_map_with_path(put, tree)


def _upgrade(tree, like):
    """A snapshot tree from before its component's state gained a key,
    brought to the layout of `like`: a time-bounded window's ring that was
    saved without its head (before PR 30) is laid out again from its live
    rows (`core/windows.py` `ring_from_legacy`); a group-by's key table that
    was saved before it took slots back (before PR 40) gets the stack of
    its unused slots (`ops/group.py` `table_from_legacy`); a key table that
    keeps a bucket index (since PR 41) gets it laid out from the saved `keys`
    and `used`, whenever it was saved (`index_from_table`); a pattern's token
    table that was saved before its tokens carried their arming order (before
    PR 43) is laid out in it (`core/pattern.py` `tokens_from_legacy`)."""
    if isinstance(tree, dict) and isinstance(like, dict):
        if "used" in tree and "used" in like:
            from siddhi_tpu.ops.group import index_from_table, table_from_legacy

            if "free" in like and "free" not in tree:
                tree = table_from_legacy(tree, own_lane="rows" in like)
            if "index" in like:
                tree = {**tree,
                        "index": index_from_table(tree["keys"], tree["used"])}
            return tree
        if "caps" in tree and "next_seq" in like and "next_seq" not in tree:
            from siddhi_tpu.core.pattern import tokens_from_legacy

            return tokens_from_legacy(tree, like)
        if "head" in like and "head" not in tree and "seq" in tree:
            from siddhi_tpu.core.windows import ring_from_legacy

            return ring_from_legacy(tree)
        if "total" in like and "seq" in tree and "seq" not in like:
            # a length window's whole ring, saved before a partitioned
            # query's ring held the aggregated columns alone (PR 32)
            return {"cols": {n: tree["cols"][n] for n in like["cols"]},
                    "total": tree["total"]}
        return {
            k: _upgrade(v, like[k]) if k in like else v for k, v in tree.items()
        }
    if isinstance(tree, (list, tuple)) and isinstance(like, (list, tuple)) \
            and len(tree) == len(like):
        return type(tree)(_upgrade(t, l) for t, l in zip(tree, like))
    return tree


def _state_like(component):
    """What a component's state looks like, for `_to_device`: the live
    state, or the shapes of a fresh one while none is materialized."""
    if component.state is not None:
        return component.state
    return jax.eval_shape(component.init_state)


def _flat_with_paths(tree) -> dict:
    """{path_str: leaf} using jax's path-aware flatten (structure-exact)."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(path): leaf for path, leaf in leaves}


def merge_snapshot_interner(interner, payload: dict) -> None:
    """Install a snapshot payload's intern table into `interner`: restored
    states carry interned string ids minted by the CHECKPOINTING process,
    so they must resolve to the original strings here. A conflicting id
    raises rather than silently mis-decoding. Shared by
    `SnapshotService.restore` and the churn state-seeding path
    (core/churn._seed_query_state)."""
    for i, v in enumerate(payload["interner"], start=1):
        if i < len(interner._from_id):
            if interner._from_id[i] != v:
                raise ValueError(
                    f"intern table conflict at id {i}: "
                    f"{interner._from_id[i]!r} != {v!r}"
                )
        else:
            interner._to_id[v] = i
            interner._from_id.append(v)


def merge_snapshot_elements(payloads: list) -> tuple:
    """Fold one full payload plus trailing incremental deltas into
    (elements, rates) — THE base+delta merge, shared by
    `SnapshotService.restore` and the churn seeding path."""
    if payloads[0]["type"] != "full":
        raise ValueError("restore needs a full snapshot first")
    elements = dict(payloads[0]["elements"])
    rates = dict(payloads[0].get("rates", {}))
    for p in payloads[1:]:
        if p["type"] != "incremental":
            raise ValueError("later snapshots must be incremental")
        for k, changed in p["delta"].items():
            if k not in elements:
                continue
            paths, treedef = jax.tree_util.tree_flatten_with_path(elements[k])
            leaves = [
                changed.get(jax.tree_util.keystr(path), leaf)
                for path, leaf in paths
            ]
            elements[k] = jax.tree_util.tree_unflatten(treedef, leaves)
        rates.update(p.get("rates", {}))
    return elements, rates


class SnapshotService:
    """reference: util/snapshot/SnapshotService.java — here the registry is
    the app runtime's component maps; the app process lock is the barrier."""

    def __init__(self, app_runtime) -> None:
        self.rt = app_runtime
        self._last_full: Optional[dict] = None  # {element: {path: leaf}}
        # base STAGED by full_snapshot(track_base=True), promoted to
        # _last_full only by commit_base() — i.e. only once the caller has
        # actually persisted the full payload. Committing eagerly would,
        # after one failed save, leave every later cycle emitting deltas
        # against a base revision that never reached the store (restore
        # then silently no-ops or applies deltas to the wrong base).
        self._pending_base: Optional[dict] = None

    # ---- collection -------------------------------------------------------

    def _elements(self) -> dict:
        """Every stateful component's live state, keyed by stable element id."""
        rt = self.rt
        out: dict[str, object] = {}
        import copy

        for qid, qr in rt.queries.items():
            if qr.state is not None:
                ks = getattr(qr, "_keyshard", None)
                if ks is not None:
                    # canonical single-device form (parallel/keyshard.py):
                    # mesh-size independent, so a restore re-hashes keys to
                    # whatever mesh the restoring app runs on (rebalance)
                    out[f"query:{qid}"] = ks.export_state(qr.state)
                else:
                    out[f"query:{qid}"] = qr.state
            rl = getattr(qr, "rate_limiter", None)
            if rl is not None:
                # deep copy: the live buffers keep mutating once the process
                # lock is released, while pickling happens outside it
                out[f"rate:{qid}"] = copy.deepcopy(dict(vars(rl)))
        for tid, t in rt.tables.items():
            out[f"table:{tid}"] = t.state
        for wid, nw in rt.named_windows.items():
            out[f"window:{wid}"] = nw.state
        for aid, ar in rt.aggregations.items():
            out[f"aggregation:{aid}"] = ar.state
        for i, pr in enumerate(rt.partitions):
            out[f"partition:{i}:keys"] = pr.ptable
        return out

    def _restore_elements(self, elements: dict) -> None:
        rt = self.rt
        for key, value in elements.items():
            kind, _, name = key.partition(":")
            if kind == "query":
                qr = rt.queries.get(name)
                if qr is not None:
                    ks = getattr(qr, "_keyshard", None)
                    if ks is not None:
                        # re-hash the canonical group table onto THIS mesh
                        qr.state = ks.import_state(value)
                    else:
                        qr.state = _to_device(value, _state_like(qr))
            elif kind == "rate":
                qr = rt.queries.get(name)
                rl = getattr(qr, "rate_limiter", None) if qr else None
                if rl is not None:
                    vars(rl).update(value)
            elif kind == "table":
                t = rt.tables.get(name)
                if t is not None:
                    t.state = _to_device(value)
            elif kind == "window":
                nw = rt.named_windows.get(name)
                if nw is not None:
                    nw.state = _to_device(value, _state_like(nw))
            elif kind == "aggregation":
                ar = rt.aggregations.get(name)
                if ar is not None:
                    ar.state = _to_device(value)
            elif kind == "partition":
                idx = int(name.split(":")[0])
                if idx < len(rt.partitions):
                    rt.partitions[idx].ptable = _to_device(value)

    # ---- full / incremental snapshots -------------------------------------

    def full_snapshot(self, track_base: bool = False) -> bytes:
        with self.rt._process_lock:  # the reference's ThreadBarrier stop-world
            all_elems = self._elements()
            elements = {
                k: _to_host(v) for k, v in all_elems.items()
                if not k.startswith("rate:")
            }
            rates = {k: v for k, v in all_elems.items() if k.startswith("rate:")}
            interner = list(self.rt.interner._from_id[1:])
        if track_base:
            # deltas are diffed against the last PERSISTED full snapshot only
            # (a bytes-API snapshot must not shift the delta base) — staged
            # here, promoted by commit_base() after the save succeeds
            self._pending_base = {
                k: _flat_with_paths(v) for k, v in elements.items()
            }
        payload = {
            "type": "full",
            "app": self.rt.name,
            "time": int(time.time() * 1000),
            "interner": interner,
            "elements": elements,
            "rates": rates,
        }
        buf = io.BytesIO()
        pickle.dump(payload, buf, protocol=pickle.HIGHEST_PROTOCOL)
        return buf.getvalue()

    def commit_base(self) -> None:
        """Promote the base staged by `full_snapshot(track_base=True)` —
        call ONLY after the payload actually reached the store."""
        if self._pending_base is not None:
            self._last_full = self._pending_base
            self._pending_base = None

    def incremental_snapshot(self) -> bytes:
        """Leaves changed since the last full snapshot (falls back to full
        when no base exists) — the analog of the reference's base/delta split."""
        if self._last_full is None:
            return self.full_snapshot(track_base=True)
        with self.rt._process_lock:
            all_elems = self._elements()
            elements = {
                k: _to_host(v) for k, v in all_elems.items()
                if not k.startswith("rate:")
            }
            rates = {k: v for k, v in all_elems.items() if k.startswith("rate:")}
            interner = list(self.rt.interner._from_id[1:])
        delta: dict[str, dict] = {}
        for k, v in elements.items():
            flat = _flat_with_paths(v)
            base = self._last_full.get(k, {})
            changed = {
                p: leaf
                for p, leaf in flat.items()
                if p not in base
                or not isinstance(leaf, np.ndarray)
                or base[p].shape != leaf.shape
                or not np.array_equal(base[p], leaf, equal_nan=True)
            }
            if changed:
                delta[k] = changed
        payload = {
            "type": "incremental",
            "app": self.rt.name,
            "time": int(time.time() * 1000),
            "interner": interner,
            "delta": delta,
            "rates": rates,
        }
        buf = io.BytesIO()
        pickle.dump(payload, buf, protocol=pickle.HIGHEST_PROTOCOL)
        return buf.getvalue()

    # ---- restore -----------------------------------------------------------

    def restore(self, *snapshots: bytes) -> None:
        """Restore a full snapshot followed by incremental deltas, in order."""
        if not snapshots:
            return
        payloads = [pickle.loads(s) for s in snapshots]
        with self.rt._process_lock:
            # interner: restored ids must resolve to their original strings
            merge_snapshot_interner(self.rt.interner, payloads[-1])
            elements, rates = merge_snapshot_elements(payloads)
            self._restore_elements(elements)
            self._restore_elements(rates)
