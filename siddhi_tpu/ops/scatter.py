"""TPU-shaped scatter helpers.

XLA:TPU lowers scatters of 64-bit values (int64 under x64, float64) to a
serialized scalar-space loop — measured 5-11 ms for a [100k] -> [1k]
scatter-set where the same scatter of int32/float32 values is sub-millisecond.
The fix is mechanical: split 64-bit lanes into hi/lo int32 halves (arithmetic
shift/mask, NOT bitcast-convert — chaining bitcasts with the wire codec's
u8 decode trips an XLA simplifier verifier bug), scatter the halves on the
32-bit fast path, recombine. Semantics are identical for `set` (whole-value
replacement); 64-bit reductions (add/min/max) cannot ride the split and
should be reformulated (sort + searchsorted) instead.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


def _is_wide(dtype) -> bool:
    return jnp.dtype(dtype).itemsize >= 8


def _split64(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    xi = (
        x
        if jnp.issubdtype(x.dtype, jnp.integer)
        else jax.lax.bitcast_convert_type(x, jnp.int64)
    )
    lo = (xi & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
    hi = (xi >> jnp.int64(32)).astype(jnp.int32)
    return lo, hi


def _join64(lo: jnp.ndarray, hi: jnp.ndarray, dtype) -> jnp.ndarray:
    xi = (hi.astype(jnp.int64) << jnp.int64(32)) | lo.astype(jnp.int64)
    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        return xi.astype(dtype)
    return jax.lax.bitcast_convert_type(xi, dtype)


@dataclasses.dataclass
class U32Pair:
    """A 64-bit lane held as its two halves, `lo` (uint32) and `hi` (int32,
    the halves of `_split64`), each shaped like the lane. The chip has no
    64-bit integers: XLA carries an `s64[N]` parameter or result of a
    program as two `u32[N]` and converts the whole array at the program's
    boundary (`X64SplitLow` / `X64SplitHigh` / `X64Combine`), whatever part
    of it the program touches. State that lives across programs keeps its
    long lanes as a pair instead, a pytree node of two 32-bit leaves, and
    joins only the rows it reads. Never a `[N, 2]` array: a minor dimension
    of 2 is padded to a full tile on the chip. Halves may be numpy arrays
    (snapshots): `split` and `join` then stay on the host."""

    lo: object
    hi: object
    dtype: np.dtype

    @classmethod
    def split(cls, x) -> "U32Pair":
        dtype = np.dtype(x.dtype)
        if isinstance(x, np.ndarray):
            xi = x.view(np.int64)
            return cls(
                (xi & 0xFFFFFFFF).astype(np.uint32),
                (xi >> 32).astype(np.int32),
                dtype,
            )
        return cls(*_split64(x), dtype)

    @classmethod
    def full(cls, shape, fill, dtype) -> "U32Pair":
        s = cls.split(np.full((), fill, dtype))
        return cls(jnp.full(shape, s.lo), jnp.full(shape, s.hi), s.dtype)

    def join(self):
        if isinstance(self.lo, np.ndarray):
            xi = (self.hi.astype(np.int64) << 32) | self.lo.astype(np.int64)
            return xi.view(self.dtype)
        return _join64(self.lo, self.hi, self.dtype)


jax.tree_util.register_dataclass(
    U32Pair, data_fields=["lo", "hi"], meta_fields=["dtype"]
)


def is_pair(x) -> bool:
    return isinstance(x, U32Pair)


def join_pairs(tree):
    """`tree` with every U32Pair joined to its 64-bit lane."""
    return jax.tree_util.tree_map(
        lambda x: x.join() if is_pair(x) else x, tree, is_leaf=is_pair
    )


def split_like(like, tree):
    """`tree` with the leaves split that `like`, a tree of the same
    structure up to its pairs, holds as a U32Pair."""
    return jax.tree_util.tree_map(
        lambda held, x: U32Pair.split(x) if is_pair(held) else x,
        like, tree, is_leaf=is_pair,
    )


@functools.lru_cache(maxsize=None)
def ring_swap(live_bound: int):
    """`swap(lanes, at, vals) -> (old, lanes')` for a pytree of [W] ring
    lanes: the rows they held at the [S] places `at`, and the lanes with
    `vals` ([S] each) written there. A place of W or more is not touched
    and reads as unspecified; no place occurs twice.

    Under `vmap` over partition slots ([P, W] lanes, each slot its own
    places) the swap stays element gathers and scatters by (slot, place),
    and of the live places alone: the chip's scalar core takes a gather or
    a scatter one index at a time, dropped ones included, so the live rows, at most `live_bound` over all slots (the rows of the
    batch the slots' sub-batches were routed from), are first moved to the
    front (`compact_front`), and what was read is moved back to its place
    (`spread_back`). A slice at a per-slot offset (`dynamic_slice` under
    `vmap`) lowers on the chip to a loop over the slots and a transpose of
    the whole [P, W] lane."""
    from siddhi_tpu.ops.prefix import compact_front, spread_back

    tmap = jax.tree_util.tree_map

    @jax.custom_batching.custom_vmap
    def swap(lanes, at, vals):
        w = jax.tree_util.tree_leaves(lanes)[0].shape[0]
        read = jnp.clip(at, 0, w - 1)
        return (
            tmap(lambda lane: lane[read], lanes),
            tmap(lambda lane, v: lane.at[at].set(
                v, mode="drop", unique_indices=True), lanes, vals),
        )

    @swap.def_vmap
    def swap_slots(axis_size, in_batched, lanes, at, vals):
        lanes, at, vals = (
            tmap(lambda x, b: x if b else jnp.broadcast_to(
                x, (axis_size, *x.shape)), tree, batched)
            for tree, batched in zip((lanes, at, vals), in_batched)
        )
        p, s = at.shape
        w = jax.tree_util.tree_leaves(lanes)[0].shape[1]
        row = jnp.broadcast_to(
            jnp.arange(p, dtype=jnp.int32)[:, None], (p, s))
        n_all = p * s
        if n_all <= live_bound:
            read = jnp.clip(at, 0, w - 1)
            old = tmap(lambda lane: lane[row, read], lanes)
            new = tmap(lambda lane, v: lane.at[row, at].set(
                v, mode="drop", unique_indices=True), lanes, vals)
        else:
            flat = lambda x: x.reshape(-1)  # noqa: E731
            live = flat(at) < w
            front = compact_front(live, {
                "row": flat(row), "at": flat(at),
                "src": jnp.arange(n_all, dtype=jnp.int32),
                "vals": tmap(flat, vals),
            })
            front = tmap(lambda x: x[:live_bound], front)
            k = jnp.arange(live_bound, dtype=jnp.int32)
            ok = k < live.sum(dtype=jnp.int32)
            r, a = front["row"], jnp.where(ok, front["at"], np.int32(w))
            read = jnp.clip(a, 0, w - 1)
            old = tmap(lambda lane: lane[r, read], lanes)
            new = tmap(lambda lane, v: lane.at[r, a].set(
                v, mode="drop", unique_indices=True), lanes, front["vals"])
            # what was read, back at the place it was asked from
            wide = lambda x: jnp.pad(x, (0, n_all - live_bound))  # noqa: E731
            _, old = spread_back(
                wide(ok), wide(front["src"] - k), tmap(wide, old))
            old = tmap(lambda x: x.reshape(p, s), old)
        batched = lambda tree: tmap(lambda _: True, tree)  # noqa: E731
        return (old, new), (batched(old), batched(new))

    return swap


def set_at(dst: jnp.ndarray, idx: jnp.ndarray, src: jnp.ndarray, *, mode: str = "drop") -> jnp.ndarray:
    """`dst.at[idx].set(src, mode=...)` that stays off the TPU scalar path for
    64-bit dtypes (first-axis index scatter)."""
    if not _is_wide(dst.dtype):
        return dst.at[idx].set(src.astype(dst.dtype), mode=mode)
    dlo, dhi = _split64(dst)
    slo, shi = _split64(src.astype(dst.dtype))
    return _join64(
        dlo.at[idx].set(slo, mode=mode),
        dhi.at[idx].set(shi, mode=mode),
        dst.dtype,
    )


def compact_set_at(
    dst: jnp.ndarray, idx: jnp.ndarray, src: jnp.ndarray
) -> jnp.ndarray:
    """Scatter-set with a LARGE sparse index vector into a SMALL target:
    `dst[G].at[idx[B]].set(src[B])` where at most one live writer exists per
    slot and dead lanes carry idx >= G (any out-of-range index is dead, not
    just the == G sentinel).

    XLA:TPU executes scatter at ~one UPDATE per scalar-core step, so a [B]
    index vector costs ~B regardless of how few writers are live. One
    multi-operand bitonic sort (~1 ns/element, vectorized) moves the live
    writers to the front, and the real scatter then touches only [G] updates.
    Net: B-update scatter -> sort(B) + G-update scatter, ~4-6x faster for
    B >> G. Falls back to the plain scatter when B <= G."""
    g = dst.shape[0]
    b = idx.shape[0]
    if b <= g:
        return set_at(dst, idx, src)
    key = jnp.where(idx < g, idx, b).astype(jnp.int32)  # dead lanes sort last
    key_s, src_s = jax.lax.sort(
        (key, src), num_keys=1, is_stable=False
    )
    return set_at(dst, jnp.where(key_s[:g] < g, key_s[:g], g), src_s[:g])


