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


