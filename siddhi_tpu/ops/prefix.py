"""Reset-aware running (prefix) reductions over a batch.

The reference updates aggregator state one event at a time, emitting the running
value after each event and zeroing state on RESET events
(reference: query/selector/attribute/aggregator/*.java — add/remove on
CURRENT/EXPIRED, reset on RESET). Batched on TPU, the per-event running values
become prefix reductions with reset barriers. For the (small, padded) batch axis
we use an O(B^2) lower-triangular mask formulation: it is one matmul / masked
reduction, which the MXU/VPU eat for B <= ~1024, and it keeps everything static.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def cummax(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive running maximum over a [B] axis (blocked full-width scan)."""
    (out,) = _blocked_scan((x,), lambda a, b: (jnp.maximum(a[0], b[0]),))
    return out


def cumsum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive running sum. 32-bit inputs use the native lowering; 64-bit
    inputs use the blocked scan — XLA:TPU lowers cumsum to a reduce-window
    whose int64 (u32-pair) variadic form blows the scoped-vmem budget inside
    larger programs (observed 'Ran out of memory in memory space vmem ...
    reduce-window (u32[2,128], u32[2,128])' AOT failures)."""
    if x.dtype.itemsize >= 8:
        (out,) = _blocked_scan((x,), lambda a, b: (a[0] + b[0],))
        return out
    return jnp.cumsum(x)


def last_reset_index(reset: jnp.ndarray) -> jnp.ndarray:
    """For each position i, the largest j <= i with reset[j], else -1. [B] int32."""
    idx = jnp.arange(reset.shape[-1], dtype=jnp.int32)
    marked = jnp.where(reset, idx, np.int32(-1))
    return cummax(marked)


def window_mask(reset: jnp.ndarray) -> jnp.ndarray:
    """[B, B] bool: M[i, j] True iff event j contributes to the running value at
    i — j <= i and j strictly after the last reset at or before i."""
    idx = jnp.arange(reset.shape[-1], dtype=jnp.int32)
    lr = last_reset_index(reset)
    return (idx[None, :] <= idx[:, None]) & (idx[None, :] > lr[:, None])


# the longest lane read through a one-hot select instead of a gather
PICK_ROWS = 256


def take_rows(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """`x[idx]` for a [B] lane. A short lane is read through a one-hot
    [B, B] select, on the vector units: under `vmap` over partition slots
    (core/partition.py steps [P, B'] sub-batches of a few dozen rows) a
    gather lowers to a batched gather that the chip takes 2.7 ms for at
    4,096 slots x 64 rows, as long as for one lane of 262,144 rows."""
    n = x.shape[0]
    if n > PICK_ROWS:
        return x[idx]
    hot = idx[:, None] == jnp.arange(n, dtype=idx.dtype)[None, :]
    return jnp.where(hot, x[None, :], jnp.zeros((), x.dtype)).sum(
        axis=1, dtype=x.dtype)


def running_sum(
    contrib: jnp.ndarray, reset: jnp.ndarray, base: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Running sum after each event with reset barriers — O(B) via cumsum:
    run_i = csum_i - csum[last_reset_i] (+ carry before the first reset).

    contrib: [B] signed contributions (0 for invalid/timer/reset rows)
    reset:   [B] bool reset-event marks
    base:    scalar carried sum from prior batches
    returns: ([B] running values, scalar new carry)
    """
    csum = cumsum(contrib)
    lr = last_reset_index(reset)
    at_lr = jnp.where(
        lr >= 0, take_rows(csum, jnp.clip(lr, 0)), jnp.zeros_like(csum[0])
    )
    run = csum - at_lr
    no_reset_yet = lr < 0
    run = run + jnp.where(no_reset_yet, base, jnp.zeros_like(base))
    return run, run[-1]


def running_extreme(
    values: jnp.ndarray,
    active: jnp.ndarray,
    reset: jnp.ndarray,
    base: jnp.ndarray,
    is_min: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Running min/max (no removal — forever semantics / non-windowed), O(B)
    via a segmented associative scan (reset starts a new segment).

    values: [B]; active: [B] bool (valid CURRENT rows); base: scalar carry
    (identity = +/-inf or int extreme when nothing seen yet).
    """
    ident = extreme_identity(values.dtype, is_min)
    op = jnp.minimum if is_min else jnp.maximum
    masked = jnp.where(active, values, ident)

    def combine(a, b):
        av, ar = a
        bv, br = b
        return jnp.where(br, bv, op(av, bv)), ar | br

    red, _ = _blocked_scan((masked, reset), combine)
    base_eff = jnp.where(last_reset_index(reset) < 0, base, ident)
    run = op(red, base_eff)
    return run, run[-1]


_SCAN_LANES = 512


def _hillis_steele(mats: tuple, combine, width: int, axis_len: int):
    """Inclusive scan along the last axis via Hillis-Steele doubling: every
    level is a full-width vectorized shift+combine (pad/slice + select), so
    nothing lands in TPU scalar space. O(n log n) work, log n levels."""
    lane = jnp.arange(width, dtype=jnp.int32)
    cur = mats
    d = 1
    while d < axis_len:
        shifted = tuple(
            jnp.pad(m, [(0, 0)] * (m.ndim - 1) + [(d, 0)])[..., :width]
            for m in cur
        )
        comb = combine(shifted, cur)
        cur = tuple(
            jnp.where(lane >= d, cm, c) for cm, c in zip(comb, cur)
        )
        d *= 2
    return cur


def _blocked_scan(elems: tuple, combine) -> tuple:
    """Inclusive scan of tuple-valued elements over a [B] axis, shaped for
    TPU: scan lanes of a [B/L, L] view in parallel, scan the per-block
    totals, then fold each block's prefix back in. `lax.associative_scan`'s
    recursive halving creates dozens of tiny odd-shaped kernels that execute
    from scalar memory and dominate whole-query step time (profiled at ~85%
    of a group-by step at B=32k); this formulation is 3 passes of full-width
    vector work."""
    b = elems[0].shape[0]
    L = _SCAN_LANES
    if b % L != 0 and b > 2 * L:
        # pad to a lane multiple: an INCLUSIVE forward scan's first b outputs
        # never depend on tail padding, so zero-fill + slice-back is exact.
        # Without this, any off-multiple flow length silently falls into
        # lax.associative_scan's recursive halving (~13x slower, measured).
        pad = (-b) % L
        padded = tuple(jnp.pad(e, (0, pad)) for e in elems)
        out = _blocked_scan(padded, combine)
        return tuple(o[:b] for o in out)
    if b % L != 0 or b // L < 2:
        import jax.lax as lax

        return lax.associative_scan(lambda a, c: combine(a, c), elems)
    # PRED tensors (sub-byte (4,1) tiling) push these fusions onto the TPU
    # scalar path — 13x slower measured at B=32k. Carry flags as int32
    # between levels; the user combine still sees bools.
    was_bool = tuple(e.dtype == jnp.bool_ for e in elems)

    def wrapped(a, c):
        ab = tuple(x.astype(bool) if wb else x for x, wb in zip(a, was_bool))
        cb = tuple(x.astype(bool) if wb else x for x, wb in zip(c, was_bool))
        out = combine(ab, cb)
        return tuple(
            x.astype(jnp.int32) if wb else x for x, wb in zip(out, was_bool)
        )

    elems = tuple(
        e.astype(jnp.int32) if wb else e for e, wb in zip(elems, was_bool)
    )
    n = b // L
    mats = tuple(e.reshape(n, L) for e in elems)
    scanned = _hillis_steele(mats, wrapped, L, L)
    # block totals -> exclusive block prefixes (scan the [N] totals)
    totals = tuple(m[:, -1] for m in scanned)
    tot_scan = _hillis_steele(totals, wrapped, n, n)
    prev = tuple(jnp.pad(t, (1, 0))[:-1] for t in tot_scan)
    has_prev = jnp.arange(n, dtype=jnp.int32) > 0
    folded = wrapped(tuple(p[:, None] for p in prev), scanned)
    out = tuple(
        jnp.where(has_prev[:, None], f, s).reshape(b)
        for f, s in zip(folded, scanned)
    )
    return tuple(
        o.astype(bool) if wb else o for o, wb in zip(out, was_bool)
    )


def _segmented_scan(vals: jnp.ndarray, seg_start: jnp.ndarray, op) -> jnp.ndarray:
    """Inclusive segment-wise scan: positions with seg_start restart the
    accumulator. Blocked full-width scan — the O(B log B) replacement for the
    [B,B] masked-reduction form of keyed running values."""

    def combine(a, b):
        av, ar = a
        bv, br = b
        return jnp.where(br, bv, op(av, bv)), ar | br

    out, _ = _blocked_scan((vals, seg_start), combine)
    return out


def segmented_cumsum(vals: jnp.ndarray, seg_start: jnp.ndarray) -> jnp.ndarray:
    """Inclusive segment-wise running sum."""
    return _segmented_scan(vals, seg_start, lambda a, b: a + b)


def segmented_cum_extreme(
    vals: jnp.ndarray, seg_start: jnp.ndarray, is_min: bool
) -> jnp.ndarray:
    """Inclusive segment-wise running min/max."""
    return _segmented_scan(
        vals, seg_start, jnp.minimum if is_min else jnp.maximum
    )


def segmented_carry(vals, seg_start: jnp.ndarray):
    """Propagate each segment's first value across the segment. `vals` is one
    [B] lane or a tuple of them: a tuple rides one scan, with one flag lane."""
    if not isinstance(vals, tuple):
        return segmented_carry((vals,), seg_start)[0]

    def combine(a, b):
        restart = b[-1]
        return (*(jnp.where(restart, y, x) for x, y in zip(a, b[:-1])),
                a[-1] | restart)

    return _blocked_scan((*vals, seg_start), combine)[:-1]


def extreme_identity(dtype, is_min: bool) -> np.ndarray:
    # numpy (NOT jnp): this is called at trace time and the result is baked
    # into compiled programs; a numpy value embeds as an HLO literal with no
    # device work, while a concrete jax.Array const is read back from the
    # device at every lowering (see executor._const_expr).
    if jnp.issubdtype(dtype, jnp.floating):
        return np.asarray(np.inf if is_min else -np.inf, dtype=dtype)
    info = jnp.iinfo(dtype)
    return np.asarray(info.max if is_min else info.min, dtype=dtype)


def first_indices(mask: jnp.ndarray, size: int, fill: int = -1) -> jnp.ndarray:
    """Indices of the first `size` True positions, int32 — the engine's
    replacement for `jnp.nonzero(mask, size=, fill_value=)[0]`, whose internal
    cumsum is int64 under x64 and lowers to the vmem-hungry u32-pair
    reduce-window on XLA:TPU (observed AOT OOM inside fused programs)."""
    n = mask.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    rank = jnp.cumsum(mask.astype(jnp.int32)) - 1
    dst = jnp.where(mask & (rank < size), rank, size)
    return (
        jnp.full((size,), fill, jnp.int32).at[dst].set(idx, mode="drop")
    )


def compact_front(valid: jnp.ndarray, lanes):
    """Every [B] lane of the pytree with its `valid` rows moved to the front
    in row order (stable); what lies behind them is unspecified.

    A row's way to the front, the count of invalid rows before it, never
    shrinks from one valid row to the next, so the rows can take it one
    binary digit at a time, lowest first, and no two ever meet: log2(B)
    passes of a shifted read and a select, all on the VPU. On the chip this
    is the cheapest of three forms at B = 32768 with fourteen 32-bit lanes:
    a gather by `argsort(~valid)` costs 0.23-0.32 ms per lane, one payload
    sort of all lanes compiles for four minutes (PERF.md, PR 25)."""
    n = valid.shape[0]
    live = valid.astype(jnp.int32)
    way = jnp.arange(n, dtype=jnp.int32) - (jnp.cumsum(live) - live)

    def ahead(x, step):
        return jnp.concatenate([x[step:], jnp.zeros((step,), x.dtype)])

    step = 1
    while step < n:
        goes = valid & ((way & step) != 0)
        comes = ahead(goes, step)
        lanes, way = jax.tree_util.tree_map(
            lambda x: jnp.where(comes, ahead(x, step), x), (lanes, way)
        )
        valid = comes | (valid & ~goes)
        step *= 2
    return lanes


def spread_back(valid: jnp.ndarray, way: jnp.ndarray, lanes):
    """`compact_front` the other way round: every `valid` row of the pytree's
    [N] lanes moves `way` places towards the end, in row order; returns
    (which places hold a row now, the lanes). What lies in the other places
    is unspecified.

    `way` must not shrink from one valid row to the next, and the last row
    must stay inside the lanes. Then the rows can take their way one binary
    digit at a time, highest first: cut to its high digits the way still
    never shrinks along the rows, so their order holds after every pass and
    no two meet. log2(N) passes of a shifted read and a select, as there."""
    n = valid.shape[0]

    def behind(x, step):
        return jnp.concatenate([jnp.zeros((step,), x.dtype), x[:-step]])

    step = 1
    while step * 2 < n:
        step *= 2
    while step >= 1:
        goes = valid & ((way & step) != 0)
        comes = behind(goes, step)
        lanes, way = jax.tree_util.tree_map(
            lambda x: jnp.where(comes, behind(x, step), x), (lanes, way)
        )
        valid = comes | (valid & ~goes)
        step //= 2
    return valid, lanes
