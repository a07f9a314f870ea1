"""Keyed (group-by) batch reductions and the device group-slot assignment.

The reference keeps one aggregator-state object per group key in a HashMap,
looked up per event by a generated string key
(reference: query/selector/GroupByKeyGenerator.java,
query/selector/attribute/processor/executor/GroupByAggregationAttributeExecutor.java).
TPU-shaped equivalent: group state is a fixed-capacity `[G]` array indexed by a
slot; a row finds its slot by a sort-merge of the batch's keys with a
persistent int64 key table (`probe_table`) or, where the table is much larger
than the flow, through a hashed bucket index kept beside it (`_probe_heads`).
Within a batch, keyed running values ride a SORTED view of the rows — one
lexsort by (key, reset-era) turns every per-key reduction into a log-depth
segmented scan (ops/prefix.py), replacing the earlier [B,B] masked-reduction
formulation that allocated a 1G-element mask at B=32k.

What the design rests on, measured on a TPU v5e at B = 65,536 rows and
G = 4,096 slots (the deployment's flow and table; ledger, PR 28 breakdown, and
the stand-alone microbenchmark of PR 29, PERF.md §6): a gather or scatter moves
one element per step of the scalar core, 7.1 ns a row, whatever the rows hold
— 0.47 ms for one flow-length gather of a 32-bit lane, 0.94 ms of a 64-bit
one, 0.31 ms for a flow-length scatter — while a payload sort of the flow
(key and one lane) takes 22 us, a segmented scan a few us, and a gather or
scatter of G rows 12-18 us. So a value that is the same along a segment of the
sorted view (the slot the segment's head was given, the group's carried
aggregate) is read once per segment, by at most G rows, and spread along the
segment by a segmented scan; never once per row of the flow. Where the flow is
no longer than the table (B <= G) a row reads for itself, which is then the
cheaper form: `SortedGroups.carry_read` says which form a program took.

The key table is probed the same way (PR 39, PERF.md §6). Up to PR 33 every
row was compared with every slot: a dense `[B, G]` equality matrix and two
reductions over it, 1.18 ms for the `argmax` and 0.31 ms for the `any`,
1.49 ms of every step of the 65,536-row flow and its longest operation. A
`searchsorted` probe was measured slower still: its log G binary-search steps
are dependent gathers, each at the scalar core's 7.1 ns a row. The sort-merge
has no gather and nothing of size B x G: one sort of the B + G keys (the
64-bit key as two 32-bit words, and a tag), one segmented carry and one
payload sort back take 0.21 ms together where the matrix took 1.49
(`jit__step_impl` 2.79 -> 1.52 ms a send, my traced runs, PR 39; PERF.md §6
has them by operation), and G is no longer bounded by what a matrix of
B x G can hold.

But the merge sorts the table: at G = 2,228,224 slots (NEXMark query 5's live
auctions) its two sorts of B + G = 2,293,760 rows took 5.56 + 2.93 ms of every
selector pass, 12.0 of the 19.2 ms of a micro-batch (ledger, PR 40), to find
the slots of 65,536 rows that sit on some 4,300 distinct keys. Such a table
keeps a hashed bucket index (PR 41, PERF.md sections 3 and 6): `[NB, 128]`
lanes of (key, slot), looked up once per segment head of the sorted view, a
tile of 8,192 heads at a time, so that a pass costs the batch's distinct keys
and not the table. The primitive it rests on, measured on a TPU v5e (my chip
runs, PR 41): a gather of 8,192 rows of 128 32-bit words out of a
`[65536, 128]` lane (32 MB) takes 0.10 ms in the chunk program, 12.4 ns a row
(0.04 ms out of a 16 MB lane alone in a loop; three lanes apart 0.121, one
`[NB, 384]` lane 0.108: not worth a layout of its own); the dense compare of
the tile's `[8192, 128]` rows 0.013 ms and its reductions 0.009; a scatter
of 8,192 elements into such a lane 0.106 ms, 13 ns an element. A whole table
step (`assign_slots`, the count lane, the release) at B = 65,536 then takes
3.5-4.05 ms whatever the table holds, where the merge's takes 3.18 ms at
G = 65,536, 3.64 at 262,144 (the index: 3.67), 4.78 at 524,288 (3.63), 6.89 at
1,048,576 and 11.61 at 2,228,224 (4.05): the crossover lies at four slots a
row of the flow, and `BUCKET_SLOTS_PER_ROW` engages the index from eight.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from siddhi_tpu.ops.prefix import (
    extreme_identity,
    segmented_carry,
    segmented_cum_extreme,
    segmented_cumsum,
)
from siddhi_tpu.ops.scatter import U32Pair, compact_set_at, set_at

# How `assign_slots` finds a row's slot in the key table: `probe_table`'s
# sort-merge of the batch with the whole table, or, where the table is much
# larger than the flow, the hashed bucket index kept beside it (`probe_for`
# decides, from the shapes alone, when the table is built). Static, reported
# as `snapshot_status()["queries"][q]["group"]["probe"]` and
# `["partition"]["probe"]`.
PROBE_MERGE, PROBE_BUCKET = "merge", "bucket"

# A table takes the bucket index where it has at least this many slots for
# every row of the flow its selector is built for. Measured on a TPU v5e at
# a flow of B = 65,536 rows (my chip runs, PR 41: the module's docstring has
# the figures): a table step by the merge and one through the index cost the
# same at four slots a row (3.64 and 3.67 ms); at eight the index is ahead
# by 1.15 ms of 4.78, at one the merge by 0.35 of 3.53. Under the crossover a
# sort of the few rows there are beats any gather, and a small table wants
# no index state at all.
BUCKET_SLOTS_PER_ROW = 8

# The index: [NB, INDEX_LANES] lanes (a key's two 32-bit words and its slot,
# -1 where the lane is empty), NB the power of two with NB x INDEX_LANES >= 2 G
# (65,536 x 128 for 2,228,224 slots: 100 MB beside 1.9 GB of state), so a
# bucket holds 32 to 64 keys of a full table on average and 128 at most
# (eight standard deviations above). The sorted view's segment heads are
# looked up PROBE_TILE at a time.
INDEX_LANES = 128
PROBE_TILE = 8192

# How a table learns that a group holds no row of the window any more, so
# that its slot can be taken back: from an aggregator's lane that counts the
# rows already (`count()`, `avg`'s count), from a lane of its own, or not at
# all (no window hands it EXPIRED rows: nothing ever leaves a group). Static,
# `snapshot_status()["queries"][q]["group"]["reclaim"]`.
RECLAIM_COUNT, RECLAIM_OWN, RECLAIM_NONE = "count_lane", "own_lane", "none"

# 64-bit mixing constants (splitmix64 finalizer) for combining composite keys.
_MIX1 = np.int64(-7046029254386353131)  # 0x9E3779B97F4A7C15 as signed
_MIX2 = np.int64(-4658895280553007687)  # 0xBF58476D1CE4E5B9 as signed


def mix_keys(cols: list[jnp.ndarray]) -> jnp.ndarray:
    """Combine one or more [B] integer-encoded key columns into one int64 key.

    Single-column keys pass through exactly (collision-free); composite keys are
    hash-mixed (the reference concatenates strings; a 64-bit mix keeps the
    device representation fixed-width — collisions are ~2^-64 per pair).
    """
    if len(cols) == 1:
        return cols[0].astype(jnp.int64)
    h = jnp.zeros_like(cols[0], dtype=jnp.int64)
    for c in cols:
        h = (h ^ c.astype(jnp.int64)) * _MIX1
        h = (h ^ (h >> 29)) * _MIX2
    return h


def permute_by(key: jnp.ndarray, *lanes: jnp.ndarray) -> tuple:
    """Apply the permutation that sorts `key` ascending to every lane with ONE
    multi-operand bitonic sort. XLA:TPU runs sorts on the vector units (22 us
    for 65,536 rows of key and one lane, 53 us with three lanes) but
    gathers/scatters on the scalar core (7.1 ns a row: 470 us), so `x[perm]`
    for a known permutation is some twenty times cheaper as a payload sort.
    `key` must be a permutation-ranking (all distinct); lanes ride along."""
    res = jax.lax.sort((key, *lanes), num_keys=1, is_stable=False)
    return res[1:]


# the most 32-bit words one payload sort carries beside its key (a 64-bit
# lane rides as two): a sort's compile time grows steeply with its operands
# (fourteen took minutes, PERF.md PR 25)
SORT_WORDS = 3


def permute_in_groups(key: jnp.ndarray, lanes: list) -> list:
    """`permute_by(key, *lanes)`, at most `SORT_WORDS` words to a sort.
    Each sort's key is shifted by its group's number: the order is the same,
    but XLA folds sorts that share one key operand back into one sort of all
    their lanes, the very sort the groups are there to avoid (one of
    fourteen operands took 213 s of the pattern step's compile for a v5e, PR 43)."""
    out, group, words = [], [], 0
    for lane in [*lanes, None]:
        w = 0 if lane is None else max(1, lane.dtype.itemsize // 4)
        if group and (lane is None or words + w > SORT_WORDS):
            shift = np.asarray(len(out), key.dtype)
            out += permute_by(key + shift, *group)
            group, words = [], 0
        if lane is not None:
            group.append(lane)
            words += w
    return out


@dataclasses.dataclass
class SortedGroups:
    """Sorted per-batch view: rows permuted by (active, reset-era, key, idx).

    perm:      [B] int32 — sorted position -> original row
    inv:       [B] int32 — original row -> sorted position
    seg_start: [B] bool  — sorted position begins a (era, key) segment

    and the step's read plan for the groups' carried values, made once by
    `assign_slots` and shared by every aggregator lane (`keyed_running_sum`,
    `keyed_running_extreme`):

    reset:     [B] bool  — the RESET rows the eras were cut at
    slot_s:    [B] int32 — each row's slot (G = none) in sorted order;
                           constant along a segment
    carried_s: [B] bool  — sorted rows whose group has a carried value: era 0
                           (no reset at or before the row) and a live slot
    writer_s:  [B] int32 — the slot, on the row that ends a live group's
                           final-era segment (its running value is the
                           group's new carry); G elsewhere
    head_pos:  [G] int32 — sorted positions of the `carried_s` segment heads,
                           moved to the front (B = none). In era 0 a live
                           slot is one segment, so G places hold them all.
                           None when B <= G
    head_slot: [G] int32 — their slots

    and, where the table takes slots back (`assign_slots(free=...)`):

    free:      [G] int32 — the table's stack of unused slots once the step's
                           new keys have taken theirs
    freed_s:   [B] bool  — sorted rows that write a group's carry (`writer_s`)
                           whose group holds no row of the window any more:
                           set by the lane that counts rows
                           (`keyed_running_sum(rows=True)`), read by every
                           lane behind it, which writes its identity there

    and, where the table keeps a bucket index (`assign_slots(index=...)`):

    index:     the index with the step's new keys in it (`release_index`
               takes out those of the groups the step freed)
    lane_s:    [B] int32 — sorted order: the flat place of the index lane
                           that holds the row's key (NB x S: none)
    """

    perm: jnp.ndarray
    inv: jnp.ndarray
    seg_start: jnp.ndarray
    reset: jnp.ndarray = None
    slot_s: jnp.ndarray = None
    carried_s: jnp.ndarray = None
    writer_s: jnp.ndarray = None
    head_pos: jnp.ndarray | None = None
    head_slot: jnp.ndarray | None = None
    free: jnp.ndarray | None = None
    freed_s: jnp.ndarray | None = None
    index: dict | None = None
    lane_s: jnp.ndarray | None = None

    @property
    def carry_read(self) -> str:
        """How a row comes by its group's carried value: `segment` (read
        once per segment head, spread by a segmented scan) or `row` (every
        row gathers for itself: the cheaper form when B <= G). Chosen from
        the shapes at trace time."""
        return "row" if self.head_pos is None else "segment"

    @property
    def seg_end(self) -> jnp.ndarray:
        """[B] bool — sorted position ends its segment."""
        return jnp.concatenate([self.seg_start[1:], jnp.ones((1,), jnp.bool_)])

    def to_sorted(self, *lanes):
        """lanes[i][perm] for every lane — one payload sort, no gathers."""
        return permute_by(self.inv, *lanes)

    def from_sorted(self, *lanes):
        """lanes[i][inv] (undo to_sorted) — one payload sort, no gathers."""
        return permute_by(self.perm, *lanes)

    def carried(self, carry: jnp.ndarray, fill, every_era: bool = False):
        """[B], sorted order: `carry[slot]` on `carried_s` rows, `fill` on
        the others. By segment, G rows are gathered from the table, put at
        their segment heads and carried along the segments. `every_era`
        (maxForever / minForever ignore resets) takes rows of later eras in
        too; their heads may outnumber G, so its rows read for themselves."""
        g = carry.shape[0]
        if every_era or self.head_pos is None:
            has = (self.slot_s < g) if every_era else self.carried_s
            return jnp.where(has, carry[jnp.clip(self.slot_s, 0, g - 1)], fill)
        at_head = carry[jnp.clip(self.head_slot, 0, g - 1)]
        heads = set_at(
            jnp.zeros(self.seg_start.shape, carry.dtype), self.head_pos, at_head
        )
        return jnp.where(
            self.carried_s, segmented_carry(heads, self.seg_start), fill
        )


def probe_table(
    table_keys: jnp.ndarray,  # [G] int64
    used: jnp.ndarray,        # [G] bool
    batch_keys: jnp.ndarray,  # [B] int64
) -> jnp.ndarray:
    """[B] int32: the slot of the used table entry that holds each batch
    row's key, -1 where none does. A sort-merge: the table's G keys and the
    batch's B are sorted together on (key, tag), the entry's slot is carried
    along its run of equal keys, and one payload sort on the tag brings the
    rows back; no gather, and nothing of size B x G.

    The tag says who a merged row is and puts a run in order: the unused
    entries first (G of them may hold key 0, which is a legal key; tag
    j - G < 0), then the one used entry of that key (its slot j), then the
    batch's rows (G + i). A carry segment opens at every table entry, so a
    batch row is handed what the last entry ahead of it in its run holds: the
    used one's slot if the key is in the table, else an unused one's -1; and a
    run that opens with a batch row has no entry, -1."""
    g = table_keys.shape[0]
    b = batch_keys.shape[0]
    slots = jnp.arange(g, dtype=jnp.int32)
    tag = jnp.concatenate(
        [jnp.where(used, slots, slots - g), jnp.arange(g, g + b, dtype=jnp.int32)]
    )
    mk, mt = jax.lax.sort(
        (jnp.concatenate([table_keys, batch_keys]), tag), num_keys=2, is_stable=False
    )
    is_entry = mt < g
    run_start = jnp.concatenate([jnp.ones((1,), jnp.bool_), mk[1:] != mk[:-1]])
    held = jnp.where(is_entry & (mt >= 0), mt, np.int32(-1))
    found = segmented_carry(held, is_entry | run_start)
    (back,) = permute_by(mt, found)
    return back[g:]


# ---- the hashed bucket index ------------------------------------------------

def probe_for(g: int, flow_rows: int | None) -> str:
    """How a table of `g` slots is probed behind a selector whose flow is
    `flow_rows` long (None: a caller that keeps no index: partitions, the
    keys mesh, joins, patterns)."""
    if flow_rows and g >= BUCKET_SLOTS_PER_ROW * flow_rows:
        return PROBE_BUCKET
    return PROBE_MERGE


def index_buckets(g: int) -> int:
    nb = 1
    while nb * INDEX_LANES < 2 * g:
        nb *= 2
    return nb


def bucket_of(keys, nb: int, xp=jnp):
    """int32 bucket of each int64 key: the high bits of splitmix64's
    finalizer (`mix_keys` passes a single column through as it is, so the
    raw key's bits say nothing). `xp=np` computes the same on the host."""
    bits = nb.bit_length() - 1
    if bits == 0:
        return xp.zeros(keys.shape, xp.int32)
    z = keys.astype(xp.uint64) + xp.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> xp.uint64(30))) * xp.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> xp.uint64(27))) * xp.uint64(0x94D049BB133111EB)
    z = z ^ (z >> xp.uint64(31))
    return (z >> xp.uint64(64 - bits)).astype(xp.int32)


def empty_index(g: int) -> dict:
    """The index of an empty table of `g` slots: the lanes, `full` (sticky:
    a bucket had no lane left for a new key, so the index no longer holds
    every key and `assign_slots` probes by the merge from then on) and
    `tiles` (head tiles looked up since deploy)."""
    shape = (index_buckets(g), INDEX_LANES)
    return {
        "lo": jnp.zeros(shape, jnp.uint32),
        "hi": jnp.zeros(shape, jnp.int32),
        "slot": jnp.full(shape, -1, jnp.int32),
        "full": jnp.zeros((), jnp.bool_),
        "tiles": jnp.zeros((), jnp.int64),
    }


def index_from_table(keys, used) -> dict:
    """The index of a saved table, laid out on the host from its `keys` and
    `used` lanes, which are the truth (a snapshot holds no index: restore
    builds it, as `table_from_legacy` builds the stack). Leading axes pass
    through."""
    keys, used = np.asarray(keys), np.asarray(used).astype(bool)
    g = used.shape[-1]
    nb, lead = index_buckets(g), used.shape[:-1]

    def one(k, u):
        slots = np.flatnonzero(u).astype(np.int32)
        bk = bucket_of(k[slots], nb, np)
        order = np.argsort(bk, kind="stable")
        bk, slots = bk[order], slots[order]
        lane = np.arange(len(bk)) - np.searchsorted(bk, bk)
        ok = lane < INDEX_LANES
        words = U32Pair.split(k[slots[ok]].astype(np.int64))
        out = {"lo": np.zeros((nb, INDEX_LANES), np.uint32),
               "hi": np.zeros((nb, INDEX_LANES), np.int32),
               "slot": np.full((nb, INDEX_LANES), -1, np.int32)}
        at = (bk[ok], lane[ok])
        out["lo"][at], out["hi"][at], out["slot"][at] = words.lo, words.hi, slots[ok]
        return {**out, "full": not ok.all()}

    tables = [one(k, u) for k, u in zip(keys.reshape(-1, g), used.reshape(-1, g))]
    index = {
        name: np.stack([t[name] for t in tables]).reshape(
            lead + np.shape(tables[0][name]))
        for name in ("lo", "hi", "slot", "full")
    }
    index["tiles"] = np.zeros(lead, np.int64)
    return index


def _nth_set_bit(words: tuple, n: jnp.ndarray) -> jnp.ndarray:
    """[B] int32: the place of the n-th set bit (from 0, lowest first) of the
    bit string whose 32-bit words are `words` ([B] uint32 each, lowest
    first), -1 where it has no more than n. Element-wise: the word by the
    running population count, the bit by halving."""
    count = jax.lax.population_count
    word = jnp.zeros_like(words[0])
    base = jnp.zeros_like(n)
    rest, done = n, jnp.zeros(n.shape, jnp.bool_)
    for i, w in enumerate(words):
        c = count(w).astype(jnp.int32)
        here = ~done & (rest < c)
        word = jnp.where(here, w, word)
        base = jnp.where(here, np.int32(32 * i), base)
        rest = jnp.where(done | here, rest, rest - c)
        done = done | here
    at = jnp.zeros_like(n)
    for width in (16, 8, 4, 2, 1):
        low = (word >> at.astype(jnp.uint32)) & np.uint32((1 << width) - 1)
        c = count(low).astype(jnp.int32)
        skip = rest >= c
        rest = jnp.where(skip, rest - c, rest)
        at = jnp.where(skip, at + width, at)
    return jnp.where(done, base + at, np.int32(-1))


def _tiles(n, b: int):
    """(tile length, how many tiles hold the first `n` of `b` rows, the
    start of tile t): the last tile of a length that the tile does not
    divide starts early and takes some rows again."""
    tile = min(PROBE_TILE, b)
    return tile, (n + (tile - 1)) // tile, lambda t: jnp.minimum(t * tile, b - tile)


def _probe_heads(index: dict, bucket_h, lo_h, hi_h, n_heads):
    """The lookup of the first `n_heads` rows (the segment heads, moved to
    the front: their bucket and their key's words), a tile at a time under
    a `while_loop`, so the trips follow the distinct keys the batch really
    holds. A tile gathers its heads' bucket rows `[T, S]` and compares them
    densely. Returns, [B] each and meaningful on those first rows: the slot
    of the key (-1: not in the index), the flat place of its lane (-1), the
    bucket's empty lanes as a bit string (S / 32 words, lowest lane first);
    and the trips."""
    nb, s = index["slot"].shape
    b = bucket_h.shape[0]
    tile, trips, start = _tiles(n_heads, b)
    lane = jnp.arange(s, dtype=jnp.int32)
    bit = np.uint32(1) << jnp.arange(32, dtype=jnp.uint32)

    def look_up(carry):
        t, found, at, empty = carry
        off = start(t)
        bk, lo, hi = (
            jax.lax.dynamic_slice(x, (off,), (tile,)) for x in (bucket_h, lo_h, hi_h)
        )
        row = jnp.minimum(bk, nb - 1)  # rows behind the heads: bucket NB
        slots = index["slot"][row]
        hit = (
            (slots >= 0) & (index["lo"][row] == lo[:, None])
            & (index["hi"][row] == hi[:, None]) & (bk < nb)[:, None]
        )
        f = jnp.max(jnp.where(hit, slots, np.int32(-1)), axis=1)
        a = jnp.max(jnp.where(hit, row[:, None] * s + lane, np.int32(-1)), axis=1)
        e = jnp.sum(
            jnp.where((slots < 0).reshape(tile, s // 32, 32), bit, np.uint32(0)),
            axis=2, dtype=jnp.uint32,
        )

        def put(x, v):
            return jax.lax.dynamic_update_slice(x, v, (off,))

        return (t + 1, put(found, f), put(at, a),
                tuple(put(x, e[:, w]) for w, x in enumerate(empty)))

    none = jnp.full((b,), -1, jnp.int32)
    _, found, at, empty = jax.lax.while_loop(
        lambda carry: carry[0] < trips, look_up,
        (jnp.zeros((), jnp.int32), none, none,
         tuple(jnp.zeros((b,), jnp.uint32) for _ in range(s // 32))),
    )
    return found, at, empty, trips


def _write_lanes(lanes: dict, first, at, vals: dict) -> dict:
    """`lanes[name]` ([NB, S] each) with `vals[name][i]` written at the flat
    place `at[i]` on the rows of `first` ([B] bool; no place twice). The
    writers are moved to the front by one sort and scattered a tile at a
    time, so the cost follows their number."""
    nb, s = next(iter(lanes.values())).shape
    b = at.shape[0]
    pos = jnp.arange(b, dtype=jnp.int32)
    names = sorted(lanes)
    _, at_c, *vals_c = jax.lax.sort(
        (jnp.where(first, pos, pos + b), jnp.where(first, at, np.int32(nb * s)),
         *(vals[name] for name in names)),
        num_keys=1, is_stable=False,
    )
    tile, trips, start = _tiles(first.sum(dtype=jnp.int32), b)

    def write(carry):
        t, held = carry
        off = start(t)
        a = jax.lax.dynamic_slice(at_c, (off,), (tile,))
        row, col = a // s, a % s  # past the writers: row NB, dropped
        return t + 1, tuple(
            lane.at[row, col].set(
                jax.lax.dynamic_slice(v, (off,), (tile,)), mode="drop",
                unique_indices=True)
            for lane, v in zip(held, vals_c)
        )

    _, held = jax.lax.while_loop(
        lambda carry: carry[0] < trips, write,
        (jnp.zeros((), jnp.int32), tuple(lanes[name] for name in names)),
    )
    return dict(zip(names, held))


def _index_insert(index: dict, bucket_h, lo_h, hi_h, empty_h, slot_h):
    """The index with the step's new keys: `slot_h` is the slot a head's key
    was given, -1 on the other rows (all in the heads' order, by bucket). A
    new key takes its bucket's r-th empty lane, r its rank among the new
    keys of that bucket. Returns (lanes, [B] flat place of each new key's
    lane (NB x S: none), whether a bucket had no lane left)."""
    nb, s = index["slot"].shape
    new = slot_h >= 0
    run_start = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), bucket_h[1:] != bucket_h[:-1]])
    rank = segmented_cumsum(new.astype(jnp.int32), run_start) - new
    lane = _nth_set_bit(empty_h, rank)
    fits = new & (lane >= 0)
    at = jnp.where(fits, bucket_h * s + lane, np.int32(nb * s))
    lanes = _write_lanes(
        {name: index[name] for name in ("lo", "hi", "slot")}, fits, at,
        {"lo": lo_h, "hi": hi_h, "slot": slot_h},
    )
    return lanes, at, (new & ~fits).any()


def release_index(index: dict, grp: "SortedGroups") -> dict:
    """The index without the keys of the groups the step freed
    (`grp.freed_s`): their lanes, where the probe found them or the step put
    them (`grp.lane_s`), read empty again."""
    freed = grp.freed_s
    lanes = _write_lanes(
        {"slot": index["slot"]}, freed, grp.lane_s,
        {"slot": jnp.full(freed.shape, -1, jnp.int32)},
    )
    return {**index, **lanes}


def free_stack(g: int) -> jnp.ndarray:
    """[G] int32: the unused slots of an empty table, as a stack whose top is
    its last live place. Place i holds slot G - 1 - i, so a table that has
    given no slot back hands them out in rising order, as the bump pointer of
    a table that takes none back does."""
    return jnp.arange(g - 1, -1, -1, dtype=jnp.int32)


def _pop_slots(free, n_free, is_alloc):
    """[B] int32: on the r-th row of `is_alloc` the r-th slot from the top of
    the stack, `free[n_free - 1 - r]`, or G where the stack has run out; what
    the other rows hold is unspecified. The top of the stack is read as one
    slice, and the slots reach their rows by two sorts of the batch (the rows
    that allocate first, the slots laid beside them, and back): no gather."""
    g, b = free.shape[0], is_alloc.shape[0]
    take = min(b, g)
    start = jnp.maximum(n_free - take, 0)
    top = jax.lax.dynamic_slice(free, (start,), (take,))
    m = n_free - start  # how many of `top` are unused slots
    cand = jax.lax.dynamic_slice(
        jnp.pad(top[::-1], (0, take), constant_values=g), (take - m,), (take,)
    )
    cand = jnp.pad(cand, (0, b - take), constant_values=g)
    idx = jnp.arange(b, dtype=jnp.int32)
    (first,) = jax.lax.sort((jnp.where(is_alloc, idx, idx + b),), num_keys=1)
    (slot_new,) = permute_by(jnp.where(first >= b, first - b, first), cand)
    return slot_new


def release_slots(free, n_used, grp: "SortedGroups"):
    """Push the slots of `grp.freed_s` (each once: a writer row per group)
    onto the stack of a table that holds `n_used` groups, the step's new
    ones included. Returns (stack, groups held now, slots freed). The slots
    are moved to the front of the batch by one sort and written as one run
    at the stack's top, G - n_used; the run is blended into a window of the
    stack that lies inside it, so it costs the batch and not the table."""
    g, b = free.shape[0], grp.freed_s.shape[0]
    take = min(b, g)
    pos = jnp.arange(b, dtype=jnp.int32)
    _, slots = jax.lax.sort(
        (jnp.where(grp.freed_s, pos, pos + b), grp.slot_s), num_keys=1,
        is_stable=False,
    )
    f = grp.freed_s.sum(dtype=jnp.int32)
    top = g - n_used
    at = jnp.clip(top, 0, g - take)
    d = top - at
    j = jnp.arange(take, dtype=jnp.int32)
    run = jax.lax.dynamic_slice(
        jnp.pad(slots[:take], (take, 0)), (take - d,), (take,)
    )
    window = jnp.where(
        (j >= d) & (j - d < f), run, jax.lax.dynamic_slice(free, (at,), (take,))
    )
    return jax.lax.dynamic_update_slice(free, window, (at,)), n_used - f, f


def table_from_legacy(snap: dict, own_lane: bool) -> dict:
    """A key table saved before it took slots back (`keys`, `used`, `n`),
    with the stack of its unused slots, lowest on top, and the counters at
    zero (on the host). A table that counts rows in a lane of its own cannot
    know how many each saved group holds: they are given more than any
    window lets go, and keep their slot as they did when saved."""
    used = np.asarray(snap["used"]).astype(bool)
    g = used.shape[-1]

    def stack(u):
        unused = np.flatnonzero(~u)[::-1].astype(np.int32)
        return np.concatenate([unused, np.zeros(g - len(unused), np.int32)])

    lead = used.shape[:-1]
    flat = used.reshape(-1, g)
    out = dict(snap)
    out["free"] = np.stack([stack(u) for u in flat]).reshape(*lead, g)
    out["n"] = flat.sum(axis=-1).astype(np.int32).reshape(lead)
    out["freed"] = np.zeros(lead, np.int64)
    out["lost"] = np.zeros(lead, np.int64)
    if own_lane:
        out["rows"] = np.where(used, np.int32(1 << 30), np.int32(0))
    return out


def assign_slots(
    table_keys: jnp.ndarray,  # [G] int64
    used: jnp.ndarray,        # [G] bool
    n_used: jnp.ndarray,      # scalar int32
    batch_keys: jnp.ndarray,  # [B] int64
    active: jnp.ndarray,      # [B] bool — rows that carry a group key
    reset: jnp.ndarray | None = None,  # [B] bool — RESET rows clear the table
    free: jnp.ndarray | None = None,   # [G] int32 — the stack of unused slots
    index: dict | None = None,         # the table's bucket index, if it keeps one
):
    """Map each active row to a stable slot in [0, G); allocate new slots in
    first-appearance order. Inactive rows get slot == G (scatter-drop lane).

    A table that takes slots back (`free`, `free_stack`; its live places are
    the first G - n_used) hands a new key the slot on top of the stack where
    the other counts on from `n_used`; the stack behind the step's pops comes
    back as `SortedGroups.free`, and `release_slots` pushes what the step
    freed once the lane that counts rows has said which groups are empty.

    A table with a bucket index (`index`, `empty_index`) finds the slots
    through it, once per segment head of the sorted view (`_probe_heads`),
    and keeps it up: the step's new keys are written into their buckets and
    the index comes back as `SortedGroups.index`; `release_index` takes out
    the keys of the groups the step freed. `table_keys` and `used` stay the
    truth: once a bucket has had no lane for a new key (`index["full"]`,
    sticky), the table is probed by the merge again, which reads them.

    RESET semantics: a reset kills every group's carried state, so rows after
    the batch's last reset re-allocate into a FRESH table (bounding table
    growth to per-bucket cardinality for batch windows — the reference's
    per-chunk group map has the same lifetime). Rows before the reset resolve
    against the old table, which only feeds the (pre-reset) carry gathers.

    Overflow: keys beyond capacity go to the dead lane G — their within-batch
    running values are still exact (computed over the sorted segments), but
    their carry is lost across batches; existing groups are never corrupted.

    Returns (new_table_keys, new_used, new_n_used, slot [B] int32,
    SortedGroups, overflow scalar bool).
    """
    g = table_keys.shape[0]
    b = batch_keys.shape[0]
    idx = jnp.arange(b, dtype=jnp.int32)

    has_reset = reset is not None and getattr(reset, "shape", None)
    rst = reset if has_reset else jnp.zeros((b,), jnp.bool_)
    glr = jnp.max(jnp.where(rst, idx, np.int32(-1)))  # last reset row, -1 if none
    any_reset = glr >= 0
    post = idx > glr  # rows whose carry lives in the (possibly fresh) new table
    era = jnp.cumsum(rst.astype(jnp.int32))  # segments never span a reset

    # ---- sorted view: actives first, grouped by (era, key), stable by idx.
    # ONE multi-key payload sort replaces lexsort + per-lane [perm] gathers
    # (sorts ride the vector units; gathers serialize on the scalar core),
    # and the inverse permutation comes from a second payload sort instead
    # of a [B]-update scatter.
    inact = (~active).astype(jnp.int32)
    inact_s, se, sk, perm, sa = jax.lax.sort(
        (inact, era, batch_keys, idx, active), num_keys=4, is_stable=False
    )
    del inact_s
    seg_start = jnp.concatenate(
        [
            jnp.ones((1,), jnp.bool_),
            (sk[1:] != sk[:-1]) | (se[1:] != se[:-1]) | (sa[1:] != sa[:-1]),
        ]
    )
    (inv,) = permute_by(perm, idx)
    grp = SortedGroups(perm=perm, inv=inv, seg_start=seg_start)

    # rows that open their (era, key) segment, in original order
    (is_head,) = grp.from_sorted(seg_start)

    # ---- resolution against the old table (pre-reset rows + no-reset case;
    # the lookup takes no notice of eras)
    if index is None:
        with jax.named_scope("group.probe"):
            t_slot = jnp.where(active, probe_table(table_keys, used, batch_keys), -1)
    else:
        with jax.named_scope("group.probe"):
            # the heads to the front, by bucket: H order. `pos_h` is the
            # sorted position a row of H came from
            nb, lanes = index["slot"].shape
            head_s = seg_start & sa
            words = U32Pair.split(sk)
            bucket_h, pos_h, lo_h, hi_h = jax.lax.sort(
                (jnp.where(head_s, bucket_of(sk, nb), np.int32(nb)), idx,
                 words.lo, words.hi),
                num_keys=2, is_stable=False,
            )

            def by_index(_):
                return _probe_heads(
                    index, bucket_h, lo_h, hi_h, head_s.sum(dtype=jnp.int32))

            def by_merge(_):
                # every bucket reads full, so nothing more is written
                (h_of,) = permute_by(pos_h, idx)
                (t_s,) = grp.to_sorted(probe_table(table_keys, used, batch_keys))
                (t_h,) = permute_by(h_of, t_s)
                none = jnp.full((b,), -1, jnp.int32)
                return (t_h, none, (jnp.zeros((b,), jnp.uint32),) * (lanes // 32),
                        jnp.zeros((), jnp.int32))

            found_h, at_h, empty_h, trips = jax.lax.cond(
                index["full"], by_merge, by_index, None)
            # back to the sorted view: a head's answer is its segment's.
            # `h_of` is the place in H of a row of the sorted view
            found_s, at_s, h_of = permute_by(pos_h, found_h, at_h, idx)
            found_s, at_s = segmented_carry((found_s, at_s), seg_start)
            probed_s = jnp.where(sa, found_s, np.int32(-1))
            (t_slot,) = grp.from_sorted(probed_s)
    in_t = t_slot >= 0

    is_alloc = active & ~in_t & is_head
    alloc_rank = (jnp.cumsum(is_alloc.astype(jnp.int32)) - is_alloc).astype(jnp.int32)
    if free is None:
        slot_new = n_used + alloc_rank
    else:
        with jax.named_scope("group.reclaim"):
            slot_new = _pop_slots(free, g - n_used, is_alloc)
    old_overflow = (jnp.where(is_alloc, slot_new, 0) >= g).any()

    # ---- fresh-table allocation for post-reset rows (a head is era-local,
    # so the same heads serve the fresh allocation pass)
    is_alloc_f = active & post & is_head
    rank_f = (jnp.cumsum(is_alloc_f.astype(jnp.int32)) - is_alloc_f).astype(jnp.int32)
    fresh_overflow = (jnp.where(is_alloc_f, rank_f, 0) >= g).any()

    # ---- every row takes the slot its segment's head was given. "The value
    # at my head" is one payload sort to the sorted view and one segmented
    # carry there, not a gather per row; the slots come out in sorted order,
    # which is where the aggregators' carried values are read
    if index is None:
        ts_s, sn_s, rf_s = grp.to_sorted(t_slot, slot_new, rank_f)
    else:
        ts_s, (sn_s, rf_s) = probed_s, grp.to_sorted(slot_new, rank_f)
    head_sn, head_rf = segmented_carry((sn_s, rf_s), seg_start)
    post_s = perm > glr
    old_s = jnp.where(ts_s >= 0, ts_s, jnp.where(head_sn < g, head_sn, g))
    fresh_s = jnp.where(head_rf < g, head_rf, g)
    slot_s = jnp.where(any_reset & post_s, fresh_s, old_s)
    slot_s = jnp.where(sa, slot_s, np.int32(g)).astype(jnp.int32)
    (slot,) = grp.from_sorted(slot_s)
    overflow = jnp.where(any_reset, fresh_overflow, old_overflow)

    grp.reset, grp.slot_s = rst, slot_s
    grp.carried_s = (se == 0) & (slot_s < g)
    grp.writer_s = jnp.where(grp.seg_end & post_s, slot_s, np.int32(g))
    if b > g:
        # the <= G heads that have a value to read, moved to the front
        head_at = jnp.where(seg_start & grp.carried_s, idx, np.int32(b))
        pos, slots = jax.lax.sort((head_at, slot_s), num_keys=1, is_stable=False)
        grp.head_pos, grp.head_slot = pos[:g], slots[:g]

    # ---- new table state (compact_set_at: sort the <=G live writers to the
    # front so the scatter touches G updates, not B — and int64 key scatters
    # ride the int32-pair path either way, ops/scatter.py)
    ones_b = jnp.ones((b,), jnp.bool_)
    # no reset: old table + this batch's allocations
    scatter_old = jnp.where(is_alloc & (slot_new < g) & ~any_reset, slot_new, g)
    keys_old = compact_set_at(table_keys, scatter_old, batch_keys)
    used_old = compact_set_at(used, scatter_old, ones_b)
    n_old = jnp.minimum(n_used + is_alloc.sum(dtype=jnp.int32), g)
    # reset: fresh table from post-reset allocations only
    scatter_f = jnp.where(is_alloc_f & (rank_f < g) & any_reset, rank_f, g)
    keys_f = compact_set_at(jnp.zeros_like(table_keys), scatter_f, batch_keys)
    used_f = compact_set_at(jnp.zeros_like(used), scatter_f, ones_b)
    n_f = jnp.minimum(is_alloc_f.sum(dtype=jnp.int32), g)

    new_keys = jnp.where(any_reset, keys_f, keys_old)
    new_used = jnp.where(any_reset, used_f, used_old)
    new_n = jnp.where(any_reset, n_f, n_old)
    if free is not None:
        # a reset empties the table: its fresh allocations count up from 0,
        # which is what an empty stack's top holds
        grp.free = jnp.where(any_reset, free_stack(g), free)
    if index is not None:
        with jax.named_scope("group.probe"):
            # the heads whose key the new table holds and the old index did
            # not: the step's allocations, or, behind a reset, which empties
            # the index as it empties the table, the fresh table's keys
            new_old = seg_start & sa & (ts_s < 0) & (sn_s < g)
            if has_reset:
                new_s = jnp.where(
                    any_reset, seg_start & sa & post_s & (rf_s < g), new_old)
                given_s = jnp.where(any_reset, rf_s, sn_s)
                index = {**index, "slot": jnp.where(
                    any_reset, np.int32(-1), index["slot"])}
                empty_h = tuple(
                    jnp.where(any_reset, np.uint32(0xFFFFFFFF), e) for e in empty_h)
            else:
                new_s, given_s = new_old, sn_s
            (slot_h,) = permute_by(h_of, jnp.where(new_s, given_s, np.int32(-1)))
            written, put_h, no_lane = _index_insert(
                index, bucket_h, lo_h, hi_h, empty_h, slot_h)
            # where each row's key lies in the index now, for `release_index`
            none = np.int32(nb * lanes)
            (put_s,) = permute_by(pos_h, put_h)
            put_s = segmented_carry(put_s, seg_start)
            found_at = jnp.where(at_s >= 0, at_s, none)
            grp.lane_s = jnp.where(
                sa, jnp.where(any_reset | (ts_s < 0), put_s, found_at), none)
            # an index built afresh behind a reset holds every key again
            grp.index = {
                **written,
                "full": no_lane | (index["full"] & ~any_reset),
                "tiles": index["tiles"] + trips.astype(jnp.int64),
            }
    return new_keys, new_used, new_n, slot, grp, overflow


def _kept(grp: SortedGroups, value_s: jnp.ndarray, ident):
    """What the writer rows write: `value_s`, or the lane's identity on the
    rows whose group the step freed."""
    if grp.freed_s is None:
        return value_s
    return jnp.where(grp.freed_s, np.asarray(ident, value_s.dtype), value_s)


def keyed_running_sum(
    contrib: jnp.ndarray,  # [B], 0 on inactive rows
    grp: SortedGroups,
    carry: jnp.ndarray,    # [G]
    rows: bool = False,
):
    """Per-event running sum within each group; returns ([B] run, [G] carry').

    `rows`: this lane counts the rows of the window that each group holds
    (+1 a CURRENT row, -1 an EXPIRED one), for a table that takes slots
    back: a group whose writer row reads none is freed (`grp.freed_s`).

    The (era, key) segmentation bounds contributions to same-key rows j <= i
    with no reset in between — exactly the reference's per-key running state
    with RESET zeroing every group. Per row the value is the segmented
    running sum plus the group's carried value, in one add, whichever way
    the carried value was fetched (`SortedGroups.carry_read`)."""
    (contrib_s,) = grp.to_sorted(contrib)
    run_s = segmented_cumsum(contrib_s, grp.seg_start)
    base = jnp.where(grp.reset.any(), jnp.zeros_like(carry), carry)
    carried_s = grp.carried(carry, jnp.zeros((), carry.dtype))
    # in the final era each live group is exactly one sorted segment, so its
    # carry is base + the segment END's running sum — one unique writer per
    # group, compacted so the scatter costs G updates (B-update scatters and
    # 64-bit scatter reductions both serialize on the TPU scalar core). A
    # writer's base is what its segment carried: the group's value where no
    # reset came (era 0 is then the final era), zero behind one
    full_s = run_s + carried_s
    (run,) = grp.from_sorted(full_s)
    if rows:
        grp.freed_s = (grp.writer_s < carry.shape[0]) & (full_s <= 0)
    return run, compact_set_at(
        base, grp.writer_s, _kept(grp, full_s.astype(carry.dtype), 0)
    )


def keyed_running_extreme(
    values: jnp.ndarray,
    active: jnp.ndarray,
    grp: SortedGroups,
    carry: jnp.ndarray,  # [G]
    is_min: bool,
    forever: bool = False,
):
    """Per-event running min/max within each group (no removal). `forever`
    takes no notice of resets: every row of a live slot starts from the
    carried value, every segment end writes it, nothing is cleared."""
    g = carry.shape[0]
    ident = extreme_identity(values.dtype, is_min)
    op = jnp.minimum if is_min else jnp.maximum
    masked = jnp.where(active, values, ident)
    (masked_s,) = grp.to_sorted(masked)
    run_s = segmented_cum_extreme(masked_s, grp.seg_start, is_min)
    carried_s = grp.carried(carry, ident, every_era=forever)
    (run,) = grp.from_sorted(op(run_s, carried_s))
    # one unique writer per live group (its final-era segment end), compacted
    # — see keyed_running_sum
    if forever:
        base, writer_s = carry, jnp.where(grp.seg_end, grp.slot_s, np.int32(g))
    else:
        base = jnp.where(grp.reset.any(), jnp.full_like(carry, ident), carry)
        writer_s = grp.writer_s
    newval = _kept(grp, op(carried_s, run_s).astype(carry.dtype), ident)
    return run, compact_set_at(base, writer_s, newval)


def keep_last_in_sorted(
    grp: SortedGroups, kind: jnp.ndarray, valid: jnp.ndarray
) -> jnp.ndarray:
    """[B] bool: valid rows that are the LAST valid row of their
    (segment, kind) — the batch-mode group-by collapse, computed inside an
    EXISTING SortedGroups view instead of re-lexsorting (the segments of
    `grp` are exactly the (reset-era, key) groups; `kind` subdivides them).
    One reverse segmented max per kind lane, no new sort.

    Precondition: `valid` is pre-masked to CURRENT|EXPIRED rows — other kinds
    would silently compete in the EXPIRED lane."""
    from siddhi_tpu.core.event import KIND_CURRENT, KIND_EXPIRED

    b = valid.shape[0]
    idx = jnp.arange(b, dtype=jnp.int32)
    sv, sk = grp.to_sorted(valid, kind.astype(jnp.int32))
    rev_start = grp.seg_end[::-1]

    def last_of(kbit):
        marked = jnp.where(sv & (sk == kbit), grp.perm, np.int32(-1))
        return segmented_cum_extreme(marked[::-1], rev_start, is_min=False)[::-1]

    last_cur = last_of(int(KIND_CURRENT))
    last_exp = last_of(int(KIND_EXPIRED))
    last_for_row = jnp.where(sk == int(KIND_CURRENT), last_cur, last_exp)
    (lfr,) = grp.from_sorted(last_for_row)
    return valid & (lfr == idx)


def keep_last_per_group(cols: list[jnp.ndarray], valid: jnp.ndarray) -> jnp.ndarray:
    """[B] bool: valid rows that are the LAST valid row of their group, where a
    group is the tuple of `cols` values (reference: QuerySelector
    processInBatchGroupBy — the map keeps one entry per key, last write wins).
    O(B log B): sort by group, find each group's last valid row index."""
    b = valid.shape[0]
    idx = jnp.arange(b, dtype=jnp.int32)
    # one payload sort: cols as keys (idx last for a total order), valid rides
    sorted_ops = jax.lax.sort(
        (*cols, idx, valid), num_keys=len(cols) + 1, is_stable=False
    )
    scols, perm, sv = sorted_ops[: len(cols)], sorted_ops[-2], sorted_ops[-1]
    boundary = jnp.zeros((b,), jnp.bool_).at[0].set(True)
    for c in scols:
        boundary = boundary | jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), c[1:] != c[:-1]]
        )
    # last valid original-row index per segment: reverse segmented cummax of
    # where(valid, original row, -1)
    marked = jnp.where(sv, perm, np.int32(-1))
    rev = marked[::-1]
    # a reversed segment starts where the forward segment ENDS
    seg_end = jnp.concatenate([boundary[1:], jnp.ones((1,), jnp.bool_)])
    rev_start = seg_end[::-1]
    last_in_seg = segmented_cum_extreme(rev, rev_start, is_min=False)[::-1]
    (last_back,) = permute_by(perm, last_in_seg)
    return valid & (last_back == idx)
