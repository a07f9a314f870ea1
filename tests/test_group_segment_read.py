"""The group-by's per-group reads, once per segment of the sorted view
(`ops/group.py`, PR 29), against the row-gather formulation they replaced,
bit for bit. The reference below is `assign_slots`, `keyed_running_sum` and
`keyed_running_extreme` as they stood before PR 29: every row gathers its
segment head's slot and its group's carried value for itself, and finds its
key in the table by the dense `[B, G]` compare that `probe_table`'s
sort-merge replaced in PR 39 (`MERGE_CASES`)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from siddhi_tpu.ops import group
from siddhi_tpu.ops.group import SortedGroups, permute_by
from siddhi_tpu.ops.prefix import (
    extreme_identity,
    last_reset_index,
    segmented_carry,
    segmented_cum_extreme,
    segmented_cumsum,
)
from siddhi_tpu.ops.scatter import compact_set_at


# ---- the reference: one gather per row of the flow -------------------------

def ref_assign_slots(table_keys, used, n_used, batch_keys, active, reset):
    g = table_keys.shape[0]
    b = batch_keys.shape[0]
    idx = jnp.arange(b, dtype=jnp.int32)
    glr = jnp.max(jnp.where(reset, idx, np.int32(-1)))
    any_reset = glr >= 0
    post = idx > glr
    era = jnp.cumsum(reset.astype(jnp.int32))
    inact = (~active).astype(jnp.int32)
    _, se, sk, perm, sa = jax.lax.sort(
        (inact, era, batch_keys, idx, active), num_keys=4, is_stable=False
    )
    seg_start = jnp.concatenate([
        jnp.ones((1,), jnp.bool_),
        (sk[1:] != sk[:-1]) | (se[1:] != se[:-1]) | (sa[1:] != sa[:-1]),
    ])
    (inv,) = permute_by(perm, idx)
    grp = SortedGroups(perm=perm, inv=inv, seg_start=seg_start)
    (first,) = grp.from_sorted(segmented_carry(perm, seg_start))

    eq_t = used[None, :] & (table_keys[None, :] == batch_keys[:, None])
    in_t = eq_t.any(axis=1) & active
    t_slot = jnp.argmax(eq_t, axis=1).astype(jnp.int32)
    is_alloc = active & ~in_t & (first == idx)
    alloc_rank = (jnp.cumsum(is_alloc.astype(jnp.int32)) - is_alloc).astype(jnp.int32)
    slot_new = n_used + alloc_rank
    old_overflow = (jnp.where(is_alloc, slot_new, 0) >= g).any()
    old_slot = jnp.where(
        in_t, t_slot, jnp.where(slot_new[first] < g, slot_new[first], g))
    old_slot = jnp.where(active, old_slot, np.int32(g)).astype(jnp.int32)

    post_active = active & post
    is_alloc_f = post_active & (first == idx)
    rank_f = (jnp.cumsum(is_alloc_f.astype(jnp.int32)) - is_alloc_f).astype(jnp.int32)
    fresh_overflow = (jnp.where(is_alloc_f, rank_f, 0) >= g).any()
    fresh_slot = jnp.where(
        post_active & (rank_f[first] < g), rank_f[first], g).astype(jnp.int32)
    slot = jnp.where(any_reset & post, fresh_slot, old_slot)
    slot = jnp.where(active, slot, np.int32(g))
    overflow = jnp.where(any_reset, fresh_overflow, old_overflow)

    ones_b = jnp.ones((b,), jnp.bool_)
    scatter_old = jnp.where(is_alloc & (slot_new < g) & ~any_reset, slot_new, g)
    keys_old = compact_set_at(table_keys, scatter_old, batch_keys)
    used_old = compact_set_at(used, scatter_old, ones_b)
    n_old = jnp.minimum(n_used + is_alloc.sum(dtype=jnp.int32), g)
    scatter_f = jnp.where(is_alloc_f & (rank_f < g) & any_reset, rank_f, g)
    keys_f = compact_set_at(jnp.zeros_like(table_keys), scatter_f, batch_keys)
    used_f = compact_set_at(jnp.zeros_like(used), scatter_f, ones_b)
    n_f = jnp.minimum(is_alloc_f.sum(dtype=jnp.int32), g)
    return (jnp.where(any_reset, keys_f, keys_old),
            jnp.where(any_reset, used_f, used_old),
            jnp.where(any_reset, n_f, n_old), slot, grp, overflow)


def _ref_writers(grp, slot, post):
    seg_end = jnp.concatenate([grp.seg_start[1:], jnp.ones((1,), jnp.bool_)])
    slot_s, post_s = grp.to_sorted(slot, post)
    return seg_end & post_s, slot_s


def ref_running_sum(contrib, grp, reset, carry, slot):
    g = carry.shape[0]
    (contrib_s,) = grp.to_sorted(contrib)
    run_s = segmented_cumsum(contrib_s, grp.seg_start)
    (run,) = grp.from_sorted(run_s)
    lr = last_reset_index(reset)
    gathered = jnp.where(slot < g, carry[jnp.clip(slot, 0, g - 1)], 0)
    run = run + jnp.where(lr < 0, gathered, jnp.zeros_like(gathered))
    post = jnp.arange(contrib.shape[0], dtype=jnp.int32) > lr[-1]
    base = jnp.where(reset.any(), jnp.zeros_like(carry), carry)
    writer, slot_s = _ref_writers(grp, slot, post)
    writer = writer & (slot_s < g)
    newval = (
        jnp.where(slot_s < g, base[jnp.clip(slot_s, 0, g - 1)], 0) + run_s
    ).astype(carry.dtype)
    return run, compact_set_at(base, jnp.where(writer, slot_s, g), newval)


def ref_running_extreme(values, active, grp, reset, carry, slot, is_min,
                        forever=False):
    if forever:  # the aggregator handed in an all-false reset lane of its own
        reset = jnp.zeros_like(reset)
    g = carry.shape[0]
    ident = extreme_identity(values.dtype, is_min)
    op = jnp.minimum if is_min else jnp.maximum
    (masked_s,) = grp.to_sorted(jnp.where(active, values, ident))
    run_s = segmented_cum_extreme(masked_s, grp.seg_start, is_min)
    (run,) = grp.from_sorted(run_s)
    lr = last_reset_index(reset)
    gathered = jnp.where(
        (slot < g) & (lr < 0), carry[jnp.clip(slot, 0, g - 1)], ident)
    run = op(run, gathered)
    post = jnp.arange(values.shape[0], dtype=jnp.int32) > lr[-1]
    base = jnp.where(reset.any(), jnp.full_like(carry, ident), carry)
    writer, slot_s = _ref_writers(grp, slot, post)
    writer = writer & (slot_s < g)
    newval = op(
        jnp.where(slot_s < g, base[jnp.clip(slot_s, 0, g - 1)], ident), run_s
    ).astype(carry.dtype)
    return run, compact_set_at(base, jnp.where(writer, slot_s, g), newval)


# ---- one selector step, both ways ------------------------------------------

def init(g):
    return {
        "keys": jnp.zeros((g,), jnp.int64), "used": jnp.zeros((g,), jnp.bool_),
        "n": jnp.zeros((), jnp.int32),
        "f32": jnp.zeros((g,), jnp.float32), "i64": jnp.zeros((g,), jnp.int64),
        "min": jnp.full((g,), np.inf, jnp.float32),
        "max": jnp.full((g,), np.iinfo(np.int64).min, jnp.int64),
        "forever": jnp.full((g,), -np.inf, jnp.float32),
    }


def step(ops, state, batch):
    """`select sum(x), count(), min(x), max(n), maxForever(x) group by key`
    over one batch: (state', per-row lanes, the view)."""
    assign, running_sum, running_extreme = ops
    keys, x, n, sign, reset = (batch[k] for k in ("key", "x", "n", "sign", "reset"))
    active = sign != 0
    tk, tu, tn, slot, grp, overflow = assign(
        state["keys"], state["used"], state["n"], keys, active, reset)
    f32, c_f32 = running_sum(
        jnp.where(active, x * sign.astype(jnp.float32), 0.0), grp, reset,
        state["f32"], slot)
    i64, c_i64 = running_sum(sign.astype(jnp.int64), grp, reset, state["i64"], slot)
    current = sign > 0
    mn, c_min = running_extreme(x, current, grp, reset, state["min"], slot, True)
    mx, c_max = running_extreme(n, current, grp, reset, state["max"], slot, False)
    fv, c_fv = running_extreme(
        x, current, grp, reset, state["forever"], slot, False, forever=True)
    new = {"keys": tk, "used": tu, "n": tn, "f32": c_f32, "i64": c_i64,
           "min": c_min, "max": c_max, "forever": c_fv}
    rows = {"slot": slot, "overflow": overflow, "f32": f32, "i64": i64,
            "min": mn, "max": mx, "forever": fv}
    return new, rows, grp


def new_running_sum(contrib, grp, reset, carry, slot):
    return group.keyed_running_sum(contrib, grp, carry)


def new_running_extreme(values, active, grp, reset, carry, slot, is_min,
                        forever=False):
    return group.keyed_running_extreme(
        values, active, grp, carry, is_min, forever=forever)


def new_assign_slots(table_keys, used, n_used, batch_keys, active, reset):
    return group.assign_slots(
        table_keys, used, n_used, batch_keys, active, reset=reset)


NEW = (new_assign_slots, new_running_sum, new_running_extreme)
REF = (ref_assign_slots, ref_running_sum, ref_running_extreme)


def make_batch(rng, b, n_keys, resets=(), active_share=0.8, owner=None,
               pool=None):
    """`pool`: the int64 keys the rows draw from, where the case names them."""
    if pool is None:
        keys = rng.integers(1, n_keys + 1, b).astype(np.int64) * 1_000_003
    else:
        keys = np.asarray(pool, np.int64)[rng.integers(0, len(pool), b)]
    sign = rng.choice([1, 1, 1, -1], b).astype(np.int32)
    sign[rng.random(b) >= active_share] = 0
    if owner is not None:  # the owner mask of @app:shard(axis='keys')
        sign[(keys // 1_000_003) % 4 != owner] = 0
    reset = np.zeros(b, bool)
    for at in resets:
        reset[at] = True
        sign[at] = 0  # a RESET row carries no key
    x = rng.exponential(60.0, b).astype(np.float32).round(3)
    x[rng.random(b) < 0.02] = 0.0
    n = rng.integers(-2**40, 2**40, b).astype(np.int64)
    return {"key": keys, "x": x, "n": n, "sign": sign, "reset": reset}


def same_bits(a, b, what):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape, what
    assert a.tobytes() == b.tobytes(), what


def compare(tree_a, tree_b, what):
    la, ta = jax.tree_util.tree_flatten(tree_a)
    lb, tb = jax.tree_util.tree_flatten(tree_b)
    assert ta == tb, what
    for i, (a, b) in enumerate(zip(la, lb)):
        same_bits(a, b, f"{what}: leaf {i} of {ta}")


def run_batches(b, g, batches, table=None):
    """Three consecutive batches through both formulations, jitted, the state
    of each carried on its own; every lane and every table compared (and the
    table against `table`, a `TableByHand`, where one is given)."""
    new_step = jax.jit(lambda s, bt: step(NEW, s, bt)[:2])
    ref_step = jax.jit(lambda s, bt: step(REF, s, bt)[:2])
    s_new, s_ref = init(g), init(g)
    for i, bt in enumerate(batches):
        s_new, rows_new = new_step(s_new, bt)
        s_ref, rows_ref = ref_step(s_ref, bt)
        compare(rows_new, rows_ref, f"rows of batch {i}")
        compare(s_new, s_ref, f"state after batch {i}")
        if table is not None:
            table.step(bt)
            table.check(s_new, rows_new, f"table after batch {i}")
    return s_new, rows_new


class TableByHand:
    """The key table as a Python list in first-appearance order: what
    `keys[:n]`, `used`, `n` and `overflow` must read after each batch,
    reckoned from the rows alone (no probe of either kind)."""

    def __init__(self, g):
        self.g, self.keys, self.overflow, self.history = g, [], False, []

    def step(self, bt):
        active, reset = bt["sign"] != 0, bt["reset"]
        last = int(np.flatnonzero(reset).max()) if reset.any() else -1
        if last >= 0:
            self.keys = []
        seen = set(self.keys)
        arrive = bt["key"][last + 1:][active[last + 1:]].tolist()
        new = [k for k in dict.fromkeys(arrive) if k not in seen]
        self.overflow = len(self.keys) + len(new) > self.g
        self.keys = (self.keys + new)[:self.g]
        # (n, rows that found their key in the table, active rows, overflow)
        self.history.append((len(self.keys), sum(k in seen for k in arrive),
                             len(arrive), self.overflow))

    def check(self, state, rows, what):
        n = len(self.keys)
        assert int(state["n"]) == n and bool(rows["overflow"]) == self.overflow, what
        assert np.asarray(state["keys"])[:n].tolist() == self.keys, what
        assert np.asarray(state["used"]).tolist() == [True] * n + [False] * (self.g - n), what


CASES = {
    # name: (B, G, distinct keys, resets of batch 0 / 1 / 2, active share, owner)
    "no_reset": (1024, 64, 40, ((), (), ()), 0.8, None),
    "one_reset": (1024, 64, 40, ((), (300,), ()), 0.8, None),
    "several_resets": (1024, 64, 40, ((5, 700), (0, 511, 1023), (512,)), 0.8, None),
    "reset_first_and_last_row": (512, 64, 30, ((0,), (511,), (0, 511)), 0.9, None),
    "overflow_lane": (1024, 32, 90, ((), (), ()), 0.8, None),
    "overflow_behind_resets": (1024, 32, 90, ((), (400,), (100, 900)), 0.8, None),
    "mostly_inactive": (1024, 64, 40, ((), (200,), ()), 0.1, None),
    "owner_mask_keeps_a_quarter": (2048, 128, 100, ((), (), (1000,)), 0.9, 1),
    "nothing_active": (512, 64, 40, ((), (17,), ()), 0.0, None),
    "row_fallback_b_below_g": (64, 128, 40, ((), (20,), ()), 0.8, None),
    "row_fallback_b_equals_g": (128, 128, 60, ((), (), (64,)), 0.8, None),
    "off_lane_multiple_length": (1500, 64, 50, ((), (750,), ()), 0.8, None),
    "one_key": (1024, 64, 1, ((), (512,), ()), 0.8, None),
}


I64 = np.iinfo(np.int64)
_A = np.arange(1, 41, dtype=np.int64)

# What the sort-merge probe has to get right where the dense compare could
# not go wrong (PR 39): an unused entry holds key 0 and 0 is a legal key; a
# 64-bit key is compared as two 32-bit words on the chip; the merged sort is
# B + G rows long. name: (B, G, the keys each of the batches draws from,
# resets of batch 0 / 1 / 2, active share, what the table must show:
# "all_hit" / "none_hit" of batches 1 and 2's rows, "full" from batch 0 on)
MERGE_CASES = {
    "merge_key_zero_into_an_empty_table":
        (1024, 64, ([0, 5, 9, -3],) * 3, ((), (), (300,)), 0.8, None),
    "merge_key_zero_into_a_part_used_table":
        (1024, 64, ([5, 9, -3], [0, 5, 11], [0, 9, 12]), ((), (), ()), 0.8, None),
    "merge_key_zero_behind_a_reset_while_the_old_table_holds_it":
        (1024, 64, ([0, 5, 9], [0, 5, 11], [0, 9, 12]), ((), (400,), (0, 1000)), 0.8, None),
    "merge_int64_min_and_max_beside_each_other":
        (1024, 64, ([I64.min, I64.min + 1, I64.max - 1, I64.max, -1, 0, 1],) * 3,
         ((), (), (512,)), 0.8, None),
    "merge_keys_that_differ_in_their_high_words_only":
        (1024, 64, (((_A - 20) << 32) | 5,) * 3, ((), (700,), ()), 0.8, None),
    "merge_keys_that_differ_in_their_low_words_only":
        (1024, 64, ((7 << 32) | (_A - 20),) * 3, ((), (700,), ()), 0.8, None),
    "merge_keys_whose_words_are_swapped":
        (1024, 64, (np.concatenate([_A[:20] << 32, _A[:20]]),) * 3, ((), (), ()), 0.8, None),
    "merge_table_full_before_the_batch":
        (1024, 32, (_A[:32], _A, _A), ((), (), (600,)), 0.9, "full"),
    "merge_table_fills_inside_the_batch":
        (1024, 32, (_A[:20], _A, _A[10:]), ((), (), ()), 0.9, None),
    "merge_every_row_hits_the_table":
        (1024, 64, (_A, _A, _A), ((), (), ()), 0.8, "all_hit"),
    "merge_no_row_hits_the_table":
        (1024, 128, (_A, _A + 40, _A + 80), ((), (), ()), 0.8, "none_hit"),
    "merge_b_plus_g_one_below_a_power_of_two":
        (959, 64, (_A,) * 3, ((), (500,), ()), 0.8, None),
    "merge_b_plus_g_a_power_of_two":
        (960, 64, (_A,) * 3, ((), (500,), ()), 0.8, None),
    "merge_b_plus_g_one_above_a_power_of_two":
        (961, 64, (_A,) * 3, ((), (500,), ()), 0.8, None),
    "merge_b_below_g": (48, 128, (_A, _A + 20, _A), ((), (), (20,)), 0.8, None),
    # 2 x 32,768 rows behind the window, 2,125 plugs; two batches, since the
    # reference's matrix is 268 M compares a batch
    "merge_the_plug_cells_own_shapes":
        (65536, 4096, (np.arange(2125, dtype=np.int64) * 7 - 5000,) * 2,
         ((), (40000,)), 0.5, None),
}


@pytest.mark.parametrize("case", sorted(CASES) + sorted(MERGE_CASES))
def test_segment_read_equals_the_row_gather_bit_for_bit(case):
    if case in MERGE_CASES:
        return check_merge_case(case)
    b, g, n_keys, resets, share, owner = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case) + 2_900_000_000)
    batches = [make_batch(rng, b, n_keys, r, share, owner) for r in resets]
    state, rows = run_batches(b, g, batches)
    if "overflow" in case:
        assert bool(rows["overflow"]) and int(state["n"]) == g
    elif share > 0 and batches[-1]["reset"][-1] == 0:
        assert 0 < int(state["n"]) <= min(g, n_keys)


def check_merge_case(case):
    b, g, pools, resets, share, shows = MERGE_CASES[case]
    rng = np.random.default_rng(sorted(MERGE_CASES).index(case) + 3_900_000_000)
    batches = [make_batch(rng, b, 0, r, share, pool=p)
               for r, p in zip(resets, pools)]
    for p, bt in zip(pools, batches):
        if len(p) <= b // 16:  # every key the case names did come, active
            assert set(np.asarray(p).tolist()) <= set(bt["key"][bt["sign"] != 0].tolist())
    table = TableByHand(g)
    run_batches(b, g, batches, table=table)
    n, hits, active, overflow = zip(*table.history)
    if shows == "all_hit":
        assert hits[1:] == active[1:] and n == (len(pools[0]),) * 3
    elif shows == "none_hit":
        assert hits == (0, 0, 0) and n == (40, 80, 120)
    elif shows == "full":  # old keys keep their slots, new ones find none
        assert n == (g, g, g) and overflow[1] and 0 < hits[1] < active[1]
    elif "fills_inside" in case:
        assert n[0] < g == n[1] and overflow[1:] == (True, True)
    if "key_zero" in case:
        assert 0 in table.keys


@pytest.mark.parametrize("b,g,want", [
    (1024, 64, "segment"), (65, 64, "segment"), (64, 64, "row"), (32, 64, "row")])
def test_the_form_is_chosen_from_the_shapes(b, g, want):
    rng = np.random.default_rng(b * g)
    bt = make_batch(rng, b, 20)
    _, _, grp = step(NEW, init(g), bt)
    assert grp.carry_read == want
    assert (grp.head_pos is not None) == (want == "segment")
    # the partition table's use, without a reset lane: the same choice
    *_, plain, _ = group.assign_slots(
        init(g)["keys"], init(g)["used"], init(g)["n"],
        jnp.asarray(bt["key"]), jnp.asarray(bt["sign"] != 0))
    assert plain.carry_read == want and not bool(plain.reset.any())


@pytest.mark.parametrize("resets", [((), (), ()), ((), (100, 600), (0,))],
                         ids=["no_reset", "resets"])
def test_under_vmap_over_four_partitions(resets):
    """The partition path: one selector step per partition lane, vmapped."""
    b, g, p = 1024, 64, 4
    rng = np.random.default_rng(2_900_000_777 + len(resets[1]))
    stack = lambda trees: jax.tree_util.tree_map(lambda *x: jnp.stack(x), *trees)
    s_new = s_ref = stack([init(g)] * p)
    new_step = jax.jit(jax.vmap(lambda s, bt: step(NEW, s, bt)[:2]))
    ref_step = jax.jit(jax.vmap(lambda s, bt: step(REF, s, bt)[:2]))
    for i, r in enumerate(resets):
        bt = stack([make_batch(rng, b, 30 + 10 * k, r, 0.7) for k in range(p)])
        s_new, rows_new = new_step(s_new, bt)
        s_ref, rows_ref = ref_step(s_ref, bt)
        compare(rows_new, rows_ref, f"rows of batch {i}")
        compare(s_new, s_ref, f"state after batch {i}")
    assert np.asarray(s_new["n"]).min() > 0


def test_keep_last_in_sorted_finds_the_view_unchanged():
    """Batch windows collapse inside the same view: its permutation and its
    segments are the parent's."""
    rng = np.random.default_rng(2_900_000_999)
    bt = make_batch(rng, 1024, 40, (100, 600))
    _, _, new = step(NEW, init(64), bt)
    _, _, ref = step(REF, init(64), bt)
    for lane in ("perm", "inv", "seg_start"):
        same_bits(getattr(new, lane), getattr(ref, lane), lane)
    kind = jnp.asarray(np.where(bt["sign"] < 0, 1, 0).astype(np.int32))
    valid = jnp.asarray(bt["sign"] != 0)
    same_bits(group.keep_last_in_sorted(new, kind, valid),
              group.keep_last_in_sorted(ref, kind, valid), "keep_last")


def test_no_flow_length_gather_where_the_segment_form_is_taken():
    b, g = 4096, 256
    bt = make_batch(np.random.default_rng(29), b, 100, (1000,))
    text = jax.jit(lambda s, x: step(NEW, s, x)[:2]).lower(init(g), bt).as_text()
    ref_text = jax.jit(lambda s, x: step(REF, s, x)[:2]).lower(init(g), bt).as_text()
    # as traced: the head's slot twice for each table, then the carried
    # value and the writer's base for each of the five aggregator lanes;
    # maxForever, whose heads may outnumber G, keeps one
    assert len(flow_gathers(ref_text, {b})) == 4 + 5 * 2
    assert flow_gathers(text, {b}) == [f"{b}xf32"]


def flow_gathers(stablehlo: str, rows) -> list:
    """Result types of the `stablehlo.gather`s that give one value per row of
    a flow of one of the lengths in `rows`."""
    import re

    found = re.findall(
        r'stablehlo\.gather"?\(.*?-> tensor<(\d+)x([a-z0-9]+)>', stablehlo)
    return [f"{n}x{t}" for n, t in found if int(n) in rows]


def test_status_names_the_probe_of_the_group_table_and_of_the_partition_table():
    """`queries.<q>.group.probe` and `queries.<q>.partition.probe` of a
    deployed app, a group-by outside a partition and one inside."""
    from siddhi_tpu import SiddhiManager

    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime("""
    @app:batch(size='64')
    @app:partitionCapacity(size='8')
    @app:groupCapacity(size='16')
    define stream S (k int, g int, v float);
    @info(name='plain') from S select g, sum(v) as s group by g insert into Plain;
    partition with (k of S) begin
    @info(name='inner') from S#window.length(4) select k, g, sum(v) as s group by g
    insert into Out;
    end;
    """)
    got = []
    rt.add_callback("Out", got.extend)
    rt.start()
    try:
        i = np.arange(10, dtype=np.int32)
        rt.get_input_handler("S").send_columns(
            i.astype(np.int64), {"k": i % 3, "g": i % 2, "v": np.ones(10, np.float32)})
        queries = rt.snapshot_status()["queries"]
    finally:
        rt.shutdown()
        mgr.shutdown()
    assert len(got) == 10
    # no window ahead of `plain`: its table takes no slot back and counts
    # neither freed slots nor lost rows; `inner` stands behind one, and sum
    # keeps no count of rows, so its table counts them in a lane of its own
    assert queries["plain"]["group"] == {
        "capacity": 16, "carry_read": "segment", "probe": "merge",
        "reclaim": "none", "used": 2, "freed": 0, "overflow_rows": None}
    assert queries["inner"]["group"]["reclaim"] == "own_lane"
    assert queries["inner"]["group"]["overflow_rows"] == 0
    assert "partition" not in queries["plain"]
    assert queries["inner"]["group"]["probe"] == "merge"
    assert queries["inner"]["partition"]["probe"] == "merge"
    assert queries["inner"]["partition"]["used"] == 3
