"""`nexmark-q5-hot-items`: its generator against the rules of the source's
(Apache Beam's `nexmark` generator under `NexmarkConfiguration`'s defaults),
the numbers its configuration states against the generated stream, its plain
reference against the definition counted out by brute force, and the
controls: the reference with its counts in bfloat16, and a key table that
never forgets (what the engine's did before PR 40), both of which have to
come out as not correct under the configuration's own limits."""

import json

import numpy as np
import pytest

import harness
from conftest import BENCH

CONFIG = "nexmark-q5-hot-items"


def load():
    cdir = BENCH / "configs" / CONFIG
    return (harness.load_module(cdir / "gen.py"),
            harness.load_module(cdir / "reference.py"),
            json.loads((cdir / "config.json").read_text()))


def bids(seed: int, n: int, lo: int = 0):
    """(gen, event times, the bids lo..lo+n-1 of a stream whose pool is n
    rows): rows beyond the pool replay its draws under new indices."""
    gen, ref, _ = load()
    stream = harness.Stream(gen, ref, seed, n)
    ts, cols = stream.columns(lo, lo + n)
    return gen, ts, cols


# ---- the generator against the source's rules --------------------------------

def test_proportions_rate_and_time_from_the_row_index():
    gen, ts, cols = bids(3, 46 * 4000)
    n = len(ts)
    event = gen.event_number(0, n)
    # 46 bids of every 50 events, behind 1 person and 3 auctions
    assert (event % 50 >= 4).all() and len(np.unique(event)) == n
    assert (np.diff(event)[np.arange(1, n) % 46 != 0] == 1).all()
    assert (np.diff(event)[np.arange(1, n) % 46 == 0] == 5).all()
    # events 100 us apart: 10 a millisecond, 9,200 bids a second
    assert (cols["dateTime"] == gen.BASE_TIME_MS + event // 10).all()
    assert (cols["dateTime"] == ts).all() and (np.diff(ts) >= 0).all()
    second = (ts - ts[0]) // 1000
    assert (np.bincount(second)[:-1] == 9200).all()
    assert set(cols) == {"auction", "bidder", "price", "dateTime"}
    assert all(v.dtype == np.int64 for v in cols.values())


def test_hot_auction_takes_half_the_bids_and_moves_every_100_auctions():
    gen, ts, cols = bids(5, 46 * 20000)
    n = len(ts)
    last = (gen.event_number(0, n) // 50) * 3 + 2          # newest auction
    hot = gen.FIRST_AUCTION_ID + (last // 100) * 100
    on_hot = cols["auction"] == hot
    assert 0.49 < on_hot.mean() < 0.52   # half, and the cold bids that hit it
    # the hot auction is a multiple of 100 and moves on by 100: every 100
    # auctions = 33.3 epochs = 1,667 events = a sixth of a second
    moves = np.flatnonzero(np.diff(hot))
    assert (np.diff(hot)[moves] == 100).all()
    assert np.allclose(np.diff(ts[moves]), 1000 / 6, atol=6)
    # the others fall evenly on the last 100 auctions and 10 of lead
    cold = cols["auction"][~on_hot] - gen.FIRST_AUCTION_ID - last[~on_hot]
    assert cold.min() == -100 and cold.max() == 10
    counts = np.bincount(cold[n // 4:] + 100, minlength=111)
    assert counts.min() > 0.8 * counts.mean()
    # a hot auction takes about 770 bids (half of a sixth of a second's),
    # another about 8 (111 auctions share the other half while in flight)
    per = np.bincount(cols["auction"] - cols["auction"].min())
    per = per[per > 0][200:-200]
    assert 700 < np.sort(per)[-20:].mean() < 840
    assert 6 < np.median(per) < 10


def test_bidders_and_prices():
    gen, ts, cols = bids(7, 46 * 20000)
    n = len(ts)
    people = gen.event_number(0, n) // 50                   # newest person
    hot = gen.FIRST_PERSON_ID + (people // 100) * 100 + 1
    assert 0.74 < (cols["bidder"] == hot).mean() < 0.77     # hotBiddersRatio 4
    cold = cols["bidder"][cols["bidder"] != hot] - gen.FIRST_PERSON_ID
    span = people[cols["bidder"] != hot] - cold
    assert span.min() >= -10 and span.max() <= 1000
    # price = round(10^(6u) * 100): from a dollar to a million, log-uniform
    assert cols["price"].min() >= 100 and cols["price"].max() <= 10**8
    assert 2.9 < np.log10(cols["price"] / 100).mean() < 3.1


def beam_bid(gen, i: int, draws: dict, j: int):
    """Bid i of the stream by the source's rules, one bid at a time, from
    the draws of pool row j: (auction, bidder, dateTime)."""
    epoch, place = divmod(i, 46)
    event = epoch * 50 + 4 + place            # behind 1 person, 3 auctions
    last_auction, last_person = epoch * 3 + 2, epoch
    if draws["hot_auction"][j]:
        auction = last_auction // 100 * 100
    else:
        first = max(last_auction - 100, 0)
        auction = first + int(draws["auction_u"][j] * (last_auction - first + 11))
    if draws["hot_bidder"][j]:
        bidder = last_person // 100 * 100 + 1
    else:
        active = min(last_person + 1, 1000)
        bidder = last_person + 1 - active + int(draws["bidder_u"][j] * (active + 10))
    return (gen.FIRST_AUCTION_ID + auction, gen.FIRST_PERSON_ID + bidder,
            gen.BASE_TIME_MS + event // 10)


@pytest.mark.parametrize("lo", [0, 46 * 30 + 5, 46 * 999 + 40, 46 * 1000,
                                46 * 2600 - 3, 2**31 + 12345],
                         ids=["start", "spans_grow", "into_steady", "steady",
                              "pool_wraps", "far"])
def test_every_bid_as_the_rules_make_it_one_by_one(lo):
    """The generator works per epoch and, once the spans are full, from
    places laid out with the pool; a loop over the bids, from the draws
    alone, gives the same rows: where the spans still grow, across the
    change to full spans, across the pool's end and far along the stream."""
    gen, ref, _ = load()
    pool = 46 * 1300
    stream = harness.Stream(gen, ref, 31, pool)
    ts, cols = stream.columns(lo, lo + 3000)
    want = [beam_bid(gen, i, stream.pool, i % pool) for i in range(lo, lo + 3000)]
    got = list(zip(cols["auction"].tolist(), cols["bidder"].tolist(),
                   cols["dateTime"].tolist()))
    assert got == want
    assert (ts == cols["dateTime"]).all()
    assert (cols["price"]
            == stream.pool["price"][np.arange(lo, lo + 3000) % pool]).all()


def test_ids_grow_across_pool_cycles_and_no_auction_comes_back():
    """The harness replays a pool of draws; the keys follow the global row
    index, so the second cycle's auctions are all new."""
    n = 46 * 3000
    gen, ts1, first = bids(9, n)
    _, ts2, again = bids(9, n, lo=n)
    assert (ts2 > ts1.max()).all()
    assert again["auction"].min() > first["auction"].max() - 112
    assert len(np.intersect1d(first["auction"][: n - 46 * 40],
                              again["auction"][46 * 40:])) == 0
    # same draws, new keys: the hot flags repeat, the ids do not
    assert (again["price"] == first["price"]).all()
    both = np.concatenate([first["auction"], again["auction"]])
    # an auction takes bids while among the last 110: at most 111 auctions'
    # worth of stream time, 0.185 s, between its first and its last bid
    order = np.argsort(both, kind="stable")
    t = np.concatenate([ts1, ts2])[order]
    edges = np.flatnonzero(np.r_[True, np.diff(both[order]) != 0, True])
    life = t[edges[1:] - 1] - t[edges[:-1]]
    assert life.max() <= 190 and 120 < np.median(life) < 170


def test_the_numbers_the_configuration_states():
    """Bids in the window and auctions alive, on a minute of the stream and
    scaled: at a flat rate both are proportional to the window."""
    gen, _, cfg = load()
    sizes = cfg["sizes"]
    per_ms_x50 = 10 * 46  # bids per 50 ms x ... : 10 events a ms, 46 of 50
    assert sizes["window_rows"] == sizes["window_ms"] * per_ms_x50 // 50
    rh = cfg["rehearse_sizes"]
    assert rh["window_rows"] == rh["window_ms"] * per_ms_x50 // 50
    window = 60_000
    _, ts, cols = bids(13, 46 * 15000)
    # whatever the alignment, `window` ms hold the same number of bids:
    # window x 10 events are whole epochs of 50
    for at in (689_999, 650_017, 600_123):
        held = np.searchsorted(ts, ts[at], "right") - np.searchsorted(
            ts, ts[at] - window, "right")
        assert held == window * per_ms_x50 // 50
    # the arriving bid included, a bid of its own millisecond not yet there
    # excluded: never more than that, so the ring never lacks room
    gone = np.searchsorted(ts, ts - window, "right")
    assert (np.arange(len(ts)) + 1 - gone).max() == window * per_ms_x50 // 50
    live = len(np.unique(cols["auction"][gone[-1]:]))
    assert abs(live - 600 * window / 1000) < 150   # 600 new auctions a second
    # 60 min: 2.16 M alive, which the table has to hold with the newcomers
    # of a micro-batch (2,137) on top
    alive = 600 * sizes["window_ms"] // 1000
    assert alive == 2_160_000
    assert sizes["group_capacity"] >= alive + 3 * 2137 + 110
    # the rehearsal streams several times its table, so that it fails
    # without slots being taken back
    assert rh["group_capacity"] > 600 * rh["window_ms"] // 1000 + 110 + 60
    assert rh["group_capacity"] < 600 * 10


# ---- the reference ------------------------------------------------------------

def brute_force(ts, auction, window):
    """num of every bid by the definition: the bids of its auction whose
    time is less than `window` behind its own, itself included, among those
    that have arrived."""
    out = np.empty(len(ts), np.int64)
    for i in range(len(ts)):
        out[i] = np.count_nonzero(
            (auction[: i + 1] == auction[i]) & (ts[: i + 1] > ts[i] - window))
    return out


@pytest.mark.parametrize("window", [7, 100, 1000])
def test_reference_against_a_brute_force_count(window):
    gen, ref, _ = load()
    _, ts, cols = bids(21, 46 * 80)
    out = ref.reference(ts, cols, {"window_ms": window})
    assert (out["num"] == brute_force(ts, cols["auction"], window)).all()
    assert (out["auction"] == cols["auction"]).all()
    assert (out["event_time"] == ts).all()
    assert ref.kept(cols).all() and ref.kept(gen.make(1, 46)).all()


def test_reference_in_steps_equals_the_whole_and_forgets_empty_auctions():
    gen, ref, cfg = load()
    sizes = {**cfg["sizes"], **cfg["rehearse_sizes"]}
    _, ts, cols = bids(23, 46 * 3000)
    whole = ref.reference(ts, cols, sizes)
    run, at, got = ref.Running(sizes), 0, []
    for step, emit in [(700, False), (50000, True), (1, True), (33333, False),
                       (len(ts), True)]:
        upto = min(at + step, len(ts))
        out = run.step(ts[at:upto], {k: v[at:upto] for k, v in cols.items()},
                       None, emit)
        assert (out is None) == (not emit)
        if emit:
            got.append((at, out))
        at = upto
    for lo, out in got:
        for lane in ("event_time", "auction", "num"):
            assert (out[lane] == whole[lane][lo:lo + len(out[lane])]).all()
    # the dict holds the auctions with a bid in the window, and no other
    gone = np.searchsorted(ts, ts[-1] - sizes["window_ms"], "right")
    assert run.live() == len(np.unique(cols["auction"][gone:]))
    assert run.live() < 600 * sizes["window_ms"] // 1000 + 150


def not_correct(cfg, got, want) -> dict:
    """The numbers of `compare` that exceed their limit."""
    gaps = {lane: harness.lane_gap(got[lane], want[lane], rule)
            for lane, rule in cfg["compare"].items()}
    return {k: v for k, v in gaps.items() if v > cfg["compare"][k]["limit"]}


def test_control_in_bfloat16_is_not_correct():
    """A hot auction holds up to about 770 bids; bfloat16 holds the integers
    up to 256, every second one up to 512 and every fourth beyond: 42 % of a
    hot auction's rows, a fifth of all rows, read wrong, by `num` alone."""
    _, ref, cfg = load()
    sizes = {**cfg["sizes"], **cfg["rehearse_sizes"]}
    _, ts, cols = bids(27, 46 * 3000)
    sound = ref.reference(ts, cols, sizes)
    control = ref.reference(ts, cols, sizes, control=True)
    bad = not_correct(cfg, control, sound)
    assert set(bad) == {"num"}
    assert 0.15 * len(ts) < bad["num"] < 0.3 * len(ts)
    assert not not_correct(cfg, sound, sound)


def never_forgetting_table(ts, auction, window, capacity, batch):
    """What the engine's table did before PR 40, in NumPy: a key keeps its
    slot for ever, slots are handed out until `capacity` keys have been
    seen, and a key that finds none keeps its count for the length of its
    micro-batch alone."""
    slots: dict = {}
    lost_carry: dict = {}
    out = np.empty(len(ts), np.int64)
    head = 0
    for lo in range(0, len(ts), batch):
        lost_carry.clear()
        for i in range(lo, min(lo + batch, len(ts))):
            while ts[head] <= ts[i] - window:
                k = int(auction[head])
                table = slots if k in slots else lost_carry
                table[k] = table.get(k, 0) - 1
                head += 1
            k = int(auction[i])
            if k in slots or len(slots) < capacity:
                table = slots
            else:
                table = lost_carry
            table[k] = table.get(k, 0) + 1
            out[i] = table[k]
    return out


def test_a_table_that_never_forgets_is_not_correct():
    """With `group_capacity` slots that are never given back, the answers
    are the reference's until that many auctions have been seen, and wrong
    from there on however few are alive."""
    _, ref, cfg = load()
    sizes = {**cfg["sizes"], **cfg["rehearse_sizes"]}
    _, ts, cols = bids(29, 46 * 1600)
    sound = ref.reference(ts, cols, sizes)
    num = never_forgetting_table(ts, cols["auction"], sizes["window_ms"],
                                 sizes["group_capacity"], sizes["batch"])
    seen = np.maximum.accumulate(cols["auction"]) - cols["auction"][0]
    full = int(np.searchsorted(seen, sizes["group_capacity"]))
    assert 0 < full < len(ts) - 4 * sizes["batch"]
    assert (num[: full - 200] == sound["num"][: full - 200]).all()
    bad = not_correct(cfg, {**sound, "num": num}, sound)
    assert set(bad) == {"num"} and bad["num"] > 0.3 * (len(ts) - full)
    # and with slots taken back the same table would do: far fewer alive
    assert ref.Running(sizes).live() == 0
    run = ref.Running(sizes)
    run.step(ts, cols, None, emit=False)
    assert run.live() < sizes["group_capacity"]


def test_cost_counts_the_probe_once_whatever_implements_it():
    _, _, cfg = load()
    cost = harness.load_module(BENCH / "configs" / CONFIG / "cost.py")
    sizes = cfg["sizes"]
    probe = cost.probe_bytes_per_microbatch(sizes)
    # 65,536 rows' keys and slot numbers, and two entries' worth for each
    # of the ~2,137 auctions that appear and the as many that empty
    assert probe == 2 * 32768 * 12 + 2 * 2 * 32768 * (600 / 9200) * 16
    assert cost.window_bytes_per_microbatch(sizes, 1.0) == 2 * 32768 * 16
    whole = cost.bytes_per_microbatch(sizes, 12.0, 1.0)
    assert whole == (32768 * 12.0 + 2 * 32768 * 16 + probe + 32768 * 24)
