"""Seeded NEXMark bids as Apache Beam's `nexmark` generator makes them under
its default configuration (`NexmarkConfiguration`, `GeneratorConfig`,
`BidGenerator`), the bids alone: query 5 reads no person and no auction.

Events come 100 us apart (10,000 a second); of every 50, the first is a
person, the next three are auctions and the other 46 are bids. Row i of this
stream is bid i % 46 of epoch i // 46, event number n = 50 (i // 46) + 4 +
i % 46, at `dateTime` BASE_TIME_MS + n // 10.

A bid's auction and bidder follow from its event number and its draws: half
of the bids go to the hot auction, `(last // 100) * 100` where `last` is the
newest auction at that event (three an epoch), which therefore moves on every
100 auctions, a sixth of a second; the others fall evenly on the last
`IN_FLIGHT_AUCTIONS` auctions and the next `AUCTION_ID_LEAD`. Three bids of
four come from the hot bidder, the others evenly from the last
`ACTIVE_PEOPLE` people and the next `PERSON_ID_LEAD`. So ids grow for ever
with the global row index: the pool that the harness replays holds each
bid's draws (whole epochs: `CYCLE_ROWS`), and `with_index` turns them into
the row, so a replayed pool never brings an auction back.

The harness makes a send's rows between two sends, inside the timed window,
so `with_index` is kept cheap: what follows from a bid's epoch alone is
worked out per epoch and repeated over its 46 bids, and where a draw falls in
a full span is laid out once with the pool (`auction_off`, `bidder_off`);
only the stream's first five seconds, while the spans grow, multiply per
row. `benchmark/tests/test_references_q5.py` holds both against a loop over
single bids."""

import numpy as np

PERSON_PROPORTION, AUCTION_PROPORTION, BID_PROPORTION = 1, 3, 46
EPOCH_EVENTS = PERSON_PROPORTION + AUCTION_PROPORTION + BID_PROPORTION
FIRST_BID = PERSON_PROPORTION + AUCTION_PROPORTION  # its place in an epoch
CYCLE_ROWS = BID_PROPORTION       # the pool is whole epochs
EVENTS_PER_MS = 10                # 10,000 events/s, flat
BASE_TIME_MS = 1_436_918_400_000  # 2015-07-15T00:00:00Z, Beam's base time
FIRST_AUCTION_ID = FIRST_PERSON_ID = 1000
IN_FLIGHT_AUCTIONS = 100
AUCTION_ID_LEAD = PERSON_ID_LEAD = 10
HOT_AUCTION_RATIO = 2             # 1 bid in 2 is not on the hot auction
HOT_AUCTION_EVERY = 100           # the hot auction is the last multiple
ACTIVE_PEOPLE = 1000
HOT_BIDDERS_RATIO = 4             # 1 bid in 4 is not the hot bidder's
HOT_BIDDER_EVERY = 100

STRINGS = {}

# from this epoch on the auctions in flight and the active people have their
# full spans (more than IN_FLIGHT_AUCTIONS auctions, ACTIVE_PEOPLE people)
STEADY_EPOCH = max(IN_FLIGHT_AUCTIONS // AUCTION_PROPORTION + 1,
                   ACTIVE_PEOPLE // PERSON_PROPORTION)
AUCTION_SPAN = IN_FLIGHT_AUCTIONS + 1 + AUCTION_ID_LEAD
PERSON_SPAN = ACTIVE_PEOPLE + PERSON_ID_LEAD


def make(seed: int, n: int) -> dict:
    """The draws of `n` bids (whole epochs): whether the bid goes to the hot
    auction, where it falls among the auctions in flight otherwise (a share
    of their span, and the place that share is once the span is full), the
    same three for its bidder, and its price in cents,
    round(10^(6u) * 100)."""
    if n % CYCLE_ROWS:
        raise ValueError(f"{n} bids are not whole epochs of {CYCLE_ROWS}")
    rng = np.random.default_rng(seed)
    draws = {
        "hot_auction": rng.integers(0, HOT_AUCTION_RATIO, n) > 0,
        "auction_u": rng.random(n),
        "hot_bidder": rng.integers(0, HOT_BIDDERS_RATIO, n) > 0,
        "bidder_u": rng.random(n),
        "price": np.floor(
            10.0 ** (rng.random(n) * 6.0) * 100.0 + 0.5).astype(np.int64),
    }
    draws["auction_off"] = _place(draws["auction_u"], AUCTION_SPAN)
    draws["bidder_off"] = _place(draws["bidder_u"], PERSON_SPAN)
    return draws


def _place(u: np.ndarray, span) -> np.ndarray:
    return np.floor(u * span).astype(np.int64)


class _Epochs:
    """The epochs that stream rows lo..hi-1 lie in. What follows from a
    bid's epoch alone is worked out once per epoch and spread over its 46
    bids (`rows`): no division per row, as the harness makes a send's 2 M
    rows between two sends, inside the timed window."""

    def __init__(self, lo: int, hi: int):
        first, skip = divmod(lo, BID_PROPORTION)
        count = -(-(skip + hi - lo) // BID_PROPORTION)
        self.number = np.arange(first, first + count, dtype=np.int64)
        self.cut = slice(skip, skip + hi - lo)

    def rows(self, per_epoch: np.ndarray) -> np.ndarray:
        return np.repeat(per_epoch, BID_PROPORTION)[self.cut]

    def place(self) -> np.ndarray:
        """Each row's place among its epoch's bids."""
        return np.tile(np.arange(BID_PROPORTION, dtype=np.int64),
                       len(self.number))[self.cut]


def event_number(lo: int, hi: int) -> np.ndarray:
    """Event number of stream rows (bids) lo..hi-1."""
    epochs = _Epochs(lo, hi)
    event = epochs.rows(epochs.number * EPOCH_EVENTS + FIRST_BID)
    event += epochs.place()
    return event


def timestamps(lo: int, hi: int) -> np.ndarray:
    """Event time (ms) of stream rows lo..hi-1: the bid's `dateTime`."""
    return BASE_TIME_MS + event_number(lo, hi) // EVENTS_PER_MS


def with_index(cols: dict, lo: int, hi: int, ts: np.ndarray) -> dict:
    """The bids lo..hi-1 of the stream from their draws: `auction`,
    `bidder`, `price`, `dateTime`."""
    epochs = _Epochs(lo, hi)
    # the newest auction and person when a bid is made, counted from 0
    last_auction = epochs.number * AUCTION_PROPORTION + (AUCTION_PROPORTION - 1)
    last_person = epochs.number * PERSON_PROPORTION + (PERSON_PROPORTION - 1)
    first = np.maximum(last_auction - IN_FLIGHT_AUCTIONS, 0)
    active = np.minimum(last_person + 1, ACTIVE_PEOPLE)
    auction = epochs.rows(first + FIRST_AUCTION_ID)
    bidder = epochs.rows(last_person + 1 - active + FIRST_PERSON_ID)
    if lo // BID_PROPORTION >= STEADY_EPOCH:
        auction += cols["auction_off"]
        bidder += cols["bidder_off"]
    else:  # the stream's first five seconds: the spans still grow
        auction += _place(cols["auction_u"], epochs.rows(
            last_auction - first + 1 + AUCTION_ID_LEAD))
        bidder += _place(cols["bidder_u"], epochs.rows(active + PERSON_ID_LEAD))
    hot = last_auction // HOT_AUCTION_EVERY * HOT_AUCTION_EVERY
    np.copyto(auction, epochs.rows(hot + FIRST_AUCTION_ID),
              where=cols["hot_auction"])
    hot = last_person // HOT_BIDDER_EVERY * HOT_BIDDER_EVERY + 1
    np.copyto(bidder, epochs.rows(hot + FIRST_PERSON_ID),
              where=cols["hot_bidder"])
    return {
        "auction": auction,
        "bidder": bidder,
        "price": cols["price"],
        "dateTime": ts,
    }
