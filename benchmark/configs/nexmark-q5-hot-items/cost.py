"""Bytes one micro-batch of NEXMark query 5's count has to move through HBM,
from shapes alone. The least any implementation of the query could move:
each byte is counted once, no padding, no temporaries."""

RING_ROW = 8 + 8          # what retiring a bid needs: its auction, its time
ROW_OUT = 8 + 8 + 8       # event time, auction, num
SLOT = 8 + 8              # a group's entry: its key and its count
# auctions a micro-batch of 32,768 bids touches on the source's defaults:
# 600 new auctions a second of stream time, 9,200 bids a second
NEW_AUCTIONS_PER_BID = 600 / 9200


def window_bytes_per_microbatch(sizes: dict, kept_share: float) -> float:
    """The window alone: rows entering plus rows leaving, a ring row each.
    In a full window at a flat rate as many leave as enter."""
    kept_rows = sizes["batch"] * kept_share
    return kept_rows * RING_ROW + kept_rows * RING_ROW


def probe_bytes_per_microbatch(sizes: dict) -> float:
    """The probe of one micro-batch against the live table, whatever
    implements it: the key of every row that enters and of every row that
    leaves is read, the entry of every group they touch (the auctions that
    appear and as many that empty, each once) is read and written, and
    every row takes a slot number away."""
    rows = 2 * sizes["batch"]                       # entering and leaving
    touched = 2 * sizes["batch"] * NEW_AUCTIONS_PER_BID
    return rows * 8 + 2 * touched * SLOT + rows * 4


def bytes_per_microbatch(sizes: dict, encoded_B_per_event: float,
                         kept_share: float) -> float:
    rows = sizes["batch"]
    return (
        rows * encoded_B_per_event   # encoded wire in
        + window_bytes_per_microbatch(sizes, kept_share)
        + probe_bytes_per_microbatch(sizes)
        + rows * kept_share * ROW_OUT  # packed output out
    )
