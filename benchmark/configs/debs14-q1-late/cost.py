"""`debs14-q1-time`'s cost functions, unchanged: the reorder stage of this
configuration is host code, and behind it the device does that
configuration's work, micro-batch for micro-batch.

Bytes one micro-batch of the smart-plug query on event time has to move
through HBM, from shapes alone. The least any implementation of the query
could move: each byte is counted once, no padding, no temporaries. As
`debs14-q1-plug`'s, plus the window time of every row written to the ring
and read at its head: a time window has to know when a row is due."""

RING_ROW = 3 * 4 + 4 + 8      # what retiring a row needs: plug, load, its time
ROW_OUT = 8 + 8 + 3 * 4 + 4   # event time, ts, plug triple, avg f32
SLOT = 4 + 4                  # group slot: load sum f32, count


def window_bytes_per_microbatch(sizes: dict, kept_share: float) -> float:
    """The window alone: rows entering plus rows leaving, a ring row each.
    In a full window as many leave as enter."""
    kept_rows = sizes["batch"] * kept_share
    return kept_rows * RING_ROW + kept_rows * RING_ROW


def bytes_per_microbatch(sizes: dict, encoded_B_per_event: float,
                         kept_share: float) -> float:
    rows = sizes["batch"]
    kept_rows = rows * kept_share
    touched = min(kept_rows, sizes["plugs"])
    return (
        rows * encoded_B_per_event   # encoded wire in
        + window_bytes_per_microbatch(sizes, kept_share)
        + 2 * 2 * touched * SLOT     # slots read+written, arrival and expiry
        + kept_rows * ROW_OUT        # packed output out
    )
