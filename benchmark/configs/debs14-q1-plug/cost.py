"""Bytes one micro-batch of the smart-plug query has to move through HBM,
from shapes alone. The least any implementation of the query could move:
each byte is counted once, no padding, no temporaries."""

RING_ROW = 3 * 4 + 4          # what retiring a row needs: its plug, its load
ROW_OUT = 8 + 8 + 3 * 4 + 4   # event time, ts, plug triple, avg f32
SLOT = 4 + 4                  # group slot: load sum f32, count


def bytes_per_microbatch(sizes: dict, encoded_B_per_event: float,
                         kept_share: float) -> float:
    rows = sizes["batch"]
    kept_rows = rows * kept_share
    touched = min(kept_rows, sizes["plugs"])
    return (
        rows * encoded_B_per_event   # encoded wire in
        + kept_rows * RING_ROW       # ring rows written
        + kept_rows * RING_ROW       # expired rows read (the ring is full)
        + 2 * 2 * touched * SLOT     # slots read+written, arrival and expiry
        + kept_rows * ROW_OUT        # packed output out
    )
