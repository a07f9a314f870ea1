"""Bytes the per-plug partitions of the smart-plug query have to move through
HBM, from shapes alone. The least any implementation could move: each byte is
counted once, no padding, no temporaries."""

ROW_IN = 8 + 8 + 8 + 4 + 1 + 3 * 4   # event time, id, ts, value, property, plug triple
RING_ROW = 4                          # what retiring a row needs: its load
ROW_OUT = 8 + 8 + 3 * 4 + 4           # event time, ts, plug triple, avg f32
SLOT = 8 + 4                          # a partition's load sum and count


def route_bytes(rows: float, kept_share: float) -> float:
    """Routing `rows` arriving rows to their partition and their emissions
    back into arrival order: each routed row read once and written once,
    each emitted row the same on the way back."""
    return 2 * rows * ROW_IN + 2 * rows * kept_share * ROW_OUT


def window_bytes(rows: float, kept_share: float) -> float:
    """The windows alone: rows entering plus rows leaving, a ring row each.
    In full windows as many leave as enter."""
    kept_rows = rows * kept_share
    return kept_rows * RING_ROW + kept_rows * RING_ROW


def window_bytes_per_microbatch(sizes: dict, kept_share: float) -> float:
    return window_bytes(sizes["batch"], kept_share)


def bytes_per_send(sizes: dict, rows: float, kept_share: float) -> float:
    """One send of `rows` rows through the per-batch path."""
    kept_rows = rows * kept_share
    touched = min(kept_rows, sizes["plugs"])
    return (
        rows * ROW_IN                 # the batch in
        + route_bytes(rows, kept_share)
        + window_bytes(rows, kept_share)
        + 2 * touched * SLOT          # sums read and written
        + kept_rows * ROW_OUT         # packed output out
    )


def bytes_per_microbatch(sizes: dict, encoded_B_per_event: float,
                         kept_share: float) -> float:
    return bytes_per_send(sizes, sizes["batch"], kept_share)
