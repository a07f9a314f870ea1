"""Bytes one micro-batch of the load-rise pattern has to move through HBM,
from shapes alone. The least any implementation of the query could move:
each byte is counted once, no padding, no temporaries."""

KEY = 3 * 4                     # house, household, plug
TOKEN = KEY + 4 + 8             # a pending match: its key, load and start time
LOAD_ROW = KEY + 4 + 8          # an arriving load row: key, load, event time
ARM = KEY + 4 + 8 + 8           # what a new token keeps: key, load, ts, start
ROW_OUT = 8 + KEY + 8 + 4 + 8 + 4  # event time, key, ts1, load1, ts2, load2
LOAD_SHARE = 0.5                # every second record of the stream is a load


def match_bytes_per_microbatch(sizes: dict, emit_share: float) -> float:
    """The NFA step alone, whatever implements the match: each live token's
    key, load and start time read once, each load row's key, load and time
    once, each completion's lanes written once and each arm's lanes written
    once. `emit_share` is the matches emitted per row of the stream."""
    rows = sizes["batch"]
    return (
        sizes["tokens_live_mean"] * TOKEN
        + rows * LOAD_SHARE * LOAD_ROW
        + rows * emit_share * ROW_OUT
        + rows * LOAD_SHARE * ARM
    )


def bytes_per_microbatch(sizes: dict, encoded_B_per_event: float,
                         emit_share: float) -> float:
    rows = sizes["batch"]
    return (
        rows * encoded_B_per_event              # encoded wire in
        + match_bytes_per_microbatch(sizes, emit_share)
        + rows * emit_share * ROW_OUT           # packed output out
    )
