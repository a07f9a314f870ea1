"""Bytes one device of the keys mesh has to move for one micro-batch of the
per-plug peak query, from shapes alone: through its HBM, and over the
interconnect. The least any key-sharded implementation that replicates the
micro-batch could move: each byte is counted once, no padding, no
temporaries, no ring (the query has no window)."""

ROW_OUT = 8 + 8 + 3 * 4 + 4 + 8  # event time, ts, plug triple, peak f32, count
SLOT = 4 + 8                     # group slot: peak f32, count i64
# 1,600 Gbit/s of chip-to-chip interconnect per chip: `peaks.json`'s source
ICI_BYTES_PER_S = 200e9


def bytes_per_microbatch(sizes: dict, encoded_B_per_event: float,
                         kept_share: float) -> float:
    """HBM bytes on ONE device: it reads the whole replicated wire, touches
    the slots of the plugs it owns and writes the whole packed output."""
    rows = sizes["batch"]
    kept_rows = rows * kept_share
    touched = min(kept_rows, sizes["plugs"]) / sizes["devices"]
    return (
        rows * encoded_B_per_event   # encoded wire in
        + 2 * touched * SLOT         # owned slots read and written
        + kept_rows * ROW_OUT        # packed output out
    )


def exchange_bytes_per_microbatch(sizes: dict, kept_share: float) -> float:
    """The least the merge must bring to the device that packs: the output
    rows whose plug another device owns."""
    devices = sizes["devices"]
    kept_rows = sizes["batch"] * kept_share
    return kept_rows * (devices - 1) / devices * ROW_OUT
