"""Seeded smart-plug records on the DEBS 2014 schedule: every second each
of the 2,125 plugs sends a work record and then a load record, the plugs in
an order drawn from the seed. Row i of the stream is record i % 2 of reading
i // 2; reading j is plug order[j % 2125] in second j // 2125. The pool the
harness replays is whole seconds (CYCLE_ROWS), so the schedule holds across
its wrap; `id` and `ts` follow from the global row index."""

import numpy as np

N_PLUGS = 2125
N_HOUSES = 40
HOUSEHOLD_PLUGS = 6
CYCLE_ROWS = 2 * N_PLUGS          # one second of the whole population
T0_S = 1_377_986_401              # the recording's first second
ID0 = 2_967_740_693               # an id of the source's sample lines
LOAD_MEAN_W = 60.0

STRINGS = {}


def layout() -> tuple:
    """(house_id, household_id, plug_id) of plug 0..2124: houses 0-4 hold
    54 plugs and the others 53, in households of 6."""
    per_house = np.full(N_HOUSES, N_PLUGS // N_HOUSES)
    per_house[:N_PLUGS % N_HOUSES] += 1
    house = np.repeat(np.arange(N_HOUSES), per_house)
    in_house = np.arange(N_PLUGS) - np.repeat(
        np.cumsum(per_house) - per_house, per_house)
    return (house.astype(np.int32),
            (in_house // HOUSEHOLD_PLUGS).astype(np.int32),
            (in_house % HOUSEHOLD_PLUGS).astype(np.int32))


def make(seed: int, n: int) -> dict:
    """Columns of `n` records (a whole number of seconds), without `id` and
    `ts` (see `with_index`)."""
    if n % CYCLE_ROWS:
        raise ValueError(f"{n} rows are not whole seconds of {CYCLE_ROWS}")
    rng = np.random.default_rng(seed)
    reading = np.arange(n) // 2
    plug = rng.permutation(N_PLUGS)[reading % N_PLUGS]
    house, household, plug_id = layout()
    load = np.round(rng.exponential(LOAD_MEAN_W, size=n // 2), 3)
    work = np.round(rng.uniform(0, 500, N_PLUGS)[plug[::2]]
                    + 1e-3 * (reading[::2] // N_PLUGS), 3)
    value = np.empty(n, dtype=np.float32)
    value[0::2], value[1::2] = work, load
    return {
        "value": value,
        "property": np.arange(n) % 2 == 1,
        "plug_id": plug_id[plug],
        "household_id": household[plug],
        "house_id": house[plug],
    }


def timestamps(lo: int, hi: int) -> np.ndarray:
    """Event time (ms) of stream rows lo..hi-1: 4,250 records per second."""
    return (T0_S + np.arange(lo, hi, dtype=np.int64) // CYCLE_ROWS) * 1000


def with_index(cols: dict, lo: int, hi: int, ts: np.ndarray) -> dict:
    """`id` counts the records; `ts` is the event time in seconds."""
    return {"id": ID0 + np.arange(lo, hi, dtype=np.int64), "ts": ts // 1000,
            **cols}
