"""The smart-plug stream of `debs14-q1-time`, which is `debs14-q1-plug`'s with
measurements missing as the source's recording has them: one stream from one
seed for the three configurations. Everything is that file's; it keeps the
second of every row of the pool it last made, so `make` and `timestamps` are
one module's."""

import importlib.util
from pathlib import Path

_SOURCE = Path(__file__).resolve().parents[1] / "debs14-q1-time" / "gen.py"
_spec = importlib.util.spec_from_file_location("bench_plug_stream_gaps", _SOURCE)
_time = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_time)

N_PLUGS = _time.N_PLUGS
N_HOUSES = _time.N_HOUSES
MISSING_SHARE = _time.MISSING_SHARE
CYCLE_ROWS = _time.CYCLE_ROWS
STRINGS = _time.STRINGS
layout = _time.layout
make = _time.make
timestamps = _time.timestamps
with_index = _time.with_index
