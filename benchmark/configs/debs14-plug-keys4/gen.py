"""The smart-plug stream of `debs14-q1-plug`, record for record: the same
schedule, layout, values and `with_index`, from the same seed. This
deployment differs from that one in what is computed per plug and where the
group table lives, not in its input, so the generator is that file's."""

import importlib.util
from pathlib import Path

_SOURCE = Path(__file__).resolve().parents[1] / "debs14-q1-plug" / "gen.py"
_spec = importlib.util.spec_from_file_location("bench_plug_stream", _SOURCE)
_plug = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_plug)

N_PLUGS = _plug.N_PLUGS
N_HOUSES = _plug.N_HOUSES
CYCLE_ROWS = _plug.CYCLE_ROWS
STRINGS = _plug.STRINGS
layout = _plug.layout
make = _plug.make
timestamps = _plug.timestamps
with_index = _plug.with_index
