"""Share of the HBM roofline of the window's step: the bytes the window
alone has to move per micro-batch (the configuration's `cost.py`
`window_bytes_per_microbatch`: rows entering and leaving, a ring row each)
over the chip's peak bytes/s, divided by the device time under the window's
scopes per micro-batch. Device trace."""

import harness
import program_spans
import readers


def read(trace, spans, counters, cell):
    ms = program_spans.device_scope_ms(trace, spans, counters, cell, "window.")
    cost_file = cell["config_dir"] / "cost.py"
    if not ms or not cost_file.exists():
        return None
    cost = harness.load_module(cost_file)
    if not hasattr(cost, "window_bytes_per_microbatch"):
        return None
    need = cost.window_bytes_per_microbatch(
        cell["sizes"], spans["stream"].emit_share)
    peak = readers.peaks(counters["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * need / peak / (ms / 1e3)
