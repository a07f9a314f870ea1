"""Share of the HBM roofline of the partitions' window step: the bytes the
windows alone have to move per send (the configuration's `cost.py`
`window_bytes`: rows entering and leaving, a ring row each) over the chip's
peak bytes/s, divided by the device time under the window's scopes per send.
Device trace."""

import harness
import part_scopes


def read(trace, spans, counters, cell):
    ms = part_scopes.device_ms_per_send(trace, spans, cell, "window.")
    cost_file = cell["config_dir"] / "cost.py"
    if not ms or not cost_file.exists():
        return None
    need = harness.load_module(cost_file).window_bytes(
        cell["traffic"]["send_rows"], spans["stream"].emit_share)
    return part_scopes.share_of_hbm_roofline(need, ms, counters)
