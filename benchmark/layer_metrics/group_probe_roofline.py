"""Share of the HBM roofline of the key probe: the bytes one probe of a
micro-batch against the live table has to move (the configuration's
`cost.py` `probe_bytes_per_microbatch`, stated so that it reads the same
whatever implements the probe) over the chip's peak bytes/s, divided by the
device time under `group.probe` per micro-batch. Device trace."""

import group_scopes
import harness
import part_scopes


def read(trace, spans, counters, cell):
    ms = group_scopes.device_ms_per_microbatch(
        trace, counters, cell, "group.probe")
    cost_file = cell["config_dir"] / "cost.py"
    if not ms or not cost_file.exists():
        return None
    cost = harness.load_module(cost_file)
    if not hasattr(cost, "probe_bytes_per_microbatch"):
        return None
    return part_scopes.share_of_hbm_roofline(
        cost.probe_bytes_per_microbatch(cell["sizes"]), ms, counters)
