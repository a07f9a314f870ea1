"""Share of the HBM roofline of the pattern's match: the bytes one
micro-batch's NFA step has to move (the configuration's `cost.py`
`match_bytes_per_microbatch`, stated so that it reads the same whatever
implements the match) over the chip's peak bytes/s, divided by the device
time under `pattern.match` per micro-batch. Device trace."""

import harness
import part_scopes
import pattern_scopes


def read(trace, spans, counters, cell):
    ms = pattern_scopes.device_ms_per_microbatch(
        trace, counters, cell, "pattern.match")
    cost_file = cell["config_dir"] / "cost.py"
    share = spans["stream"].emit_share
    if not ms or share is None or not cost_file.exists():
        return None
    cost = harness.load_module(cost_file)
    if not hasattr(cost, "match_bytes_per_microbatch"):
        return None
    return part_scopes.share_of_hbm_roofline(
        cost.match_bytes_per_microbatch(cell["sizes"], share), ms, counters)
