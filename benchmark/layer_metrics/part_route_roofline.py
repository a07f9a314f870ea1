"""Share of the HBM roofline of the partition's route and merge: the bytes
they have to move per send (the configuration's `cost.py` `route_bytes`: each
routed row and each emitted row read once and written once) over the chip's
peak bytes/s, divided by their device time per send. Device trace."""

import harness
import part_scopes


def read(trace, spans, counters, cell):
    ms = part_scopes.device_ms_per_send(
        trace, spans, cell, "partition.route", "partition.merge")
    cost_file = cell["config_dir"] / "cost.py"
    if not ms or not cost_file.exists():
        return None
    need = harness.load_module(cost_file).route_bytes(
        cell["traffic"]["send_rows"], spans["stream"].emit_share)
    return part_scopes.share_of_hbm_roofline(need, ms, counters)
