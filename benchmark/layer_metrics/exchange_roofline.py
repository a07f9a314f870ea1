"""Share of the interconnect's peak that the key-sharded step's merge reaches:
the bytes the merge must bring to the device that packs (the configuration's
`cost.py`: the output rows other devices own) over the chip's interconnect
bytes/s, divided by the device time under `keyshard.exchange` per micro-batch.
Bound by latency, not bytes: a handful of small all-reduces per micro-batch.
Device trace."""

import harness
import program_spans


def read(trace, spans, counters, cell):
    ms = program_spans.device_scope_ms(trace, spans, counters, cell, "keyshard.exchange")
    cost_file = cell["config_dir"] / "cost.py"
    if not ms or not cost_file.exists():
        return None
    cost = harness.load_module(cost_file)
    if not hasattr(cost, "exchange_bytes_per_microbatch"):
        return None
    need = cost.exchange_bytes_per_microbatch(
        cell["sizes"], spans["stream"].emit_share)
    return 100.0 * need / cost.ICI_BYTES_PER_S / (ms / 1e3)
