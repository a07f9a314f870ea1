"""Share of the HBM roofline of one micro-batch step of the chunk program:
the bytes the query has to move per micro-batch (the configuration's
`cost.py`) over the chip's peak bytes/s, divided by the device time per
micro-batch. Bound by bytes: the query does a handful of operations per
byte. Device trace."""

import harness
import readers


def read(trace, spans, counters, cell):
    per_chunk_ms = readers.chunk_device_ms(trace)
    cost_file = cell["config_dir"] / "cost.py"
    depth = readers.chunk_batches(counters, cell)
    if per_chunk_ms is None or not depth or not cost_file.exists():
        return None
    wire = counters["status"]["streams"][cell["config"]["stream"]][
        "pipeline"]["wire"]["encoded_B_per_ev"]
    need = harness.load_module(cost_file).bytes_per_microbatch(
        cell["sizes"], wire, spans["stream"].emit_share)
    kind = counters["device_kind"]
    least_s = need / readers.peaks(kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / (per_chunk_ms / 1e3 / depth)
