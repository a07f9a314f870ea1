"""Encoded bytes per event on the host-to-device wire, as the fused ingest
engine reports it (`snapshot_status()`): a count, it repeats exactly."""


def read(trace, spans, counters, cell):
    stream = counters["status"]["streams"][cell["config"]["stream"]]
    wire = (stream.get("pipeline") or {}).get("wire")
    return None if wire is None else wire["encoded_B_per_ev"]
