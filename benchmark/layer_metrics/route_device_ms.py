"""Device time of the key-sharded step's routing (scope `keyshard.route`: the
owner hash of every row's group key and the mask that keeps a device's own
rows) per micro-batch of the chunk program, on the first device. Device trace.

`program_spans.SCOPE` does not list this scope, so its operations are found by
their `tf_op` here and their exclusive time summed as `device_ms_by_scope`
does; they are under no other per-layer metric."""

import numpy as np

import program_spans
import readers

SCOPE = "keyshard.route"


def read(trace, spans, counters, cell):
    ps = program_spans.of(cell, trace)
    ex = readers.chunk_executions(trace)
    depth = readers.chunk_batches(counters, cell)
    if ps is None or not len(ex) or not depth:
        return None
    routed = {op for (program, op), tf_op in ps.scopes.items()
              if program == readers.CHUNK_PROGRAM and SCOPE in tf_op.split("/")}
    dev = trace.devices[0]
    own = program_spans.exclusive_ns(dev.ops)
    k = np.searchsorted(ex[:, 0], dev.ops[:, 0], side="right") - 1
    inside = (k >= 0) & (dev.ops[:, 1] <= ex[np.maximum(k, 0), 1])
    ns = sum(own[i] for i in np.flatnonzero(inside) if dev.op_names[i] in routed)
    return ns / 1e6 / (len(ex) * depth)
