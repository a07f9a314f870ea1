"""Device time of the group-by's key table by its own scopes, per
micro-batch of the chunk program, and the table's counters.

Inside `selector` the engine names `group.probe` (finding each row's slot in
the key table) and `group.reclaim` (handing unused slots to new keys and
taking back those of the groups a step emptied). `program_spans.SCOPE` lists
neither, so their operations are found by their `tf_op` here, as
`layer_metrics/route_device_ms.py` and `part_scopes.py` do, and their
exclusive time summed as `program_spans.device_ms_by_scope` does: both are
part of what `group_device_ms.*` reads. A trace of a program without the
scopes, or a status without the counters (any commit before they came),
reduces to None."""

import numpy as np

import program_spans
import readers


def device_ms_per_microbatch(trace, counters, cell, scope: str):
    """Exclusive device ms per micro-batch of the chunk program's operations
    under `scope`, on the first device."""
    ps = program_spans.of(cell, trace)
    ex = readers.chunk_executions(trace)
    depth = readers.chunk_batches(counters, cell)
    if ps is None or not len(ex) or not depth:
        return None
    under = {op for (program, op), tf_op in ps.scopes.items()
             if program == readers.CHUNK_PROGRAM and scope in tf_op.split("/")}
    if not under:
        return None
    dev = trace.devices[0]
    own = program_spans.exclusive_ns(dev.ops)
    k = np.searchsorted(ex[:, 0], dev.ops[:, 0], side="right") - 1
    inside = (k >= 0) & (dev.ops[:, 1] <= ex[np.maximum(k, 0), 1])
    ns = sum(own[i] for i in np.flatnonzero(inside) if dev.op_names[i] in under)
    return ns / 1e6 / (len(ex) * depth)


def counter(counters, cell, name):
    """`snapshot_status()["queries"][<query>]["group"][name]`."""
    group = (counters["status"].get("queries") or {}).get(
        cell["config"]["query"], {}).get("group") or {}
    return group.get(name)
