"""Device time of a partitioned query's per-batch step by scope, per send.

The partitioned step is a program of its own (`jit__pstep_outer_impl`), and
`program_spans.SCOPE` lists neither its name nor the scopes it adds
(`partition.keys`, `partition.route`, `partition.merge`): the readers of the
partition's metrics find an operation's scopes in its `tf_op` themselves, as
`layer_metrics/route_device_ms.py` does, and sum exclusive time as
`program_spans.device_ms_by_scope` does. A trace of a program without the
step or the scopes reduces to None.

An operation the compiler made inside the step (the scatter a ring is
written by and the relayouts round it, a sort of a scatter's indices, a
copy) carries the loop's `tf_op` or none, whatever stage it serves:
`device_ms_per_send` with no scope named sums those, so that the scopes and
that rest add up to the step."""

import numpy as np

import program_spans
import trace_reduce

STEP_PROGRAM = "jit__pstep_outer_impl"
# the scopes of the step's stages, by how their names start
SCOPES = ("partition.keys", "partition.route", "partition.merge", "filter",
          "fn.", "window.", "selector")


def device_ms_per_send(trace, spans, cell, *scopes):
    """Exclusive device ms per send of the step's operations under a scope
    that starts with one of `scopes`, on the first device; with no scope
    named, of its operations under none of `SCOPES`."""
    ps = program_spans.of(cell, trace)
    if ps is None or trace is None or not trace.devices or not len(spans["sends"]):
        return None
    ex = trace_reduce.executions(trace, STEP_PROGRAM)
    # a scope round a vmapped stage reads `vmap(window.length)`
    under = {op for (program, op), tf_op in ps.scopes.items()
             if program == STEP_PROGRAM and any(
                 part.removeprefix("vmap(").startswith(s)
                 for part in tf_op.split("/") for s in scopes or SCOPES)}
    if not len(ex) or not under:
        return None
    dev = trace.devices[0]
    own = program_spans.exclusive_ns(dev.ops)
    k = np.searchsorted(ex[:, 0], dev.ops[:, 0], side="right") - 1
    inside = (k >= 0) & (dev.ops[:, 1] <= ex[np.maximum(k, 0), 1])
    ns = sum(own[i] for i in np.flatnonzero(inside)
             if (dev.op_names[i] in under) == bool(scopes))
    return ns / 1e6 / len(spans["sends"])


def share_of_hbm_roofline(need_bytes: float, ms, counters):
    """100 x the least time `need_bytes` take at the chip's peak over `ms`."""
    import readers

    if not ms:
        return None
    peak = readers.peaks(counters["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * need_bytes / peak / (ms / 1e3)


def counter(counters, cell, name):
    """`snapshot_status()["queries"][<query>]["partition"][name]`."""
    part = (counters["status"].get("queries") or {}).get(
        cell["config"]["query"], {}).get("partition") or {}
    return part.get(name)
