"""Device time of the pattern's NFA step by its own scopes, per micro-batch
of the chunk program, and the token table's counters.

Inside the query's scope the engine names `pattern.arm` (an `every` state
arming its tokens into their lanes), `pattern.match` (finding each token's
first matching row, whatever implements it: key sort, carries, residual),
`pattern.emit` (ordering and writing the completions) and `pattern.purge`
(the `within` expiry). `program_spans.SCOPE` lists none of them, so their
operations are found by their `tf_op`, as `group_scopes.py` finds the key
table's. The counters are `snapshot_status()["queries"][<query>]["pattern"]`.
A trace of a program without the scopes, or a status without the block (any
commit before they came), reduces to None."""

import group_scopes


def device_ms_per_microbatch(trace, counters, cell, scope: str):
    """Exclusive device ms per micro-batch of the chunk program's operations
    under `scope`, on the first device."""
    return group_scopes.device_ms_per_microbatch(trace, counters, cell, scope)


def counter(counters, cell, name):
    """`snapshot_status()["queries"][<query>]["pattern"][name]`."""
    block = (counters["status"].get("queries") or {}).get(
        cell["config"]["query"], {}).get("pattern")
    return block.get(name) if isinstance(block, dict) else None
