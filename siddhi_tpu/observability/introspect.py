"""State introspection: aggregate `describe_state()` hooks into one status.

Every stateful runtime component grows a cheap, pull-only `describe_state()
-> dict` (junction queue depth and subscriber health, window type/fill/
capacity and oldest/newest timestamps, NFA active-instance counts per state
and within-clause deadlines, aggregation bucket counts and watermarks,
table row counts and index info, ingest-pipeline depth/occupancy/slots in
flight, error-store depth). `SiddhiAppRuntime.snapshot_status()` walks
them; `SiddhiManager.snapshot_status()` adds the shared error store; the
`MetricsServer` serves both as `/status` (human text) and `/status.json`.

The hooks are PULL-only: nothing is collected, sampled, or scheduled until
a caller asks, so the hot dispatch path cost of the whole subsystem is
zero. Reads that touch device state (window fills, table occupancy, NFA
token pulls) do one host transfer per component — an on-demand operator
action, not a steady cost. A field degrades to None only when a concurrent
donated-state dispatch deleted the buffers under the read.
"""

from __future__ import annotations


def _fmt(v) -> str:
    if isinstance(v, float):
        return f"{v:.3f}"
    return str(v)


def _render_component(lines: list, name: str, d: dict, indent: str) -> None:
    flat = {k: v for k, v in d.items() if not isinstance(v, dict)}
    nested = {k: v for k, v in d.items() if isinstance(v, dict)}
    body = ", ".join(f"{k}={_fmt(v)}" for k, v in flat.items())
    lines.append(f"{indent}{name}: {body}" if body else f"{indent}{name}:")
    for k, sub in nested.items():
        _render_component(lines, k, sub, indent + "  ")


def render_status(status: dict) -> str:
    """Human-readable rendering of a manager/runtime status snapshot (the
    `/status` endpoint body)."""
    lines: list[str] = []
    apps = status.get("apps")
    if apps is None:  # a single runtime's snapshot
        apps = {status.get("app", "app"): status}
    for name, app in apps.items():
        running = "running" if app.get("running") else "stopped"
        lines.append(f"app {name} [{running}]")
        for section in (
            "streams", "queries", "windows", "tables", "aggregations",
        ):
            comps = app.get(section) or {}
            if not comps:
                continue
            lines.append(f"  {section}:")
            for cid, d in comps.items():
                _render_component(lines, cid, d, "    ")
        for extra in ("shard", "selfmon", "admission", "autopersist", "health"):
            d = app.get(extra)
            if d:
                _render_component(lines, extra, d, "  ")
    es = status.get("error_store")
    if es:
        _render_component(lines, "error_store", es, "")
    sup = status.get("supervisor")
    if sup:
        _render_component(lines, "supervisor", sup, "")
    return "\n".join(lines) + "\n"
