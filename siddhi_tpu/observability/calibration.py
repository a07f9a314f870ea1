"""Plan-vs-actual calibration: join static predictions to live meters.

The analyzer prices every app before it runs — per-query selectivity and
state bytes (analysis/cost.py), compile-cause counts, group dispatch
reductions and encoded wire B/ev (analysis/fusion.py) — and the runtime
meters what actually happened (registry throughput/memory, the compile
ledger, group_report, the roofline split). The join key is the component
name, which both sides share *by design* (`query.{qid}`,
`stream.{sid}.fused`, `stream.{sid}.fusedgroup.{g}`). This module closes
the loop: a CalibrationLedger pairs each prediction with its live
counterpart, tracks the live/predicted error ratio with EWMA drift, and
flags mispricings with stable reason codes:

    selectivity_off_4x             metered selectivity >4x off the estimate
    wire_full_width_fallback       a hinted wire lane fell back full-width
    unpredicted_recompile_cause    the compile ledger recorded a cause the
                                   plan did not price (full_width_rebuild
                                   with no hazard, deliver_set_change,
                                   donation_mismatch)
    shared_state_refcount_collapsed  a priced shared-state ring is refcounted
                                   by <2 queries ("To Share, or not to
                                   Share", PAPERS.md: sharing gone stale)

Pairing happens at `start()` and re-pairs on every churn splice / fused
rebuild (the `rearm_keyshard` precedent) — predictions are rebuilt from the
*current* AST, while cumulative mispriced counters survive re-pairing.
With `@app:statistics` absent no ledger exists at all: the zero-overhead
contract is one `is None` check.
"""

from __future__ import annotations

import math

# stable mispricing reason codes (the flag vocabulary is API: tests, CI
# and dashboards match on these strings)
REASON_SELECTIVITY = "selectivity_off_4x"
REASON_WIRE_FALLBACK = "wire_full_width_fallback"
REASON_RECOMPILE = "unpredicted_recompile_cause"
REASON_SHARED_STATE = "shared_state_refcount_collapsed"

# the six prediction kinds the ledger pairs (acceptance surface: CI
# asserts all six show up with live values on the sentinel app)
KIND_SELECTIVITY = "selectivity"
KIND_STATE_BYTES = "state_bytes"
KIND_COMPILES = "compiles"
KIND_DISPATCH = "dispatch_reduction"
KIND_WIRE_DECLARED = "wire_declared_B_per_ev"
KIND_WIRE_INFERRED = "wire_inferred_B_per_ev"

_SELECTIVITY_FACTOR = 4.0
_MIN_EVENTS = 64  # selectivity flags need this much evidence to arm
_EWMA_ALPHA = 0.3
# causes that fire in normal operation even when the plan priced none of
# them precisely (first compile of a variant, organic shape changes):
# only causes outside BOTH the prediction and this set flag a mispricing
_BASELINE_CAUSES = frozenset(
    ("first_compile", "shape_change", "tail_variant_k")
)


def _safe_ratio(live, pred):
    """live/predicted kept finite: both-zero pairs are perfectly priced
    (1.0); a zero prediction with live signal saturates at the live value
    (rather than inf, which JSON and Prometheus both reject)."""
    try:
        live = float(live)
        pred = float(pred)
    except (TypeError, ValueError):
        return None
    if not (math.isfinite(live) and math.isfinite(pred)):
        return None
    if pred == 0.0:
        return 1.0 if live == 0.0 else round(1.0 + live, 4)
    return round(live / pred, 4)


class CalibrationLedger:
    """Pairs one app's static predictions with its live meters (owned by
    SiddhiAppRuntime; exists only when `@app:statistics` is armed)."""

    def __init__(self, runtime):
        self.runtime = runtime
        self.generation = 0  # pair() count: 1 at start, +1 per re-pair
        self._pred: dict = {}  # (kind, component) -> prediction entry
        self._ewma: dict = {}  # (kind, component) -> smoothed error ratio
        # cumulative mispriced counters: (reason, component) -> count.
        # `_active` dedups while a flag persists (one increment per
        # raise, re-raised after it clears); both SURVIVE pair().
        self.mispriced: dict = {}
        self._active: set = set()

    # ---- pairing ---------------------------------------------------------

    def pair(self) -> None:
        """(Re)build the prediction table from the app's *current* AST —
        called at start() and from every fused rebuild (churn splices and
        re-formed groups re-price automatically). Never raises: the plan
        pass is advisory and must not take start() or a splice down."""
        try:
            self._pred = self._build_predictions()
            self.generation += 1
        except Exception:
            import logging

            logging.getLogger(__name__).exception(
                "calibration pairing for app '%s' raised", self.runtime.name
            )

    def _build_predictions(self) -> dict:
        from siddhi_tpu.analysis.cost import iter_query_entries
        from siddhi_tpu.analysis.fusion import build_fusion_plan

        app = self.runtime.app
        plan = build_fusion_plan(app)
        model = plan.costs
        pred: dict = {}
        # qid -> produced stream (the selectivity denominator/numerator
        # pair needs both junction meters)
        produces = {}
        for qid, q, _in_part in iter_query_entries(app):
            out = getattr(q, "output_stream", None)
            if out is not None and not getattr(out, "is_inner", False):
                produces[qid] = getattr(out, "target", None)
        group_of = {g["stream"]: g for g in plan.groups}
        for qid, qc in model.queries.items():
            comp = f"query.{qid}"
            pred[(KIND_SELECTIVITY, comp)] = {
                "predicted": qc.est_selectivity,
                "consumes": list(qc.consumed_streams),
                "produces": produces.get(qid),
            }
            pred[(KIND_STATE_BYTES, comp)] = {"predicted": qc.state_bytes}
            for p in qc.programs:
                pred[(KIND_COMPILES, p.component)] = {
                    "predicted": p.predicted_compiles,
                    "causes": dict(p.predicted_causes),
                }
        for sid, sc in model.streams.items():
            # fused-group members compile under the GROUP component
            g = group_of.get(sid)
            comp = g["component"] if g is not None else f"stream.{sid}.fused"
            causes = sc.predicted_causes()
            pred[(KIND_COMPILES, comp)] = {
                "predicted": sum(causes.values()),
                "causes": causes,
                "stream": sid,
            }
        shared_of: dict = {}
        for s in plan.shared_state:
            shared_of.setdefault(s["stream"], []).append(s)
        for g in plan.groups:
            pred[(KIND_DISPATCH, g["component"])] = {
                "predicted": g["est_dispatch_reduction"],
                "stream": g["stream"],
                "shared": [
                    {"queries": list(s["queries"]),
                     "refcount": len(s["queries"])}
                    for s in shared_of.get(g["stream"], [])
                ],
            }
        for sid, w in plan.wire.items():
            if w.get("disabled"):
                continue
            comp = f"stream.{sid}"
            inferred = set(w.get("inferred_lanes", ()))
            declared = set(w.get("encodings", ())) - inferred
            entry = {
                "predicted": w.get("encoded_B_per_ev_est"),
                "logical": w.get("logical_B_per_ev"),
                "stream": sid,
                "narrow": bool(w.get("encodings")),
            }
            # a stream with no encodings at all is still a static
            # full-width price — keep it under the declared kind
            if declared or not inferred:
                pred[(KIND_WIRE_DECLARED, comp)] = dict(entry)
            if inferred:
                pred[(KIND_WIRE_INFERRED, comp)] = {
                    **entry, "inferred_lanes": sorted(inferred),
                }
        return pred

    # ---- live observation ------------------------------------------------

    def _live_value(self, kind, component, p):
        """The live counterpart of one prediction, or None when the meter
        has no signal yet. Also returns per-pair flags."""
        rt = self.runtime
        sm = rt.statistics_manager
        flags: list = []
        if sm is None:
            return None, flags
        if kind == KIND_SELECTIVITY:
            ins = 0
            seen = False
            for sid in p["consumes"]:
                tt = sm.throughput.get(f"stream.{sid}")
                if tt is not None:
                    ins += tt.count
                    seen = True
            out = sm.throughput.get(f"stream.{p['produces']}") \
                if p.get("produces") else None
            if not seen or ins <= 0 or out is None:
                return None, flags
            live = out.count / ins
            if ins >= _MIN_EVENTS and p["predicted"]:
                r = live / p["predicted"]
                if r > _SELECTIVITY_FACTOR or r < 1.0 / _SELECTIVITY_FACTOR:
                    flags.append(REASON_SELECTIVITY)
            return round(live, 4), flags
        if kind == KIND_STATE_BYTES:
            fn = sm.memory.get(component)
            if fn is None:
                return None, flags
            try:
                return int(fn()), flags
            except Exception:
                return None, flags
        if kind == KIND_COMPILES:
            ent = sm.compile_telemetry.component(component)
            if ent is None:
                return None, flags
            predicted_causes = set(p.get("causes", ()))
            for cause, n in ent.get("causes", {}).items():
                if (
                    n > 0
                    and cause not in predicted_causes
                    and cause not in _BASELINE_CAUSES
                ):
                    flags.append(REASON_RECOMPILE)
                    break
            return ent.get("compiles", 0), flags
        if kind == KIND_DISPATCH:
            j = rt.junctions.get(p["stream"])
            fi = getattr(j, "fused_ingest", None) if j is not None else None
            gr = fi.group_report() if fi is not None else None
            if gr is None:
                return None, flags
            live = gr.get("achieved_dispatch_reduction")
            # shared-state collapse: the plan priced a >=2-query ring but
            # the live group refcounts no ring above 1 (only meaningful
            # once the group has actually fused batches)
            if (
                live is not None
                and any(s["refcount"] >= 2 for s in p.get("shared", ()))
            ):
                live_rc = [
                    s.get("refcount", 0)
                    for s in gr.get("shared_state", ())
                ]
                if not live_rc or max(live_rc) < 2:
                    flags.append(REASON_SHARED_STATE)
            return live, flags
        if kind in (KIND_WIRE_DECLARED, KIND_WIRE_INFERRED):
            sid = p["stream"]
            ent = sm.roofline().get(f"stream.{sid}")
            j = rt.junctions.get(sid)
            fi = getattr(j, "fused_ingest", None) if j is not None else None
            if p.get("narrow") and fi is not None:
                # {} is the permanent full-width fallback; None just means
                # no batch has chosen encodings yet
                narrow = getattr(fi, "_narrow", None)
                if narrow == {}:
                    flags.append(REASON_WIRE_FALLBACK)
            if ent is None:
                return None, flags
            return ent.get("wire_bytes_per_event"), flags
        return None, flags

    def observe(self) -> list[dict]:
        """One entry per prediction with its live counterpart, error ratio
        (raw + EWMA) and any active flags; updates the cumulative mispriced
        counters on flag transitions."""
        pairs: list[dict] = []
        now_active: set = set()
        for (kind, component), p in sorted(self._pred.items()):
            try:
                live, flags = self._live_value(kind, component, p)
            except Exception:
                live, flags = None, []
            ratio = _safe_ratio(live, p.get("predicted"))
            key = (kind, component)
            if ratio is not None:
                prev = self._ewma.get(key)
                self._ewma[key] = round(
                    ratio if prev is None
                    else _EWMA_ALPHA * ratio + (1 - _EWMA_ALPHA) * prev,
                    4,
                )
            for reason in flags:
                fkey = (reason, component)
                now_active.add(fkey)
                if fkey not in self._active:
                    self.mispriced[fkey] = self.mispriced.get(fkey, 0) + 1
                    bb = getattr(self.runtime, "_blackbox", None)
                    if bb is not None:  # mispricing transition = incident
                        bb.fire("calibration", f"{reason} at {component}")
            entry = {
                "kind": kind,
                "component": component,
                "predicted": p.get("predicted"),
                "live": live,
                "ratio": ratio,
                "ratio_ewma": self._ewma.get(key),
            }
            if flags:
                entry["flags"] = flags
            pairs.append(entry)
        self._active = now_active
        return pairs

    # ---- surfaces --------------------------------------------------------

    def report(self) -> dict:
        """The `/calibration(.json)` payload for one app."""
        pairs = self.observe()
        return {
            "app": self.runtime.name,
            "generation": self.generation,
            "pairs": pairs,
            "kinds_paired": sorted(
                {p["kind"] for p in pairs if p["live"] is not None}
            ),
            "flags": sorted(
                {f for p in pairs for f in p.get("flags", ())}
            ),
            "mispriced": [
                {"reason": reason, "component": component, "count": n}
                for (reason, component), n in sorted(self.mispriced.items())
            ],
            "mispriced_total": sum(self.mispriced.values()),
        }

    def prometheus_section(self) -> dict:
        """The `calibration` section of StatisticsManager.report(), feeding
        `siddhi_calibration_error_ratio{kind=,component=}` and
        `siddhi_calibration_mispriced_total` (reporters.py)."""
        pairs = self.observe()
        return {
            "pairs": [
                {
                    "kind": p["kind"],
                    "component": p["component"],
                    "ratio": p["ratio_ewma"],
                }
                for p in pairs
                if p.get("ratio_ewma") is not None
            ],
            "mispriced": [
                {"reason": reason, "component": component, "count": n}
                for (reason, component), n in sorted(self.mispriced.items())
            ],
        }

    def pairs_for_component(self, component: str) -> dict:
        """{kind: pair entry} for one component — explain()'s `calib:`
        lines (observability/explain.py) read this per query/stream node."""
        out = {}
        for p in self.observe():
            if p["component"] == component:
                out[p["kind"]] = p
        return out

    def describe_state(self) -> dict:
        return {
            "generation": self.generation,
            "pairs": len(self._pred),
            "mispriced_total": sum(self.mispriced.values()),
        }


def render_calibration_text(reports: dict) -> str:
    """Plain-text `/calibration` rendering over
    manager.calibration_reports()."""
    lines = []
    for app, rep in sorted(reports.items()):
        lines.append(
            f"app '{app}'  generation={rep['generation']}  "
            f"kinds={','.join(rep['kinds_paired']) or '-'}  "
            f"mispriced={rep['mispriced_total']}"
        )
        for p in rep["pairs"]:
            flag = (
                "  !! " + ",".join(p["flags"]) if p.get("flags") else ""
            )
            lines.append(
                f"  {p['kind']} {p['component']}: "
                f"pred={p['predicted']} live={p['live']} "
                f"x{p['ratio']} ewma={p['ratio_ewma']}{flag}"
            )
        for m in rep["mispriced"]:
            lines.append(
                f"  mispriced {m['reason']} {m['component']}: {m['count']}"
            )
    return "\n".join(lines) + "\n"
