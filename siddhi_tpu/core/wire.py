"""Compact wire encodings: analyzer-chosen per-column codecs for the h2d link.

The r04 roofline attribution showed the headline ingest legs are
TRANSFER-bound, not compute-bound: `filter_window_avg` shipped 12 B/event
over a ~54 MB/s h2d link while the device could sustain 3x the delivered
rate. This module attacks the bytes, not the kernel (TiLT's
compile-to-compact-representation, PAPERS.md): the analysis package selects
per-column wire encodings STATICALLY from the declared types and value
ranges, the host encodes into the compact form, and the matching decode is
fused into the already-jitted chunk program (core/ingest.py) — bytes stay
compressed across the link and the host never materializes wide columns.

Encoders (per lane of the fused wire):

* ``narrow``  — integer downcast (int64 -> int32/int16/int8). Chosen
  statically from a declared `@app:wire(range.S.col='lo..hi')` contract, or
  sampled from the first engaged send (the pre-existing
  `StreamSchema.propose_narrow` behavior, kept as the fallback).
* ``dict``    — per-chunk dictionary encoding for low-cardinality
  string/interned columns (`@app:wire(dict.S.col='N')`): each micro-batch
  ships uint8/uint16 codes plus an N-slot dictionary of the original int32
  ids; decode is a device-side gather.
* ``delta``   — per-batch base + consecutive diffs for declared-monotone
  int/long columns (`@app:wire(delta.S.col='int16')`), reconstructed with a
  device cumsum — the same trick the built-in timestamp lane (`__tsd__`)
  already plays, extended to payload columns (event-time seqs, counters).
* ``bitpack`` — BOOL columns ride 1 bit/value (np.packbits on the host,
  shift-and-mask unpack on device). Always safe, applied whenever wire
  encoding is enabled; no hint needed.

Every encoder is guarded per chunk: a batch that violates the static
assumption (value out of the declared range, dictionary cardinality
overflow, delta outside the narrow dtype) raises `WireNarrowMisfit` and the
sender rebuilds the chunk program FULL-WIDTH (once, permanent) — the same
fallback path the sampled narrow wire has always used — so emissions are
byte-identical encode-on vs encode-off.

Toggle: `@app:wire(disable='true')` on the app, overridden process-wide by
SIDDHI_TPU_WIRE=1 (force on) / SIDDHI_TPU_WIRE=0 (force off: the wire ships
FULL-WIDTH lanes — no narrowing, no sampling — which is what the CI parity
step diffs against). The annotation is validated here (the runtime analog
of the analyzer's SA132, one shared rule set like SA125-SA131).

The per-stream `WireSpec` (versioned) is also emitted into the FusionPlan
(analysis/fusion.py `plan.wire`) so the static contract — which encoder
serves which column, and the predicted logical-vs-encoded bytes/event — is
inspectable before any runtime exists.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

import numpy as np

from siddhi_tpu.core.types import AttrType, PHYSICAL_DTYPE

WIRE_ENV = "SIDDHI_TPU_WIRE"

# value-analysis inferred encoders (analysis/values.py): default ON; set
# SIDDHI_TPU_WIRE_INFER=0 to fall back to declared @app:wire hints only.
# Independent of WIRE_ENV: inference chooses encoders, WIRE_ENV gates
# whether any encoder runs at all.
WIRE_INFER_ENV = "SIDDHI_TPU_WIRE_INFER"

WIRE_SPEC_VERSION = 1

_TRUE = ("1", "on", "true", "force")
_FALSE = ("0", "off", "false")

# hint kinds accepted as `@app:wire(<kind>.<Stream>.<col>='...')`
_HINT_KINDS = ("range", "dict", "delta")

_DELTA_DTYPES = {
    "true": np.dtype(np.int16),  # delta.S.col='true' -> default int16 diffs
    "int8": np.dtype(np.int8),
    "int16": np.dtype(np.int16),
    "int32": np.dtype(np.int32),
}

_INTEGRAL = (AttrType.INT, AttrType.LONG)
_INTERNED = (AttrType.STRING, AttrType.OBJECT)


def wire_env_override() -> Optional[bool]:
    """Process-wide wire-encoding toggle: True (forced on), False (forced
    off), or None (defer to the app's @app:wire annotation)."""
    v = os.environ.get(WIRE_ENV, "").strip().lower()
    if v in _TRUE:
        return True
    if v in _FALSE:
        return False
    return None


def wire_inference_enabled() -> bool:
    """Whether inferred wire hints (analysis/values.py) overlay the
    declared ones. On by default; SIDDHI_TPU_WIRE_INFER=0 disables."""
    return os.environ.get(WIRE_INFER_ENV, "").strip().lower() not in _FALSE


def _parse_range(v) -> Optional[tuple[int, int]]:
    try:
        lo_s, hi_s = str(v).split("..", 1)
        lo, hi = int(lo_s), int(hi_s)
    except (TypeError, ValueError):
        return None
    return (lo, hi) if lo <= hi else None


def iter_wire_annotation_problems(ann, streams: Optional[dict] = None):
    """Yield one message per malformed `@app:wire` element — THE validation
    rules, shared by the runtime resolver (raises on the first) and the
    analyzer's SA132 diagnostics (reports them all), so the two can never
    drift. With `streams` (the analyzer's symbol table: sid -> {attr ->
    AttrType}), hint targets are also checked for existence and encoder/type
    compatibility."""
    for k, v in ann.elements:
        if k == "disable":
            if str(v).strip().lower() not in ("true", "false"):
                yield f"@app:wire disable '{v}' must be true or false"
            continue
        if k is None:
            yield (
                f"unknown @app:wire option '{v}' (expected disable, "
                "range.<stream>.<col>, dict.<stream>.<col>, "
                "delta.<stream>.<col>)"
            )
            continue
        parts = str(k).split(".")
        if len(parts) != 3 or parts[0] not in _HINT_KINDS:
            yield (
                f"unknown @app:wire option '{k}' (expected disable, "
                "range.<stream>.<col>, dict.<stream>.<col>, "
                "delta.<stream>.<col>)"
            )
            continue
        kind, sid, col = parts
        if kind == "range":
            if _parse_range(v) is None:
                yield (
                    f"@app:wire {k} '{v}' must be 'lo..hi' with integer "
                    "lo <= hi"
                )
        elif kind == "dict":
            try:
                ok = 2 <= int(v) <= 65536
            except (TypeError, ValueError):
                ok = False
            if not ok:
                yield (
                    f"@app:wire {k} '{v}' must be an integer dictionary "
                    "capacity in 2..65536"
                )
        elif kind == "delta":
            if str(v).strip().lower() not in _DELTA_DTYPES:
                yield (
                    f"@app:wire {k} '{v}' must be true, int8, int16, or "
                    "int32"
                )
        if streams is None:
            continue
        schema = streams.get(sid)
        if sid not in streams:
            yield f"@app:wire {k}: unknown stream '{sid}'"
            continue
        if schema is None:
            continue  # open schema: attribute checks are skipped
        if col not in schema:
            yield f"@app:wire {k}: stream '{sid}' has no attribute '{col}'"
            continue
        t = schema[col]
        if t is None:
            continue
        if kind in ("range", "delta") and t not in _INTEGRAL:
            yield (
                f"@app:wire {k}: '{col}' is {t.name}; {kind} encoding "
                "needs an INT or LONG column"
            )
        elif kind == "dict" and t not in _INTEGRAL + _INTERNED:
            yield (
                f"@app:wire {k}: '{col}' is {t.name}; dict encoding needs "
                "a STRING/OBJECT (interned) or INT/LONG column"
            )


def parse_wire_hints(ann) -> dict:
    """(stream_id, col) -> hint tuple from a (validated) `@app:wire`
    annotation: ("range", lo, hi) | ("dict", card) | ("delta", np.dtype).
    Malformed elements are skipped (the validator reports them)."""
    hints: dict = {}
    if ann is None:
        return hints
    for k, v in ann.elements:
        if k is None or k == "disable":
            continue
        parts = str(k).split(".")
        if len(parts) != 3 or parts[0] not in _HINT_KINDS:
            continue
        kind, sid, col = parts
        if kind == "range":
            r = _parse_range(v)
            if r is not None:
                hints[(sid, col)] = ("range",) + r
        elif kind == "dict":
            try:
                card = int(v)
            except (TypeError, ValueError):
                continue
            if 2 <= card <= 65536:
                hints[(sid, col)] = ("dict", card)
        elif kind == "delta":
            dt = _DELTA_DTYPES.get(str(v).strip().lower())
            if dt is not None:
                hints[(sid, col)] = ("delta", dt)
    return hints


def resolve_wire_annotation(ann) -> tuple[bool, dict]:
    """(enabled, hints) for one app from its `@app:wire` annotation (or
    None) plus the SIDDHI_TPU_WIRE env override. Raises
    SiddhiAppCreationError on malformed options — the runtime analog of the
    analyzer's SA132 diagnostic."""
    from siddhi_tpu.core.errors import SiddhiAppCreationError

    enabled = True
    hints: dict = {}
    if ann is not None:
        for problem in iter_wire_annotation_problems(ann):
            raise SiddhiAppCreationError(problem)
        enabled = (
            str(ann.element("disable", "false")).strip().lower() != "true"
        )
        hints = parse_wire_hints(ann)
    env = wire_env_override()
    if env is not None:
        enabled = env
    return enabled, hints


# ---------------------------------------------------------------------------
# WireSpec: the static per-stream encoding choice
# ---------------------------------------------------------------------------


def _narrow_for_range(lo: int, hi: int, wide: np.dtype) -> Optional[np.dtype]:
    """Smallest integer dtype covering the DECLARED [lo, hi] contract (no
    sampling margin — out-of-range values hit the runtime guard)."""
    for nd in (np.int8, np.int16, np.int32):
        dt = np.dtype(nd)
        if dt.itemsize >= wide.itemsize:
            return None
        info = np.iinfo(dt)
        if lo >= info.min and hi <= info.max:
            return dt
    return None


@dataclasses.dataclass
class WireSpec:
    """Versioned static wire-encoding choice for one stream.

    `encodings` maps lane names (attribute names; "__tsd__" for the
    timestamp-delta lane) to normalized entries:
    ("narrow", np.dtype) | ("dict", code np.dtype, card) |
    ("delta", np.dtype) | ("bitpack",). Lanes absent from the map ride
    full-width."""

    stream_id: str
    encodings: dict = dataclasses.field(default_factory=dict)
    source: str = "static"
    version: int = WIRE_SPEC_VERSION
    # lanes whose encoding was PROVEN by value analysis rather than
    # declared via @app:wire (provenance for the plan + explain())
    inferred_lanes: list = dataclasses.field(default_factory=list)

    def to_dict(self) -> dict:
        out = {
            "version": self.version,
            "stream": self.stream_id,
            "source": self.source,
            "encodings": {
                lane: encoding_label(e)
                for lane, e in sorted(self.encodings.items())
            },
        }
        if self.inferred_lanes:
            out["inferred_lanes"] = sorted(self.inferred_lanes)
        return out


def encoding_label(entry) -> str:
    """Human/JSON-stable label for one encoding entry (also used by
    explain() and the FusionPlan wire section)."""
    if isinstance(entry, np.dtype) or not isinstance(entry, tuple):
        return f"narrow:{np.dtype(entry).name}"
    kind = entry[0]
    if kind == "narrow":
        return f"narrow:{np.dtype(entry[1]).name}"
    if kind == "dict":
        return f"dict:{np.dtype(entry[1]).name}[{entry[2]}]"
    if kind == "delta":
        return f"delta:{np.dtype(entry[1]).name}"
    if kind == "bitpack":
        return "bitpack:1bit"
    return str(entry)


def _hint_entry(hint, t: AttrType, wide: np.dtype) -> Optional[tuple]:
    """Encoding entry for one hint tuple against one declared type, or
    None when the hint does not apply / does not shrink the lane."""
    if hint is None:
        return None
    if hint[0] == "range" and t in _INTEGRAL:
        dt = _narrow_for_range(int(hint[1]), int(hint[2]), wide)
        if dt is not None:
            return ("narrow", dt)
    elif hint[0] == "dict" and t in _INTEGRAL + _INTERNED:
        card = int(hint[1])
        code = np.dtype(np.uint8 if card <= 256 else np.uint16)
        if code.itemsize < wide.itemsize:
            return ("dict", code, card)
    elif hint[0] == "delta" and t in _INTEGRAL:
        dt = np.dtype(hint[1])
        if dt.itemsize < wide.itemsize:
            return ("delta", dt)
    return None


def build_wire_spec(
    stream_id: str,
    attrs,
    hints: dict,
    capacity: Optional[int] = None,
    inferred: Optional[dict] = None,
) -> Optional[WireSpec]:
    """Static per-stream spec from declared attribute types + `@app:wire`
    hints, optionally overlaid with value-analysis `inferred` hints (same
    (sid, col) -> hint-tuple shape; a DECLARED hint wins its lane — the
    user's contract beats a proof, and both ride the same per-chunk misfit
    guard, so a wrong proof can only cost a full-width rebuild, never
    wrong bytes). `attrs` is [(name, AttrType)] (StreamSchema.attrs or the
    analyzer's schema items). With `capacity` (the micro-batch row count
    each chunk amortizes a dictionary/delta header over) an encoding is
    kept only when its amortized bytes/row actually undercut the wide
    lane — e.g. dict.col='64' on an int32 column at batch 64 would SHIP
    64 codes + a 256-byte dictionary per chunk (320 B vs 256 B full
    width), so it is dropped. Returns None when nothing is statically
    encodable (the sampled narrow wire then stands alone)."""
    enc: dict = {}
    inferred_lanes: list = []
    for name, t in attrs:
        if t is None:
            continue
        wide = np.dtype(PHYSICAL_DTYPE[t])
        entry = None
        from_inference = False
        if t is AttrType.BOOL:
            # 1 bit/value, lossless, guard-free: on whenever wire
            # encoding is enabled
            entry = ("bitpack",)
        else:
            entry = _hint_entry(hints.get((stream_id, name)), t, wide)
            if entry is None and inferred is not None:
                entry = _hint_entry(
                    inferred.get((stream_id, name)), t, wide
                )
                from_inference = entry is not None
        if entry is None:
            continue
        if capacity is not None and lane_bytes_per_row(
            name, wide, entry, capacity
        ) >= wide.itemsize:
            continue  # net loss at this chunk shape: stay wide
        enc[name] = entry
        if from_inference:
            inferred_lanes.append(name)
    if not enc:
        return None
    declared = [
        lane for lane in enc
        if lane not in inferred_lanes and enc[lane][0] != "bitpack"
    ]
    source = "static"
    if inferred_lanes:
        source = "static+inferred" if declared else "inferred"
    return WireSpec(
        stream_id, enc, source=source, inferred_lanes=inferred_lanes
    )


def app_wire_specs(
    app, sym_streams: dict, stream_ids, capacity: int,
    inferred: Optional[dict] = None,
):
    """(disabled, {sid: (attrs, spec)}) for the given consumed streams —
    ONE preamble (annotation fetch, disable parse, hint parsing, spec
    building with the optional inferred overlay) shared by the analyzer's
    SA133/SA138 lint (analysis/cost.py) and the FusionPlan wire section
    (analysis/fusion.py), so hint resolution can never drift between
    them. Streams with open/unknown schemas are skipped."""
    from siddhi_tpu.query_api.annotation import find_annotation

    ann = find_annotation(app.annotations, "app:wire")
    disabled = ann is not None and str(
        ann.element("disable", "false")
    ).strip().lower() == "true"
    hints = parse_wire_hints(ann)
    if not wire_inference_enabled():
        inferred = None
    out: dict = {}
    for sid in stream_ids:
        schema = sym_streams.get(sid)
        if not schema or any(t is None for t in schema.values()):
            continue
        attrs = list(schema.items())
        out[sid] = (
            attrs, build_wire_spec(sid, attrs, hints, capacity, inferred)
        )
    return disabled, out


def choose_encodings(
    schema,
    keep,
    spec: Optional[WireSpec],
    enabled: bool,
    ts_sample,
    cols_sample,
) -> dict:
    """The one place the wire-encoding decision is made for an engaging
    fused ingest: disabled -> {} (FULL-WIDTH wire, no sampling, no
    narrowing — the parity baseline); enabled -> the sampled narrow map
    (`propose_narrow`, the pre-existing behavior) overlaid with the static
    spec's entries (static wins per lane: a declared contract beats a
    sample)."""
    if not enabled:
        return {}
    enc = schema.propose_narrow(ts_sample, cols_sample, keep)
    if spec is not None:
        for lane, entry in spec.encodings.items():
            if keep is not None and lane not in keep and lane != "__tsd__":
                continue
            enc[lane] = entry
    return enc


def encodings_source(enc: dict, spec: Optional[WireSpec]) -> str:
    """'full-width' | 'sampled' | 'static' | 'static+sampled' — for
    describe_state()/explain()."""
    if not enc:
        return "full-width"
    has_static = any(isinstance(e, tuple) for e in enc.values())
    has_sampled = any(not isinstance(e, tuple) for e in enc.values())
    if has_static and has_sampled:
        return "static+sampled"
    return "static" if has_static else "sampled"


def logical_row_bytes(attrs) -> int:
    """Full-width bytes/event the h2d link would carry with NO wire
    encoding (the packed per-batch codec: int64 ts + every column at its
    physical width) — the roofline's logical numerator."""
    total = 8  # int64 timestamp
    for _name, t in attrs:
        total += np.dtype(PHYSICAL_DTYPE[t or AttrType.LONG]).itemsize
    return total


def estimate_wire_bytes(
    attrs, spec: Optional[WireSpec], capacity: int = 8192
) -> int:
    """Static per-event estimate of the encoded wire (tsd int32 default —
    sampling may shrink it further at runtime), for the FusionPlan wire
    section and SA133."""
    enc = dict(spec.encodings) if spec is not None else {}
    total = 4.0  # __tsd__ int32 default
    for name, t in attrs:
        wide = np.dtype(PHYSICAL_DTYPE[t or AttrType.LONG])
        total += lane_bytes_per_row(name, wide, enc.get(name), capacity)
    return int(round(total))


def lane_bytes_per_row(
    name: str, wide: np.dtype, entry, capacity: int
) -> float:
    """Amortized wire bytes/row of one lane under an encoding entry."""
    if entry is None:
        return wide.itemsize
    if not isinstance(entry, tuple):
        return np.dtype(entry).itemsize
    kind = entry[0]
    if kind == "narrow":
        return np.dtype(entry[1]).itemsize
    if kind == "dict":
        return np.dtype(entry[1]).itemsize + entry[2] * wide.itemsize / max(
            capacity, 1
        )
    if kind == "delta":
        return np.dtype(entry[1]).itemsize + 8.0 / max(capacity, 1)
    if kind == "bitpack":
        return 0.125
    return wide.itemsize


# ---------------------------------------------------------------------------
# the generalized codec builder (hosts encode, device decode)
# ---------------------------------------------------------------------------


def _normalize(entry) -> tuple:
    """Plain dtypes (the sampled-narrow legacy form) normalize to
    ("narrow", dtype); tuples pass through."""
    if isinstance(entry, tuple):
        return entry
    return ("narrow", np.dtype(entry))


def _lane_nbytes(kind: str, cap: int, wire_dt, wide_dt, card: int) -> int:
    if kind == "dict":
        return cap * wire_dt.itemsize + card * wide_dt.itemsize
    if kind == "delta":
        return 8 + cap * wire_dt.itemsize
    if kind == "bitpack":
        return -(-cap // 8)
    return cap * wire_dt.itemsize  # narrow / wide


def build_codec(schema, capacity: int, keep, narrow: dict):
    """The fused-ingest wire codec: encode(ts, cols, n) -> (buf u8[total],
    base int64); decode(buf, n, base) -> EventBatch. Generalizes the
    original narrow-downcast codec with the dict/delta/bitpack encoders;
    `narrow` maps lane names to encoding entries (plain np.dtype = legacy
    narrow downcast). Invoked through `StreamSchema.wire_codec` (which owns
    the cache); see that docstring for the wire-shrinking contract."""
    import jax
    import jax.numpy as jnp

    from siddhi_tpu.core.event import (
        EventBatch,
        WireNarrowMisfit,
        _bitcast_split,
    )
    from siddhi_tpu.core.types import null_value
    from siddhi_tpu.ops.prefix import cumsum as prefix_cumsum

    narrow = {k: _normalize(v) for k, v in (narrow or {}).items()}
    cap = int(capacity)
    kept = [
        (name, t) for name, t in schema.attrs
        if keep is None or name in keep
    ]
    dropped = [
        (name, t) for name, t in schema.attrs
        if not (keep is None or name in keep)
    ]

    # (lane, kind, wire dtype, decoded dtype, dict card)
    tsd_entry = narrow.get("__tsd__", ("narrow", np.dtype(np.int32)))
    sections: list[tuple] = [(
        "__tsd__", "narrow", np.dtype(tsd_entry[1]), np.dtype(np.int32), 0
    )]
    for name, t in kept:
        wide = np.dtype(PHYSICAL_DTYPE[t])
        entry = narrow.get(name)
        if entry is None:
            sections.append((name, "wide", wide, wide, 0))
            continue
        kind = entry[0]
        if kind == "narrow":
            sections.append((name, "narrow", np.dtype(entry[1]), wide, 0))
        elif kind == "dict":
            sections.append(
                (name, "dict", np.dtype(entry[1]), wide, int(entry[2]))
            )
        elif kind == "delta":
            sections.append((name, "delta", np.dtype(entry[1]), wide, 0))
        elif kind == "bitpack":
            sections.append((name, "bitpack", np.dtype(np.uint8), wide, 0))
        else:
            sections.append((name, "wide", wide, wide, 0))
    offsets = []
    off = 0
    for _name, kind, wire_dt, wide_dt, card in sections:
        offsets.append(off)
        off += _lane_nbytes(kind, cap, wire_dt, wide_dt, card)
    total = off

    tsd_diff = sections[0][2].itemsize < 4  # narrow tsd = diff-coded

    def _check_fits(src, dt: np.dtype, name: str) -> None:
        if src.size == 0:
            return
        info = np.iinfo(dt)
        if int(src.min()) < info.min or int(src.max()) > info.max:
            raise WireNarrowMisfit(name)

    def encode(timestamps: np.ndarray, cols: dict, n: int):
        base = np.int64(timestamps[0]) if n > 0 else np.int64(0)
        buf = np.zeros((total,), dtype=np.uint8)
        for (name, kind, dt, wide, card), o in zip(sections, offsets):
            if name == "__tsd__":
                ts64 = timestamps[:n].astype(np.int64, copy=False)
                if n > 0 and (
                    int(ts64.max()) - int(base) >= (1 << 31)
                    or int(ts64.min()) - int(base) < -(1 << 31)
                ):
                    raise ValueError(
                        "wire_codec: timestamp span exceeds int32 deltas "
                        "(>~24.8 days per batch); use packed_codec"
                    )
                src = (
                    np.diff(ts64, prepend=base) if tsd_diff
                    else ts64 - base
                )
                if dt.itemsize < 4:
                    _check_fits(src, dt, name)
                buf[o : o + cap * dt.itemsize].view(dt)[:n] = src.astype(
                    dt, copy=False
                )
                continue
            src = np.asarray(cols[name])[:n]
            if kind == "wide":
                buf[o : o + cap * dt.itemsize].view(dt)[:n] = src.astype(
                    dt, copy=False
                )
            elif kind == "narrow":
                if dt.itemsize < wide.itemsize:
                    _check_fits(src, dt, name)
                buf[o : o + cap * dt.itemsize].view(dt)[:n] = src.astype(
                    dt, copy=False
                )
            elif kind == "dict":
                # per-chunk dictionary: codes + the batch's unique values;
                # cardinality overflow = the runtime guard (full-width
                # fallback), so a mis-declared stream stays correct
                uniq, inv = np.unique(src, return_inverse=True)
                if uniq.size > card:
                    raise WireNarrowMisfit(name)
                codes = buf[o : o + cap * dt.itemsize].view(dt)
                if n > 0:
                    codes[:n] = inv.astype(dt, copy=False)
                vals = buf[
                    o + cap * dt.itemsize
                    : o + cap * dt.itemsize + card * wide.itemsize
                ].view(wide)
                vals[: uniq.size] = uniq.astype(wide, copy=False)
            elif kind == "delta":
                d_base = np.int64(src[0]) if n > 0 else np.int64(0)
                d = np.diff(
                    src.astype(np.int64, copy=False), prepend=d_base
                )
                _check_fits(d, dt, name)
                buf[o : o + 8].view(np.int64)[0] = d_base
                buf[o + 8 : o + 8 + cap * dt.itemsize].view(dt)[:n] = (
                    d.astype(dt, copy=False)
                )
            elif kind == "bitpack":
                if n > 0:
                    packed = np.packbits(src.astype(bool), bitorder="big")
                    buf[o : o + packed.size] = packed
        return buf, base

    def decode(buf, n, base):
        cols_out = {}
        ts = None
        for (name, kind, dt, wide, card), o in zip(sections, offsets):
            if name == "__tsd__":
                arr = _bitcast_split(buf, o, cap, dt)
                if tsd_diff:
                    arr = jnp.cumsum(arr.astype(jnp.int32))
                ts = base + arr.astype(jnp.int64)
            elif kind == "dict":
                codes = _bitcast_split(buf, o, cap, dt)
                vals = _bitcast_split(
                    buf, o + cap * dt.itemsize, card, wide
                )
                cols_out[name] = vals[codes.astype(jnp.int32)]
            elif kind == "delta":
                d_base = _bitcast_split(buf, o, 1, np.dtype(np.int64))[0]
                d = _bitcast_split(buf, o + 8, cap, dt)
                # ops.prefix.cumsum, not jnp.cumsum: the native int64
                # lowering ran out of scoped vmem on the v5e at B=32768
                # (RESOURCE_EXHAUSTED in reduce-window, PR 21); integer
                # adds are exact under the blocked scan's reassociation
                vals = d_base + prefix_cumsum(d.astype(jnp.int64))
                cols_out[name] = vals.astype(jnp.dtype(wide))
            elif kind == "bitpack":
                nb = -(-cap // 8)
                seg = jax.lax.slice(buf, (o,), (o + nb,))
                idx = jnp.arange(cap, dtype=jnp.int32)
                byte = seg[idx >> 3]
                bit = (byte >> (7 - (idx & 7))) & 1
                cols_out[name] = bit.astype(jnp.bool_)
            else:
                arr = _bitcast_split(buf, o, cap, dt)
                cols_out[name] = arr.astype(jnp.dtype(wide))
        for name, t in dropped:
            nv = null_value(t)
            cols_out[name] = jnp.full(
                (cap,),
                np.asarray(0 if nv is None else nv, PHYSICAL_DTYPE[t]),
                dtype=PHYSICAL_DTYPE[t],
            )
        cols_out = {n2: cols_out[n2] for n2, _ in schema.attrs}
        valid = jnp.arange(cap, dtype=jnp.int32) < n
        return EventBatch(
            ts=ts,
            kind=jnp.zeros((cap,), jnp.int8),
            valid=valid,
            cols=cols_out,
        )

    return encode, decode, total


def wire_report(
    schema, keep, narrow: dict, spec: Optional[WireSpec],
    capacity: int = 8192,
) -> dict:
    """describe_state()/explain() wire summary for one engaged fused
    ingest: per-lane encoding labels + encoded vs logical bytes/event,
    amortizing dict/delta headers over `capacity` (the junction's real
    micro-batch rows — a hard-coded large capacity would overstate the
    reduction on small batches)."""
    enc = {k: _normalize(v) for k, v in (narrow or {}).items()}
    kept = [
        (name, t) for name, t in schema.attrs
        if keep is None or name in keep
    ]
    lanes = {
        "__tsd__": encoding_label(
            enc.get("__tsd__", ("narrow", np.dtype(np.int32)))
        )
    }
    encoded = np.dtype(
        enc.get("__tsd__", ("narrow", np.dtype(np.int32)))[1]
    ).itemsize * 1.0
    for name, t in kept:
        wide = np.dtype(PHYSICAL_DTYPE[t])
        e = enc.get(name)
        lanes[name] = encoding_label(e) if e is not None else (
            f"wide:{wide.name}"
        )
        encoded += lane_bytes_per_row(name, wide, e, capacity)
    return {
        "source": encodings_source(narrow or {}, spec),
        "spec_version": spec.version if spec is not None else None,
        "lanes": lanes,
        "encoded_B_per_ev": round(encoded, 2),
        "logical_B_per_ev": logical_row_bytes(schema.attrs),
        "projected_out": [name for name, _t in schema.attrs
                          if keep is not None and name not in keep],
    }
