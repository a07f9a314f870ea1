"""The engine's stage spans on the profiler's clock (observability/profiler.py
`stage`), the device scopes of the chunk program, and the process-wide
compile counter.

A small fused deliver-mode app and a per-batch app run inside a
`jax.profiler` session (TraceMe events only, as the benchmark's traced run
records them); the `.xplane.pb` is read back with `ProfileData` and the
`siddhi:*` events are checked by name, by the ids that tie them together and
by containment. Span and scope names are fixed (PERF.md §3): a reader of a
trace finds them by these strings.
"""

from __future__ import annotations

import gc
import glob
import sys
import time

import jax
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.observability import profiler as profiler_mod

B = 32
K = 4  # micro-batches per chunk

APP = f"""
@app:batch(size='{B}')
@app:ingestChunk(size='{K}')
define stream S (k int, v float);
@info(name='q')
from S[v > 10]#window.length(64)
select k, avg(v) as a group by k insert into Out;
"""

FUSED_SENDER = {
    "siddhi:encode", "siddhi:h2d", "siddhi:lock_wait", "siddhi:dispatch",
    "siddhi:slot_wait", "siddhi:submit_wait", "siddhi:barrier",
    "siddhi:readback_start",
}
DRAIN_CHILDREN = {
    "siddhi:readback_wait", "siddhi:readback", "siddhi:decode",
    "siddhi:callback", "siddhi:release",
}
BATCH_CHILDREN = {
    "siddhi:encode", "siddhi:publish", "siddhi:step", "siddhi:readback",
    "siddhi:decode", "siddhi:callback",
}


def _deploy(text=APP, slow_callback_s=0.0):
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(text)
    got = []

    def callback(ts, ins, removed):
        got.extend(ins or [])
        if slow_callback_s:
            time.sleep(slow_callback_s)

    rt.add_callback("q", callback)
    rt.start()
    return mgr, rt, got


def _send(rt, n, start, low=False):
    """n rows; `low` keeps all but a few under the filter, so that the next
    send's drain undershoots its guess and tops up (`siddhi:readback`)."""
    v = np.full((n,), 5.0 if low else 50.0, dtype=np.float32)
    v[:: 16] = 50.0
    rt.get_input_handler("S").send_columns(
        np.arange(n, dtype=np.int64) + start,
        {"k": (np.arange(n) % 5).astype(np.int32), "v": v},
    )


def _traced(tmp_path, body):
    """Run `body()` inside a profiler session; the `siddhi:*` events as
    dicts (name, start, end, and their stats)."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(profiler_mod.SPAN_PREFIX):
                    events.append({
                        "name": ev.name, "line": line.name,
                        "t0": ev.start_ns, "t1": ev.start_ns + ev.duration_ns,
                        **dict(ev.stats),
                    })
    return events


def _inside(child, parent) -> bool:
    return parent["t0"] <= child["t0"] and child["t1"] <= parent["t1"]


def test_fused_path_spans(tmp_path, monkeypatch):
    # these chunks hold fewer rows than the least a read asks for at a
    # deployment's size: without that floor the small guess below is short
    # here too, and the second read's span (`readback`) is there to see
    import siddhi_tpu.core.ingest as ingest

    monkeypatch.setattr(ingest, "_LEAST_READ_ROWS", 1)
    # a callback slower than a chunk's dispatch backs the drain up, so the
    # sender meets the bounded queue (submit_wait) and the barrier
    mgr, rt, got = _deploy(slow_callback_s=0.004)
    chunk = B * K
    _send(rt, 2 * chunk, 0, low=True)  # builds the program, small guess

    def body():
        _send(rt, 6 * chunk, 10_000)
        gc.collect()
        rt.queries["q"].flush_aux_warnings()

    events = _traced(tmp_path, body)
    rt.shutdown()
    mgr.shutdown()
    assert got
    by_name: dict = {}
    for ev in events:
        by_name.setdefault(ev["name"], []).append(ev)
    wanted = FUSED_SENDER | DRAIN_CHILDREN | {
        "siddhi:send", "siddhi:drain", "siddhi:aux_drain", "siddhi:gc",
    }
    assert wanted <= set(by_name), sorted(wanted - set(by_name))

    (send,) = by_name["siddhi:send"]
    assert send["path"] == "fused" and send["stream"] == "S"
    assert send["rows"] == 6 * chunk
    drains = by_name["siddhi:drain"]
    assert len(drains) == 6
    for d in drains:
        assert d["send"] == send["send"] and d["queued_us"] >= 0
        # the hand-off: a drain shares its chunk with the sender's spans
        for name in ("siddhi:encode", "siddhi:dispatch"):
            assert any(e["chunk"] == d["chunk"] for e in by_name[name]), name
    for name in FUSED_SENDER:
        for ev in by_name[name]:
            assert ev["send"] == send["send"], ev
            assert _inside(ev, send), ev
    for name in DRAIN_CHILDREN:
        for ev in by_name[name]:
            (parent,) = [d for d in drains if d["chunk"] == ev["chunk"]]
            assert _inside(ev, parent), ev
    # every chunk's read is started once by the sender, between its dispatch
    # and the next chunk's encode, finished once on the reader thread and
    # awaited once by its drain
    for name in ("siddhi:readback_start", "siddhi:readback_copy",
                 "siddhi:readback_wait"):
        assert sorted(e["chunk"] for e in by_name[name]) == sorted(
            d["chunk"] for d in drains
        ), name
    for copy in by_name["siddhi:readback_copy"]:
        (wait,) = [
            e for e in by_name["siddhi:readback_wait"]
            if e["chunk"] == copy["chunk"]
        ]
        assert copy["send"] == send["send"] and copy["t1"] <= wait["t1"]
    for start in by_name["siddhi:readback_start"]:
        (dispatch,) = [
            e for e in by_name["siddhi:dispatch"]
            if e["chunk"] == start["chunk"]
        ]
        (wait,) = [
            e for e in by_name["siddhi:readback_wait"]
            if e["chunk"] == start["chunk"]
        ]
        assert dispatch["t1"] <= start["t0"] and start["t1"] <= wait["t1"]
    calls = by_name["siddhi:callback"]
    assert sum(c["rows"] for c in calls) == len(got) - sum(
        1 for e in got if e[0] < 10_000
    )
    assert {c["batch"] for c in calls} == set(range(K))
    # a chunk's first decode span cuts the lane views; each micro-batch's
    # says which body built its Events (native/decode.cpp, built at deploy)
    decodes = by_name["siddhi:decode"]
    assert [d["impl"] for d in decodes if "impl" in d] == (
        ["native"] * len(calls)
    )
    assert len(decodes) == len(calls) + len(drains)
    assert any(g["generation"] == 2 for g in by_name["siddhi:gc"])
    assert all("collected" in g for g in by_name["siddhi:gc"])
    assert by_name["siddhi:aux_drain"][0]["flags"] >= 1


@pytest.mark.parametrize("impl", ["native", "python"])
def test_the_drain_holds_one_micro_batch_of_events_at_a_time(
    impl, monkeypatch
):
    """`deliver_endpoint` decodes a micro-batch's rows just before its
    callbacks and drops them right after: while callback k runs, the
    `Event`s of the chunk's other micro-batches do not exist (a chunk's
    worth of them, mapped and unmapped once per chunk, made a bulk send's
    time drift; PERF.md §6, PR 26)."""
    import siddhi_tpu.native as native
    from siddhi_tpu.core.event import Event

    if impl == "python":
        monkeypatch.setattr(native, "_DECODE_LIB", None)
        monkeypatch.setattr(native, "_DECODE_FAILED", True)
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(APP)
    alive, rows = [], []

    def events_alive():
        # an instance of a heap type holds a reference to it; the native
        # builder's Events are not in the collector's lists to be counted
        return sys.getrefcount(Event)

    def callback(ts, ins, removed):
        rows.append(len(ins))
        alive.append(events_alive())

    rt.add_callback("q", callback)
    rt.start()
    gc.collect()
    before = events_alive()  # what earlier tests of this process still hold
    n = 2 * B * K  # two chunks on the fused path, every row passes the filter
    rt.get_input_handler("S").send_columns(
        np.arange(n, dtype=np.int64),
        {"k": (np.arange(n) % 5).astype(np.int32),
         "v": np.full((n,), 50.0, dtype=np.float32)},
    )
    status = rt.snapshot_status()["streams"]["S"]["pipeline"]
    rt.shutdown()
    mgr.shutdown()
    assert status["enabled"] and status["chunk_batches"] == K
    assert status["decode"] == impl
    assert status["decode_native_rows"] == (n if impl == "native" else 0)
    assert rows == [B] * (2 * K)
    # with a chunk-wide decode: K * B in every call
    assert [a - before for a in alive] == rows


def test_per_batch_path_spans(tmp_path):
    mgr, rt, got = _deploy()
    _send(rt, B, 0)  # one micro-batch: below 2 x batch, per-batch path
    events = _traced(tmp_path, lambda: _send(rt, B + 8, 10_000))
    rt.shutdown()
    mgr.shutdown()
    by_name: dict = {}
    for ev in events:
        by_name.setdefault(ev["name"], []).append(ev)
    assert BATCH_CHILDREN | {"siddhi:send"} <= set(by_name), sorted(by_name)
    (send,) = by_name["siddhi:send"]
    assert send["path"] == "batch" and send["rows"] == B + 8
    for name in BATCH_CHILDREN:
        assert len(by_name[name]) == 2, name  # two micro-batches
        for ev in by_name[name]:
            assert ev["send"] == send["send"] and _inside(ev, send), ev
    assert {e["query"] for e in by_name["siddhi:step"]} == {"q"}
    for step, publish in zip(by_name["siddhi:step"], by_name["siddhi:publish"]):
        assert _inside(step, publish)


def test_no_session_no_statistics_touches_no_collector(monkeypatch):
    """With no profiler session open and no @app:statistics, a send on
    either path builds no waterfall, enters no Profiler and opens no span."""

    def never(*a, **k):
        raise AssertionError("a collector was touched")

    monkeypatch.setattr(profiler_mod, "StageWaterfall", never)
    monkeypatch.setattr(profiler_mod.Profiler, "begin", never)
    monkeypatch.setattr(profiler_mod.Profiler, "end", never)
    monkeypatch.setattr(profiler_mod, "TraceAnnotation", _NoSession)
    mgr, rt, got = _deploy()
    assert rt.profile_report() is None
    _send(rt, 3 * B * K, 0)
    _send(rt, B, 10_000)
    rt.shutdown()
    mgr.shutdown()
    assert got


class _NoSession:
    """TraceAnnotation's face with no session open; building one fails."""

    def __init__(self, *a, **k):
        raise AssertionError("a span was opened with no session")

    @staticmethod
    def is_enabled() -> bool:
        return False


@pytest.fixture(scope="module")
def chunk_program_hlo():
    """Compiled HLO text of the fused deliver-mode chunk program of APP,
    lowered again from the program the engine holds."""
    mgr, rt, _got = _deploy()
    _send(rt, 2 * B * K, 0)
    fi = rt.junctions["S"].fused_ingest
    prog = fi._fused_deliver
    assert prog is not None
    states = fi._pack_arg0([ep.qr.state for ep in fi.endpoints])
    lowered = prog.lower(
        states, {}, np.zeros((K, fi._wire_bytes), np.uint8),
        np.zeros((K,), np.int32), np.zeros((K,), np.int64), np.int64(0),
    )
    text = lowered.compile().as_text()
    rt.shutdown()
    mgr.shutdown()
    return text


@pytest.mark.parametrize("scope", [
    "wire_decode", "q.q", "filter", "window.length", "ring_emit",
    "ring_update", "selector", "deliver_mask", "deliver_pack",
])
def test_chunk_program_names_its_stages(chunk_program_hlo, scope):
    named = [
        line for line in chunk_program_hlo.splitlines()
        if "op_name=" in line and f"/{scope}/" in line
    ]
    assert named, f"no instruction of the chunk program is under {scope!r}"
    if scope not in ("wire_decode", "q.q", "deliver_mask", "deliver_pack"):
        # the query's own stages nest under the query's scope
        assert all("q.q/" in line for line in named)


def test_compile_events_need_no_statistics():
    """An eager program built after deploy is counted, with its clock
    reading, on an app without @app:statistics."""
    mgr, rt, _got = _deploy()
    before = rt.snapshot_status()["compile_events"]
    t0 = time.perf_counter()
    # a shape no other test builds: the eager concatenate compiles now
    jax.numpy.concatenate(
        [jax.numpy.ones((1237,)), jax.numpy.ones((3,))]
    ).block_until_ready()
    t1 = time.perf_counter()
    after = rt.snapshot_status()["compile_events"]
    rt.shutdown()
    mgr.shutdown()
    assert after["compiles"] > before["compiles"]
    assert after["compile_s"] > before["compile_s"]
    assert after["cache_loads"] <= after["compiles"]
    assert len(after["recent"]) <= profiler_mod.CompileEvents.RING
    fresh = [e for e in after["recent"] if t0 <= e["t"] <= t1]
    assert any("concatenate" in e["name"] for e in fresh), after["recent"]
    assert all(e["seconds"] >= 0 for e in fresh)


def test_gc_hook_lives_with_the_runtimes():
    """Installed when the first app runtime starts, gone with the last."""
    hook = profiler_mod.GC_SPANS._hook
    base = gc.callbacks.count(hook)
    users = profiler_mod.GC_SPANS._users
    mgr, rt, _ = _deploy()
    mgr2, rt2, _ = _deploy()
    assert gc.callbacks.count(hook) == 1
    rt.shutdown()
    rt.shutdown()  # a second shutdown releases nothing twice
    assert gc.callbacks.count(hook) == 1
    rt2.shutdown()
    assert profiler_mod.GC_SPANS._users == users
    assert gc.callbacks.count(hook) == base
    mgr.shutdown()
    mgr2.shutdown()
