"""Pipelined fused ingest (core/pipeline.py) must be observationally
identical to the per-batch path: byte-identical outputs, identical
delivery order and per-micro-batch callback grouping, identical
failure-policy semantics when delivery fails on the drain worker.

Each parity case runs the same columnar feed twice — fused (the default)
and per batch (`@app:fuse(disable='true')`, the reference of every fuse
on/off parity in the suite) — plus configuration, error-routing, the
re-entrant send (the pipeline's inline side) and observability coverage.
"""

from __future__ import annotations

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager


@pytest.fixture(autouse=True)
def _isolate_fuse_env(monkeypatch):
    """CI runs part of the suite under SIDDHI_TPU_FUSE=1|0, which beats the
    annotation both ways; these tests choose the path themselves."""
    monkeypatch.delenv("SIDDHI_TPU_FUSE", raising=False)


HEAD = "@app:batch(size='64')\ndefine stream S (symbol string, price float, volume long);\n"
PER_BATCH_HEAD = "@app:fuse(disable='true')\n" + HEAD


def _feed(n, seed=42):
    rng = np.random.default_rng(seed)
    return (
        np.arange(n, dtype=np.int64) + 1_700_000_000_000,
        {
            "symbol": rng.integers(1, 5, size=n).astype(np.int32),
            "price": rng.uniform(0.0, 100.0, size=n).astype(np.float32),
            "volume": rng.integers(1, 100, size=n).astype(np.int64),
        },
    )


def _boot(ql, callback=None):
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(ql)
    if callback is not None:
        rt.add_callback("q", callback)
    for s in ["A", "B", "C", "D"]:
        mgr.interner.intern(s)
    rt.start()
    return mgr, rt


def _run_rows(ql, n, store_q="from T select *"):
    mgr, rt = _boot(ql)
    ts, cols = _feed(n)
    rt.get_input_handler("S").send_columns(ts, cols)
    rows = sorted(map(repr, rt.query(store_q)))
    rt.shutdown()
    mgr.shutdown()
    return rows


TABLE_BODY = """
    @capacity(size='4096') define table T (symbol string, total long);
    @info(name='q') from S[price > 10]#window.lengthBatch(32)
    select symbol, sum(volume) as total group by symbol insert into T;
"""

CB_BODY = """@info(name='q') from S#window.length(16)
    select symbol, avg(price) as ap insert into Out;"""


def test_pipelined_matches_per_batch_table():
    n = 64 * 40
    assert _run_rows(HEAD + TABLE_BODY, n) == _run_rows(
        PER_BATCH_HEAD + TABLE_BODY, n
    )


def _run_cb(ql, n):
    got = []
    mgr, rt = _boot(
        ql,
        callback=lambda ts, ins, rem: got.append(
            (
                ts,
                [tuple(e.data) for e in (ins or [])],
                [tuple(e.data) for e in (rem or [])],
            )
        ),
    )
    ts, cols = _feed(n)
    rt.get_input_handler("S").send_columns(ts, cols)
    rt.shutdown()
    mgr.shutdown()
    return got


def test_pipelined_delivery_matches_per_batch():
    """Drain-worker delivery: identical events, identical per-micro-batch
    grouping, identical order."""
    n = 64 * 40
    pipelined = _run_cb(HEAD + CB_BODY, n)
    per_batch = _run_cb(PER_BATCH_HEAD + CB_BODY, n)
    assert pipelined == per_batch
    assert sum(len(i) for _t, i, _r in pipelined) > 50


def test_callbacks_complete_before_send_returns():
    """try_send barriers on the drain, so a per-row send AFTER a pipelined
    send_columns observes every pipelined callback already delivered."""
    order = []
    mgr, rt = _boot(
        HEAD + "@info(name='q') from S[price >= 0] select symbol, price "
        "insert into Out;",
        callback=lambda ts, ins, rem: order.extend(
            p for _s, p in (e.data for e in (ins or []))
        ),
    )
    h = rt.get_input_handler("S")
    ts, cols = _feed(64 * 8)
    cols["price"] = np.arange(64 * 8, dtype=np.float32)
    h.send_columns(ts, cols)
    n_before = len(order)
    assert n_before == 64 * 8  # everything drained before send returned
    h.send(("A", 1e6, 1))
    assert order[-1] == 1e6 and len(order) == n_before + 1
    rt.shutdown()
    mgr.shutdown()


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------


def _fused(rt):
    fi = rt.junctions["S"].fused_ingest
    assert fi is not None
    return fi


def test_pipeline_annotation_depth_and_disable():
    mgr, rt = _boot(
        "@app:batch(size='64')\n@pipeline(depth='3')\n"
        "define stream S (symbol string, price float, volume long);\n"
        + CB_BODY
    )
    fi = _fused(rt)
    assert fi.pipeline_depth == 3
    assert fi.describe_state()["pipeline_enabled"] is True
    rt.shutdown()
    mgr.shutdown()

    # no switch turns the pipeline off: `disable` is an unknown key
    from siddhi_tpu.core.errors import SiddhiAppCreationError

    with pytest.raises(SiddhiAppCreationError, match="unknown @pipeline"):
        SiddhiManager().create_siddhi_app_runtime(
            "@app:batch(size='64')\n@pipeline(disable='true')\n"
            "define stream S (symbol string, price float, volume long);\n"
            + CB_BODY
        )


def test_pipeline_annotation_rejects_bad_options():
    from siddhi_tpu.core.errors import SiddhiAppCreationError

    for ann in ("@pipeline(depth='x')", "@pipeline(depth='0')",
                "@pipeline(depth='64')", "@pipeline(disable='maybe')",
                "@pipeline(bogus='1')"):
        with pytest.raises(SiddhiAppCreationError):
            SiddhiManager().create_siddhi_app_runtime(
                f"@app:batch(size='64')\n{ann}\n"
                "define stream S (symbol string, price float, volume long);\n"
                + CB_BODY
            )


def test_wire_slot_reuse_gated_per_shipment():
    """device_put may alias the host buffer (size/alignment-dependent on
    CPU): an aliased slot must be gated on the consuming dispatch
    (retire), a copied one on its transfer (ship)."""
    import numpy as np

    import jax

    from siddhi_tpu.core.pipeline import IngestPipeline

    class _Schema:
        stream_id = "S"

    class _Junction:
        schema = _Schema()
        exception_handler = None
        fault_policy = None

    pl = IngestPipeline(_Junction(), depth=2)
    for wire_bytes in (64, 1 << 20):  # small: alias candidate; big: copied
        slot = pl.acquire(2, wire_bytes)
        dev = pl.ship(slot)
        want_alias = dev.unsafe_buffer_pointer() == slot.buf.ctypes.data
        assert slot.aliased == want_alias
        assert slot.ref is dev  # transfer gate until retired
        completion = jax.numpy.zeros(())
        pl.retire(slot, completion)
        if want_alias:
            assert slot.ref is completion  # program gate replaced it
        else:
            assert slot.ref is dev  # copy: transfer gate suffices
    # no safe gate at all (only-donated-outputs dispatch): an aliased slot
    # must abandon its buffer rather than ever reuse it
    slot = pl.acquire(2, 64)
    old_buf = slot.buf
    pl.ship(slot)
    was_aliased = slot.aliased
    pl.retire(slot, None)
    if was_aliased:
        assert slot.buf is not old_buf and slot.ref is None
    pl.close()


# ---------------------------------------------------------------------------
# drain-worker failure semantics
# ---------------------------------------------------------------------------


def _boom(ts, ins, rem):
    raise RuntimeError("poisoned callback")


def test_drain_error_routes_to_exception_handler():
    """A delivery failure on the drain worker goes through the junction's
    failure machinery (mirroring @async drain workers): the sender never
    sees it once a handler owns the stream."""
    mgr, rt = _boot(HEAD + CB_BODY, callback=_boom)
    seen = []
    rt.set_exception_handler(seen.append)
    ts, cols = _feed(64 * 8)
    rt.get_input_handler("S").send_columns(ts, cols)  # must not raise
    assert seen and isinstance(seen[0], RuntimeError)
    rt.shutdown()
    mgr.shutdown()


def test_drain_error_with_onerror_policy_spares_sender():
    """A stream-level @OnError policy owns drain-worker delivery failures:
    the sender keeps sending, the junction's error counter ticks."""
    mgr, rt = _boot(
        "@app:statistics(reporter='none')\n@app:batch(size='64')\n"
        "@OnError(action='LOG')\n"
        "define stream S (symbol string, price float, volume long);\n"
        + CB_BODY,
        callback=_boom,
    )
    ts, cols = _feed(64 * 8)
    rt.get_input_handler("S").send_columns(ts, cols)  # must not raise
    assert rt.statistics_manager.error_tracker("stream.S").count > 0
    rt.shutdown()
    mgr.shutdown()


def test_drain_error_propagates_without_handler():
    """No handler, no @OnError policy: the failure surfaces to the sender
    at the end of the call."""
    mgr, rt = _boot(HEAD + CB_BODY, callback=_boom)
    ts, cols = _feed(64 * 8)
    with pytest.raises(RuntimeError, match="poisoned callback"):
        rt.get_input_handler("S").send_columns(ts, cols)
    rt.shutdown()
    mgr.shutdown()


# ---------------------------------------------------------------------------
# the re-entrant send: the same chunk loop on the pipeline's inline side
# ---------------------------------------------------------------------------

PASS_BODY = (
    "@info(name='q') from S[price >= 0] select symbol, price, volume "
    "insert into Out;"
)


def _inner_feed(n, base=1000.0):
    ts, cols = _feed(n, seed=7)
    cols["price"] = base + np.arange(n, dtype=np.float32)
    return ts + 10_000, cols


class _Reenter:
    """A query callback that records the prices it receives and, on its
    first call, sends `inner` on the same stream from where it runs."""

    def __init__(self, inner, poison_inner=False):
        self.rt = None  # set once the runtime exists
        self.inner = inner
        self.poison_inner = poison_inner
        self.order = []
        self.thread = None  # the thread the inner send was made from
        self.seen_when_inner_returned = None
        self.inner_error = None
        self.around_inner = lambda send: send()

    def __call__(self, ts, ins, rem):
        prices = [e.data[1] for e in (ins or [])]
        if self.poison_inner and prices and prices[0] >= 1000.0:
            raise RuntimeError("poisoned callback")
        self.order.extend(prices)
        if self.thread is None:
            import threading

            self.thread = threading.current_thread().name
            try:
                self.around_inner(
                    lambda: self.rt.get_input_handler("S").send_columns(
                        *self.inner
                    )
                )
            except Exception as e:
                self.inner_error = e
                raise
            self.seen_when_inner_returned = len(self.order)


def _boot_reentrant(ql, n_inner, **kw):
    cb = _Reenter(_inner_feed(n_inner), **kw)
    mgr, cb.rt = _boot(ql, callback=cb)
    return mgr, cb.rt, cb


def _outer_feed(n):
    ts, cols = _feed(n)
    cols["price"] = np.arange(n, dtype=np.float32)
    return ts, cols


def test_reentrant_send_from_drain_worker_delivers_in_order():
    """A callback that re-enters send_columns on its own stream from the
    drain worker returns (it must not wait on the pipeline it is draining),
    and every row of the inner and the outer send is delivered, in order,
    the inner send's callbacks complete before it returns."""
    n_out, n_in = 64 * 8, 64 * 4
    mgr, rt, cb = _boot_reentrant(HEAD + PASS_BODY, n_in)
    rt.get_input_handler("S").send_columns(*_outer_feed(n_out))
    assert cb.thread.startswith("siddhi-pipeline-")
    # the outer's first micro-batch, then the whole inner send, delivered
    # by the time it returned, then the rest of the outer
    assert cb.seen_when_inner_returned == 64 + n_in
    assert cb.order == (
        list(range(64))
        + [1000.0 + i for i in range(n_in)]
        + list(range(64, n_out))
    )
    fi = _fused(rt)
    assert fi.events_fused == n_out + n_in  # both rode the chunk loop
    assert fi.chunks_dispatched == 2
    rt.shutdown()
    mgr.shutdown()


def test_reentrant_send_into_table_matches_per_batch():
    body = (
        "@capacity(size='4096') define table T "
        "(symbol string, price float);\n"
        + PASS_BODY
        + "\n@info(name='w') from S select symbol, price insert into T;"
    )
    rows = {}
    for head in (HEAD, PER_BATCH_HEAD):
        mgr, rt, cb = _boot_reentrant(head + body, 64 * 4)
        rt.get_input_handler("S").send_columns(*_outer_feed(64 * 8))
        rows[head] = sorted(map(repr, rt.query("from T select *")))
        assert len(cb.order) == 64 * 12
        rt.shutdown()
        mgr.shutdown()
    assert rows[HEAD] == rows[PER_BATCH_HEAD]
    assert len(rows[HEAD]) == 64 * 12


def test_reentrant_send_drains_one_chunk_late_off_the_pool():
    """A re-entrant send of several chunks: each chunk is drained on the
    calling thread once the next one is dispatched, and the outer send's
    pooled wire slots are left alone (the inner's K=2 tail would have
    added a pool entry)."""
    mgr, rt, cb = _boot_reentrant(
        "@app:ingestChunk(size='4')\n" + HEAD + PASS_BODY, 64 * 10
    )
    fi = _fused(rt)
    log = []
    dispatch, drain = fi._dispatch_chunk, fi._drain

    def spy_dispatch(*a, **kw):
        log.append(("dispatch", kw["chunk"]))
        return dispatch(*a, **kw)

    def spy_drain(packs, reads, K, wf, ids, *rest):
        log.append(("drain", ids["chunk"]))
        return drain(packs, reads, K, wf, ids, *rest)

    fi._dispatch_chunk, fi._drain = spy_dispatch, spy_drain
    slots = {}

    def around(send):
        pl = fi.pipeline
        slots["before"] = pl.describe_state()["wire_slots"]
        mark = len(log)
        send()
        slots["inner"] = [e for e in log[mark:] if e[1] >= 3]
        slots["after"] = pl.describe_state()["wire_slots"]

    cb.around_inner = around
    # outer: 8 batches = chunks 1, 2 (K=4); inner: 10 = chunks 3, 4 (K=4)
    # and the tail 5 (K=2)
    rt.get_input_handler("S").send_columns(*_outer_feed(64 * 8))
    assert slots["inner"] == [
        ("dispatch", 3), ("dispatch", 4), ("drain", 3),
        ("dispatch", 5), ("drain", 4), ("drain", 5),
    ]
    assert slots["before"] == slots["after"] == 2
    assert len(cb.order) == 64 * 18
    rt.shutdown()
    mgr.shutdown()


def test_reentrant_send_narrow_misfit_rebuilds_once_and_delivers():
    mgr, rt, cb = _boot_reentrant(HEAD + PASS_BODY, 64 * 4)
    cb.inner[1]["volume"] = cb.inner[1]["volume"] + 10**12
    fi = _fused(rt)
    rebuilds = []
    rebuild = fi._rebuild_full_width
    fi._rebuild_full_width = lambda *a: (rebuilds.append(a), rebuild(*a))[1]
    narrow_before = []
    cb.around_inner = lambda send: (
        narrow_before.append(dict(fi._narrow)), send()
    )
    got = []
    rt.add_callback("q", lambda t, ins, rem: got.extend(
        e.data[2] for e in (ins or [])
    ))
    rt.get_input_handler("S").send_columns(*_outer_feed(64 * 8))
    assert narrow_before[0].get("volume")  # the sampled wire was narrow
    assert len(rebuilds) == 1 and fi._narrow == {}
    assert len(cb.order) == 64 * 12
    assert sorted(v for v in got if v > 10**12) == sorted(
        int(v) for v in cb.inner[1]["volume"]
    )
    rt.shutdown()
    mgr.shutdown()


@pytest.mark.parametrize("policy", ["handler", "onerror", "none"])
def test_reentrant_drain_error_follows_junction_policy(policy):
    """A delivery failure inside a re-entrant send is drained on the
    caller, and the junction's policy owns it as on the worker: a handler
    or @OnError spares the sender, with neither it raises out of the inner
    send (and so, here, out of the outer's callback and the outer send)."""
    head = HEAD
    if policy == "onerror":
        head = (
            "@app:statistics(reporter='none')\n@app:batch(size='64')\n"
            "@OnError(action='LOG')\n"
            "define stream S (symbol string, price float, volume long);\n"
        )
    mgr, rt, cb = _boot_reentrant(head + PASS_BODY, 64 * 4, poison_inner=True)
    seen = []
    if policy == "handler":
        rt.set_exception_handler(seen.append)
    h = rt.get_input_handler("S")
    if policy == "none":
        with pytest.raises(RuntimeError, match="poisoned callback"):
            h.send_columns(*_outer_feed(64 * 8))
        assert isinstance(cb.inner_error, RuntimeError)
    else:
        h.send_columns(*_outer_feed(64 * 8))  # must not raise
        assert cb.inner_error is None
        assert cb.order == list(range(64 * 8))  # the outer is whole
        if policy == "handler":
            assert seen and isinstance(seen[0], RuntimeError)
        else:
            assert rt.statistics_manager.error_tracker("stream.S").count > 0
    rt.shutdown()
    mgr.shutdown()


def test_reentrant_send_from_failure_handler_on_sender_thread():
    """The other re-entrant caller: an exception handler run on the
    sending thread (it holds the send lock) that sends again. It takes the
    inline side too instead of deadlocking on the lock it holds."""
    import threading

    from siddhi_tpu.testing import faults

    got = []
    mgr, rt = _boot(
        HEAD + PASS_BODY,
        callback=lambda t, ins, rem: got.extend(
            e.data[1] for e in (ins or [])
        ),
    )
    h = rt.get_input_handler("S")
    threads = []

    def handler(exc):
        threads.append(threading.current_thread())
        h.send_columns(*_inner_feed(64 * 4))

    rt.set_exception_handler(handler)
    faults.install(faults.parse_plan("device_dispatch:times=1"))
    try:
        h.send_columns(*_outer_feed(64 * 8))  # its one chunk fails
    finally:
        faults.uninstall()
    assert threads == [threading.current_thread()]
    assert got == [1000.0 + i for i in range(64 * 4)]
    fi = _fused(rt)
    assert fi.events_fused == 64 * 4 and fi.pipeline.in_flight() == 0
    rt.shutdown()
    mgr.shutdown()


# ---------------------------------------------------------------------------
# a chunk's first read: started at dispatch, awaited by the drain
# ---------------------------------------------------------------------------

CHUNK_HEAD = "@app:ingestChunk(size='4')\n" + HEAD  # a chunk is 256 rows
GATE_BODY = (
    "@info(name='q') from S[price >= 50] select symbol, price, volume "
    "insert into Out;"
)


def _gated_feed(n, passing, seed=42):
    """n rows of which the first `passing` of every 64 pass GATE_BODY's
    filter: every chunk of a send delivers the same number of rows."""
    ts, cols = _feed(n, seed)
    cols["price"] = np.where(
        np.arange(n) % 64 < passing, 50.0 + np.arange(n), 1.0
    ).astype(np.float32)
    return ts, cols


def _watch_hosts(fi):
    """The header-stripped arrays `_drain` hands `deliver_endpoint` from
    now on, as a list that fills."""
    hosts = []
    deliver = fi.deliver_endpoint

    def spy(i, host, *a):
        hosts.append(host)
        return deliver(i, host, *a)

    fi.deliver_endpoint = spy
    return hosts


def _run_gated(head, sends, body=GATE_BODY, seen=None):
    """The callback's rows, the stream's pipeline status and the chunks
    dispatched; into `seen`, where given: `hosts`, the arrays the drain
    handed on, and `compiles`, the process's compile count after each
    send."""
    got = []
    mgr, rt = _boot(
        head + body,
        callback=lambda ts, ins, rem: got.append(
            (ts, [tuple(e.data) for e in ins])
        ),
    )
    fi = rt.junctions["S"].fused_ingest
    if seen is not None:
        seen["hosts"] = _watch_hosts(fi) if fi is not None else []
        seen["compiles"] = []
    for n, passing in sends:
        rt.get_input_handler("S").send_columns(*_gated_feed(n, passing))
        if seen is not None:
            seen["compiles"].append(
                rt.snapshot_status()["compile_events"]["compiles"]
            )
    status = rt.snapshot_status()["streams"]["S"].get("pipeline")
    chunks = fi.chunks_dispatched if fi is not None else 0
    rt.shutdown()
    mgr.shutdown()
    return got, status, chunks


@pytest.fixture
def reads_of_any_size(monkeypatch):
    """These chunks' buffers hold 256 rows, fewer than the least a read asks
    for at a deployment's size: without that floor a short guess is short
    here too."""
    import siddhi_tpu.core.ingest as ingest

    monkeypatch.setattr(ingest, "_LEAST_READ_ROWS", 1)


@pytest.mark.parametrize(
    "sends, topups",
    [
        # first chunks after deploy: no total is known, all rows are asked for
        pytest.param([(256 * 3, 40)], (0, 0), id="unknown"),
        # the last drained chunk had this chunk's total: one read
        pytest.param([(256 * 3, 40), (256 * 3, 40)], (0, 0), id="exact"),
        # it had far fewer rows: the prefix is short and the rest is read
        # behind it (how many of the second send's chunks were started
        # before the first of them was drained is the threads' business)
        pytest.param([(256 * 3, 2), (256 * 3, 64)], (1, 3), id="short"),
    ],
)
def test_read_started_at_dispatch_delivers_what_per_batch_does(
        sends, topups, reads_of_any_size):
    """The sender starts each chunk's first read when it hands the chunk to
    the drain; whatever the prefix it asked for, the callback sees the rows
    of the per-batch path, in its order and grouping."""
    fused, status, chunks = _run_gated(CHUNK_HEAD, sends)
    per_batch, _status, _chunks = _run_gated(
        "@app:fuse(disable='true')\n" + CHUNK_HEAD, sends
    )
    assert fused == per_batch
    assert sum(len(rows) for _ts, rows in fused) == sum(
        n // 64 * passing for n, passing in sends
    )
    assert chunks == 3 * len(sends)
    assert status["readback_started"] == chunks
    assert 0 <= status["readback_ready"] <= chunks
    assert topups[0] <= status["readback_topups"] <= topups[1]


def test_readback_counters_read_what_happened():
    """`readback_started` counts the chunks whose read the sender started,
    `readback_ready` those whose bytes were on the host when the drain
    asked: behind a slow callback every chunk but a send's first. An
    endpoint nobody listens to starts no read."""
    import time

    mgr, rt = _boot(
        CHUNK_HEAD + GATE_BODY + "\n@info(name='q2') from S[price >= 50] "
        "select symbol insert into Out2;",
        callback=lambda ts, ins, rem: time.sleep(0.05),
    )
    fi = _fused(rt)
    started = []
    start_read = fi._start_read

    def spy(i, pack, K):
        started.append(fi.endpoints[i].qr.query_id)
        return start_read(i, pack, K)

    fi._start_read = spy
    rt.get_input_handler("S").send_columns(*_gated_feed(256 * 3, 8))
    status = rt.snapshot_status()["streams"]["S"]["pipeline"]
    assert fi.chunks_dispatched == 3 and started == ["q"] * 3
    assert status["readback_started"] == 3
    assert status["readback_ready"] in (2, 3)
    assert status["readback_topups"] == 0
    rt.shutdown()
    mgr.shutdown()


def test_reentrant_send_starts_its_reads_too():
    """The inline side parks a chunk with the read the caller started for
    it: same drain, same counters, delivery in order."""
    n_out, n_in = 64 * 8, 64 * 8
    mgr, rt, cb = _boot_reentrant(CHUNK_HEAD + PASS_BODY, n_in)
    rt.get_input_handler("S").send_columns(*_outer_feed(n_out))
    assert cb.order == (
        list(range(64))
        + [1000.0 + i for i in range(n_in)]
        + list(range(64, n_out))
    )
    fi = _fused(rt)
    assert fi.chunks_dispatched == 4  # two of the outer send, two inside
    assert fi.readback_started == 4 and fi.readback_topups == 0
    rt.shutdown()
    mgr.shutdown()


def test_reads_ahead_under_a_short_switch_interval():
    """Sender, reader and drain worker hand chunks to one another across
    many sends with the interpreter switching threads every few
    microseconds: every row arrives once, in order, and every chunk's read
    was started once and awaited."""
    import sys

    got = []
    mgr, rt = _boot(
        CHUNK_HEAD + PASS_BODY,
        callback=lambda ts, ins, rem: got.extend(e.data[1] for e in ins),
    )
    h = rt.get_input_handler("S")
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for _ in range(25):
            h.send_columns(*_outer_feed(256 * 3))
    finally:
        sys.setswitchinterval(interval)
    fi = _fused(rt)
    assert got == list(range(256 * 3)) * 25
    assert fi.chunks_dispatched == fi.readback_started == 75
    assert fi.readback_topups == 0 and fi.pipeline.in_flight() == 0
    rt.shutdown()
    mgr.shutdown()


class _FailedBuffer:
    """A packed buffer whose chunk program failed on the device: the
    failure shows when the bytes of the prefix cut from it are asked for
    (`at="await"`) or, on a backend that knows by then, when the prefix
    program is queued (`"enqueue"`). Stands for its own prefix too."""

    def __init__(self, shape, at):
        self.shape = shape
        self.at = at

    def prefix(self, start):
        if self.at == "enqueue":
            raise RuntimeError("chunk program failed")
        return self

    def copy_to_host_async(self):
        pass

    def __array__(self, *a, **kw):
        raise RuntimeError("chunk program failed")


@pytest.fixture
def failing_prefix(monkeypatch):
    """The engine's prefix program, with a `_FailedBuffer` behaving as a
    buffer would whose chunk program failed."""
    from siddhi_tpu.core import ingest

    program = ingest._prefix_program

    def prefix_program(n, W):
        real = program(n, W)
        return lambda buf, start: (
            buf.prefix(start)
            if isinstance(buf, _FailedBuffer)
            else real(buf, start)
        )

    monkeypatch.setattr(ingest, "_prefix_program", prefix_program)


@pytest.mark.parametrize("policy", ["handler", "none"])
@pytest.mark.parametrize("at", ["await", "enqueue"])
def test_failed_chunk_program_surfaces_at_the_drain(at, policy, failing_prefix):
    """Starting a chunk's read on the sender's thread moves no failure
    there: a program that failed is met by the drain, which hands it to the
    junction's handler or, with none, to the barrier that ends the send."""
    got = []
    mgr, rt = _boot(
        CHUNK_HEAD + PASS_BODY,
        callback=lambda ts, ins, rem: got.extend(e.data[1] for e in ins),
    )
    seen = []
    if policy == "handler":
        rt.set_exception_handler(seen.append)
    fi = _fused(rt)
    dispatch = fi._dispatch_chunk

    def failing_second_chunk(*a, **kw):
        packs, completion = dispatch(*a, **kw)
        if fi.chunks_dispatched == 2:
            packs = [
                {**p, "buf": _FailedBuffer(p["buf"].shape, at)} for p in packs
            ]
        return packs, completion

    fi._dispatch_chunk = failing_second_chunk
    h = rt.get_input_handler("S")
    if policy == "handler":
        h.send_columns(*_outer_feed(256 * 3))  # must not raise
        assert [str(e) for e in seen] == ["chunk program failed"]
        # the chunks before and behind it are delivered whole
        assert got == list(range(256)) + list(range(512, 768))
    else:
        with pytest.raises(RuntimeError, match="chunk program failed"):
            h.send_columns(*_outer_feed(256 * 3))
        assert got[:256] == list(range(256))
    assert fi.pipeline.in_flight() == 0
    rt.shutdown()
    mgr.shutdown()


# ---------------------------------------------------------------------------
# a read's bytes: laid out dense on the device, viewed on the host
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("hdr", [0, 5], ids=["no_header", "header"])
@pytest.mark.parametrize("W", [28, 32, 25])
def test_dense_prefix_is_the_contiguous_slice(W, hdr):
    """What the prefix program hands the host is, byte for byte, what
    `np.ascontiguousarray(buf[:n])` was: for rows of 28 and 32 bytes and a
    width that is no multiple of 4 (a bool lane), every size a read comes
    in up to the buffer's `R` rows, with and without header rows, and from
    the offset of a top-up behind every shorter prefix. The host's array is
    a view, not a copy."""
    import jax.numpy as jnp

    from siddhi_tpu.core.ingest import _bucket, read_dense

    R = 256
    rows = np.random.default_rng(W + hdr).integers(
        0, 256, size=(hdr + R, W), dtype=np.uint8
    )
    buf = jnp.asarray(rows)
    sizes = sorted({_bucket(n, R) for n in range(1, R + 1)})
    assert sizes == [1 << k for k in range(9)]
    for n in sizes:
        got = read_dense(buf, 0, hdr + n)
        want = np.ascontiguousarray(np.asarray(buf[: hdr + n]))
        assert got.dtype == np.uint8 and got.shape == (hdr + n, W)
        assert got.tobytes() == want.tobytes() == rows[: hdr + n].tobytes()
        assert got.flags.c_contiguous
        assert not got.flags.owndata and got.base is not None
        for guess in sizes[: sizes.index(n)]:
            tail = read_dense(buf, hdr + guess, n - guess)
            assert tail.tobytes() == rows[hdr + guess : hdr + n].tobytes()


# the delivered row: ts and volume 8 bytes each, symbol and price 4, and then
WIDTHS = {
    28: "price * 2 as twice",  # a float: 4 more
    32: "volume + 1 as more",  # a long: 8 more
    25: "price >= 75 as hot",  # a bool: 1 more, no multiple of 4
}


@pytest.mark.parametrize("W", sorted(WIDTHS))
def test_dense_read_delivers_rows_of_any_width(W, reads_of_any_size):
    """Through the engine: first chunks (all rows asked for), a steady
    prefix, a prefix that undershoots (the top-up goes through the same
    program) and one that overshoots, for each row width: the per-batch
    path's rows, from arrays that are dense without a copy, and no program
    built past the first chunk of a size."""
    body = (
        "@info(name='q') from S[price >= 50] select symbol, price, volume, "
        f"{WIDTHS[W]} insert into Out;"
    )
    sends = [(256 * 3, 40), (256 * 3, 40), (256 * 3, 2), (256 * 3, 64)] + [
        (256 * 3, 64)
    ] * 3
    seen = {}
    fused, status, chunks = _run_gated(CHUNK_HEAD, sends, body, seen)
    per_batch, _status, _chunks = _run_gated(
        "@app:fuse(disable='true')\n" + CHUNK_HEAD, sends, body
    )
    assert fused == per_batch
    assert sum(len(rows) for _ts, rows in fused) == sum(
        n // 64 * passing for n, passing in sends
    )
    assert status["readback_layout"] == "dense"
    assert status["readback_started"] == chunks == 3 * len(sends)
    assert 1 <= status["readback_topups"] <= 3
    hosts = seen["hosts"]
    assert len(hosts) == chunks
    for host in hosts:
        assert host.dtype == np.uint8 and host.shape[1] == W
        assert host.flags.c_contiguous
    # a read that needed no top-up is a view of the bytes that arrived
    views = [h for h in hosts if not h.flags.owndata]
    assert len(views) >= len(hosts) - status["readback_topups"]
    assert all(h.base is not None for h in views)
    # the last three sends read the sizes the one before them read
    assert len(set(seen["compiles"][-3:])) == 1


def test_dense_read_on_the_keys_mesh(monkeypatch):
    """On the virtual keys mesh the packed buffer is replicated: one copy
    is read, through the prefix program on that copy's device, and the
    callback sees what it sees with no mesh."""
    ql = (
        "@app:ingestChunk(size='4')\n@app:batch(size='64')\n{HEAD}"
        "define stream S (symbol string, price float, volume long);\n"
        "@info(name='q') from S select symbol, sum(volume) as sv, "
        "count() as c group by symbol insert into Out;"
    )

    def run(head, shard):
        monkeypatch.setenv("SIDDHI_TPU_SHARD", shard)
        got = []
        mgr, rt = _boot(
            ql.replace("{HEAD}", head),
            callback=lambda ts, ins, rem: got.append(
                (ts, [tuple(e.data) for e in ins])
            ),
        )
        hosts = _watch_hosts(_fused(rt))
        for _ in range(3):
            rt.get_input_handler("S").send_columns(*_feed(256 * 3))
        status = rt.snapshot_status()
        rt.shutdown()
        mgr.shutdown()
        return got, hosts, status

    sharded, hosts, status = run("@app:shard(devices='4', axis='keys')\n", "4")
    plain, _hosts, _status = run("", "0")
    pipe = status["streams"]["S"]["pipeline"]
    assert pipe["mesh_devices"] == 4
    assert status["shard"]["keyshard"]["q"]["path"] == "fused"
    assert pipe["readback_layout"] == "dense"
    assert pipe["readback_started"] == 9 and pipe["readback_topups"] == 0
    assert sharded == plain and len(sharded) == 36
    assert len(hosts) == 9
    assert all(h.flags.c_contiguous and not h.flags.owndata for h in hosts)


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------


def test_pipeline_stage_metrics_and_occupancy():
    mgr, rt = _boot(
        "@app:statistics(reporter='none')\n" + HEAD + CB_BODY,
        callback=lambda ts, ins, rem: None,  # deliver mode: drain runs
    )
    ts, cols = _feed(64 * 16)
    rt.get_input_handler("S").send_columns(ts, cols)
    sm = rt.statistics_manager
    rep = sm.report()
    ent = rep["pipeline"]["stream.S"]
    assert ent["depth"] == 2  # default
    assert ent["occupancy"] > 0.0
    for op in ("encode", "h2d", "dispatch", "drain"):
        assert sm.device_time[f"stream.S.pipeline.{op}"].samples > 0, op
    text = sm.prometheus_text()
    assert "siddhi_pipeline_occupancy" in text
    assert "siddhi_pipeline_depth" in text
    assert 'op="pipeline.encode"' in text
    rt.shutdown()
    mgr.shutdown()


def test_stats_off_pays_one_gate_check():
    """With statistics never configured the pipelined hot path must not
    touch any tracker (junction.pipeline_stats stays None)."""
    mgr, rt = _boot(HEAD + CB_BODY)
    assert rt.junctions["S"].pipeline_stats is None
    fi = _fused(rt)
    ts, cols = _feed(64 * 8)
    rt.get_input_handler("S").send_columns(ts, cols)
    assert fi.pipeline is not None and fi.pipeline.stats is None
    rt.shutdown()
    mgr.shutdown()
