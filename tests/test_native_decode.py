"""The fused drain's native Event builder (native/decode.cpp) against the
Python body it replaces (core/event.py `events_from_arrays_py`): the same
`Event`s, element by element and by type, for every attribute type, with
nulls in every lane; out of the cyclic collector's sight exactly where the
schema is atomic; built at deploy, reported in the status, and withheld
loudly where no compiler is found."""

from __future__ import annotations

import gc
import logging
import os
import platform
import subprocess
import sys

import numpy as np
import pytest

import siddhi_tpu.native as native
from siddhi_tpu import SiddhiManager
from siddhi_tpu.core import event as event_mod
from siddhi_tpu.core.event import (
    Event,
    StreamSchema,
    events_from_arrays,
    events_from_arrays_py,
)
from siddhi_tpu.core.types import AttrType, InternTable, null_value

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OFFSET = 5  # lanes reach the builder as slices of a chunk's lane views


@pytest.fixture(autouse=True)
def builder():
    assert native.load_event_builder() is not None, "no compiler / Python.h"


def _interner():
    it = InternTable()
    for v in ("WSO2", "IBM", ("an", "object"), 7):
        it.intern(v)
    return it


def _lane(t: AttrType, n: int, rng, dtype=None) -> np.ndarray:
    """OFFSET + n elements of type t with a null at every third place."""
    m = n + OFFSET
    if t in (AttrType.STRING, AttrType.OBJECT):
        arr = rng.integers(1, 5, size=m).astype(dtype or np.int32)
        arr[::3] = 0
    elif t is AttrType.BOOL:
        arr = (rng.integers(0, 2, size=m) > 0).astype(dtype or np.bool_)
    elif t in (AttrType.FLOAT, AttrType.DOUBLE):
        arr = rng.uniform(-6e4, 6e4, size=m).astype(dtype or np.float32)
        arr[::3] = np.nan
    else:
        dt = np.dtype(dtype or (np.int32 if t is AttrType.INT else np.int64))
        info = np.iinfo(dt)
        arr = rng.integers(info.min + 1, info.max, size=m).astype(dt)
        arr[::3] = np.asarray(null_value(t), dt)  # as the lane can hold it
    return arr


def _same(native_events, python_events):
    assert type(native_events) is list
    assert len(native_events) == len(python_events)
    for got, want in zip(native_events, python_events):
        assert type(got) is Event and type(got.data) is tuple
        assert got == want
        assert type(got.timestamp) is type(want.timestamp)
        assert [type(v) for v in got.data] == [type(v) for v in want.data]


def _both(schema, ts, cols, n, interner):
    args = (
        schema, ts[OFFSET:OFFSET + n],
        {k: v[OFFSET:OFFSET + n] for k, v in cols.items()}, n, interner,
    )
    return events_from_arrays(*args), events_from_arrays_py(*args)


@pytest.mark.parametrize("n", [0, 1, 2, 32768])
@pytest.mark.parametrize("t", list(AttrType), ids=lambda t: t.name)
def test_one_attribute_equals_the_python_body(t, n):
    rng = np.random.default_rng(n + 1)
    schema = StreamSchema("S", [("a", t)])
    ts = rng.integers(0, 2**62, size=n + OFFSET)
    got, want = _both(schema, ts, {"a": _lane(t, n, rng)}, n, _interner())
    _same(got, want)
    if n >= 2:  # OFFSET + 1 is a multiple of three: the second row is null
        assert got[1].data == (False if t is AttrType.BOOL else None,)
        assert got[0].data != (None,)


WIDE = [
    ("symbol", AttrType.STRING), ("price", AttrType.FLOAT),
    ("mean", AttrType.DOUBLE), ("volume", AttrType.INT),
    ("stamp", AttrType.LONG), ("flag", AttrType.BOOL),
    ("thing", AttrType.OBJECT),
]
# the lanes as `deliver_endpoint` cuts them, then every dtype a caller of
# the seam may hand in (the benchmark's rehearsal multiplies a lane)
DTYPES = {
    "physical": {},
    "wide": {"symbol": np.int64, "price": np.float64, "mean": np.float64,
             "volume": np.int64, "flag": np.uint8, "thing": np.int64},
    "odd": {"symbol": np.uint16, "price": np.float16, "volume": np.int16,
            "stamp": np.int64, "flag": np.int32, "thing": np.uint8},
}


@pytest.mark.parametrize("ts_dtype", [np.int64, np.int32])
@pytest.mark.parametrize("dtypes", sorted(DTYPES))
def test_whole_schema_equals_the_python_body(dtypes, ts_dtype):
    rng = np.random.default_rng(7)
    n = 1000
    schema = StreamSchema("S", WIDE)
    cols = {
        name: _lane(t, n, rng, DTYPES[dtypes].get(name)) for name, t in WIDE
    }
    ts = rng.integers(0, 2**31 - 1, size=n + OFFSET).astype(ts_dtype)
    got, want = _both(schema, ts, cols, n, _interner())
    _same(got, want)
    # float32 widens as `tolist()` widens it
    if dtypes == "physical":
        assert got[0].data[1] == float(cols["price"][OFFSET])


def test_strided_lanes_and_a_short_count():
    rng = np.random.default_rng(3)
    schema = StreamSchema("S", WIDE[:4])
    n = 64
    cols = {name: _lane(t, 2 * n, rng)[::2] for name, t in WIDE[:4]}
    ts = np.arange(4 * n, dtype=np.int64)[::2]
    got, want = _both(schema, ts, cols, n - 9, _interner())
    _same(got, want)
    assert len(got) == n - 9


@pytest.mark.parametrize("pad", [0, 1, 3])
def test_lanes_cut_from_packed_rows(pad):
    """What `deliver_endpoint` hands in: each lane a view of the readback
    buffer's rows, strided by the row's bytes and aligned to nothing."""
    rng = np.random.default_rng(pad)
    n = 500
    attrs = WIDE[:6]
    schema = StreamSchema("S", attrs)
    cols = {name: _lane(t, n, rng) for name, t in attrs}
    cols["__ts"] = rng.integers(0, 2**62, size=n + OFFSET)
    row_bytes = pad + sum(a.dtype.itemsize for a in cols.values())
    host = np.zeros((n + OFFSET, row_bytes), np.uint8)
    views, off = {}, pad
    for name, a in cols.items():
        w = a.dtype.itemsize
        host[:, off:off + w] = a.view(np.uint8).reshape(-1, w)
        views[name] = host[:, off:off + w].view(a.dtype)[:, 0]
        off += w
    assert not views["stamp"].flags.c_contiguous
    assert pad == 0 or not views["__ts"].flags.aligned
    ts = views.pop("__ts")
    got, want = _both(schema, ts, views, n, _interner())
    _same(got, want)
    cols.pop("__ts")
    _same(got, events_from_arrays_py(
        schema, ts[OFFSET:], {k: v[OFFSET:] for k, v in cols.items()}, n,
        _interner()))


@pytest.mark.parametrize("n", [0, -3])
def test_no_rows(n):
    schema = StreamSchema("S", WIDE)
    assert events_from_arrays(schema, np.zeros(0, np.int64), {}, n, None) == []


@pytest.mark.parametrize("fault", ["id_out_of_range", "lane_too_short"])
def test_a_bad_lane_raises_and_leaks_nothing(fault):
    schema = StreamSchema("S", [("symbol", AttrType.STRING)])
    it = _interner()
    n = 100
    ids = np.ones(n, np.int32)
    if fault == "id_out_of_range":
        ids[n // 2] = 99
        with pytest.raises(IndexError):
            events_from_arrays(schema, np.arange(n), {"symbol": ids}, n, it)
        ids[n // 2] = 1
    else:
        with pytest.raises(ValueError):
            events_from_arrays(
                schema, np.arange(n), {"symbol": ids[: n // 2]}, n, it
            )
    base = sys.getrefcount(Event)
    events = events_from_arrays(schema, np.arange(n), {"symbol": ids}, n, it)
    assert sys.getrefcount(Event) == base + n  # one per live instance
    del events
    assert sys.getrefcount(Event) == base


@pytest.mark.parametrize(
    "attrs,tracked",
    [(WIDE[:6], False), (WIDE, True), ([("thing", AttrType.OBJECT)], True),
     ([("symbol", AttrType.STRING)], False)],
    ids=["atomic", "with_object", "object_alone", "string_alone"],
)
def test_only_an_atomic_schema_leaves_the_collector(attrs, tracked):
    rng = np.random.default_rng(5)
    n = 50
    schema = StreamSchema("S", attrs)
    cols = {name: _lane(t, n, rng) for name, t in attrs}
    got, want = _both(
        schema, np.arange(n + OFFSET, dtype=np.int64), cols, n, _interner()
    )
    assert all(gc.is_tracked(e) == tracked for e in got)
    assert all(gc.is_tracked(e.data) == tracked for e in got)
    assert all(gc.is_tracked(e) for e in want)  # why the builder untracks
    gc.collect()  # an untracked Event outlives a collection and still frees
    _same(got, want)
    base = sys.getrefcount(Event)
    del got
    assert sys.getrefcount(Event) == base - n


# -- a fused app end to end --------------------------------------------------

B, K = 32, 4
APP = f"""
@app:batch(size='{B}')
@app:ingestChunk(size='{K}')
define stream S (symbol string, price float, volume long, up bool);
@info(name='q')
from S[price > 10] select symbol, price, volume, up, price * 2.0 as twice
insert into Out;
"""


def _send_app(fused: bool, rows=3 * B * K):
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(APP)
    calls = []
    rt.add_callback("q", lambda ts, ins, rem: calls.append((ts, ins, rem)))
    ids = np.array([mgr.interner.intern(s) for s in "ABCD"], np.int32)
    rt.start()
    if not fused:
        for j in rt.junctions.values():
            j.fused_ingest = None
    rng = np.random.default_rng(11)
    rt.get_input_handler("S").send_columns(
        np.arange(rows, dtype=np.int64) + 1_700_000_000_000,
        {"symbol": rng.choice(ids, rows),
         "price": rng.uniform(0, 100, rows).astype(np.float32),
         "volume": rng.integers(1, 2**40, rows),
         "up": rng.integers(0, 2, rows) > 0},
    )
    status = rt.snapshot_status()["streams"]["S"].get("pipeline")
    rt.shutdown()
    mgr.shutdown()
    return calls, status


def _withhold(monkeypatch, tmp_path):
    """A process that has no compiler and no binary built earlier."""
    monkeypatch.setattr(native, "_COMPILER", str(tmp_path / "no-such-g++"))
    monkeypatch.setattr(native, "_build_dir", lambda: str(tmp_path))
    monkeypatch.setattr(native, "_DECODE_LIB", None)
    monkeypatch.setattr(native, "_DECODE_FAILED", False)


def test_fused_callbacks_get_the_per_batch_paths_events():
    fused, status = _send_app(fused=True)
    per_batch, _ = _send_app(fused=False)
    assert fused == per_batch
    delivered = sum(len(ins) for _ts, ins, _rem in fused)
    assert delivered > B * K
    assert all(
        type(e) is Event and not gc.is_tracked(e)
        for _ts, ins, _rem in fused for e in ins
    )
    assert status["decode"] == "native"
    assert status["decode_native_rows"] == delivered


def test_without_a_compiler_the_python_body_runs_and_says_so(
    monkeypatch, tmp_path, caplog
):
    native_calls, _ = _send_app(fused=True)
    _withhold(monkeypatch, tmp_path)
    with caplog.at_level(logging.WARNING, logger="siddhi_tpu.native"):
        calls, status = _send_app(fused=True)
        _send_app(fused=True)  # a second deploy does not warn again
    warnings = [r for r in caplog.records if r.name == "siddhi_tpu.native"]
    assert len(warnings) == 1, caplog.text
    assert "decodes in Python" in warnings[0].getMessage()
    assert status["decode"] == "python"
    assert status["decode_native_rows"] == 0
    assert calls == native_calls
    assert all(gc.is_tracked(e) for _ts, ins, _rem in calls for e in ins)
    assert list(tmp_path.iterdir()) == []  # nothing half-written left


def test_a_send_never_builds(monkeypatch, tmp_path):
    """`events_from_arrays` asks for the loaded builder and compiles
    nothing: with none loaded it is the Python body, whatever is installed."""
    _withhold(monkeypatch, tmp_path)
    schema = StreamSchema("S", [("volume", AttrType.INT)])
    got = events_from_arrays(
        schema, np.arange(4), {"volume": np.arange(4, dtype=np.int32)}, 4, None
    )
    assert all(gc.is_tracked(e) for e in got)
    assert list(tmp_path.iterdir()) == []
    assert event_mod.event_builder() is None


def test_processes_that_build_at_once_all_load_a_whole_binary(tmp_path):
    """The driver's six test workers may all find `_build/` empty: each
    compiles to a temp file of its own and renames it into place."""
    code = (
        "import sys, numpy as np\n"
        "import siddhi_tpu.native as native\n"
        "native._build_dir = lambda: sys.argv[1]\n"
        "build = native.load_event_builder()\n"
        "assert build is not None\n"
        "from siddhi_tpu.core.event import Event\n"
        "out = build(Event, np.arange(3), ((0, np.arange(3), -1, None),), 3, 1)\n"
        "print(out[2])\n"
    )
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", code, str(tmp_path)], cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for _ in range(4)
    ]
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, err[-2000:]
        assert out.strip().endswith("Event(timestamp=2, data=(2,))")
    built = sorted(p.name for p in tmp_path.iterdir())
    assert len(built) == 1 and built[0].endswith(".so"), built
    assert ".cpython-" in built[0] or ".abi" in built[0]


# ---- the allocator's word on the chunks' host buffers (native.keep_host_blocks)

glibc = pytest.mark.skipif(
    platform.libc_ver()[0] != "glibc", reason="mallopt is glibc's"
)


@glibc
def test_a_fused_engine_tells_the_allocator_to_keep_its_host_blocks():
    """In a process of its own (the setting is the process's, for good): a
    16 MiB block is a mapping of its own before an engine is built and comes
    from a heap after, so freeing it unmaps nothing."""
    code = (
        "import ctypes, sys\n"
        "sys.path.insert(0, sys.argv[1])\n"
        "libc = ctypes.CDLL(None)\n"
        "class MI(ctypes.Structure):\n"
        "    _fields_ = [(n, ctypes.c_size_t) for n in 'arena ordblks smblks "
        "hblks hblkhd usmblks fsmblks uordblks fordblks keepcost'.split()]\n"
        "libc.mallinfo2.restype = MI\n"
        "libc.malloc.restype = ctypes.c_void_p\n"
        "libc.malloc.argtypes = [ctypes.c_size_t]\n"
        "libc.free.argtypes = [ctypes.c_void_p]\n"
        "def mapped_by(n):\n"
        "    before = libc.mallinfo2().hblks\n"
        "    p = libc.malloc(n)\n"
        "    got = libc.mallinfo2().hblks - before\n"
        "    libc.free(p)\n"
        "    return got\n"
        "first = mapped_by(16 << 20)\n"
        "from tests.test_native_decode import _send_app\n"
        "_calls, status = _send_app(fused=True)\n"
        "print(first, mapped_by(16 << 20), status['host_blocks'])\n"
    )
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("MALLOC_") and k != "GLIBC_TUNABLES"}
    p = subprocess.run(
        [sys.executable, "-c", code, REPO], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.split()[-3:] == ["1", "0", "kept"], p.stdout


@pytest.mark.parametrize("env, no_mallopt, want", [
    ({"MALLOC_TRIM_THRESHOLD_": "262144"}, False, "as_set"),
    ({"MALLOC_TOP_PAD_": "0"}, False, "as_set"),
    ({"MALLOC_MMAP_THRESHOLD_": "1048576"}, False, "as_set"),
    ({"GLIBC_TUNABLES": "glibc.malloc.arena_max=1"}, False, "as_set"),
    ({"GLIBC_TUNABLES": "glibc.pthread.rseq=0"}, True, "default"),
    ({}, True, "default"),
])
def test_the_allocator_is_left_as_the_operator_or_the_platform_has_it(
    monkeypatch, env, no_mallopt, want
):
    """Neither branch calls `mallopt`: glibc's own environment variables for
    the same parameters stand, and a C library without it is left alone."""
    monkeypatch.setattr(native, "_HOST_BLOCKS", None)
    for k in native._MALLOC_ENV + ("GLIBC_TUNABLES",):
        monkeypatch.delenv(k, raising=False)
    for k, v in env.items():
        monkeypatch.setenv(k, v)

    def cdll(_name):
        assert no_mallopt, "mallopt must not be reached"
        raise OSError("no such library")

    with monkeypatch.context() as m:
        m.setattr(native.ctypes, "CDLL", cdll)
        assert native.keep_host_blocks() == want
    assert native.keep_host_blocks() == want  # decided once per process
    _calls, status = _send_app(fused=True)
    assert status["host_blocks"] == want
