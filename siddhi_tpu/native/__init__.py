"""Native host components, compiled on first use with the system toolchain.

Two sources live here, each built once into `_build/` under a name made of
its source's hash, written through a temp file and `os.replace`, and loaded
once under a lock:

* ring.cpp — the C++ ingress ring with ctypes bindings: the native analog of
  the reference's LMAX Disruptor substrate (StreamJunction.java:262-298), a
  lock-free bounded MPSC queue of fixed-width numeric rows, drained by one
  consumer into columnar batches. Environments without g++ fall back to the
  pure-Python queue path transparently.
* decode.cpp — the fused drain's `Event` builder: one call per micro-batch
  turns the segment's lane arrays into the list of `Event`s that
  `core/event.py` `events_from_arrays` hands to the callbacks, and takes
  them out of the cyclic collector's sight where the schema holds only
  atomic values. It is bound to the CPython C API: compiled against the
  interpreter's headers, named by its `SOABI` too, and loaded with
  `ctypes.PyDLL` so that the GIL is held across the call. It is built when a
  fused engine is built (deploy), never inside a send; without a compiler or
  `Python.h` one WARNING is logged and the Python body stays
  (`snapshot_status().streams.<S>.pipeline.decode` says which runs).

Beside them `keep_host_blocks`, which compiles nothing: it tells glibc's
allocator, once per process, to keep what the engine's large host buffers
free (below).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import sysconfig
import threading
from typing import Optional

import numpy as np

logger = logging.getLogger(__name__)

_COMPILER = "g++"
_LIB_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_LIB_FAILED = False
_DECODE_LIB: Optional[ctypes.PyDLL] = None
_DECODE_FAILED = False
_HOST_BLOCKS: Optional[str] = None


def _build_dir() -> str:
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_build")
    os.makedirs(d, exist_ok=True)
    return d


def _build(source: str, stem: str, *flags: str, abi: str = "") -> str:
    """Path of `source`'s binary, compiling it first where `_build/` has
    none. The binary is named by the source it was built from: `_build/` is
    git-ignored, so a copied tree can carry a binary of some other source,
    and an mtime says nothing about that."""
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), source)
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    out = os.path.join(_build_dir(), f"lib{stem}_{digest}{abi}.so")
    if not os.path.exists(out):
        tmp = f"{out}.{os.getpid()}.tmp"
        subprocess.run(
            [_COMPILER, "-O2", "-shared", "-fPIC", "-std=c++17", *flags,
             "-o", tmp, src],
            check=True, capture_output=True,
        )
        os.replace(tmp, out)  # never expose a half-written binary
    return out


def load_ring_library() -> Optional[ctypes.CDLL]:
    """Compile (once) and load the ring library; None when no toolchain."""
    global _LIB, _LIB_FAILED
    with _LIB_LOCK:
        if _LIB is not None or _LIB_FAILED:
            return _LIB
        try:
            lib = ctypes.CDLL(_build("ring.cpp", "siddhi_ring"))
        except Exception:
            _LIB_FAILED = True
            return None
        lib.ring_create.restype = ctypes.c_void_p
        lib.ring_create.argtypes = [ctypes.c_size_t, ctypes.c_size_t]
        lib.ring_destroy.argtypes = [ctypes.c_void_p]
        lib.ring_push.restype = ctypes.c_int
        lib.ring_push.argtypes = [
            ctypes.c_void_p, ctypes.c_longlong, ctypes.POINTER(ctypes.c_double),
        ]
        lib.ring_pop_batch.restype = ctypes.c_size_t
        lib.ring_pop_batch.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_longlong),
            ctypes.POINTER(ctypes.c_double),
            ctypes.c_size_t,
        ]
        lib.ring_size.restype = ctypes.c_size_t
        lib.ring_size.argtypes = [ctypes.c_void_p]
        _LIB = lib
        return _LIB


def load_event_builder():
    """Compile (once) and load decode.cpp; its `siddhi_build_events`, or
    None (and one WARNING) where no compiler or no `Python.h` is found.
    Called where a fused engine is built; a send only asks `event_builder`."""
    global _DECODE_LIB, _DECODE_FAILED
    with _LIB_LOCK:
        if _DECODE_LIB is None and not _DECODE_FAILED:
            try:
                paths = sysconfig.get_paths()
                lib = ctypes.PyDLL(_build(
                    "decode.cpp", "siddhi_decode",
                    *(f"-I{paths[k]}" for k in ("include", "platinclude")),
                    abi="." + (sysconfig.get_config_var("SOABI") or "abi"),
                ))
                lib.siddhi_build_events.restype = ctypes.py_object
                lib.siddhi_build_events.argtypes = [
                    ctypes.py_object, ctypes.py_object, ctypes.py_object,
                    ctypes.c_ssize_t, ctypes.c_int,
                ]
                _DECODE_LIB = lib
            except Exception as e:
                _DECODE_FAILED = True
                detail = getattr(e, "stderr", None) or e
                if isinstance(detail, bytes):
                    detail = detail.decode(errors="replace")
                logger.warning(
                    "native Event builder unavailable, the fused drain "
                    "decodes in Python: %s", str(detail).strip()[-400:],
                )
    return event_builder()


def event_builder():
    """The loaded `siddhi_build_events`, or None; never compiles."""
    lib = _DECODE_LIB
    return None if lib is None else lib.siddhi_build_events


# glibc's mallopt parameters (malloc.h) and what `keep_host_blocks` sets them
# to: the largest `M_MMAP_THRESHOLD` it takes (half a thread arena's 64 MiB
# heap), so that a block below it comes from a heap and not from a mapping of
# its own; more than a heap as `M_TOP_PAD` (an empty heap is unmapped only
# where the one before it could take `top_pad` more, which none can); and a
# top that is trimmed only beyond 1 GiB. The values the chip runs were made
# with (PERF.md, PR 40).
_M_TRIM_THRESHOLD, _M_TOP_PAD, _M_MMAP_THRESHOLD = -1, -2, -3
_HEAP_BYTES = 64 << 20
_KEEP = (
    (_M_MMAP_THRESHOLD, _HEAP_BYTES // 2),
    (_M_TOP_PAD, 2 * _HEAP_BYTES),
    (_M_TRIM_THRESHOLD, 1 << 30),
)
# the operator's own word on the same parameters: then they stay as set
_MALLOC_ENV = ("MALLOC_MMAP_THRESHOLD_", "MALLOC_TOP_PAD_",
               "MALLOC_TRIM_THRESHOLD_")


def keep_host_blocks() -> str:
    """Tell glibc's allocator to keep and reuse the memory of the large host
    buffers that are made anew for every chunk of a fused send (the packed
    readback, 16-30 MB at a chunk of 32 x 32,768 rows: the wire buffers on
    the way in are pooled; and the caller's own columns beside it), instead
    of handing it back to the kernel and mapping it again. Left to itself the
    allocator moves its thresholds with what it has seen freed, trims the
    top of its heap and unmaps a thread's heap whenever one falls empty, so
    whether a chunk's buffers come back already mapped or are faulted in
    page by page depends on what else happens to lie in the same heap: the
    same process ran a 2,097,152-row send in 0.36 s or in 0.40 s from some
    chunk on, and with every large block mapped anew in 0.50 s
    (PERF.md, PR 40). Called where a fused engine is built, once per
    process; returns what `snapshot_status().streams.<S>.pipeline.
    host_blocks` reports: `kept`; `as_set` where the environment sets one of
    glibc's own `MALLOC_*_` variables for these parameters or
    `GLIBC_TUNABLES` names `glibc.malloc`, which then stand; `default`
    where the C library has no `mallopt` or refuses a value."""
    global _HOST_BLOCKS
    with _LIB_LOCK:
        if _HOST_BLOCKS is None:
            if any(k in os.environ for k in _MALLOC_ENV) or (
                "glibc.malloc" in os.environ.get("GLIBC_TUNABLES", "")
            ):
                _HOST_BLOCKS = "as_set"
            else:
                try:
                    mallopt = ctypes.CDLL(None).mallopt
                    mallopt.argtypes = [ctypes.c_int, ctypes.c_int]
                    mallopt.restype = ctypes.c_int
                    done = [mallopt(k, v) for k, v in _KEEP]
                except (OSError, AttributeError):
                    done = [0]
                _HOST_BLOCKS = "kept" if all(done) else "default"
    return _HOST_BLOCKS


class NativeIngressRing:
    """Python handle over the C++ MPSC ring; one consumer thread drains
    row-major double payloads into per-column numpy arrays."""

    def __init__(self, capacity: int, width: int):
        lib = load_ring_library()
        if lib is None:
            raise RuntimeError("native ring unavailable (no C++ toolchain)")
        self._lib = lib
        self.width = int(width)
        self._ptr = lib.ring_create(int(capacity), self.width)
        if not self._ptr:
            raise MemoryError("ring_create failed")
        # reusable drain buffers
        self._ts_buf = np.empty((0,), dtype=np.int64)
        self._row_buf = np.empty((0,), dtype=np.float64)

    def push(self, ts: int, row) -> bool:
        arr = np.asarray(row, dtype=np.float64)
        return bool(
            self._lib.ring_push(
                self._ptr, int(ts),
                arr.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            )
        )

    def push_many(self, timestamps, rows) -> int:
        """Blocking bulk push (spins on back-pressure); returns count."""
        n = 0
        for ts, row in zip(timestamps, rows):
            while not self.push(ts, row):
                pass  # ring full: busy-wait back-pressure like Disruptor
            n += 1
        return n

    def pop_batch(self, max_rows: int):
        """-> (ts [n] int64, rows [n, width] float64)."""
        if self._ts_buf.shape[0] < max_rows:
            self._ts_buf = np.empty((max_rows,), dtype=np.int64)
            self._row_buf = np.empty((max_rows * self.width,), dtype=np.float64)
        n = self._lib.ring_pop_batch(
            self._ptr,
            self._ts_buf.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
            self._row_buf.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            int(max_rows),
        )
        n = int(n)
        return (
            self._ts_buf[:n].copy(),
            self._row_buf[: n * self.width].reshape(n, self.width).copy(),
        )

    def size(self) -> int:
        return int(self._lib.ring_size(self._ptr))

    def close(self) -> None:
        if self._ptr:
            self._lib.ring_destroy(self._ptr)
            self._ptr = None

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass
