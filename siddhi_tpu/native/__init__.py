"""Native host components: the C++ ingress ring with ctypes bindings.

The ring (ring.cpp) is the native analog of the reference's LMAX Disruptor
substrate (StreamJunction.java:262-298): a lock-free bounded MPSC queue of
fixed-width numeric rows, drained by one consumer into columnar batches. It
compiles on first use with the system toolchain; environments without g++
fall back to the pure-Python queue path transparently.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_LIB_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_LIB_FAILED = False


def _build_dir() -> str:
    d = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_build")
    os.makedirs(d, exist_ok=True)
    return d


def load_ring_library() -> Optional[ctypes.CDLL]:
    """Compile (once) and load the ring library; None when no toolchain."""
    global _LIB, _LIB_FAILED
    with _LIB_LOCK:
        if _LIB is not None or _LIB_FAILED:
            return _LIB
        src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "ring.cpp")
        try:
            # the binary is named by the source it was built from: _build/
            # is git-ignored, so a copied tree can carry a binary of some
            # other ring.cpp, and an mtime says nothing about that
            with open(src, "rb") as f:
                digest = hashlib.sha256(f.read()).hexdigest()[:16]
            out = os.path.join(_build_dir(), f"libsiddhi_ring_{digest}.so")
            if not os.path.exists(out):
                tmp = f"{out}.{os.getpid()}.tmp"
                subprocess.run(
                    ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", "-o", tmp, src],
                    check=True, capture_output=True,
                )
                os.replace(tmp, out)  # never expose a half-written binary
            lib = ctypes.CDLL(out)
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
