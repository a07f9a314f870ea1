"""How a packed buffer lies on the device and what reading it costs.

Part 1: shape, element type and layout of a [hdr + R, W] uint8 buffer, the
time of the slice, of the copy to the host and of np.ascontiguousarray,
beside the same bytes held densely (what the engine paid up to PR 44).

Part 2: the engine's own prefix program (`core/ingest.py`
`_prefix_program`: rows cut from an offset and laid out row-major on the
device) beside its variants: 32-bit words and bytes, a traced and a static
offset, for 32- and 28-byte rows and a row with a bool lane (25 bytes), at
the sizes the cells' reads come in. Per variant: the program's time on the
device, the wait for the bytes, and the whole read as the engine makes it
(program, `copy_to_host_async`, `np.asarray`, view), checked byte for byte
against `np.ascontiguousarray(buf[start : start + n])`.

Run on the chip: `chiprun -- python3 chipcheck/d2h_probe.py`; the lines go
to `chiprun_out/d2h_probe.txt` as well.
"""
import os
import statistics
import sys
import time

import jax
import jax.lax as lax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from siddhi_tpu.core.ingest import _prefix_program, read_dense  # noqa: E402

R = 1 << int(os.environ.get("D2H_LOG2_ROWS", "20"))
REPS = int(os.environ.get("D2H_REPS", "7"))
OUT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "chiprun_out",
)
os.makedirs(OUT, exist_ok=True)
_log = open(os.path.join(OUT, "d2h_probe.txt"), "w")


def say(*a):
    line = " ".join(str(x) for x in a)
    print(line, flush=True)
    _log.write(line + "\n")
    _log.flush()


def ms(vals):
    return "[" + " ".join("%.2f" % v for v in vals) + "] med %.2f" % (
        statistics.median(vals)
    )


def layout_of(x):
    last = None
    for attr in ("format", "layout"):
        try:
            return repr(getattr(x, attr))
        except Exception as e:
            last = e
    return f"not shown ({last})"


def timed(f, n=REPS):
    out = []
    for _ in range(n):
        t = time.perf_counter()
        r = f()
        out.append((time.perf_counter() - t) * 1e3)
    return r, out


def packed(shape, dtype=jnp.uint8, seed=0):
    x = jax.random.randint(
        jax.random.PRNGKey(seed), shape, 0, 255, dtype=jnp.int32
    ).astype(dtype)
    x.block_until_ready()
    return x


def probe(label, shape, dtype, rows):
    x = packed(shape, dtype)
    nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
    say(
        f"--- {label}: shape {shape} {np.dtype(dtype).name}, "
        f"{nbytes/1e6:.1f} MB logical; layout {layout_of(x)}"
    )
    sl = (lambda: x[:rows].block_until_ready()) if rows else (lambda: x)
    _y, t_slice = timed(sl)

    # a fresh slice each time: a jax array keeps its host copy once read
    def read():
        y = x[:rows] if rows else x + 0
        y.block_until_ready()
        t = time.perf_counter()
        a = np.asarray(y)
        return a, (time.perf_counter() - t) * 1e3

    reads = [read() for _ in range(REPS)]
    a = reads[-1][0]
    d2h = [dt for _a, dt in reads]
    _c, t_contig = timed(lambda: np.ascontiguousarray(a))
    say(f"    slice ms {ms(t_slice)}")
    say(
        f"    np.asarray ms {ms(d2h)} for {a.nbytes/1e6:.1f} MB -> "
        f"{a.nbytes/1e6/statistics.median(d2h):.3f} GB/s"
    )
    say(
        f"    host array: c_contiguous {a.flags.c_contiguous}, strides "
        f"{a.strides}; np.ascontiguousarray ms {ms(t_contig)}"
    )


def variants(n, W, start):
    """name -> program of (buf, start): the engine's, and beside it the
    forms it could have taken. `words, late`: the vector's bytes joined four
    by four; `words, early`: each row's, before the rows are flattened."""

    def flat(form, static):
        def f(buf, s):
            rows = (
                lax.slice_in_dim(buf, start, start + n, axis=0)
                if static
                else lax.dynamic_slice_in_dim(buf, s, n, 0)
            )
            if form == "bytes":
                return rows.reshape(-1)
            if form == "words, late":
                return lax.bitcast_convert_type(
                    rows.reshape(-1, 4), jnp.uint32
                )
            return lax.bitcast_convert_type(
                rows.reshape(n, W // 4, 4), jnp.uint32
            ).reshape(-1)

        return jax.jit(f)

    out = {
        "engine": _prefix_program(n, W),
        "bytes, static start": flat("bytes", True),
    }
    if W % 4 == 0:
        out["words, early"] = flat("words, early", False)
        # 2.4 GB of temporaries at the plug prefix, 4.3 GB at the filter's
        # (compiled for the chip without it): only where it is small
        if n * W <= 1 << 22:
            out["words, late"] = flat("words, late", False)
    return out


def prefix_probe(label, shape, start, n):
    x = packed(shape, seed=1)
    W = shape[1]
    # the parent's read of the same rows: what every variant must equal
    t = time.perf_counter()
    want = np.ascontiguousarray(np.asarray(x[start : start + n]))
    t_parent = (time.perf_counter() - t) * 1e3
    parent = []
    for _ in range(REPS):
        t = time.perf_counter()
        y = x[start : start + n]
        y.copy_to_host_async()
        np.ascontiguousarray(y)
        parent.append((time.perf_counter() - t) * 1e3)
    say(
        f"--- {label}: buffer {shape} u8, rows {start}:{start + n} "
        f"({n * W / 1e6:.1f} MB); the parent's read (slice, async copy, "
        f"np.ascontiguousarray) ms {ms(parent)} (first {t_parent:.1f})"
    )
    for name, prog in variants(n, W, start).items():
        s = np.int32(start)
        t = time.perf_counter()
        prog(x, s).block_until_ready()
        t_compile = time.perf_counter() - t
        dev, wait, whole = [], [], []
        a = None
        for _ in range(REPS):
            t = time.perf_counter()
            y = prog(x, s)
            y.block_until_ready()
            dev.append((time.perf_counter() - t) * 1e3)
            t = time.perf_counter()
            np.asarray(y)
            wait.append((time.perf_counter() - t) * 1e3)
        for _ in range(REPS):
            t = time.perf_counter()
            y = prog(x, s)
            y.copy_to_host_async()
            a = np.asarray(y).view(np.uint8).reshape(n, W)
            whole.append((time.perf_counter() - t) * 1e3)
        same = bool(np.array_equal(a, want))
        say(
            f"    {name:<22} device ms {ms(dev)}; np.asarray ms {ms(wait)}"
            f" -> {n * W / 1e6 / statistics.median(wait):.2f} GB/s; whole "
            f"read ms {ms(whole)}; equal {same}; view owndata "
            f"{a.flags.owndata} c_contiguous {a.flags.c_contiguous}; "
            f"out {layout_of(y)[:110]}; first call {t_compile:.2f} s"
        )
        if not same:
            raise SystemExit(f"{label} / {name}: bytes differ")
    # and through the engine's own two halves, start and finish
    whole = []
    for _ in range(REPS):
        t = time.perf_counter()
        a = read_dense(x, start, n)
        whole.append((time.perf_counter() - t) * 1e3)
    say(
        f"    the engine's read_dense ms {ms(whole)}; equal "
        f"{bool(np.array_equal(a, want))}"
    )


say(jax.devices())
if os.environ.get("D2H_PART1", "1") == "1":
    # the plug cells' pack: [4 + 2R, 32] u8, of which 4 + R/2 rows are read
    # (16.8 MB); the filter's: [5 + R, 28] u8, all rows read (29.4 MB)
    probe("plug pack [4+2R, 32], 4+R/2 rows read", (4 + 2 * R, 32), jnp.uint8, 4 + R // 2)
    probe("filter pack [5+R, 28], all rows read", (5 + R, 28), jnp.uint8, 5 + R)
    probe("flat bytes [N*16] u8", (R * 16,), jnp.uint8, 0)
    probe("flat words [N*4] u32", (R * 4,), jnp.uint32, 0)

# the reads the cells make: the steady prefix, the first chunk's whole
# buffer, a top-up behind a short prefix, a small bucket
prefix_probe("plug pack, steady prefix", (4 + 2 * R, 32), 0, 4 + R // 2)
prefix_probe("filter pack, steady prefix (all rows)", (5 + R, 28), 0, 5 + R)
prefix_probe("plug pack, first chunk (all rows)", (4 + 2 * R, 32), 0, 4 + 2 * R)
prefix_probe("plug pack, top-up R/2 rows behind R/2", (4 + 2 * R, 32), 4 + R // 2, R // 2)
prefix_probe("filter pack, quarter prefix", (5 + R, 28), 0, 5 + R // 4)
prefix_probe("bool-lane pack [6+R, 25], half prefix", (6 + R, 25), 0, 6 + R // 2)
prefix_probe("plug pack, small bucket", (4 + 2 * R, 32), 0, 4 + 4096)
