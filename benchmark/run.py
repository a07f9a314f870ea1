"""Run one cell of BENCHMARK.json once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (import, deploy, state fill, warm-up on the cell's own traffic) is
timed as `setup_s`; then the cell's driver offers its traffic for `--seconds`
and what the query callback received is compared with the plain reference.
The last line of stdout is the result: one JSON object with `correct`,
`attempted`, `failed`, `metrics`, `device`, traced `breakdown`, and last
`compared` (every number compared, with its limit). With
`--trace 0` the metrics are the cell's end-to-end metrics; with `--trace 1`
the window is traced (and cut to the mix's `trace_seconds`) and the metrics
are its per-layer ones.

Exits non-zero, printing no result, unless `jax.devices()[0].platform` is
"tpu" with as many chips as the cell asks for. `--rehearse` skips that for a
run at the configuration's `rehearse_sizes` on whatever backend is there: it
proves the plumbing and reports no metric (`"rehearsal": true`, `metrics`
empty), and is never the manifest's command.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))

import harness  # noqa: E402
from harness import say  # noqa: E402


class EngineLog(logging.Handler):
    """What the engine logged at WARNING or above."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.records: list[str] = []
        self.errors = 0

    def emit(self, record: logging.LogRecord) -> None:
        self.records.append(
            f"{record.levelname} {record.name}: {record.getMessage()}")
        self.errors += record.levelno >= logging.ERROR


class SpanRecorder(harness.Recorder):
    """The traced run's callback also notes when it returned, so that idle
    gaps can be told apart by whether a callback was running."""

    def __init__(self, sample_callbacks: int):
        super().__init__(sample_callbacks)
        self.spans: list[tuple] = []

    def __call__(self, ts, ins, removed):
        t0 = time.perf_counter()
        super().__call__(ts, ins, removed)
        self.spans.append((t0, time.perf_counter()))


class CompileLog(logging.Handler):
    """Names of the programs XLA built or loaded, from JAX's own debug log:
    a compile inside the window is a fault to find, so it needs a name."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.names: list[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if msg.startswith("Finished XLA compilation of "):
            self.names.append(msg.split(" ")[4])


def layer_values(cell: dict, trace, spans: dict, counters: dict) -> dict:
    """{per-layer metric: (value, unit)} of the cell's entries, each from its
    reader; a reader that finds nothing to read leaves its metric out."""
    values = {}
    for m in cell["per_layer"]:
        reader = harness.load_module(
            harness.reader_file(cell["bench_dir"], m["name"]))
        value = reader.read(trace, spans, counters, cell)
        if value is not None:
            values[m["name"]] = (float(value), m["unit"])
    return values


def main(argv=None, manifest: Path | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; reports no metric")
    args = ap.parse_args(argv)
    manifest = manifest or HERE.parent / "BENCHMARK.json"
    opened = harness.open_cell(manifest, args.workload, args.rehearse)
    if opened is None:
        return 3
    cell, driver, device, cache_dir = opened
    import jax

    traffic = cell["traffic"]
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(f"compile cache: {cache_dir} ({entries} entries at start)")

    compiles = CompileLog()
    dispatch_log = logging.getLogger("jax._src.dispatch")
    dispatch_log.addHandler(compiles)
    dispatch_log.setLevel(logging.DEBUG)
    dispatch_log.propagate = False
    log = EngineLog()
    logging.getLogger("siddhi_tpu").addHandler(log)

    rec = (SpanRecorder if args.trace else harness.Recorder)(
        traffic["sample_callbacks"])
    dep = harness.Deployment(cell, args.seed, rec,
                             with_statistics=bool(args.trace))
    say(f"deployed {cell['config']['name']} at "
        f"{time.perf_counter() - T_PROCESS:.1f} s")

    # ---- set-up: fill the state, then warm up on the window's own traffic
    dep.fill()
    bad_setup = harness.check_paths(
        cell, dep.status(), cell["config"]["ready"], "state before the window")
    say(f"state filled at {time.perf_counter() - T_PROCESS:.1f} s")
    driver.run(dep, traffic, traffic["warmup_seconds"])
    say(f"warmed up at {time.perf_counter() - T_PROCESS:.1f} s: "
        f"{len(dep.sends)} sends, {len(compiles.names)} programs built or loaded")
    # draining the engine's deferred overflow flags now restarts its 5 s
    # period, so every run meets its first periodic drain at +5 s
    harness.prebuild_flag_drain()
    dep.flush_warnings()
    gc.collect()
    # what set-up allocated stays out of later collections: on the trickle
    # cell this narrowed the run-to-run range of send_p50_ms (PERF.md, PR 23)
    gc.freeze()

    # ---- the window
    seconds = args.seconds
    trace_dir = None
    if args.trace:
        seconds = min(seconds, traffic["trace_seconds"])
        trace_dir = HERE.parent / "bench_out" / cell["name"] / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
        # device and TraceMe events only: the Python tracer would slow the
        # host path that the traced window is there to show
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    ledger_before = dep.compile_ledger()
    s0, c0, n_compiles = len(dep.sends), len(rec.t), len(compiles.names)
    moments = harness.sample_moments(
        args.seed, seconds, traffic["samples"],
        traffic.get("sample_skip_upto", 0))
    setup_s = time.perf_counter() - T_PROCESS
    rec.arm([(time.perf_counter() + m, skip) for m, skip in moments])
    window_entered_ns = time.perf_counter_ns()
    if args.trace:
        with jax.profiler.TraceAnnotation("bench:window"):
            info = driver.run(dep, traffic, seconds)
    else:
        info = driver.run(dep, traffic, seconds)
    t_end = time.perf_counter()
    built = compiles.names[n_compiles:]
    if args.trace:
        jax.profiler.stop_trace()
    dep.flush_warnings()
    win = {"sends": dep.sends[s0:], "cb_lo": c0, "cb_hi": len(rec.t),
           "info": info, "seconds": t_end - info["t0"]}
    say(f"window: {len(win['sends'])} sends, {win['cb_hi'] - c0} callbacks, "
        f"{sum(rec.n[c0:])} rows delivered in {win['seconds']:.3f} s; "
        f"programs built or loaded inside it: {len(built)} {built}")
    for line in log.records:
        say("engine log: " + line)
    out_dir = HERE.parent / "bench_out" / cell["name"]
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / "window.json").write_text(json.dumps({
        "seed": args.seed, "trace": args.trace,
        "sends": [list(s) for s in win["sends"]],
        "callbacks": [[rec.t[j], rec.n[j]] for j in range(c0, len(rec.t))],
    }))
    status = dep.status()
    ledger_after = dep.compile_ledger()
    for name, ent in ledger_after.items():
        say(f"compile telemetry: {name}: compiles {ent['compiles']} "
            f"causes {ent['causes']}")

    # ---- correct: counts, order, and the samples compared row by row
    end_to_end = driver.end_to_end(dep, traffic, win)
    say(driver.describe(dep, traffic, win))
    bad_path = bad_setup + harness.check_paths(
        cell, status, traffic["expect"], "path engaged")
    # the reference first: under the replay form what was due is its to say
    t_ref = time.perf_counter()
    compared = harness.compare_samples(dep, rec.samples)
    due = int(dep.stream.kept_before(dep.cursor))
    several = dep.calls if len(dep.stream.names) > 1 else ()
    numbers = {
        "delivered.missing": (abs(due - rec.delivered), 0),
        "order.faults": (
            harness.order_faults(rec, c0, win["cb_hi"], several), 0),
        "expired.delivered": (rec.expired_seen, 0),
        "engine.errors": (log.errors, 0),
        **compared,
    }
    say(f"reference along the stream, {len(rec.samples)} callbacks compared, took "
        f"{time.perf_counter() - t_ref:.2f} s")
    correct = True
    for name, (value, limit) in numbers.items():
        say(f"compared {name} = {value!r} (limit {limit!r})")
        if limit is not None and not value <= limit:
            correct = False
    after = np.cumsum(rec.n)  # delivered once callback j has returned
    failed = 0
    for _, t1, lo, hi, ok in win["sends"]:
        # send_columns returns after delivery: the rows are due by then
        came = np.searchsorted(rec.t, t1, side="right")
        delivered = came and after[came - 1] >= dep.stream.kept_before(hi)
        failed += not (ok and delivered and not bad_path)
    if bad_path or not win["sends"]:
        correct = False

    # ---- metrics
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    result = {"correct": bool(correct), "attempted": len(win["sends"]),
              "failed": int(failed), "metrics": {}, "device": device}
    if args.rehearse:
        result["rehearsal"] = True
    values = {}
    if args.trace:
        import trace_reduce

        spans = {
            "sends": np.asarray([s[:4] for s in win["sends"]], dtype=np.float64),
            "callbacks": np.stack([
                np.asarray(rec.t[c0:]), np.asarray(rec.n[c0:], dtype=np.float64),
                np.concatenate([[0.0], after[:-1]])[c0:]], axis=1),
            "callback_spans": np.asarray(rec.spans[c0:], dtype=np.float64),
            "info": info,
            "stream": dep.stream,
        }
        trace = trace_reduce.load(trace_reduce.find_xplane(str(trace_dir)))
        has_device = bool(trace.devices) and trace.window_ns is not None
        if has_device:
            offset = trace_reduce.clock_offset(trace, window_entered_ns)
            spans["to_trace_ns"] = lambda t: np.asarray(t) * 1e9 + offset
            device["busy_s"] = trace_reduce.busy_seconds(trace)
            device["window_s"] = trace.window_s
            host = {
                "in_callback": spans["to_trace_ns"](spans["callback_spans"]),
                "in_generator_wait": spans["to_trace_ns"](
                    np.asarray(info.get("waits", [])).reshape(-1, 2)),
                "in_send_columns": spans["to_trace_ns"](spans["sends"][:, :2]),
            }
            result["breakdown"] = trace_reduce.breakdown(trace, host)
            say(f"trace: {[d.name for d in trace.devices]} busy "
                f"{device['busy_s']:.4f} s of {device['window_s']:.4f} s")
        elif not args.rehearse:
            say("trace: no device plane or no window span found")
            result["correct"] = False
        counters = {
            "status": status,
            "compile_before": ledger_before,
            "compile_after": ledger_after,
            "programs_built_in_window": len(built),
            "device_kind": device["kind"],
        }
        values = layer_values(cell, trace if has_device else None, spans,
                              counters)
    else:
        end_to_end["setup_s"] = setup_s
        for m in cell["end_to_end"]:
            # a driver names the quantity; the manifest may split it by cell
            value = end_to_end.get(m["name"],
                                   end_to_end.get(harness.stem(m["name"])))
            if value is not None:
                values[m["name"]] = (float(value), m["unit"])
    if args.rehearse:
        say(f"rehearsal computed (not reported): {sorted(values)}")
    else:
        result["metrics"] = {k: {"value": v, "unit": u}
                             for k, (v, u) in values.items()}
    dep.close()
    # each number compared beside its limit: last in the result and on stderr
    big = sys.float_info.max
    result["compared"] = {
        name: {"value": min(float(value), big), "limit": limit}
        for name, (value, limit) in numbers.items()}
    print(json.dumps(result), flush=True)
    for name, (value, limit) in numbers.items():
        print(f"compared {name} = {value!r} (limit {limit!r})",
              file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
