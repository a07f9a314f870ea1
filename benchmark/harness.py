"""The yardstick's shared machinery: the seeded stream, the deployment under
test, the query callback that records deliveries, and the comparison with
the plain reference that decides `correct`.

Nothing here names a cell, a configuration or a metric: those are data
(`BENCHMARK.json`, `configs/<config>/`, `traffic/<mix>.json`) and small
modules found by name (`drivers/<driver>.py`, `layer_metrics/<name>.py`).
From the program the benchmark takes `SiddhiManager`, `send_columns`, the
query callback, `snapshot_status()` and `profile_report()["compile"]`.

A configuration's `reference.py` says what the query callback is owed, in
one of two forms, told apart by what the file defines:

- kept form: `kept(cols)` marks the input rows that emit, one emission each,
  in arrival order, and `Running(sizes, control=False)` with
  `step(ts, cols, leaving, emit)` carries the state over the kept rows alone.
  Emission number d is the d-th kept row, so what is due at any row is known
  before a reference has run.
- replay form: no `kept`; `Replay(sizes, control=False)` with
  `feed(stream, ts, cols, emit) -> (n, lanes)`. After the window `feed` is
  called once for every `send_columns` call the deployment made, in the order
  they were made, from the first send of the fill, with the stream's name and
  the timestamps and columns as a reference sees them (string columns as
  indices into `gen.STRINGS`). `n` is how many emissions the query callback is
  owed by the time that call returns; `lanes`, where `emit` is true, is one
  array per name in the configuration's `outputs` plus `event_time`, each of
  length `n`, in the order in which the callback has to receive them (with
  `emit` false it may be None: a reference may count more cheaply than it
  emits). For patterns, joins, tumbling windows and `@app:watermark`
  reordering: queries stated on event time. An emission that a wall-clock
  timer causes arrives between sends and is outside this contract.

With `"streams": ["A", "B"]` in place of `"stream"`, `gen.make(seed, rows)`
returns one pool per stream, `gen.split(lo, hi)` the rows `(a, b)` of each
stream, in that stream's own count, that lie inside rows lo..hi-1 of the
merged stream, and `gen.timestamps(a, b, stream)` (and `with_index(cols, a, b,
ts, stream)`, where there is one) take the stream's name. One send of a driver
is then one `send_columns` per stream, in the order of `streams`.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
import traceback
from pathlib import Path

import numpy as np

def sample_moments(seed: int, seconds: float, n: int, skip_upto: int = 0):
    """When, inside a window, the compared samples are taken: the window is
    cut into `n` equal parts and each gives one moment drawn from the seed,
    so the sample covers the whole window and differs from seed to seed.
    Each moment comes with a number of callbacks to let pass first, drawn
    from 0..skip_upto: callbacks come in bursts, one per micro-batch of a
    send, and every place in a burst has to be able to fall into a sample."""
    rng = np.random.default_rng(seed)
    drawn = rng.uniform(0.0, 1.0, n)
    skips = rng.integers(0, skip_upto + 1, n)
    return [((k + float(u)) * seconds / n, int(skip))
            for k, (u, skip) in enumerate(zip(drawn, skips))]


def say(msg: str) -> None:
    """A human-readable line; the result line is the only JSON-object line
    that comes last."""
    print(msg, flush=True)


def load_module(path: Path):
    """Import a file found by name (names may hold '.' and '-')."""
    key = "bench_" + "_".join(path.with_suffix("").parts[-3:]).replace(
        "-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(key, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def stem(name: str) -> str:
    """A quantity that several end-to-end metrics are moved by is split into
    one metric for each (`chunk_device_ms.bulk`, `chunk_device_ms.filter`):
    the part before the last '.' names the quantity they share."""
    return name.rpartition(".")[0] or name


def reader_file(bench_dir: Path, metric: str) -> Path:
    """The reader of a per-layer metric: a file of the metric's own name,
    or else the one of the quantity it is split from."""
    own = bench_dir / "layer_metrics" / f"{metric}.py"
    return own if own.exists() else own.with_name(f"{stem(metric)}.py")


def load_cell(manifest_path: Path, workload: str, rehearse: bool) -> dict:
    """Resolve one entry of `workloads` into its configuration, traffic mix,
    driver and metric lists. Paths come from the manifest's own fields."""
    manifest = json.loads(manifest_path.read_text())
    root = manifest_path.resolve().parent
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in {manifest_path}; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == w["config"])
    cfg_file = root / cfg_entry["file"]
    cfg = json.loads(cfg_file.read_text())
    # readers and `<stream>` ask for one input stream: the first
    cfg.setdefault("streams", [cfg.get("stream")])
    cfg.setdefault("stream", cfg["streams"][0])
    bench_dir = root / manifest["paths"][0]
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    sizes = dict(cfg["sizes"])
    if rehearse:
        sizes.update(cfg.get("rehearse_sizes", {}))
        traffic = {**traffic, **traffic.get("rehearse", {})}

    def wanted(entries):
        return [m for m in entries
                if workload in m.get("workloads", [workload])]

    return {
        "name": workload,
        "chips": w["chips"],
        "config": cfg,
        "config_dir": cfg_file.parent,
        "sizes": sizes,
        "traffic": traffic,
        "bench_dir": bench_dir,
        "end_to_end": wanted(manifest["end_to_end"]),
        "per_layer": wanted(manifest["per_layer"]),
    }


def place_compile_cache(root: Path) -> str:
    """JAX's persistent cache at a fixed path inside the checkout, so that
    only a checkout's first run of a cell compiles."""
    import jax

    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    cache = root / ".jax_cache"
    cache.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    return str(cache)


def open_cell(manifest: Path, workload: str, rehearse: bool):
    """(cell, driver module, device as JAX reports it, cache directory), or
    None where the cell's chips are not there: a measurement path that finds
    no chip fails, it does not fall back to the CPU."""
    cell = load_cell(manifest, workload, rehearse)
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"jax {jax.__version__} platform={device['platform']} "
        f"kind={device['kind']} count={device['count']}")
    if rehearse:
        say("REHEARSAL: tiny sizes, no metric is reported")
    elif device["platform"] != "tpu" or device["count"] < cell["chips"]:
        print(f"benchmark: needs {cell['chips']} TPU chip(s), found "
              f"{device['count']} x {device['platform']}", file=sys.stderr)
        return None
    cache_dir = place_compile_cache(Path(__file__).resolve().parents[1])
    driver = load_module(
        cell["bench_dir"] / "drivers" / f"{cell['traffic']['driver']}.py")
    return cell, driver, device, cache_dir


def size_of(cell: dict, value):
    """A number, or the name of one of the configuration's sizes."""
    return cell["sizes"][value] if isinstance(value, str) else value


def cyclic(pool: dict, n: int, lo: int, hi: int) -> dict:
    """Rows lo..hi-1 of a pool of `n` rows replayed in cycles."""
    a, b = lo % n, lo % n + (hi - lo)
    if b <= n:
        return {k: v[a:b] for k, v in pool.items()}
    # the stretch wraps round the pool: piece it together
    cuts = [(a, n)] + [(0, n)] * (b // n - 1) + [(0, b % n)]
    return {k: np.concatenate([v[i:j] for i, j in cuts])
            for k, v in pool.items()}


class Stream:
    """The seeded input stream. A pool of rows is generated once from the
    seed and replayed in cycles; event time follows the global row index, so
    it never repeats. Every row of the stream is addressable, which is what
    lets the reference be run on any stretch of it after the window.

    With several input streams (`names`) the pool is one per stream and a
    stretch of the merged stream is cut into one part for each (`parts`).
    Under a reference of the replay form, which rows emit is known only once
    the reference has replayed the sends (`note_due`)."""

    def __init__(self, gen, reference, seed: int, pool_rows: int,
                 names: list | None = None):
        self.gen = gen
        self.reference = reference
        self.names = names or [None]
        # a generator with a schedule replays whole cycles of it
        cycle = getattr(gen, "CYCLE_ROWS", 1)
        pool_rows = -(-pool_rows // cycle) * cycle
        self.pool = gen.make(seed, pool_rows)
        self.n = pool_rows
        self.replayed = not hasattr(reference, "kept")
        if self.replayed:
            self.due_at = {0: 0}   # send boundary (stream row) -> emissions
            self.emit_share = None
            return
        keep = reference.kept(self.pool)
        self.cumk = np.concatenate([[0], np.cumsum(keep, dtype=np.int64)])
        self.kept_per_cycle = int(self.cumk[-1])
        # the share of the rows sent that emit
        self.emit_share = self.kept_per_cycle / self.n

    def columns(self, lo: int, hi: int, pool: dict | None = None):
        """(timestamps, columns) of stream rows lo..hi-1."""
        cols = cyclic(self.pool if pool is None else pool, self.n, lo, hi)
        ts = self.gen.timestamps(lo, hi)
        with_index = getattr(self.gen, "with_index", None)
        return ts, (with_index(cols, lo, hi, ts) if with_index else cols)

    def parts(self, lo: int, hi: int, pool: dict | None = None) -> list:
        """Rows lo..hi-1 of the (merged) stream as the `send_columns` calls
        that carry them: (stream name, timestamps, columns), one per input
        stream in the order of `names`."""
        if len(self.names) == 1:
            return [(self.names[0], *self.columns(lo, hi, pool))]
        pool = self.pool if pool is None else pool
        with_index = getattr(self.gen, "with_index", None)
        out = []
        cut = self.gen.split(lo, hi)
        for name in self.names:
            a, b = cut[name]
            rows = len(next(iter(pool[name].values())))
            cols = cyclic(pool[name], rows, a, b)
            ts = self.gen.timestamps(a, b, name)
            out.append((name, ts, with_index(cols, a, b, ts, name)
                        if with_index else cols))
        return out

    def kept_columns(self, d_lo: int, d_hi: int):
        """(timestamps, columns) of emissions d_lo..d_hi-1: the stream rows
        that produce them, and no others."""
        if d_hi <= d_lo:
            a = b = 0
        else:
            a, b = self.raw_of_kept(d_lo), self.raw_of_kept(d_hi - 1) + 1
        ts, cols = self.columns(a, b)
        keep = self.reference.kept(cols)
        return ts[keep], {k: v[keep] for k, v in cols.items()}

    def kept_before(self, i):
        """How many emissions stream rows 0..i-1 produce. Under the replay
        form only the ends of sends can be asked for, once the reference has
        replayed them."""
        i = np.asarray(i, dtype=np.int64)
        if self.replayed:
            try:
                return np.asarray([self.due_at[int(r)] for r in i.ravel()],
                                  dtype=np.int64).reshape(i.shape)
            except KeyError as row:
                raise LookupError(
                    f"what is due at stream row {row} is not known: under a "
                    f"reference of the replay form only the ends of sends "
                    f"are, and only after `compare_samples`") from None
        return (i // self.n) * self.kept_per_cycle + self.cumk[i % self.n]

    def note_due(self, ends, due) -> None:
        """Replay form: the emissions owed once the send that ends at each
        stream row of `ends` has returned."""
        self.due_at.update(zip(map(int, ends), map(int, due)))
        if len(ends) and ends[-1]:
            self.emit_share = int(due[-1]) / int(ends[-1])

    def raw_of_kept(self, d: int) -> int:
        """Stream row that produces emission number d (0-based)."""
        cyc, r = divmod(int(d), self.kept_per_cycle)
        return cyc * self.n + int(
            np.searchsorted(self.cumk, r, side="right")) - 1


class Recorder:
    """The query callback: cheap and constant. Per call it notes the clock,
    the row count and the first and last event time. At moments spread over
    the whole window (see `arm`) it keeps the event lists of the next
    `sample_callbacks` calls, so that those rows can be compared one by one
    once the window has closed; every other list dies when the engine lets
    go of it, as under a callback that keeps nothing. No per-row Python in
    the window."""

    def __init__(self, sample_callbacks: int):
        self.sample_callbacks = sample_callbacks
        self.t: list[float] = []
        self.n: list[int] = []
        self.first: list[int] = []
        self.last: list[int] = []
        self.delivered = 0
        self.expired_seen = 0
        self.samples: list[tuple] = []  # (rows delivered before, events)
        self._due: list[tuple] = []     # (clock time, callbacks to skip)
        self._skip = self._keeping = 0

    def __call__(self, ts, ins, removed):
        now = time.perf_counter()
        if removed:
            self.expired_seen += len(removed)
        if not ins:
            return
        n = len(ins)
        self.t.append(now)
        self.n.append(n)
        self.first.append(ins[0][0])
        self.last.append(ins[-1][0])
        if self._due and now >= self._due[0][0]:
            self._skip = self._due[0][1]
            while self._due and now >= self._due[0][0]:
                self._due.pop(0)
            self._keeping = self.sample_callbacks
        elif self._skip:
            self._skip -= 1
        elif self._keeping:
            self._keeping -= 1
            self.samples.append((self.delivered, ins))
        self.delivered += n

    def arm(self, moments: list[tuple]) -> None:
        """(clock time, callbacks to let pass) of each sample to keep."""
        self._due = sorted(moments)


class Deployment:
    """One app runtime of the system under test, fed through `send_columns`
    with the recorder attached as the query callback."""

    def __init__(self, cell: dict, seed: int, recorder: Recorder,
                 with_statistics: bool):
        from siddhi_tpu import SiddhiManager

        cfg, cdir = cell["config"], cell["config_dir"]
        self.cell = cell
        self.gen = load_module(cdir / "gen.py")
        self.reference = load_module(cdir / "reference.py")
        text = (cdir / "app.siddhi").read_text().format(**cell["sizes"])
        if with_statistics:
            # CompileTelemetry needs it; only the traced run pays for it
            text = "@app:statistics(reporter='none')\n" + text
        self.mgr = SiddhiManager()
        self.string_index = {}
        ids = {}
        for col, names in self.gen.STRINGS.items():
            ids[col] = np.array(
                [self.mgr.interner.intern(s) for s in names], dtype=np.int32)
            self.string_index[col] = {s: i for i, s in enumerate(names)}
        batch = cell["sizes"]["batch"]
        pool_rows = cell["traffic"]["pool_batches"] * batch
        names = cfg["streams"]
        self.stream = Stream(self.gen, self.reference, seed, pool_rows, names)

        def interned(pool: dict) -> dict:
            return {k: (ids[k][v] if k in ids else v) for k, v in pool.items()}

        # what is sent carries interned ids; the reference sees indices
        self.send_pool = (
            interned(self.stream.pool) if len(names) == 1 else
            {name: interned(pool) for name, pool in self.stream.pool.items()})
        self.recorder = recorder
        self.rt = self.mgr.create_siddhi_app_runtime(text)
        self.rt.add_callback(cfg["query"], self.recorder)
        self.rt.start()
        self.handlers = {name: self.rt.get_input_handler(name)
                         for name in names}
        self.cursor = 0           # next stream row to send
        self.sends: list[tuple] = []  # (t_start, t_end, lo, hi, ok)
        # (t_start, t_end) of every `send_columns` call, in the order made:
        # the parts of sends[0], then those of sends[1], ...
        self.calls: list[tuple] = []

    def prepare(self, rows: int):
        """Columns of the next `rows` stream rows, ready to send."""
        lo, hi = self.cursor, self.cursor + rows
        self.cursor = hi
        return lo, hi, self.stream.parts(lo, hi, self.send_pool)

    def send(self, prepared) -> float:
        """One send of a driver: one `send_columns` call per input stream;
        returns the seconds the caller was blocked. A send that raises is
        logged and counted as failed."""
        lo, hi, parts = prepared
        ok = True
        for name, ts, cols in parts:
            t0 = time.perf_counter()
            try:
                self.handlers[name].send_columns(ts, cols)
            except Exception:
                ok = False
                say("send raised:\n" + traceback.format_exc())
            t1 = time.perf_counter()
            self.calls.append((t0, t1))
        first = self.calls[-len(parts)][0]
        self.sends.append((first, t1, lo, hi, ok))
        return t1 - first

    def status(self) -> dict:
        return self.rt.snapshot_status()

    def compile_ledger(self) -> dict:
        """program -> CompileTelemetry entry; empty without statistics."""
        return (self.rt.profile_report() or {}).get("compile", {})

    def fill(self) -> None:
        """Sends that fill the configuration's device state: at least one,
        and as many as bring `fill_kept_rows` rows into it. Under the replay
        form, where no row is known to be kept before the reference has run,
        the configuration states the fill in rows sent: `fill_rows`."""
        cfg = self.cell["config"]
        rows = cfg["fill_send_batches"] * self.cell["sizes"]["batch"]
        self.send(self.prepare(rows))
        if self.stream.replayed:
            need = size_of(self.cell, cfg["fill_rows"])
            while self.cursor < need:
                self.send(self.prepare(rows))
            return
        need = size_of(self.cell, cfg["fill_kept_rows"])
        while self.stream.kept_before(self.cursor) < need:
            self.send(self.prepare(rows))

    def flush_warnings(self) -> None:
        """Capacity-overflow flags surface as engine log records."""
        for qr in self.rt.queries.values():
            flush = getattr(qr, "flush_aux_warnings", None)
            if flush is not None:
                flush()

    def close(self) -> None:
        self.rt.shutdown()
        self.mgr.shutdown()


def prebuild_flag_drain(longest: int = 64) -> None:
    """Build, before the window, the small eager programs of the engine's
    periodic overflow-flag drain (`core/query_runtime.py` `_AuxWarnPool`):
    it stacks however many device flags fell into its 5 s period, at most
    64, so each length is a program of its own (`jit(concatenate)`,
    `jit(_reduce_any)`) and which lengths a window meets varies from run to
    run. They are tiny, so the cache keeps them all after a first run."""
    import jax.numpy as jnp

    flag = jnp.zeros((), dtype=bool)
    for n in range(1, longest + 1):
        jnp.stack([jnp.asarray(flag).astype(bool)] * n).any()


def lookup(tree: dict, dotted: str):
    node = tree
    for part in dotted.split("."):
        node = node[part]
    return node


def check_paths(cell: dict, status: dict, expect: dict, what: str) -> list:
    """Dotted paths into `snapshot_status()` against expected values;
    `<stream>` stands for the configuration's (first) input stream. A string
    that names one of the configuration's sizes stands for that size, any
    other string for itself. Returns the failures as text."""
    bad = []
    for path, want in expect.items():
        path = path.replace("<stream>", cell["config"]["stream"])
        if isinstance(want, str):
            want = cell["sizes"].get(want, want)
        try:
            got = lookup(status, path)
        except (KeyError, TypeError):
            got = "<missing>"
        say(f"{what}: {path} = {got!r} (expected {want!r})")
        if got != want:
            bad.append(f"{path} = {got!r}, expected {want!r}")
    return bad


def events_to_lanes(dep: Deployment, events: list) -> dict:
    """Event lists of compared samples -> one array per output lane."""
    outputs = dep.cell["config"]["outputs"]
    n = len(events)
    lanes = {"event_time": np.fromiter((e[0] for e in events), np.int64, n)}
    for k, name in enumerate(outputs):
        index = dep.string_index.get(name)
        if index is not None:
            lanes[name] = np.fromiter(
                (index[e[1][k]] for e in events), np.int64, n)
        else:
            lanes[name] = np.array([e[1][k] for e in events])
    return lanes


def lane_gap(got: np.ndarray, ref: np.ndarray, rule: dict) -> float:
    """The number compared for one lane: mismatching rows where the limit
    is 0, else the widest |got - ref| / max(|ref|, floor)."""
    if rule["limit"] == 0:
        return float(np.count_nonzero(got != ref))
    got = got.astype(np.float64)
    if not np.isfinite(got).all():
        return float("inf")
    return float((np.abs(got - ref) / np.maximum(
        np.abs(ref), rule.get("floor", 0.0))).max())


SWEEP_ROWS = 1 << 20  # emissions per step while the reference only moves on


class KeptEmissions:
    """What a reference of the kept form owes the callback, through the
    interface the replay form is served by (`ReplayEmissions`): emission
    number d is the d-th kept row of the stream, so what is due after a send
    is arithmetic, and the reference is carried over the kept rows alone."""

    def __init__(self, dep: Deployment, control: bool):
        cell = dep.cell
        self.stream = dep.stream
        self.history = size_of(cell, cell["config"]["history_kept_rows"])
        self.runs = [dep.reference.Running(cell["sizes"])]
        if control:
            self.runs.append(dep.reference.Running(cell["sizes"], control=True))
        self.at = 0

    def _advance(self, upto: int, emit: bool) -> list:
        stream, at, history = self.stream, self.at, self.history
        ts, cols = stream.kept_columns(at, upto)
        _, leaving = stream.kept_columns(max(at - history, 0),
                                         max(upto - history, 0))
        self.at = upto
        return [run.step(ts, cols, leaving, emit) for run in self.runs]

    def lanes(self, d0: int, d1: int) -> list:
        """Emissions d0..d1-1 as each run states them; asked for in rising
        order."""
        if not self.history:  # nothing is carried: no row before counts
            self.at = d0
        while self.at < d0:  # the rows between samples move the state alone
            self._advance(min(self.at + SWEEP_ROWS, d0), emit=False)
        return self._advance(d1, emit=True)

    def finish(self) -> None:
        """What is due after every send needs no further step."""


class ReplayEmissions:
    """What a reference of the replay form owes the callback: its `Replay`
    is fed every `send_columns` call the deployment made, in order, in one
    pass. A call emits where a compared sample was delivered during it (the
    sample's callback ran between the call's start and its return); every
    other call only counts."""

    def __init__(self, dep: Deployment, control: bool):
        cell, rec = dep.cell, dep.recorder
        self.dep = dep
        self.runs = [dep.reference.Replay(cell["sizes"])]
        if control:
            self.runs.append(dep.reference.Replay(cell["sizes"], control=True))
        self.call_end = np.asarray([t1 for _, t1 in dep.calls])
        # a sample is known by the rows delivered before it: find its clock
        self.before = np.cumsum(rec.n) - np.asarray(rec.n)
        self.fed = 0                 # send_columns calls fed so far
        self.due = [0]               # the reference's emissions after each
        self.pending = self._calls()
        self.held = None             # (call, emissions before it, lanes per run)

    def _calls(self):
        for _, _, lo, hi, _ in self.dep.sends:
            yield from self.dep.stream.parts(lo, hi)

    def _feed(self, emit: bool):
        name, ts, cols = next(self.pending)
        out = [run.feed(name, ts, cols, emit) for run in self.runs]
        self.held = (self.fed, self.due[-1], [lanes for _, lanes in out])
        self.due.append(self.due[-1] + int(out[0][0]))
        self.fed += 1

    def lanes(self, d0: int, d1: int) -> list:
        """Emissions d0..d1-1 as each run states them, or None for a run
        that states no such emissions in the call that delivered them."""
        j = int(np.searchsorted(self.before, d0))
        call = min(int(np.searchsorted(self.call_end, self.dep.recorder.t[j])),
                   len(self.call_end) - 1)
        while self.fed < call:
            self._feed(emit=False)
        if self.fed == call:
            self._feed(emit=True)
        k, start, stated = self.held
        out = []
        for lanes in stated:
            n = len(lanes["event_time"]) if k == call and lanes else -1
            inside = 0 <= d0 - start and d1 - start <= n
            out.append({name: lane[d0 - start:d1 - start]
                        for name, lane in lanes.items()} if inside else None)
        return out

    def finish(self) -> None:
        """Feed what is left, and tell the stream what is due after each
        send: that of its last call."""
        while self.fed < len(self.dep.calls):
            self._feed(emit=False)
        parts = len(self.dep.stream.names)
        self.dep.stream.note_due([s[3] for s in self.dep.sends],
                                 self.due[parts::parts])


def compare_samples(dep: Deployment, samples: list, control=False):
    """Carry the configuration's reference along the whole stream, from its
    first row, and compare what the callback received in each kept sample
    with the emissions the reference states at those places: count, order
    and values, as {number compared: (value, limit)}. The reference sees the
    input stream alone. With `control`, the reference in its lower precision
    is carried along too and stands in the program's place: a second dict of
    the same numbers, its emissions at the same places against the
    reference's, is returned beside the first. Afterwards
    `dep.stream.kept_before` answers at the end of every send."""
    rules = dep.cell["config"]["compare"]
    form = ReplayEmissions if dep.stream.replayed else KeptEmissions
    stated = form(dep, control)
    worst = [{lane: 0.0 for lane in rules} for _ in stated.runs]
    rows = 0
    for d0, events in sorted(samples, key=lambda s: s[0]):
        want, *others = stated.lanes(d0, d0 + len(events))
        for w, got in zip(worst, [events_to_lanes(dep, events), *others]):
            for lane, rule in rules.items():
                gap = (lane_gap(got[lane], want[lane], rule)
                       if got is not None and want is not None else
                       float(len(events)) if rule["limit"] == 0 else
                       float("inf"))
                w[lane] = max(w[lane], gap)
        rows += len(events)
    stated.finish()
    numbers = [{**{f"{lane}.gap": (w[lane], rules[lane]["limit"])
                   for lane in rules}, "rows_compared": (rows, None)}
               for w in worst]
    return tuple(numbers) if control else numbers[0]


def order_faults(rec: Recorder, lo: int, hi: int, calls=()) -> int:
    """Callbacks lo..hi-1 whose event times run backwards, within a
    callback or against the one before. With several input streams (`calls`:
    every `send_columns` call's start and end) each call starts over in
    event time, so a callback is held against the one before only where
    both were delivered during one call."""
    first = np.asarray(rec.first[lo:hi])
    last = np.asarray(rec.last[lo:hi])
    back = first[1:] < last[:-1]
    if len(calls):
        of = np.searchsorted([t1 for _, t1 in calls], rec.t[lo:hi])
        back &= of[1:] == of[:-1]
    return int(np.count_nonzero(first > last) + np.count_nonzero(back))
