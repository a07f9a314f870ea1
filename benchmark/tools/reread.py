"""One traced run of a cell, read by two manifests: this checkout's
`per_layer` entries and readers, and then those of another checkout (a
parent's, unpacked beside it) over the same trace, spans and status. Prints,
for every entry of the other manifest that the cell reports, its name, the
name the quantity has here, and both values; exits 1 where a quantity both
read differs in any digit. By hand, on the chip, when entries are renamed or
merged; the benchmark's own runs never run it.

    python benchmark/tools/reread.py --workload <cell> --seed <n> \\
        --seconds <s> --other <root of the other checkout> [--rehearse]
"""

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent)]

import harness  # noqa: E402
import run as bench_run  # noqa: E402

SHARED = ("readers", "trace_reduce", "program_spans", "group_scopes",
          "part_scopes", "harness")


def read_as(other_root: Path, workload: str, taken: dict) -> dict:
    """{entry: (value, reader file's name)} of the other checkout's entries
    for the cell, by its own readers and the modules they share."""
    manifest = json.loads((other_root / "BENCHMARK.json").read_text())
    bench = other_root / manifest["paths"][0]
    entries = [m for m in manifest["per_layer"]
               if workload in m.get("workloads", [workload])]
    kept = {name: sys.modules.pop(name, None) for name in SHARED}
    sys.path.insert(0, str(bench))
    try:
        import harness as theirs

        out = {}
        for m in entries:
            path = theirs.reader_file(bench, m["name"])
            value = theirs.load_module(path).read(
                taken["trace"], taken["spans"], taken["counters"],
                taken["cell"])
            out[m["name"]] = (None if value is None else float(value),
                              path.name)
    finally:
        sys.path.remove(str(bench))
        for name in SHARED:
            sys.modules.pop(name, None)
            if kept[name] is not None:
                sys.modules[name] = kept[name]
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--other", type=Path, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    taken = {}
    real = bench_run.layer_values

    def keep(cell, trace, spans, counters):
        taken.update(cell=cell, trace=trace, spans=spans, counters=counters,
                     values=real(cell, trace, spans, counters))
        return taken["values"]

    bench_run.layer_values = keep  # the run's own reading, kept for here
    rc = bench_run.main(
        ["--workload", args.workload, "--seed", str(args.seed), "--seconds",
         str(args.seconds), "--trace", "1"] + ["--rehearse"] * args.rehearse)
    if rc or not taken:
        return rc or 2
    values = taken["values"]
    here = {m["name"]: (values.get(m["name"], (None,))[0],
                        harness.reader_file(BENCH, m["name"]).name)
            for m in taken["cell"]["per_layer"]}
    there = read_as(args.other.resolve(), args.workload, taken)
    rows, differ = [], 0
    for old, (was, file) in there.items():
        now = [(name, value) for name, (value, f) in here.items()
               if f == file and harness.stem(name) == harness.stem(old)]
        name, value = now[0] if now else (None, None)
        same = was == value
        differ += not same and was is not None and name is not None
        rows.append({"cell": args.workload, "was": old, "now": name,
                     "reader": file, "value_was": was, "value_now": value,
                     "same": same})
        print(f"reread {args.workload}: {old} -> {name} ({file}): "
              f"{was!r} / {value!r}{'' if same else '  DIFFERS'}")
    out = BENCH.parent / "chiprun_out" / "reread"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{args.workload}.json").write_text(json.dumps(rows, indent=1))
    print(f"reread {args.workload}: {len(rows)} entries of the other "
          f"manifest, {differ} differ")
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main())
