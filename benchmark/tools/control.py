"""The readings that each limit of `correct` is set from: over several seeds
in one process, the numbers compared when the program is sound, and the same
numbers with the control (the configuration's reference in the next lower
precision, or with one guarantee broken) put in the program's place. Each
seed is a short window at the cell's own load and sizes. By hand, on the
chip; the benchmark's own runs never run it.

    python benchmark/tools/control.py --workload <cell> --seeds 12 --seconds 4
"""

import argparse
import sys
import time
from pathlib import Path


BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent)]

import harness  # noqa: E402


def readings(cell: dict, driver, seed: int, seconds: float) -> tuple:
    """({number: sound value}, {number: control's value}) for one seed."""
    traffic = cell["traffic"]
    dep = harness.Deployment(cell, seed, harness.Recorder(
        traffic["sample_callbacks"]), with_statistics=False)
    try:
        dep.fill()
        driver.run(dep, traffic, traffic["warmup_seconds"])
        dep.recorder.arm([
            (time.perf_counter() + m, skip) for m, skip in
            harness.sample_moments(seed, seconds, traffic["samples"],
                                   traffic.get("sample_skip_upto", 0))])
        driver.run(dep, traffic, seconds)
        sound, broken = harness.compare_samples(dep, dep.recorder.samples,
                                                control=True)
        missing = abs(int(dep.stream.kept_before(dep.cursor))
                      - dep.recorder.delivered)
        sound["delivered.missing"] = (missing, 0)
    finally:
        dep.close()
    return sound, broken


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_200_000_000)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    opened = harness.open_cell(BENCH.parent / "BENCHMARK.json", args.workload,
                               args.rehearse)
    if opened is None:
        return 3
    cell, driver, _, _ = opened
    sound_max, control_min = {}, {}
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        sound, broken = readings(cell, driver, seed, args.seconds)
        print(f"seed {seed}: sound "
              f"{ {k: v for k, (v, _) in sound.items()} } control "
              f"{ {k: v for k, (v, _) in broken.items()} }", flush=True)
        for k, (v, _) in sound.items():
            sound_max[k] = max(sound_max.get(k, 0), v)
        for k, (v, _) in broken.items():
            control_min[k] = min(control_min.get(k, float("inf")), v)
    rules = cell["config"]["compare"]
    for k in sound_max:
        limit = rules.get(k.removesuffix(".gap"), {}).get("limit")
        print(f"{cell['name']} {k}: largest sound {sound_max[k]!r}, smallest "
              f"control {control_min.get(k)!r}, limit {limit!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
