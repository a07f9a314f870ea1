"""The replay form's proof: four tiny configurations under `data/configs/`
(a pattern, a two-stream join, a tumbling window, an `@app:watermark`
reordering), each with a row-by-row Python reference, and their one traffic
mix under `data/traffic/`. They are fixtures, not cells: `tree` lays a copy
of the benchmark out in a temporary directory, adds them there as files and
entries alone, the way a later PR adds a deployment, and writes the manifest
that `run.py --rehearse` is given."""

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
FIXTURES = sorted(p.name for p in (DATA / "configs").iterdir())
MIX = "t-sends"


def tree(tmp_path: Path, manifest: dict) -> Path:
    """A copy of the benchmark with the fixtures added; returns the path of
    its manifest: `manifest` plus one configuration and one cell
    (`<fixture>.sends`) for each fixture."""
    bench = tmp_path / "benchmark"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests", "*.pb.gz"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    shutil.copy(DATA / "traffic" / f"{MIX}.json", bench / "traffic")
    manifest = json.loads(json.dumps(manifest))
    for name in FIXTURES:
        shutil.copytree(DATA / "configs" / name, bench / "configs" / name)
        cfg = json.loads((bench / "configs" / name / "config.json").read_text())
        manifest["configs"].append({
            "name": name, "source": cfg["source"],
            "file": f"benchmark/configs/{name}/config.json", "reduced": [],
            "why": "fixture"})
        manifest["workloads"].append({
            "name": f"{name}.sends", "config": name, "traffic": MIX,
            "chips": 1, "why": "fixture"})
    for m in manifest["end_to_end"]:
        if m["name"] == "events_per_s":
            m["workloads"] += [f"{name}.sends" for name in FIXTURES]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    assert all(p.read_bytes() == b for p, b in before.items())
    return tmp_path / "BENCHMARK.json"
