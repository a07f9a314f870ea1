"""`run.py --rehearse` end to end on the CPU at batch 512, for every cell
of the manifest; the same run with the timed path broken underneath has to
come out as not correct; and a new traffic mix, a new per-layer metric and a
configuration of the replay form with two input streams are added as files
and entries alone, in a temporary copy."""

import json
import shutil

import pytest

import run as bench_run
from conftest import BENCH
from fixtures import tree

MANIFEST = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def rehearse(capsys, workload, trace=0, seconds=1.5, manifest=None):
    rc = bench_run.main(
        ["--workload", workload, "--seed", str(2**31 + 77), "--seconds",
         str(seconds), "--trace", str(trace), "--rehearse"], manifest=manifest)
    out = capsys.readouterr().out
    assert rc == 0, out
    return json.loads(out.strip().splitlines()[-1]), out


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_of_each_cell(capsys, workload):
    result, out = rehearse(capsys, workload)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device", "rehearsal", "compared"}
    assert list(result)[-1] == "compared"
    assert result["correct"] is True, out
    assert result["attempted"] > 0 and result["failed"] == 0
    # a CPU run reports no number under a metric's name
    assert result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"
    assert "compared delivered.missing = 0 (limit 0)" in out


def test_without_rehearse_a_cpu_is_refused(capsys):
    rc = bench_run.main(["--workload", CELLS[0], "--seed", "1", "--seconds",
                         "1", "--trace", "0"])
    captured = capsys.readouterr()
    assert rc != 0
    assert "{" not in captured.out.strip().splitlines()[-1]


@pytest.mark.parametrize("fault", ["answer_altered", "rows_dropped"])
def test_broken_timed_path_is_not_correct(capsys, monkeypatch, fault):
    """Alter what the engine's decode produces, where it is produced."""
    import siddhi_tpu.core.event as event

    real = event.events_from_arrays

    def broken(schema, ts, cols, n, interner):
        if fault == "rows_dropped":
            # one row fewer per chunk: the engine slices per micro-batch
            # from this list, so the last micro-batch comes up a row short
            return real(schema, ts, cols, n, interner)[:-1]
        cols = dict(cols)
        cols["avgLoad"] = cols["avgLoad"] * 1.001
        return real(schema, ts, cols, n, interner)

    monkeypatch.setattr(event, "events_from_arrays", broken)
    result, out = rehearse(capsys, "q1-plug.bulk")
    assert result["correct"] is False, out
    if fault == "rows_dropped":
        assert result["failed"] == result["attempted"]


def test_cells_and_metrics_are_added_as_data(capsys, tmp_path):
    """A cell is one `workloads` entry over one new JSON file of an existing
    driver; a per-layer metric is one new reader file and one entry. No file
    that exists is edited."""
    bench = tmp_path / "benchmark"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests", "*.pb.gz"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    mix = json.loads((bench / "traffic" / "trickle-4096.json").read_text())
    mix["rehearse"] = {"send_rows": 128}
    (bench / "traffic" / "trickle-8192.json").write_text(json.dumps(mix))
    mix = json.loads((bench / "traffic" / "bulk-2m.json").read_text())
    mix["send_batches"] = 128
    (bench / "traffic" / "bulk-4m.json").write_text(json.dumps(mix))
    (bench / "layer_metrics" / "sends_in_window.py").write_text(
        "def read(trace, spans, counters, cell):\n"
        "    return float(len(spans['sends']))\n")
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["workloads"] += [
        {"name": "q1-plug.trickle8k", "config": "debs14-q1-plug",
         "traffic": "trickle-8192", "chips": 1, "why": "a later PR's mix"},
        {"name": "filter.bulk4m", "config": "siddhi-simple-filter",
         "traffic": "bulk-4m", "chips": 1, "why": "a later PR's mix"}]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] == "send_p50_ms":
            m["workloads"].append("q1-plug.trickle8k")
        if m["name"] in ("events_per_s.filter", "chunk_device_ms.filter",
                         "compile_events.filter"):
            m["workloads"].append("filter.bulk4m")
    manifest["per_layer"].append({
        "name": "sends_in_window", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "per-batch path",
        "moves": "send_p50_ms", "workloads": ["q1-plug.trickle8k"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    result, out = rehearse(capsys, "q1-plug.trickle8k", trace=1,
                           manifest=tmp_path / "BENCHMARK.json")
    assert result["correct"] is True, out
    assert "rehearsal computed (not reported): ['sends_in_window']" in out
    for trace, computed in ((0, "['events_per_s.filter', 'setup_s']"),
                            (1, "['compile_events.filter']")):
        result, out = rehearse(capsys, "filter.bulk4m", trace=trace,
                               manifest=tmp_path / "BENCHMARK.json")
        assert result["correct"] is True, out
        assert f"rehearsal computed (not reported): {computed}" in out
    assert all(p.read_bytes() == b for p, b in before.items())


def test_a_replay_configuration_with_two_streams_is_added_as_data(
        capsys, tmp_path):
    """What the next deployment brings: a configuration whose reference
    replays the sends (`t-join`: two input streams, emissions per pair), its
    traffic mix, a cell and a per-layer reader that reads what the replay
    found out. `tree` adds them to a copy and checks that no file that was
    there changed."""
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["per_layer"].append({
        "name": "emissions_per_row", "unit": "ratio", "better": "lower",
        "source": "program_counter", "layer": "operators / kernels",
        "moves": "events_per_s", "workloads": ["t-join.sends"]})
    path = tree(tmp_path, manifest)
    before = {p: p.read_bytes()
              for p in (tmp_path / "benchmark").rglob("*") if p.is_file()}
    (tmp_path / "benchmark" / "layer_metrics" / "emissions_per_row.py"
     ).write_text(
        "def read(trace, spans, counters, cell):\n"
        "    assert cell['config']['streams'] == ['A', 'B']\n"
        "    assert cell['config']['stream'] == 'A'\n"
        "    return spans['stream'].emit_share\n")
    result, out = rehearse(capsys, "t-join.sends", trace=1, seconds=1,
                           manifest=path)
    assert result["correct"] is True and result["failed"] == 0, out
    assert "rehearsal computed (not reported): ['emissions_per_row']" in out
    result, out = rehearse(capsys, "t-join.sends", trace=0, seconds=1,
                           manifest=path)
    assert result["correct"] is True, out
    assert "rehearsal computed (not reported): ['events_per_s', 'setup_s']" in out
    assert all(p.read_bytes() == b for p, b in before.items())
