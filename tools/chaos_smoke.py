"""Chaos smoke: subprocess crash -> auto-restore -> replay, diffed against a
clean control run (the CI half of ISSUE 9's chaos e2e proof; the in-process
half lives in tests/test_supervision.py).

Orchestration (parent, default mode):

 1. CONTROL   one child feeds seq 1..N cleanly; outputs land in JSONL files.
 2. CHAOS #1  a second child runs the same feed under SIDDHI_TPU_FAULTS
              (injected sink outages spill payloads to the restart-surviving
              FileErrorStore via on.error='STORE') and @app:persist
              auto-checkpoints; the parent SIGKILLs it mid-feed.
 3. CHAOS #2  the child restarts with --resume: restore_last_revision(),
              replay_errors(), then continues the feed from the last
              checkpointed sequence (read back from a checkpointed table).
 4. DIFF      query outputs and sink deliveries across both chaos runs are
              deduped by sequence number and compared against the control:
              every sequence 1..N must be present, every (seq -> total)
              must agree, and the error-store entries stored before the
              kill must have been replayed. Exit 0 = contract holds.

Duplicates are EXPECTED (events between the last checkpoint and the kill
re-run after restore — at-least-once), silent loss is not: dedup-by-seq
must recover exactly the control outputs.

Churn leg (`--churn`): the chaos child ALSO hot-deploys/undeploys queries
while the feed runs (core/churn.py `add_query`/`remove_query` at fixed
sequence points, printing `splicing K` markers), and the parent SIGKILLs
it on a mid-feed splice marker — so the kill lands around a live splice.
The resume child restores from the last auto-checkpoint (whose snapshot
may contain hot-query elements the rebuilt base app does not know —
restore must skip them, never tear) and re-runs the churn schedule for
the remaining sequences. The diff contract is unchanged and PROVES churn
consistency: the surviving base query's outputs are byte-identical to a
churn-free control (dedup by seq), and no STORE'd sink event is lost.

Usage:
    python tools/chaos_smoke.py [--events N] [--dir D] [--json] [--churn]
    python tools/chaos_smoke.py child --dir D --events N [--resume] [--churn]
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

APP = """
@app:name('Chaos')
@app:persist(interval='150 millisec', keep='3')
define stream S (seq long, v long);
define table M (k long, s long);
@sink(type='inMemory', topic='chaos-out', on.error='STORE',
      @map(type='json'))
define stream Out (seq long, total long);
@info(name='q')
from S#window.length(8) select seq, sum(v) as total insert into Out;
@info(name='m')
from S select 0 as k, seq as s update or insert into M on M.k == k;
"""


# churn schedule for the --churn child: seq -> (op, hot query id). Exact
# seq matches only, so a resumed child skips ops its predecessor already
# passed and re-runs the ones still ahead of its start_seq.
CHURN_OPS = {
    60: ("add", "hot1"),
    120: ("remove", "hot1"),
    180: ("add", "hot2"),
    240: ("remove", "hot2"),
}


def _churn_op(rt, op: str, qid: str, hot_f, splice_no: int) -> None:
    """One scheduled churn op with mid-splice markers the parent kills on."""
    print(f"splicing {splice_no} {op} {qid}", flush=True)
    if op == "add":
        rt.add_query(
            f"@info(name='{qid}') from S[seq % 2 == 0] "
            "select seq, v insert into HotOut;"
        )
        rt.add_callback(qid, lambda ts, ins, rem, _q=qid: [
            hot_f.write(json.dumps(
                {"q": _q, "seq": e.data[0], "v": e.data[1]}
            ) + "\n")
            for e in ins or []
        ])
    elif qid in rt.queries:  # a resumed child never deployed this one
        rt.remove_query(qid)
    print(f"spliced {splice_no} {op} {qid}", flush=True)


def _child(args) -> int:
    import logging

    logging.basicConfig(level=logging.ERROR)
    from siddhi_tpu import FileErrorStore, SiddhiManager
    from siddhi_tpu.core.io import InMemoryBroker, _BrokerSubscriber
    from siddhi_tpu.core.persistence import FileSystemPersistenceStore

    d = args.dir
    mgr = SiddhiManager()
    mgr.set_persistence_store(
        FileSystemPersistenceStore(os.path.join(d, "snap"))
    )
    mgr.set_error_store(FileErrorStore(os.path.join(d, "errors")))
    rt = mgr.create_siddhi_app_runtime(APP)

    # line-buffered appends: a SIGKILL loses at most one torn tail line,
    # which the parent's reader tolerates
    out_f = open(os.path.join(d, "out.jsonl"), "a", buffering=1)
    sink_f = open(os.path.join(d, "sink.jsonl"), "a", buffering=1)
    rt.add_callback("q", lambda ts, ins, rem: [
        out_f.write(json.dumps({"seq": e.data[0], "total": e.data[1]}) + "\n")
        for e in ins or []
    ])
    InMemoryBroker.subscribe(_BrokerSubscriber(
        "chaos-out", lambda payload: sink_f.write(str(payload) + "\n")
    ))

    start_seq = 1
    if args.resume:
        rt.restore_last_revision()
        rows = rt.query("from M select k, s")
        if rows:
            start_seq = int(rows[0].data[1]) + 1
    rt.start()
    if args.resume:
        # replay AFTER start — sinks connect at start(); same order as the
        # supervisor's restart sequence
        replayed = mgr.replay_errors(skip_unavailable=True)
        print(f"resumed from seq {start_seq}, replayed {replayed}",
              flush=True)
    hot_f = open(os.path.join(d, "hot.jsonl"), "a", buffering=1)
    splice_no = 0
    h = rt.get_input_handler("S")
    for seq in range(start_seq, args.events + 1):
        if args.churn and seq in CHURN_OPS:
            op, qid = CHURN_OPS[seq]
            splice_no += 1
            _churn_op(rt, op, qid, hot_f, splice_no)
        h.send((seq, seq % 10), timestamp=seq)
        print(f"fed {seq}", flush=True)  # the parent kills on this marker
        time.sleep(0.002)
    # a final explicit checkpoint so a clean exit retains everything
    rt.persist()
    mgr.shutdown()
    print("done", flush=True)
    return 0


def _read_jsonl(path):
    out = []
    if not os.path.exists(path):
        return out
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except ValueError:
                continue  # torn tail line from the SIGKILL
    return out


def _children_platform() -> str:
    """This is a crash/restore parity check, not a device check: children
    run on the CPU unless the caller's JAX_PLATFORMS chooses otherwise. The
    result names the platform so a reader cannot take it for a chip run."""
    return os.environ.get("JAX_PLATFORMS") or "cpu"


def _spawn(d, events, resume=False, env_extra=None, churn=False):
    env = dict(os.environ)
    env.pop("SIDDHI_TPU_FAULTS", None)
    env["JAX_PLATFORMS"] = _children_platform()
    if env_extra:
        env.update(env_extra)
    cmd = [
        sys.executable, os.path.abspath(__file__), "child",
        "--dir", d, "--events", str(events),
    ]
    if resume:
        cmd.append("--resume")
    if churn:
        cmd.append("--churn")
    return subprocess.Popen(
        cmd, env=env, cwd=os.path.dirname(os.path.dirname(__file__)) or ".",
        stdout=subprocess.PIPE, text=True,
    )


def run_chaos(
    events: int = 300, base_dir: str | None = None, churn: bool = False
) -> dict:
    """Run the full control/kill/resume/diff sequence; returns the result
    dict (raises AssertionError on contract violation). With `churn=True`
    the chaos children hot-deploy/undeploy queries while feeding and the
    SIGKILL lands on a mid-feed splice marker — the diff then proves the
    surviving query's outputs ride through live churn AND a crash around
    a splice byte-identically."""
    import tempfile

    base = base_dir or tempfile.mkdtemp(prefix="chaos_smoke_")
    ctl_dir = os.path.join(base, "control")
    chaos_dir = os.path.join(base, "chaos")
    os.makedirs(ctl_dir, exist_ok=True)
    os.makedirs(chaos_dir, exist_ok=True)

    # 1. control: churn-free — the base query's outputs must be identical
    # WHETHER OR NOT the chaos runs churned (the splice parity contract)
    p = _spawn(ctl_dir, events)
    out, _ = p.communicate(timeout=600)
    assert p.returncode == 0, f"control run failed:\n{out}"

    # 2. chaos run 1: injected sink outages + SIGKILL mid-feed (churn mode:
    # on the second splice marker, so the kill lands around a live splice
    # with one hot query's deploy already committed)
    p = _spawn(chaos_dir, events, churn=churn, env_extra={
        "SIDDHI_TPU_FAULTS": "seed=7;sink_publish@Chaos:after=25,times=5",
    })
    kill_at = events // 2
    killed = False
    # watchdog, not an in-loop deadline check: `for line in p.stdout` blocks
    # in readline, so a child that wedges SILENTLY (stops printing) would
    # never reach an in-loop check — the timer kills it, readline returns
    # EOF, and the assertion below reports the hang
    import threading

    hung = threading.Event()
    watchdog = threading.Timer(600, lambda: (hung.set(), p.kill()))
    watchdog.start()
    try:
        for line in p.stdout:
            if churn and line.startswith("splicing 2 "):
                p.send_signal(signal.SIGKILL)
                killed = True
                break
            if not churn and line.startswith("fed ") and int(
                line.split()[1]
            ) >= kill_at:
                p.send_signal(signal.SIGKILL)
                killed = True
                break
    finally:
        watchdog.cancel()
    p.wait(timeout=60)
    assert not hung.is_set(), "chaos run 1 hung before the kill point"
    assert killed, "chaos run 1 exited before the kill point"
    hot_rows_before_kill = 0
    if churn:
        # the first hot deploy committed before the kill: the hot query
        # must have produced rows while deployed (counted NOW — the
        # resume child appends to the same file)
        hot_rows_before_kill = len(
            _read_jsonl(os.path.join(chaos_dir, "hot.jsonl"))
        )
        assert hot_rows_before_kill, (
            "no hot-query output before the mid-splice kill"
        )

    # the kill must have left durable state behind: checkpoints + stored
    # sink payloads (FileErrorStore JSONL survives SIGKILL)
    snaps = os.listdir(os.path.join(chaos_dir, "snap", "Chaos"))
    assert snaps, "no checkpoint survived the kill"
    err_dir = os.path.join(chaos_dir, "errors")
    stored_before = sum(
        len(_read_jsonl(os.path.join(err_dir, f)))
        for f in os.listdir(err_dir)
    ) if os.path.isdir(err_dir) else 0
    assert stored_before > 0, (
        "the injected sink outages stored nothing before the kill"
    )

    # 3. chaos run 2: restore + replay + finish (no faults). In churn mode
    # the restore consumes a checkpoint that may carry hot-query elements
    # the rebuilt base app does not define — landing on a CONSISTENT (old)
    # runtime, never a torn one — and the remaining churn schedule re-runs.
    p = _spawn(chaos_dir, events, resume=True, churn=churn)
    out, _ = p.communicate(timeout=600)
    assert p.returncode == 0, f"resume run failed:\n{out}"
    resumed_line = next(
        (ln for ln in out.splitlines() if ln.startswith("resumed")), ""
    )
    resume_splices = sum(
        1 for ln in out.splitlines() if ln.startswith("spliced ")
    )

    # 4. diff against control, dedup by seq
    def collate(d):
        rows = {}
        for r in _read_jsonl(os.path.join(d, "out.jsonl")):
            prev = rows.setdefault(r["seq"], r["total"])
            assert prev == r["total"], (
                f"divergent replayed output at seq {r['seq']}: "
                f"{prev} != {r['total']}"
            )
        return rows

    control = collate(ctl_dir)
    chaos = collate(chaos_dir)
    assert set(control) == set(range(1, events + 1)), "control feed incomplete"
    missing = set(control) - set(chaos)
    assert not missing, f"chaos run LOST outputs for seqs {sorted(missing)[:10]}"
    diverged = [s for s in control if control[s] != chaos[s]]
    assert not diverged, (
        f"restored state diverged from control at seqs {diverged[:10]}"
    )

    # sink deliveries: every stored payload must have been replayed — the
    # union of both runs' sink lines covers every sequence
    def sink_seqs(d):
        seqs = set()
        for line in open(os.path.join(d, "sink.jsonl")):
            try:
                for ev in json.loads(line.replace("'", '"')):
                    seqs.add(ev["event"]["seq"])
            except (ValueError, KeyError, TypeError):
                continue
        return seqs

    ctl_sink = sink_seqs(ctl_dir)
    chaos_sink = sink_seqs(chaos_dir)
    lost_sink = ctl_sink - chaos_sink
    assert not lost_sink, (
        f"STORE'd sink events lost across the crash: {sorted(lost_sink)[:10]}"
    )

    result = {
        "events": events,
        "children_jax_platforms": _children_platform(),
        "killed_at": "splicing 2" if churn else kill_at,
        "checkpoints_after_kill": len(snaps),
        "stored_entries_before_resume": stored_before,
        "resume": resumed_line,
        "outputs_control": len(control),
        "outputs_chaos_deduped": len(chaos),
        "sink_seqs_recovered": len(chaos_sink),
        "parity": "ok",
    }
    if churn:
        result["churn"] = {
            "hot_rows_before_kill": hot_rows_before_kill,
            "resume_splices": resume_splices,
        }
        assert resume_splices >= 1, (
            "the resumed child re-ran no churn ops — the schedule should "
            "still have splices ahead of the restore point"
        )
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", nargs="?", default="run")
    ap.add_argument("--dir")
    ap.add_argument("--events", type=int, default=300)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--churn", action="store_true")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    if args.mode == "child":
        return _child(args)
    result = run_chaos(events=args.events, base_dir=args.dir, churn=args.churn)
    print(json.dumps(result) if args.json else
          "chaos smoke OK: " + json.dumps(result, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
