"""CI smoke for the black-box incident recorder + deterministic replay
(tier1.yml "Incident replay parity").

Boots an app with `@app:blackbox` armed and `@OnError(action='LOG')` on
the input stream, drives a deterministic feed while collecting the live
emissions, then installs a one-shot `junction_dispatch` FaultPlan rule
and sends one poison event: the guarded dispatch failure fires the
`dispatch_error` trigger and the recorder freezes an incident bundle.
The bundle is replayed in a FRESH SUBPROCESS via tools/incident_replay.py
(no fault plan installed there — the replay regenerates the emissions
from the recorded rings alone), and the replayed per-stream rows must be
BYTE-IDENTICAL to the live run's collected emissions, checksums included.

The poison event is filtered by the query predicate, so the swallowed
dispatch changes no comparable output — live and replay agree exactly.
Runs under whatever SIDDHI_TPU_FUSE / SIDDHI_TPU_SHARD the environment
sets (tier1.yml repeats the step across legs); the replay subprocess
inherits the same env, so the parity holds per-leg AND the checksum is
stable across legs. Exit 0 = pass.

This is a parity check, not a device check, and it needs two processes:
the live side and the replay child are BOTH pinned to JAX_PLATFORMS=cpu
here, explicitly — a chip belongs to one process at a time, so a live
side that took it would leave the replay child failing or hanging.

With SMOKE_OUT_DIR=<dir> the live + replayed emission JSONs (and the
bundle itself) land there for the `incident-replay` workflow artifact.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def main() -> int:
    # before anything imports jax; the replay child inherits it
    os.environ["JAX_PLATFORMS"] = "cpu"
    print("incident smoke: live side and replay child pinned to "
          "JAX_PLATFORMS=cpu (parity check, not a device run)")

    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.observability.blackbox import (
        attach_emission_collector, emissions_checksum,
    )
    from siddhi_tpu.testing import faults

    out_dir = os.environ.get("SMOKE_OUT_DIR")
    leg = os.environ.get("SIDDHI_TPU_FUSE", "d")
    if os.environ.get("SIDDHI_TPU_SHARD"):
        leg += "_shard" + os.environ["SIDDHI_TPU_SHARD"]
    bundle_dir = tempfile.mkdtemp(prefix="incident_smoke_")
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(f"""
    @app:name('incidentsmoke')
    @app:blackbox(window='30 sec', triggers='dispatch_error,crash',
                  keep='4', dir='{bundle_dir}')
    @OnError(action='LOG')
    define stream S (symbol string, price float, volume int);
    @info(name='q')
    from S[price > 10.0]#window.length(8)
    select symbol, sum(volume) as v, avg(price) as ap insert into Out;
    """)
    live = attach_emission_collector(rt)
    rt.start()
    h = rt.get_input_handler("S")
    syms = ("AAA", "BBB", "CCC")
    rows = [
        (syms[i % 3], 5.0 + i * 1.5, i + 1)
        for i in range(48)
    ]
    ts = [1_700_000_000_000 + i * 25 for i in range(48)]
    h.send_many(rows, timestamps=ts)

    # one-shot dispatch fault on the NEXT junction dispatch for S: the
    # poison row is filtered (price <= 10) so the swallowed batch changes
    # no comparable output, and @OnError(action='LOG') makes the failure
    # guarded -> dispatch_error trigger -> frozen bundle
    faults.install(faults.parse_plan("seed=7;junction_dispatch@S:times=1"))
    try:
        h.send(("POISON", 1.0, 999), timestamp=ts[-1] + 25)
    finally:
        faults.uninstall()

    incidents = rt.incidents()
    assert incidents, "dispatch fault must freeze an incident bundle"
    inc = incidents[-1]
    assert inc["trigger"] == "dispatch_error", inc
    assert os.path.isfile(inc["path"]), inc
    live_payload = {
        "emissions": {
            sid: [[t, list(r)] for t, r in rws]
            for sid, rws in sorted(live.items())
        },
        "checksum": emissions_checksum(live),
    }
    mgr.shutdown()

    # replay in a FRESH subprocess (the time machine must not depend on
    # any state of the live process), fault-plan env scrubbed
    replay_out = os.path.join(bundle_dir, "replay.json")
    env = dict(os.environ)
    env.pop("SIDDHI_TPU_FAULTS", None)
    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "incident_replay.py")
    proc = subprocess.run(
        [sys.executable, tool, inc["path"], "--json", replay_out, "--quiet"],
        env=env, timeout=300,
    )
    assert proc.returncode == 0, f"replay subprocess rc={proc.returncode}"
    with open(replay_out, encoding="utf-8") as f:
        replay = json.load(f)

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, f"live_fuse{leg}.json"), "w",
                  encoding="utf-8") as f:
            json.dump(live_payload, f, indent=1)
        with open(os.path.join(out_dir, f"replay_fuse{leg}.json"), "w",
                  encoding="utf-8") as f:
            json.dump(replay, f, indent=1)
        shutil.copy2(inc["path"], out_dir)

    assert replay["trigger"] == "dispatch_error", replay
    assert replay["events_fed"] == 49, replay["events_fed"]
    # THE parity gate: every replayed stream's rows byte-identical to the
    # live run IN EMISSION ORDER (exact equality, no tolerance, no
    # re-sorting), checksums equal
    r_emis = {
        sid: [(int(t), tuple(r)) for t, r in rws]
        for sid, rws in replay["emissions"].items()
    }
    l_emis = {sid: list(rws) for sid, rws in live.items()}
    assert set(r_emis) == set(l_emis), (set(r_emis), set(l_emis))
    for sid in sorted(l_emis):
        assert r_emis[sid] == l_emis[sid], (
            f"stream {sid} diverged:\nlive   {l_emis[sid][:5]}...\n"
            f"replay {r_emis[sid][:5]}..."
        )
    assert replay["checksum"] == live_payload["checksum"], (
        replay["checksum"], live_payload["checksum"],
    )
    print(
        f"incident replay parity OK (leg fuse={leg}): "
        f"{replay['events_fed']} events re-fed, "
        f"{sum(len(v) for v in l_emis.values())} emissions byte-identical, "
        f"checksum {replay['checksum'][:12]}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
