"""Emit the static FusionPlan for every analysis-corpus app and every app of
`siddhi_tpu/testing/apps.py`.

CI (tier1.yml lint job) runs this and uploads the output directory as a
workflow artifact, so every push carries the machine-readable plan the
fusion PR will consume — and a planner crash on ANY app (including the
intentionally-bad corpus) fails the job. Warnings-only and even
error-carrying apps must still plan: the planner is best-effort by
contract, like EXPLAIN.

Usage:
    python tools/plan_apps.py [--out plan-artifacts]

Exit codes: 0 every app planned; 1 a planner crash (the defect report is
printed per app).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="plan-artifacts")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    from siddhi_tpu.analysis import build_fusion_plan

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    jobs: list[tuple[str, str]] = []  # (name, SiddhiQL source)
    for path in sorted(glob.glob(
        os.path.join(repo, "tests", "analysis_corpus", "*.siddhi")
    )):
        name = os.path.basename(path)[:-len(".siddhi")]
        jobs.append((f"corpus_{name}", open(path).read()))

    from siddhi_tpu.testing import apps

    for name, (ql, _stream, _batch) in sorted(apps.WORKLOADS.items()):
        jobs.append((f"app_{name}", ql))
    # the one app here whose plan actually FORMS a group (the BASELINE.json
    # configurations are single-query)
    jobs.append(("app_fusedgroup", apps.FUSED_GROUP_QL))
    # the wire apps: their plans carry the inferred wire lanes and value
    # domains
    for name, (ql, _stream) in sorted(apps.WIRE_WORKLOADS.items()):
        jobs.append((f"app_{name}", ql))

    failures = 0
    index = []
    for name, source in jobs:
        try:
            plan = build_fusion_plan(source).to_dict()
        except Exception as exc:
            print(f"PLAN CRASH on {name}: {exc!r}", file=sys.stderr)
            failures += 1
            continue
        out_path = os.path.join(args.out, f"{name}.plan.json")
        with open(out_path, "w") as f:
            json.dump(plan, f, indent=2)
        index.append({
            "app": name,
            "groups": len(plan["groups"]),
            "blockers": len(plan["blockers"]),
            "shared_state": len(plan["shared_state"]),
            "rewrites": len(plan["rewrites"]),
            "domains": len(plan["domains"]),
        })
        print(
            f"{name}: {len(plan['groups'])} group(s), "
            f"{len(plan['blockers'])} blocker(s), "
            f"{len(plan['shared_state'])} shared-state candidate(s), "
            f"{len(plan['rewrites'])} rewrite(s), "
            f"{len(plan['domains'])} stream(s) with domains"
        )
    with open(os.path.join(args.out, "index.json"), "w") as f:
        json.dump(index, f, indent=2)
    print(f"{len(index)}/{len(jobs)} apps planned -> {args.out}/")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
