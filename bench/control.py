#!/usr/bin/env python3
"""The control of a cell's check: the reference, one step lower in
precision, put in the program's place.

    python bench/control.py --workload huge_solve --seeds 11,12,13

For each seed it takes the jobs a run of the cell with that seed would
have checked, solves each with the reference at the configuration's
precision (``precision``) and at the control's (``control``: float32 for
float64, bfloat16 for float32), reads the gaps of ``check.py`` between the two, and prints one JSON line
with the worst of each gap, the limits and whether the check would have
called the control correct. A limit is sound only if it would not: each
run here should print ``"correct": false``. It runs on the chip at the
cell's own sizes (exit 1 without a TPU); ``tests/`` runs
:func:`readings` at small sizes on the CPU. The benchmark's own runs
never run it.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parent / "src"))

import check  # noqa: E402
import harness  # noqa: E402
import traffic as gen  # noqa: E402


def jobs_checked(config: dict, traffic: dict, seed: int) -> list[dict]:
    """The jobs a full-length run with this seed checks
    (``traffic.checked``)."""
    stream = gen.ClosedStream(traffic, config["job"], seed)
    return [stream.job(k) for k in gen.checked(traffic, seed)]


def readings(config: dict, jobs: list[dict], pad_to: int) -> dict:
    """Worst gaps of the control against the reference over ``jobs``,
    and the verdict the configuration's limits give them."""
    per_job = []
    for job in jobs:
        ref = check.reference_for(job, config, pad_to=pad_to)
        low = check.reference_for(job, config, pad_to=pad_to, lower=True)
        per_job.append(check.gaps(job["objective"], low, ref))
    worst = check.worst(per_job)
    ok, held = check.verdict(worst, config["limits"], failed=0)
    return {"correct": ok, "worst": worst, "held": held}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    bm = harness.load_json(HERE.parent / "BENCHMARK.json")
    cell = {c["name"]: c for c in bm["workloads"]}[args.workload]
    config = harness.load_json(HERE / "configs" / f"{cell['config']}.json")
    traffic = gen.load(cell["traffic"])
    try:
        harness.setup_jax(config, cell["chips"])
    except harness.NoChip as e:
        harness.eprint(f"control: {e}; the control runs on the chip only")
        return 1
    pad_to = max(gen.sizes(traffic.get("n"), config["job"]))
    for seed in (int(s) for s in args.seeds.split(",")):
        jobs = jobs_checked(config, traffic, seed)
        out = readings(config, jobs, pad_to)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "jobs": len(jobs), **out}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
