#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip this process owns.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration, ``bench/configs/<config>.json``, and a traffic mix,
``bench/traffic/<traffic>.json``. The configuration's ``entry`` names
the code that drives the system, ``bench/entries/<entry>.py``; each
metric is read by ``bench/metrics/<name>.py``. The run loads, warms up
(set-up), measures for ``--seconds``, checks a sample of the answers
against the plain reference (``reference.py``, ``check.py``) and prints,
as its last line, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``compared``: each number the check held, with
its limit, which also close standard error.

It exits 1, with no result, where JAX finds no TPU or fewer chips than
the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parent / "src"))

import harness  # noqa: E402
import traffic as gen  # noqa: E402


def measure(benchmark: dict, name: str, seed: int, seconds: float,
            trace: bool, *, require_tpu: bool = True,
            t_start: float = T_START) -> dict:
    """One run of the named cell; the result line's object."""
    cells = {c["name"]: c for c in benchmark["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = harness.load_json(HERE / "configs" / f"{cell['config']}.json")
    return measure_cell(cell, config, gen.load(cell["traffic"]),
                        harness.metric_specs(benchmark, name, trace),
                        seed, seconds, trace, require_tpu=require_tpu,
                        t_start=t_start)


def measure_cell(cell: dict, config: dict, traffic: dict, specs: list,
                 seed: int, seconds: float, trace: bool, *,
                 require_tpu: bool = True,
                 t_start: float = T_START) -> dict:
    devices = harness.setup_jax(config, cell["chips"], require_tpu)
    peaks = harness.peaks_for(devices) if require_tpu else None
    r = harness.Run(cell=cell, config=config, traffic=traffic, seed=seed,
                    seconds=seconds, trace=trace, devices=devices,
                    t_start=t_start)
    out = harness.load_module("entries", config["entry"]).run(r)
    peak = harness.memory_peak(devices)
    dtrace = r.device_trace(out["spans"])
    jobs = out["jobs"]
    job_s = sorted(j["t_recv"] - j["t_sent"] for j in jobs if j["delivered"])
    info = {"cell": cell["name"], "seed": seed, "setup_s": r.setup_s,
            "compiles_in_window": r.compiles_in_window,
            "executables_loaded_in_window": r.loads_in_window,
            "compiles_total": r.compiles.count,
            "cache_hits_total": r.compiles.hits,
            "memory_peak_bytes": peak, "jobs_due": len(jobs),
            "jobs_delivered": sum(bool(j["delivered"]) for j in jobs),
            "failures": [j.get("status") for j in jobs
                         if not j["delivered"]][:5],
            "job_s_min_median_max": [job_s[0], job_s[len(job_s) // 2],
                                     job_s[-1]] if job_s else None}
    print(json.dumps({"info": info}), flush=True)
    t_check = time.perf_counter()
    pad_to = max(gen.sizes(traffic.get("n"), config["job"]))
    ok, held, readings = harness.run_check(config, out["sample"],
                                           out["failed"], pad_to)
    print(json.dumps({"check_s": time.perf_counter() - t_check,
                      "checked": readings}), flush=True)
    record = {"cell": cell["name"], "seconds": seconds,
              "setup_s": r.setup_s,
              "window": {"wall0": r.wall0, "wall1": r.wall1,
                         "end": r.wall0 + seconds, "t0": r.t0, "t1": r.t1},
              "jobs": jobs, "counters": out["counters"],
              "spans": out["spans"], "device_trace": dtrace,
              "peaks": peaks}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": ok, "attempted": out["attempted"],
              "failed": out["failed"],
              "metrics": harness.read_metrics(specs, record),
              "device": device}
    if dtrace is not None:
        device.update(busy_s=dtrace["busy_s"], window_s=dtrace["window_s"])
        result["breakdown"] = {"device_ops": dtrace["device_ops"],
                               "idle_gaps": dtrace["idle_gaps"]}
    result["compared"] = held
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    benchmark = harness.load_json(HERE.parent / "BENCHMARK.json")
    try:
        result = measure(benchmark, args.workload, args.seed, args.seconds,
                         bool(args.trace))
    except harness.NoChip as e:
        harness.eprint(f"bench: {e}; the benchmark runs on the chip only")
        return 1
    for name, v in result["compared"].items():
        harness.eprint(f"compared {name} {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
