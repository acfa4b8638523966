#!/usr/bin/env python3
"""Record a small profiler trace of the library entry on the chip and
write it as a text excerpt for the CPU tests.

    python3 bench/tests/record_trace_excerpt.py OUT.textproto [--n N]

Runs ``huge_solve``'s configuration and traffic through ``run.py`` at
``N`` coordinates (default 12,288: three block rows, so that the trace
stays small), traced, with the window's first two solves under the
profiler, then writes the newest trace under the harness's trace
directory, cut to the ``bench.traced`` window: each TPU plane's ``XLA
Ops`` and ``XLA Modules`` lines, and the host's ``bench.*`` and
``engine.*`` spans with their stats. ``ProfileData.from_text_proto``
reads it back. Exits 1 without a TPU, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

import devtrace  # noqa: E402
import enginetrace  # noqa: E402
import harness  # noqa: E402
import run  # noqa: E402
import traffic as gen  # noqa: E402

CELL = "huge_solve"
HOST_PREFIXES = (devtrace.BENCH_PREFIX, enginetrace.ENGINE)
DEVICE_LINES = (devtrace.OPS_LINE, enginetrace.MODULES_LINE)


def _q(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"').replace(
        "\n", "\\n") + '"'


def _value(v) -> str:
    if isinstance(v, float):
        return f"double_value: {v!r}"
    if isinstance(v, (bool, int)):
        return f"int64_value: {int(v)}"
    return f"str_value: {_q(str(v))}"


def _plane(pid: int, name: str, lines) -> list[str]:
    """One XPlane as text: ``lines`` is [(line name, [(start_ns, dur_ns,
    event name, stats)])]."""
    t0 = int(min(ev[0] for _, evs in lines for ev in evs))
    names: dict[str, int] = {}
    stat_names: dict[str, int] = {}
    out = ["planes {", f"  id: {pid}", f"  name: {_q(name)}"]
    for lid, (lname, evs) in enumerate(lines, 1):
        out.append(f"  lines {{ id: {lid} name: {_q(lname)} "
                   f"timestamp_ns: {t0}")
        for s, d, n, stats in evs:
            mid = names.setdefault(n, len(names) + 1)
            st = "".join(
                f" stats {{ metadata_id: "
                f"{stat_names.setdefault(k, len(stat_names) + 1)} "
                f"{_value(v)} }}" for k, v in stats.items())
            out.append(f"    events {{ metadata_id: {mid} offset_ps: "
                       f"{round((s - t0) * 1000)} duration_ps: "
                       f"{round(d * 1000)}{st} }}")
        out.append("  }")
    out += [f"  event_metadata {{ key: {i} value {{ id: {i} name: {_q(n)} "
            f"}} }}" for n, i in names.items()]
    out += [f"  stat_metadata {{ key: {i} value {{ id: {i} name: {_q(n)} "
            f"}} }}" for n, i in stat_names.items()]
    return out + ["}"]


def excerpt(profile, header: str) -> str:
    """The trace cut to its window span, as text (see the docstring)."""
    host = [(e.start_ns, e.duration_ns, e.name, dict(e.stats))
            for p in profile.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events
            if e.name.startswith(HOST_PREFIXES)]
    (lo, dur, _, _), = [h for h in host if h[2] == enginetrace.WINDOW]
    hi = lo + dur
    out = ["# " + line for line in header.splitlines()]
    pid = 0
    for p in profile.planes:
        if not devtrace.DEVICE_PLANE.match(p.name):
            continue
        lines = [(ln.name, [(e.start_ns, e.duration_ns, e.name, {})
                            for e in ln.events
                            if e.start_ns < hi and
                            e.start_ns + e.duration_ns > lo])
                 for ln in p.lines if ln.name in DEVICE_LINES]
        pid += 1
        out += _plane(pid, p.name, [ln for ln in lines if ln[1]])
    out += _plane(pid + 1, "/host:CPU", [("python", host)])
    return "\n".join(out) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("out")
    ap.add_argument("--n", type=int, default=12_288)
    ap.add_argument("--seed", type=int, default=3000001401)
    args = ap.parse_args(argv)
    benchmark = harness.load_json(BENCH.parent / "BENCHMARK.json")
    cell, = [c for c in benchmark["workloads"] if c["name"] == CELL]
    config = harness.load_json(BENCH / "configs" / f"{cell['config']}.json")
    config["job"]["n"] = args.n
    traffic = gen.load(cell["traffic"])
    traffic["trace"]["jobs"] = 2
    try:
        result = run.measure_cell(
            cell, config, traffic,
            harness.metric_specs(benchmark, CELL, True), args.seed, 1.0,
            True)
    except harness.NoChip as e:
        harness.eprint(f"record_trace_excerpt: {e}")
        return 1
    print(json.dumps(result), flush=True)
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(enginetrace.newest(harness.TRACE_DIR))
    header = (f"Profiler trace recorded on a {result['device']['kind']} by "
              f"bench/tests/record_trace_excerpt.py --n {args.n} --seed "
              f"{args.seed}:\n{CELL}'s configuration at n = {args.n}, the "
              "window's first two solves traced; cut to the bench.traced "
              "window.")
    pathlib.Path(args.out).write_text(excerpt(profile, header))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
