"""Engine throughput: batched multi-tenant engine vs a sequential
``abo_minimize`` loop at K ∈ {1, 8, 32}, plus the heterogeneous-n paged
scenario at paper sampling rates.

    PYTHONPATH=src python -m benchmarks.engine_bench

Emits the same ``name,us_per_call,derived`` CSV rows as benchmarks/run.py
(also mounted there as ``--only engine`` / ``--only engine_mixed`` /
``--only engine_sharded``), and writes/extends ``BENCH_engine.json`` — a
machine-readable perf trajectory (jobs/s, speedup over the in-bench
sequential lap, compiled-executable count, padded-compute waste from
``pad_stats``, the elastic-pool / checkpoint-journal economics of
``engine_elastic``: peak vs settled device bytes, journal records/
segments after compaction, and ``engine_sharded``'s multi-device
scaling) so regressions show up as data, not vibes. Speedups are always
against a lap measured in the same process (or an interleaved sibling
process) on the same inputs: container wall-clock drifts up to 2x, so
absolute seconds are noise but the ratio is signal — which is also why
every scenario runs >= REPEATS in-bench repeats and reports the MEDIAN
(a min rewards lucky drift; a single lap is a coin flip).

The sharded scenario needs forced host devices, which must be set before
jax initializes — so it spawns one child process per device count with

    XLA_FLAGS=--xla_force_host_platform_device_count=D

interleaving D=1/2/4 children across rounds so machine-speed drift hits
every device count equally, and medians across rounds x in-child repeats
decide the scaling ratios. Each child also digests its per-job fun/x
bytes; the parent asserts the digests are identical across device counts
(and the child checks job 0 against standalone ``abo_minimize``), so the
reported speedup can never come from computing something different.

"us_per_call" is per *job*; "derived" reports jobs/sec, probe-FE/sec, and
the batched/sequential speedup. Both paths are warmed first so the
comparison is steady-state compute + dispatch, not compile time.

The mixed-n scenario is the realistic-traffic case the paged pool exists
for: 32 jobs over 8 distinct n in [670, 3050] at the paper's sampling
rate (m=50 per pass, 250 probes/coordinate) — the regime where the old
rung-padded layout's padded compute nearly cancelled its batching win
(~1.1x). The paged layout sweeps only occupied block rows, so every lane
pays for its true ``ceil(n/block)`` blocks while all 8 lanes share one
executable family; padded compute shrinks to the row-width ladder's
residue (a few percent, reported as ``swept_waste``).

Workload for the K sweep: paper-default sampling (m=250 probes/coordinate)
at n=100 — the exact Gauss-Seidel regime where each job is a
coordinate-scan over (1, 50) tiles and a sequential abo_minimize loop is
dominated by per-call dispatch and host-sync latency. That is precisely
the workload class (many small/medium solves) the engine exists for. The
headline sweep uses the sphere objective; the K=32 per-objective rows show
the spread — transcendental-heavy objectives (griewank) are compute-bound
on CPU and gain less from batching than dispatch-bound ones (sphere,
rastrigin).
"""
from __future__ import annotations

import hashlib
import json
import os
import pathlib
import statistics
import subprocess
import sys
import time

from repro.core import ABOConfig, abo_minimize
from repro.engine.jobs import JobSpec
from repro.engine.scheduler import SolveEngine
from repro.objectives import OBJECTIVES

N = 100
CFG = ABOConfig()
OBJ = "sphere"
KS = (1, 8, 32)
MAX_LANES = 32
REPEATS = 3

ARTIFACT = "BENCH_engine.json"

# scenario -> metrics dict, filled as scenarios run (see write_artifact)
_METRICS: dict[str, dict] = {}


def _cpu_rehearsal_only(scenario: str):
    """The scenarios that start child processes are CPU rehearsals: their
    children run JAX on forced host devices (or, for the router, one
    worker per device). On a TPU host they cannot work — a chip belongs
    to one process, and a parent that has touched JAX holds it — so they
    stop here, before any child starts. Checked from sysfs, without
    loading the TPU library."""
    from repro.serve.router import host_tpu_chips
    if host_tpu_chips():
        raise SystemExit(
            f"{scenario} is a CPU rehearsal: its child processes cannot "
            "share this host's TPU with this process. Run it with "
            "JAX_PLATFORMS=cpu on a host without a TPU; chip_smoke.py "
            "checks the sharded and spanning paths on the chip")


def _median(values):
    return statistics.median(values)


def _sequential(specs) -> float:
    t0 = time.perf_counter()
    for s in specs:
        abo_minimize(OBJECTIVES[s.objective], s.n, config=s.config,
                     seed=s.seed)
    return time.perf_counter() - t0


# set by --sanitize: every engine the bench builds runs under the
# repro.analysis runtime sanitizers (host-sync guard + donation checks)
SANITIZE = False


def _engine(specs, lanes) -> tuple[float, SolveEngine]:
    eng = SolveEngine(lanes=lanes, sanitize=SANITIZE)
    eng.submit_many(specs)
    t0 = time.perf_counter()
    eng.run()
    return time.perf_counter() - t0, eng


def _k_specs(obj, k, seed0):
    return [JobSpec(obj, N, CFG, seed=seed0 + i) for i in range(k)]


def _pair(obj: str, k: int):
    """(sequential, batched) wall time for k jobs, MEDIAN of REPEATS —
    wall-clock in this container drifts up to 2x, and a min rewards
    whichever lap got lucky."""
    dt_seq = _median(_sequential(_k_specs(obj, k, 1000 + r))
                     for r in range(REPEATS))
    dt_eng = _median(_engine(_k_specs(obj, k, 1000 + r),
                             min(k, MAX_LANES))[0] for r in range(REPEATS))
    return dt_seq, dt_eng


# K=1 floor: a single job pays the engine's dispatch/bookkeeping overhead
# with nothing to amortize it over, so engine/sequential at K=1 sits BELOW
# 1.0 by design (measured ~0.61x on the reference container). The floor is
# the regression tripwire — container drift spans ~2x on absolute seconds
# but the in-process ratio is stable, so a reading under 0.45x means the
# single-job dispatch path actually got slower, not that the machine did.
# See benchmarks/README.md "The K=1 overhead floor".
SPEEDUP_K1_FLOOR = 0.45


def _rows(tag: str, k: int, dt_seq: float, dt_eng: float):
    fe = CFG.n_passes * CFG.samples_per_pass * N
    _METRICS[f"{tag}_k{k}"] = {
        "jobs": k, "jobs_per_s": k / dt_eng,
        "jobs_per_s_sequential": k / dt_seq,
        "speedup": dt_seq / dt_eng,
    }
    if k == 1:
        # the trajectory records the floor next to the reading so a
        # regression is flagged by the data itself, not by archaeology
        _METRICS[f"{tag}_k1"].update({
            "speedup_k1": dt_seq / dt_eng,
            "speedup_k1_floor": SPEEDUP_K1_FLOOR,
            "above_floor": dt_seq / dt_eng >= SPEEDUP_K1_FLOOR,
        })
    yield (f"{tag}_seq_k{k}", dt_seq / k * 1e6,
           f"jobs_per_s={k / dt_seq:.1f} fe_per_s={k * fe / dt_seq:.3g}")
    yield (f"{tag}_batched_k{k}", dt_eng / k * 1e6,
           f"jobs_per_s={k / dt_eng:.1f} fe_per_s={k * fe / dt_eng:.3g} "
           f"speedup={dt_seq / dt_eng:.2f}x")


def engine_vs_sequential(ks=KS):
    _sequential(_k_specs(OBJ, 1, 0))     # warm abo_minimize's jit cache
    for k in ks:                         # warm the engine's compile caches
        _engine(_k_specs(OBJ, k, 0), min(k, MAX_LANES))
    for k in ks:
        dt_seq, dt_eng = _pair(OBJ, k)
        yield from _rows(f"engine_{OBJ}", k, dt_seq, dt_eng)
    # per-objective spread at the deepest queue
    for obj in ("rastrigin", "griewank"):
        _sequential(_k_specs(obj, 1, 0))
        _engine(_k_specs(obj, max(ks), 0), min(max(ks), MAX_LANES))
        dt_seq, dt_eng = _pair(obj, max(ks))
        yield from _rows(f"engine_{obj}", max(ks), dt_seq, dt_eng)


# ---- heterogeneous-n: paged pool vs sequential at paper sampling ----------
# 8 distinct n with 8 distinct page counts (11..48 blocks at block=64), all
# riding ONE executable family. Paper sampling (m=50/pass, 5 passes) makes
# this compute-bound — the regime where padded compute is fatal: the old
# rung-padded layout measured only ~1.1x here because every lane swept its
# canonical rung. The paged sweep's compute is Σ ceil(n_i/block), so the
# batching win survives.
MIXED_NS = (670, 730, 1100, 1340, 1400, 1500, 2600, 3050)
MIXED_JOBS = 32
MIXED_LANES = 8
MIXED_OBJ = "sphere"
MIXED_CFG = ABOConfig(samples_per_pass=50, block_size=64)


def _mixed_specs(seed0):
    return [JobSpec(MIXED_OBJ, MIXED_NS[i % len(MIXED_NS)], MIXED_CFG,
                    seed=seed0 + i) for i in range(MIXED_JOBS)]


def engine_mixed_n():
    from repro.engine import batched
    _sequential(_mixed_specs(0))         # warm both paths' compile caches
    _engine(_mixed_specs(0), MIXED_LANES)
    dt_seq = _median(_sequential(_mixed_specs(1000 + r))
                     for r in range(REPEATS))
    runs = sorted((_engine(_mixed_specs(1000 + r), MIXED_LANES)
                   for r in range(REPEATS)), key=lambda t: t[0])
    dt_eng, eng = runs[len(runs) // 2]   # the median lap (and its engine)
    waste = eng.pad_stats()["swept_waste"]
    fe = sum(MIXED_CFG.n_passes * MIXED_CFG.samples_per_pass * s.n
             for s in _mixed_specs(0))
    speedup = dt_seq / dt_eng
    _METRICS["engine_mixedn"] = {
        "jobs": MIXED_JOBS, "ns": list(MIXED_NS),
        "samples_per_pass": MIXED_CFG.samples_per_pass,
        "jobs_per_s": MIXED_JOBS / dt_eng,
        "jobs_per_s_sequential": MIXED_JOBS / dt_seq,
        "speedup": speedup,
        "swept_waste": waste,
        "families": len(eng.family_keys_seen),
        # executables THIS engine's families own, not the whole process
        "executables": batched.compiled_executable_count(
            eng.family_keys_seen),
    }
    yield (f"engine_mixedn_seq_k{MIXED_JOBS}", dt_seq / MIXED_JOBS * 1e6,
           f"jobs_per_s={MIXED_JOBS / dt_seq:.1f} fe_per_s={fe / dt_seq:.3g}")
    yield (f"engine_mixedn_paged_k{MIXED_JOBS}", dt_eng / MIXED_JOBS * 1e6,
           f"jobs_per_s={MIXED_JOBS / dt_eng:.1f} "
           f"fe_per_s={fe / dt_eng:.3g} speedup={speedup:.2f}x "
           f"swept_waste={waste:.1%} "
           f"families={len(eng.family_keys_seen)}")


# ---- elastic pools + journal under churn ----------------------------------
# The zero-RAM claim applied to the engine itself: run the mixed-n burst
# through a journaled, retention-bounded engine and measure (a) device
# footprint at the traffic peak vs after the drain (elastic pools release
# free tails past the high-water hysteresis) and (b) the checkpoint
# journal's residue after compaction (client-input records, not
# whole-state snapshots, carry the steps between bases).
def engine_elastic():
    import shutil
    import tempfile

    def one_run(seed0):
        tmp = tempfile.mkdtemp(prefix="bench_engine_elastic_")
        try:
            # journal_every=2: the 32-job burst drains in ~4 fused
            # generations, so this exercises base cuts + segment
            # compaction, not just appends
            eng = SolveEngine(lanes=MIXED_LANES, checkpoint_dir=tmp,
                              journal_every=2, retain_done=8)
            ids = eng.submit_many(_mixed_specs(seed0))
            t0 = time.perf_counter()
            peak = 0
            while eng.pending():
                eng.step()
                peak = max(peak, eng.memory_stats()["pool_device_bytes"])
            dt = time.perf_counter() - t0
            for jid in ids:
                eng.result(jid)          # deliver -> retention GC kicks in
            settled = eng.memory_stats()["pool_device_bytes"]
            jst = eng.ckpt.journal_stats()
            bases = len([p for p in pathlib.Path(tmp).glob("step_*")
                         if not p.name.endswith(".tmp")])
            return {
                "jobs": MIXED_JOBS, "dt_s": dt,
                "peak_pool_bytes": peak,
                "settled_pool_bytes": settled,
                "shrink_ratio": settled / peak if peak else None,
                "journal_records": jst["records"],
                "journal_segments": jst["segments"],
                "journal_bytes": jst["bytes"],
                "journal_last_seq": jst["last_seq"],
                "base_snapshots": bases,
                "retained_jobs": len(eng.jobs),
            }
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    runs = sorted((one_run(r) for r in range(REPEATS)),
                  key=lambda m: m["dt_s"])
    m = runs[len(runs) // 2]             # median lap's metrics
    _METRICS["engine_elastic"] = m
    yield (f"engine_elastic_k{MIXED_JOBS}", m["dt_s"] / MIXED_JOBS * 1e6,
           f"peak_pool_bytes={m['peak_pool_bytes']} "
           f"settled_pool_bytes={m['settled_pool_bytes']} "
           f"journal_records={m['journal_records']} "
           f"journal_segments={m['journal_segments']} "
           f"bases={m['base_snapshots']}")


# ---- sharded page pools: D=1 vs D=2/4 forced host devices -----------------
# Same workload at every device count; lanes place whole onto devices, so
# per-job results are bit-identical (digest-asserted) and the jobs/s ratio
# is pure scheduling/parallelism. The workload is the regime sharding
# helps on CPU: many concurrent lanes of moderate n at a small block size,
# where the per-row tile at D=1 is wide (K lanes gathered) and the row
# loop's fixed overheads dominate — splitting lanes across devices narrows
# every device's tiles and overlaps their loop overheads. Forced host
# devices must exist before jax initializes, hence one child process per
# device count (see module docstring).
SHARD_N = 4000
SHARD_CFG_KW = dict(samples_per_pass=50, n_passes=5, block_size=8)
SHARD_JOBS = 64
SHARD_LANES = 32
SHARD_DEVICES = (1, 2, 4)
SHARD_ROUNDS = 3


def _sharded_specs(seed0):
    cfg = ABOConfig(**SHARD_CFG_KW)
    return [JobSpec(OBJ, SHARD_N, cfg, seed=seed0 + i)
            for i in range(SHARD_JOBS)]


def sharded_child(n_dev: int):
    """Run inside a child process with n_dev forced host devices: warm
    lap, then REPEATS timed laps; print one JSON line with per-lap
    jobs/s, the per-job fun/x digest, and a job-0 abo_minimize cross-
    check. (The digest covers exact solution BYTES — equal digests across
    device counts mean equal bits.)"""
    import numpy as np

    def run_once(seed0):
        eng = SolveEngine(lanes=SHARD_LANES, devices=n_dev)
        ids = eng.submit_many(_sharded_specs(seed0))
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        return dt, [eng.result(j) for j in ids], eng

    _, results, eng = run_once(1000)     # warm lap (compiles)
    h = hashlib.sha256()
    for r in results:
        h.update(np.float64(r.fun).tobytes())
        h.update(np.asarray(r.x).tobytes())
    s0 = _sharded_specs(1000)[0]
    ref = abo_minimize(OBJECTIVES[s0.objective], s0.n, config=s0.config,
                       seed=s0.seed)
    bit_ok = (results[0].fun == ref.fun
              and np.asarray(results[0].x).tobytes()
              == np.asarray(ref.x).tobytes())
    laps = [run_once(1000)[0] for _ in range(REPEATS)]
    print(json.dumps({
        "devices": n_dev,
        "jobs_per_s": [SHARD_JOBS / dt for dt in laps],
        "digest": h.hexdigest(),
        "bit_identical_to_solo": bool(bit_ok),
        "memory": eng.memory_stats(),
    }), flush=True)


def engine_sharded():
    _cpu_rehearsal_only("engine_sharded")
    repo = pathlib.Path(__file__).resolve().parent.parent
    rates: dict[int, list[float]] = {d: [] for d in SHARD_DEVICES}
    digests: dict[int, set] = {d: set() for d in SHARD_DEVICES}
    bit_ok = True
    mem_by_dev = {}
    for _ in range(SHARD_ROUNDS):        # interleave Ds against drift
        for d in SHARD_DEVICES:
            env = dict(os.environ)
            env["XLA_FLAGS"] = \
                f"--xla_force_host_platform_device_count={d}"
            env["PYTHONPATH"] = f"{repo / 'src'}:{repo}"
            out = subprocess.run(
                [sys.executable, "-m", "benchmarks.engine_bench",
                 "--sharded-child", str(d)],
                capture_output=True, text=True, env=env, cwd=repo,
                timeout=1800)
            if out.returncode != 0:
                raise RuntimeError(
                    f"sharded child D={d} failed:\n{out.stderr[-3000:]}")
            rec = json.loads(out.stdout.strip().splitlines()[-1])
            rates[d].extend(rec["jobs_per_s"])
            digests[d].add(rec["digest"])
            bit_ok = bit_ok and rec["bit_identical_to_solo"]
            mem_by_dev[d] = rec["memory"]
    same_bits = (len(set().union(*digests.values())) == 1) and bit_ok
    if not same_bits:
        # the documented contract: a reported speedup can never come from
        # computing something different — divergent bits are a FAILURE of
        # the scenario, not a data point
        raise AssertionError(
            f"engine_sharded bit-identity broken: digests={digests}, "
            f"abo_minimize cross-check ok={bit_ok}")
    med = {d: _median(rates[d]) for d in SHARD_DEVICES}
    base = med[SHARD_DEVICES[0]]
    _METRICS["engine_sharded"] = {
        "jobs": SHARD_JOBS, "n": SHARD_N, "lanes": SHARD_LANES,
        **{f"jobs_per_s_d{d}": med[d] for d in SHARD_DEVICES},
        **{f"speedup_d{d}": med[d] / base for d in SHARD_DEVICES[1:]},
        "bit_identical": bool(same_bits),
        "rounds": SHARD_ROUNDS, "repeats_per_round": REPEATS,
        "memory_stats": mem_by_dev,
    }
    for d in SHARD_DEVICES:
        yield (f"engine_sharded_d{d}_k{SHARD_JOBS}",
               1e6 / med[d],
               f"jobs_per_s={med[d]:.1f} speedup={med[d] / base:.2f}x "
               f"bit_identical={same_bits}")


# ---- roofline: achieved vs peak DRAM bandwidth ----------------------------
# The paper's model is a pure bandwidth roofline: pass throughput ~ DRAM
# bandwidth / working-set bytes. This scenario measures how close the
# fused engine sweep gets. Workload: few jobs x large n at a low sampling
# rate, so the pass streams a multi-MB working set and probe arithmetic
# can't hide the memory traffic. Three numbers land in BENCH_engine.json:
#   bytes/coordinate/pass   from engine_est_bytes_moved_total (the
#                           analytic obs.roofline model, accumulated at
#                           dispatch time) over jobs*n*n_passes
#   achieved bandwidth      est bytes / median drain wall time
#   peak bandwidth          measured_peak_bandwidth() — best-of-N donated
#                           x+1 stream on THIS backend, not a datasheet
# plus an HLO cost_analysis cross-check of one dispatched pass against
# the analytic plan.pass_bytes (order-of-magnitude only: XLA costs scan
# bodies once and counts cache-resident traffic — see obs.roofline).
ROOF_N = 400_000
ROOF_JOBS = 4
ROOF_LANES = 4
ROOF_CFG = ABOConfig(samples_per_pass=5, n_passes=4, block_size=4096)


def _roof_specs(seed0):
    return [JobSpec(OBJ, ROOF_N, ROOF_CFG, seed=seed0 + i)
            for i in range(ROOF_JOBS)]


def engine_roofline():
    from repro.engine import batched
    from repro.obs.roofline import (hlo_bytes_accessed,
                                    measured_peak_bandwidth)

    peak = measured_peak_bandwidth()

    # probe engine at max_fuse=1: one step dispatches exactly one pass,
    # leaving a live plan to read pass_bytes from and to cross-check
    # against XLA's cost model on the same (state, r=1, *args) signature
    probe = SolveEngine(lanes=ROOF_LANES, max_fuse=1)
    probe.submit_many(_roof_specs(0))
    probe.step()
    pool = next(p for p in probe.pools.values() if p.plan is not None)
    plan = pool.plan
    ops = batched.get_pool_ops(pool.obj, pool.key, pool.slots,
                               pool.capacity, pool.mesh)
    hlo = hlo_bytes_accessed(ops.fused_step(*plan.signature()),
                             pool.state, probe._r_const(1), *plan.args)
    plan_bytes = plan.pass_bytes

    # timed drains: the median lap's engine carries the est-bytes counter
    probe.run()                          # also warms the compile caches
    runs = sorted((_engine(_roof_specs(1000 + r), ROOF_LANES)
                   for r in range(REPEATS)), key=lambda t: t[0])
    dt, eng = runs[len(runs) // 2]
    est = eng.stats()["engine_est_bytes_moved_total"]
    coord_passes = ROOF_JOBS * ROOF_N * ROOF_CFG.n_passes
    bpcp = est / coord_passes            # incl. padding + sync residue;
    #                                      the un-padded floor is
    #                                      3*itemsize (r/w sweep + sync)
    achieved = est / dt
    _METRICS["engine_roofline"] = {
        "jobs": ROOF_JOBS, "n": ROOF_N,
        "n_passes": ROOF_CFG.n_passes,
        "samples_per_pass": ROOF_CFG.samples_per_pass,
        "block_size": ROOF_CFG.block_size,
        "plan_pass_bytes": plan_bytes,
        "hlo_pass_bytes": hlo,
        "hlo_vs_plan": (hlo / plan_bytes) if hlo and plan_bytes else None,
        "est_bytes_total": est,
        "bytes_per_coordinate_per_pass": bpcp,
        "dt_s": dt,
        "achieved_gb_s": achieved / 1e9,
        "peak_gb_s": peak / 1e9,
        "achieved_vs_peak": achieved / peak,
    }
    yield (f"engine_roofline_k{ROOF_JOBS}", dt / ROOF_JOBS * 1e6,
           f"bytes_per_coord_pass={bpcp:.1f} "
           f"achieved_gb_s={achieved / 1e9:.2f} "
           f"peak_gb_s={peak / 1e9:.2f} "
           f"roofline_frac={achieved / peak:.3f} "
           f"hlo_vs_plan={(hlo / plan_bytes) if hlo and plan_bytes else float('nan'):.2f}")


# ---- sanitized laps: the guardrails as a bench scenario -------------------
# `--sanitize` runs the K-sweep and mixed-n workloads with every engine
# under the repro.analysis runtime sanitizers (host-sync guard on step(),
# donation checks on every fused dispatch) and each steady-state timed lap
# additionally under compile_guard(0) — zero executables may be built once
# the caches are warm, proving one-executable-per-plan-signature over the
# full drain/regrow cycle. Per-job fun/x are asserted bit-identical to
# standalone abo_minimize, and the plain-vs-sanitized lap ratio is the
# measured sanitizer overhead reported in benchmarks/README.md.
def engine_sanitized():
    import numpy as np

    from repro.analysis import compile_guard

    global SANITIZE

    def check_bits(eng, spec0):
        rec = eng.jobs[min(eng.jobs)]     # job-000000: first submitted
        ref = abo_minimize(OBJECTIVES[spec0.objective], spec0.n,
                           config=spec0.config, seed=spec0.seed)
        ok = (rec.fun == float(ref.fun)
              and np.asarray(rec.x).tobytes()
              == np.asarray(ref.x).tobytes())
        if not ok:
            raise AssertionError(
                f"--sanitize bit-identity broken for {spec0}: "
                f"engine fun={rec.fun!r} vs abo_minimize {ref.fun!r}")
        return ok

    scenarios = (
        ("k", lambda s0: _k_specs(OBJ, max(KS), s0), min(max(KS), MAX_LANES)),
        ("mixedn", _mixed_specs, MIXED_LANES),
    )
    global SANITIZE
    for tag, mk, lanes in scenarios:
        jobs = len(mk(0))
        SANITIZE = False
        _engine(mk(0), lanes)            # warm compile caches (plain)
        dt_plain = _median(_engine(mk(1000 + r), lanes)[0]
                           for r in range(REPEATS))
        SANITIZE = True
        _engine(mk(0), lanes)            # warm the sanitized path too: the
        #                                  guard itself never compiles, but
        #                                  the warm lap covers every resize
        #                                  rung a fresh engine regrows over
        laps = []
        eng = None
        for r in range(REPEATS):
            with compile_guard(0, f"sanitized {tag} steady lap"):
                dt, eng = _engine(mk(1000 + r), lanes)
            laps.append(dt)
        dt_san = _median(laps)
        SANITIZE = False
        check_bits(eng, mk(1000 + REPEATS - 1)[0])
        overhead = dt_san / dt_plain - 1.0
        _METRICS[f"engine_sanitized_{tag}"] = {
            "jobs": jobs,
            "jobs_per_s_plain": jobs / dt_plain,
            "jobs_per_s_sanitized": jobs / dt_san,
            "overhead_frac": overhead,
            "steady_lap_compiles": 0,    # compile_guard(0) just proved it
            "bit_identical": True,       # check_bits just proved it
        }
        yield (f"engine_sanitized_{tag}{jobs}", dt_san / jobs * 1e6,
               f"jobs_per_s={jobs / dt_san:.1f} "
               f"overhead={overhead:+.1%} steady_compiles=0 "
               "bit_identical=True")


# ---- faulted traffic: quarantine cost under injected poison ---------------
# The mixed-n burst with ~10% of jobs deterministically poisoned at
# objective_eval (NaN x0 lanes -> non-finite results quarantined to FAILED
# at harvest). Measures what a realistic failure rate costs the healthy
# jobs: FAILED lanes are evicted and their pages recycled at the same
# harvest boundary as DONE ones, so throughput degradation should be
# roughly the lost jobs' share of compute, not a stall.
FAULT_SPEC = "objective_eval:every=10:seed=7"
FAULT_EXPECTED = MIXED_JOBS // 10        # every=10 on 1-based job ordinals


def engine_faulted():
    import numpy as np

    from repro.engine.jobs import FAILED

    def faulted(specs):
        eng = SolveEngine(lanes=MIXED_LANES, sanitize=SANITIZE,
                          faults=FAULT_SPEC)
        eng.submit_many(specs)
        t0 = time.perf_counter()
        eng.run()
        return time.perf_counter() - t0, eng

    _engine(_mixed_specs(0), MIXED_LANES)    # warm clean path
    faulted(_mixed_specs(0))                 # warm place_x poison path too
    dt_clean = _median(_engine(_mixed_specs(1000 + r), MIXED_LANES)[0]
                       for r in range(REPEATS))
    runs = sorted((faulted(_mixed_specs(1000 + r)) for r in range(REPEATS)),
                  key=lambda t: t[0])
    dt_fault, eng = runs[len(runs) // 2]
    failed = sum(1 for rec in eng.jobs.values() if rec.status == FAILED)
    if failed != FAULT_EXPECTED:
        raise AssertionError(
            f"deterministic fault plan drifted: {failed} FAILED, "
            f"expected {FAULT_EXPECTED}")
    # a surviving job must still match standalone abo_minimize bit-for-bit
    rec0 = eng.jobs[min(eng.jobs)]           # job-000000: ordinal 1, clean
    s0 = _mixed_specs(1000 + REPEATS - 1)[0]
    ref = abo_minimize(OBJECTIVES[s0.objective], s0.n, config=s0.config,
                       seed=s0.seed)
    if not (rec0.fun == float(ref.fun)
            and np.asarray(rec0.x).tobytes()
            == np.asarray(ref.x).tobytes()):
        raise AssertionError(
            f"faulted-run survivor drifted from abo_minimize for {s0}: "
            f"{rec0.fun!r} vs {ref.fun!r}")
    survivors = MIXED_JOBS - failed
    degradation = dt_fault / dt_clean - 1.0
    _METRICS["engine_faulted"] = {
        "jobs": MIXED_JOBS, "failed": failed,
        "fault_spec": FAULT_SPEC,
        "jobs_per_s_clean": MIXED_JOBS / dt_clean,
        "survivor_jobs_per_s": survivors / dt_fault,
        "degradation_frac": degradation,
        "survivors_bit_identical": True,     # just proved it
    }
    yield (f"engine_faulted_k{MIXED_JOBS}", dt_fault / survivors * 1e6,
           f"survivor_jobs_per_s={survivors / dt_fault:.1f} "
           f"failed={failed} degradation={degradation:+.1%} "
           "survivors_bit_identical=True")


# ---- spanning lanes: one job striped across the mesh ----------------------
# The paper's headline is a SINGLE 1e9-variable Griewank solve (64,485 s /
# 7.6 GB on one laptop thread); spanning lanes are the engine's path to
# that regime — a lane too large for one device's page budget stripes
# across the mesh, rows run Gauss-Seidel within a span shard and Jacobi
# across shards, and fun/x stay bit-identical to abo_minimize under the
# same span config at every device count (digest-asserted below, plus a
# kill at D=2 resumed at D=4 that must land the same bits through a
# reshard). The scenario extrapolates a time/RAM line to the paper's N
# from the measured per-coordinate-per-pass cost — an extrapolation, not
# a measurement (see benchmarks/README.md "Extrapolating the paper line").
SPAN_N = 24576                    # 6 span shards of 4096 coords (block 8)
SPAN_CFG_KW = dict(samples_per_pass=5, n_passes=3, block_size=8)
SPAN_COORDS = 4096                # lcm(block, REDUCE_TILE): smallest shard
SPAN_PAGES = 600                  # per-device budget: the 3072-page lane
#                                   cannot place whole, so it stripes
SPAN_OBJ = "griewank"
SPAN_SEED = 5
SPAN_DEVICES = (1, 2, 4)
PAPER_HEADLINE = {"n": 1e9, "time_s": 64485.0, "ram_gb": 7.6}


def _span_cfgs():
    import dataclasses as _dc
    cfg = ABOConfig(**SPAN_CFG_KW)
    return cfg, _dc.replace(cfg, span_coords=SPAN_COORDS)


def spanning_child(n_dev: int):
    """One forced-host-device child: solve the spanning job (plain config
    at D>1 so the engine's span_pages derivation is exercised; explicit
    span_coords at D=1 where there is no mesh to stripe over), digest
    fun/x, check D=1 against standalone abo_minimize, and report
    per-coordinate cost + footprint for the extrapolated paper line."""
    import numpy as np

    cfg, span_cfg = _span_cfgs()
    spec_cfg = cfg if n_dev > 1 else span_cfg

    def run_once():
        eng = SolveEngine(lanes=4, devices=n_dev, max_fuse=1,
                          span_pages=SPAN_PAGES if n_dev > 1 else None,
                          sanitize=SANITIZE)
        jid = eng.submit(JobSpec(SPAN_OBJ, SPAN_N, spec_cfg,
                                 seed=SPAN_SEED))
        t0 = time.perf_counter()
        eng.step()                       # pass 1: the lane is live —
        pool = next(iter(eng.pools.values()))
        striped = sum(isinstance(d, list) for d in pool.lane_dev)
        mem = eng.memory_stats()["pool_device_bytes"]
        eng.run()
        dt = time.perf_counter() - t0
        est = eng.stats()["engine_est_bytes_moved_total"]
        return dt, eng.result(jid), striped, mem, est

    dt, res, striped, mem, est = run_once()      # warm lap (compiles)
    h = hashlib.sha256()
    h.update(np.float64(res.fun).tobytes())
    h.update(np.asarray(res.x).tobytes())
    bit_ok = True
    if n_dev == 1:
        ref = abo_minimize(OBJECTIVES[SPAN_OBJ], SPAN_N, config=span_cfg,
                           seed=SPAN_SEED)
        bit_ok = (res.fun == ref.fun
                  and np.asarray(res.x).tobytes()
                  == np.asarray(ref.x).tobytes())
    laps = [run_once()[0] for _ in range(REPEATS)]
    n_passes = SPAN_CFG_KW["n_passes"]
    bpcp = est / (SPAN_N * n_passes)
    dt_med = _median(laps)
    print(json.dumps({
        "devices": n_dev,
        "laps_s": laps,
        "digest": h.hexdigest(),
        "bit_identical_to_solo": bool(bit_ok),
        "striped_lanes": striped,
        "pool_device_bytes": mem,
        "bytes_per_coordinate_per_pass": bpcp,
        # same workload shape scaled to the paper's N: linear in coords
        # for both time (per-coordinate sweep+sync cost) and RAM (pool
        # bytes per resident coordinate)
        "extrapolated_time_s_1e9": dt_med * (PAPER_HEADLINE["n"] / SPAN_N),
        "extrapolated_ram_gb_1e9": mem / SPAN_N,
    }), flush=True)


def spanning_kill_child(n_dev: int, ckpt: str):
    """Start the spanning job journaled, run ONE pass, snapshot, exit —
    the 'kill' half of the reshard chain."""
    cfg, _ = _span_cfgs()
    eng = SolveEngine(lanes=4, devices=n_dev, max_fuse=1,
                      span_pages=SPAN_PAGES, checkpoint_dir=ckpt,
                      journal_every=1, sanitize=SANITIZE)
    eng.submit(JobSpec(SPAN_OBJ, SPAN_N, cfg, seed=SPAN_SEED))
    eng.step()
    eng.snapshot()
    print(json.dumps({"devices": n_dev, "killed_after_steps": 1}),
          flush=True)


def spanning_resume_child(n_dev: int, ckpt: str):
    """Resume the killed spanning job on a DIFFERENT device count
    (reshard on load: the striped lane re-derives its shard round-robin)
    and report the finished digest — the parent asserts it equals the
    uninterrupted runs'."""
    import numpy as np

    eng = SolveEngine.resume(ckpt, devices=n_dev, sanitize=SANITIZE)
    pool = next(iter(eng.pools.values()))
    striped = sum(isinstance(d, list) for d in pool.lane_dev)
    eng.run()
    res = eng.result(min(eng.jobs))
    h = hashlib.sha256()
    h.update(np.float64(res.fun).tobytes())
    h.update(np.asarray(res.x).tobytes())
    print(json.dumps({"devices": n_dev, "striped_lanes": striped,
                      "digest": h.hexdigest()}), flush=True)


def _span_spawn(args: list[str], n_dev: int, timeout: int = 1800) -> dict:
    repo = pathlib.Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = f"{repo / 'src'}:{repo}"
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.engine_bench", *args],
        capture_output=True, text=True, env=env, cwd=repo, timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(
            f"spanning child {args} failed:\n{out.stderr[-3000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def engine_spanning():
    import shutil
    import tempfile

    _cpu_rehearsal_only("engine_spanning")
    recs = {d: _span_spawn(["--spanning-child", str(d)], d)
            for d in SPAN_DEVICES}
    digests = {recs[d]["digest"] for d in SPAN_DEVICES}
    if len(digests) != 1 or not recs[1]["bit_identical_to_solo"]:
        raise AssertionError(
            "engine_spanning bit-identity broken: "
            f"digests={ {d: recs[d]['digest'] for d in recs} }, "
            f"abo_minimize cross-check ok={recs[1]['bit_identical_to_solo']}")
    for d in SPAN_DEVICES[1:]:
        if recs[d]["striped_lanes"] != 1:
            raise AssertionError(
                f"spanning lane did not stripe at D={d}: "
                f"{recs[d]['striped_lanes']} striped lanes")
    # kill at D=2, resume at D=4: the reshard must land the same bits
    ck = tempfile.mkdtemp(prefix="bench_span_resume_")
    try:
        _span_spawn(["--spanning-kill", "2", ck], 2)
        rr = _span_spawn(["--spanning-resume", "4", ck], 4)
    finally:
        shutil.rmtree(ck, ignore_errors=True)
    if rr["digest"] != next(iter(digests)) or rr["striped_lanes"] != 1:
        raise AssertionError(
            f"spanning kill/resume reshard diverged: {rr} vs {digests}")
    meds = {d: _median(recs[d]["laps_s"]) for d in SPAN_DEVICES}
    base = meds[1]
    _METRICS["engine_spanning"] = {
        "n": SPAN_N, "objective": SPAN_OBJ,
        "span_coords": SPAN_COORDS, "span_pages": SPAN_PAGES,
        **SPAN_CFG_KW,
        **{f"dt_s_d{d}": meds[d] for d in SPAN_DEVICES},
        **{f"speedup_d{d}": base / meds[d] for d in SPAN_DEVICES[1:]},
        "bit_identical": True,
        "resume_reshard_d2_to_d4_bit_identical": True,
        "striped_lanes": {str(d): recs[d]["striped_lanes"]
                          for d in SPAN_DEVICES},
        "bytes_per_coordinate_per_pass": {
            str(d): recs[d]["bytes_per_coordinate_per_pass"]
            for d in SPAN_DEVICES},
        "pool_device_bytes": {str(d): recs[d]["pool_device_bytes"]
                              for d in SPAN_DEVICES},
        "paper_headline": PAPER_HEADLINE,
        "extrapolated_time_s_1e9": {
            str(d): recs[d]["extrapolated_time_s_1e9"]
            for d in SPAN_DEVICES},
        "extrapolated_ram_gb_1e9": {
            str(d): recs[d]["extrapolated_ram_gb_1e9"]
            for d in SPAN_DEVICES},
        "extrapolated_vs_paper_time": {
            str(d): recs[d]["extrapolated_time_s_1e9"]
            / PAPER_HEADLINE["time_s"] for d in SPAN_DEVICES},
        "extrapolated_vs_paper_ram": {
            str(d): recs[d]["extrapolated_ram_gb_1e9"]
            / PAPER_HEADLINE["ram_gb"] for d in SPAN_DEVICES},
    }
    for d in SPAN_DEVICES:
        ex_t = recs[d]["extrapolated_time_s_1e9"]
        ex_r = recs[d]["extrapolated_ram_gb_1e9"]
        yield (f"engine_spanning_d{d}_n{SPAN_N}", meds[d] * 1e6,
               f"dt_s={meds[d]:.2f} speedup={base / meds[d]:.2f}x "
               f"striped={recs[d]['striped_lanes']} "
               f"extrap_1e9_time_s={ex_t:.0f} "
               f"extrap_1e9_ram_gb={ex_r:.2f} "
               f"paper=64485s/7.6GB bit_identical=True")


def spanning_smoke(artifact: str | None = None):
    """CI-sized spanning gate (forced >= 4 host devices): one spanning
    lane + mixed small traffic under the runtime sanitizers and a
    compile budget, per-job bits asserted against standalone
    abo_minimize, then a kill/resume that reshards D=4 -> 2 and must
    finish with the same bits. Writes the BENCH fragment (artifact
    path or ./BENCH_engine.json) for CI upload."""
    import dataclasses as _dc
    import shutil
    import tempfile

    import jax
    import numpy as np

    from repro.analysis import compile_guard

    assert len(jax.devices()) >= 4, (
        "spanning smoke needs 4 forced host devices: launch with "
        "XLA_FLAGS=--xla_force_host_platform_device_count=4")
    n = 12288
    cfg = ABOConfig(samples_per_pass=5, n_passes=3, block_size=8)
    span_cfg = _dc.replace(cfg, span_coords=SPAN_COORDS)
    small = ABOConfig(samples_per_pass=7, n_passes=3, block_size=8)
    specs = [JobSpec(SPAN_OBJ, n, cfg, seed=SPAN_SEED)]
    specs += [JobSpec("sphere", 40 + 17 * i, small, seed=i)
              for i in range(6)]
    refs = []
    for s in specs:
        c = span_cfg if s.objective == SPAN_OBJ else s.config
        r = abo_minimize(OBJECTIVES[s.objective], s.n, config=c,
                         seed=s.seed)
        refs.append((r.fun, np.asarray(r.x).tobytes()))

    def check(eng, ids):
        for (fun, xb), jid in zip(refs, ids):
            r = eng.result(jid)
            assert r.fun == fun and np.asarray(r.x).tobytes() == xb, jid

    with compile_guard(80, "spanning smoke"):
        eng = SolveEngine(lanes=4, devices=4, span_pages=SPAN_PAGES,
                          max_fuse=1, sanitize=True)
        ids = eng.submit_many(specs)
        eng.step()
        pool = next(p for p in eng.pools.values()
                    if any(isinstance(d, list) for d in p.lane_dev))
        striped = sum(isinstance(d, list) for d in pool.lane_dev)
        assert striped == 1, striped
        eng.run()
        check(eng, ids)

        # kill mid-run, resume with a reshard D=4 -> 2, same bits
        ck = tempfile.mkdtemp(prefix="span_smoke_resume_")
        try:
            e1 = SolveEngine(lanes=4, devices=4, span_pages=SPAN_PAGES,
                             max_fuse=1, sanitize=True,
                             checkpoint_dir=ck, journal_every=1)
            ids = e1.submit_many(specs)
            e1.step()
            e1.snapshot()
            del e1
            e2 = SolveEngine.resume(ck, devices=2, sanitize=True)
            assert any(isinstance(d, list) for p in e2.pools.values()
                       for d in p.lane_dev), "reshard lost the stripe"
            e2.run()
            check(e2, ids)
        finally:
            shutil.rmtree(ck, ignore_errors=True)
    _METRICS["engine_spanning_smoke"] = {
        "n": n, "devices": 4, "resume_devices": 2,
        "striped_lanes": striped, "mixed_jobs": len(specs) - 1,
        "sanitized": True, "bit_identical": True,
        "resume_reshard_bit_identical": True,
    }
    out = write_artifact(artifact) if artifact else write_artifact()
    print(f"spanning smoke OK -> {out}", flush=True)


# ---- serving tier: sustained req/s, shed rate, tail latency ---------------
# The hardened HTTP front door under concurrent clients with a queue
# sized to overflow: measures sustained request throughput, the shed
# rate (deliberate 429/503 answers — the overload contract), and client-
# observed p99 request latency. Every delivered fun/x is asserted
# bit-identical to standalone abo_minimize: load shedding must never
# change what the survivors compute.
SERVE_JOBS = 24
SERVE_CLIENTS = 4
SERVE_N = 64
SERVE_CFG = ABOConfig(samples_per_pass=12, n_passes=3)
SERVE_MAX_QUEUE = 6                  # forces queue_full sheds mid-burst


def _pctl(sorted_vals, q):
    if not sorted_vals:
        return None
    i = min(len(sorted_vals) - 1, int(q * len(sorted_vals)))
    return sorted_vals[i]


def engine_serving():
    import http.client
    import threading

    import numpy as np

    from repro.engine.service import SolveService
    from repro.serve.frontend import Frontend, FrontendConfig

    svc = SolveService(lanes=8, max_queue=SERVE_MAX_QUEUE,
                       sanitize=SANITIZE)
    fe = Frontend(svc, 0, FrontendConfig(poll_s=0.005))
    threading.Thread(target=fe.httpd.serve_forever, daemon=True).start()
    fe.stepper_thread.start()
    port = fe.httpd.server_address[1]

    lat: list[float] = []            # client-observed request seconds
    shed = [0]                       # deliberate 429/503 answers
    bad = []                         # anything outside the contract
    results: dict[int, dict] = {}
    lock = threading.Lock()

    def rq(method, path, body=None):
        t0 = time.perf_counter()
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        try:
            conn.request(method, path, body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            payload = json.loads(resp.read())
            retry = resp.getheader("Retry-After")
        finally:
            conn.close()
        dt = time.perf_counter() - t0
        with lock:
            lat.append(dt)
            if resp.status in (429, 503):
                shed[0] += 1
                if retry is None:    # a shed without Retry-After is a bug
                    bad.append((resp.status, payload))
            elif resp.status not in (200, 202):
                bad.append((resp.status, payload))
        return resp.status, payload, retry

    def client(worker: int):
        deadline = time.monotonic() + 300
        jids = {}
        # burst phase: fire every submission back-to-back — 24 rapid
        # submits against max_queue=6 is the overload the shed-rate
        # number measures; Retry-After paces the retries
        for seed in range(worker, SERVE_JOBS, SERVE_CLIENTS):
            body = json.dumps({"objective": OBJ, "n": SERVE_N,
                               "seed": seed,
                               "config": {"samples_per_pass":
                                          SERVE_CFG.samples_per_pass,
                                          "n_passes": SERVE_CFG.n_passes}})
            while True:              # submit, honoring Retry-After sheds
                st, out, retry = rq("POST", "/submit", body)
                if st == 200:
                    jids[seed] = out["job_id"]
                    break
                assert st in (429, 503) and time.monotonic() < deadline, \
                    (st, out)
                time.sleep(min(float(retry or 1), 0.5))
        for seed, jid in jids.items():   # long-poll each to delivery
            while True:
                st, out, _ = rq("GET", f"/result?job_id={jid}&wait=10")
                if st == 200 and out.get("status") == "done":
                    with lock:
                        results[seed] = out
                    break
                assert st in (202, 429, 503) \
                    and time.monotonic() < deadline, (st, out)

    # warm lap: compiles outside the timed window
    rq("POST", "/submit", json.dumps(
        {"objective": OBJ, "n": SERVE_N, "seed": 10_000,
         "config": {"samples_per_pass": SERVE_CFG.samples_per_pass,
                    "n_passes": SERVE_CFG.n_passes}}))
    t_warm = time.monotonic() + 60
    while svc.engine.pending() and time.monotonic() < t_warm:
        time.sleep(0.05)
    lat.clear(); shed[0] = 0

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(w,), daemon=True)
               for w in range(SERVE_CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    dt = time.perf_counter() - t0

    fe._stop_stepper.set()
    with fe._wake:
        fe._wake.notify_all()
    fe.httpd.shutdown()
    fe.httpd.server_close()

    if bad:
        raise AssertionError(f"serving contract broken: {bad[:5]}")
    if len(results) != SERVE_JOBS:
        raise AssertionError(
            f"lost jobs under load: {len(results)}/{SERVE_JOBS} delivered")
    # shedding must never change what the survivors compute
    h_got, h_ref = hashlib.sha256(), hashlib.sha256()
    for seed in range(SERVE_JOBS):
        out = results[seed]
        h_got.update(np.float64(out["fun"]).tobytes())
        h_got.update(np.asarray(out["x"], np.float64).tobytes())
        ref = abo_minimize(OBJECTIVES[OBJ], SERVE_N, config=SERVE_CFG,
                           seed=seed)
        h_ref.update(np.float64(ref.fun).tobytes())
        h_ref.update(np.asarray(ref.x, np.float64).tobytes())
    if h_got.hexdigest() != h_ref.hexdigest():
        raise AssertionError(
            "engine_serving bit-identity broken: delivered results "
            "diverge from abo_minimize")

    laps = sorted(lat)
    reqs = len(lat)
    p50, p99 = _pctl(laps, 0.50), _pctl(laps, 0.99)
    shed_rate = shed[0] / reqs if reqs else 0.0
    _METRICS["engine_serving"] = {
        "jobs": SERVE_JOBS, "clients": SERVE_CLIENTS,
        "max_queue": SERVE_MAX_QUEUE,
        "requests": reqs, "req_per_s": reqs / dt,
        "shed": shed[0], "shed_rate": shed_rate,
        "p50_request_s": p50, "p99_request_s": p99,
        "jobs_per_s": SERVE_JOBS / dt,
        "bit_identical": True,       # the digest gate just proved it
    }
    yield (f"engine_serving_k{SERVE_JOBS}", dt / SERVE_JOBS * 1e6,
           f"req_per_s={reqs / dt:.1f} shed_rate={shed_rate:.1%} "
           f"p99_request_s={p99:.3f} jobs_per_s={SERVE_JOBS / dt:.1f} "
           "bit_identical=True")


def serving_smoke(artifact: str | None = None):
    """CI-sized router chaos gate: two journaled workers, one murdered
    mid-traffic by an injected ``worker_crash`` fault; assert supervised
    restart, zero lost acked jobs, only deliberate sheds, and survivor
    fun/x bit-identical to abo_minimize. Writes the BENCH fragment and
    the aggregated router metrics (``router_metrics.prom`` next to the
    artifact) for CI upload."""
    import http.client
    import tempfile
    import threading

    import numpy as np

    from repro.serve.router import Router, WorkerHandle

    _cpu_rehearsal_only("serving_smoke")
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="bench_serving_smoke_"))
    worker_args = ["--lanes", "2", "--journal-every", "2"]
    handles = [WorkerHandle(i, tmp / f"w{i}", worker_args)
               for i in range(2)]
    rt = Router(handles, port=0, probe_s=0.2)
    port = rt.httpd.server_address[1]
    obj0, obj1 = "shifted_sphere", "sphere"   # w0 (doomed) / w1 families
    assert rt.worker_for_family(obj0).index == 0
    assert rt.worker_for_family(obj1).index == 1
    rt.spawn_all(inject={0: "worker_crash:nth=3:kind=kill"})
    assert all(w.port is not None for w in handles), "worker spawn failed"
    serve_thread = threading.Thread(target=rt.serve, daemon=True)
    serve_thread.start()

    def rq(method, path, body=None):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        try:
            conn.request(method, path, body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            raw = resp.read()
            return (resp.status, json.loads(raw),
                    resp.getheader("Retry-After"))
        finally:
            conn.close()

    cfg = {"samples_per_pass": 12, "n_passes": 3}
    plan = [(obj0, 48, s) for s in range(4)] + \
        [(obj1, 32, s) for s in range(2)]
    try:
        acked = {}
        for obj, n, seed in plan:
            body = json.dumps({"objective": obj, "n": n, "seed": seed,
                               "config": cfg})
            deadline = time.monotonic() + 180
            while True:
                st, out, retry = rq("POST", "/submit", body)
                if st == 200:
                    acked[out["job_id"]] = (obj, n, seed)
                    break
                assert st == 503 and out["code"] in (
                    "worker_unavailable", "shutting_down") \
                    and retry is not None \
                    and time.monotonic() < deadline, (st, out)
                time.sleep(min(float(retry), 1.0))

        results = {}
        pending = set(acked)
        deadline = time.monotonic() + 300
        while pending and time.monotonic() < deadline:
            for jid in sorted(pending):
                st, out, retry = rq("GET", f"/result?job_id={jid}&wait=5")
                if st == 200 and out.get("status") == "done":
                    results[jid] = out
                    pending.discard(jid)
                elif st == 503:
                    assert out["code"] in ("worker_unavailable",
                                           "shutting_down"), out
                    time.sleep(min(float(retry or 1), 1.0))
                else:
                    assert st == 202, (st, out)
        assert not pending, f"lost jobs after restart: {sorted(pending)}"
        assert handles[0].restarts >= 1, "worker 0 was never killed"

        for jid, (obj, n, seed) in acked.items():
            ref = abo_minimize(OBJECTIVES[obj], n,
                               config=ABOConfig(**cfg), seed=seed)
            out = results[jid]
            assert out["fun"] == float(ref.fun), jid
            assert (np.asarray(out["x"], np.float64).tobytes()
                    == np.asarray(ref.x, np.float64).tobytes()), jid

        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        metrics_text = resp.read().decode()
        conn.close()
        assert 'router_worker_restarts_total{worker="w0"}' in metrics_text
    finally:
        rt.begin_shutdown("smoke done")
        serve_thread.join(timeout=60)
        for w in handles:
            w.terminate(grace_s=5)

    _METRICS["engine_serving_smoke"] = {
        "workers": 2, "jobs": len(plan),
        "inject": "worker_crash:nth=3:kind=kill",
        "worker0_restarts": handles[0].restarts,
        "lost_jobs": 0, "bit_identical": True,
    }
    out_path = write_artifact(artifact) if artifact else write_artifact()
    prom = out_path.parent / "router_metrics.prom"
    prom.write_text(metrics_text)
    print(f"serving smoke OK -> {out_path} (+ {prom})", flush=True)


def write_artifact(path: str | pathlib.Path = ARTIFACT) -> pathlib.Path:
    """Append this run's metrics to the JSON perf trajectory (a list of
    run records, newest last). Partial runs append whatever scenarios
    actually executed."""
    path = pathlib.Path(path)
    history = []
    if path.exists():
        try:
            history = json.loads(path.read_text())
            if not isinstance(history, list):
                history = []
        except (OSError, json.JSONDecodeError):
            history = []                 # unreadable -> start a fresh file
    history.append({
        "unix_time": time.time(),
        "scenarios": dict(_METRICS),
    })
    path.write_text(json.dumps(history, indent=1))
    return path


def main():
    if len(sys.argv) >= 3 and sys.argv[1] == "--sharded-child":
        sharded_child(int(sys.argv[2]))
        return
    if len(sys.argv) >= 3 and sys.argv[1] == "--spanning-child":
        spanning_child(int(sys.argv[2]))
        return
    if len(sys.argv) >= 4 and sys.argv[1] == "--spanning-kill":
        spanning_kill_child(int(sys.argv[2]), sys.argv[3])
        return
    if len(sys.argv) >= 4 and sys.argv[1] == "--spanning-resume":
        spanning_resume_child(int(sys.argv[2]), sys.argv[3])
        return
    if "--spanning-smoke" in sys.argv[1:]:
        # CI gate: sanitized spanning lane + mixed traffic + reshard
        # resume on forced host devices; optional artifact path follows
        idx = sys.argv.index("--spanning-smoke")
        art = sys.argv[idx + 1] if len(sys.argv) > idx + 1 else None
        spanning_smoke(art)
        return
    if "--serving-smoke" in sys.argv[1:]:
        # CI gate: router chaos — two journaled workers, one killed
        # mid-traffic; supervised restart, zero lost jobs, bit-identical
        # delivery; optional artifact path follows
        idx = sys.argv.index("--serving-smoke")
        art = sys.argv[idx + 1] if len(sys.argv) > idx + 1 else None
        serving_smoke(art)
        return
    if "--sanitize" in sys.argv[1:]:
        # sanitizer mode: the guardrail scenarios only (fast enough for
        # CI; the full bench is the perf gate, this is the invariant gate)
        print("name,us_per_call,derived")
        for name, us, derived in engine_sanitized():
            print(f"{name},{us:.1f},{derived}")
        print(f"# wrote {write_artifact()}")
        return
    print("name,us_per_call,derived")
    for name, us, derived in engine_vs_sequential():
        print(f"{name},{us:.1f},{derived}")
    for name, us, derived in engine_elastic():
        print(f"{name},{us:.1f},{derived}")
    for name, us, derived in engine_mixed_n():
        print(f"{name},{us:.1f},{derived}")
    for name, us, derived in engine_faulted():
        print(f"{name},{us:.1f},{derived}")
    for name, us, derived in engine_roofline():
        print(f"{name},{us:.1f},{derived}")
    for name, us, derived in engine_serving():
        print(f"{name},{us:.1f},{derived}")
    for name, us, derived in engine_sharded():
        print(f"{name},{us:.1f},{derived}")
    for name, us, derived in engine_spanning():
        print(f"{name},{us:.1f},{derived}")
    print(f"# wrote {write_artifact()}")


if __name__ == "__main__":
    main()
