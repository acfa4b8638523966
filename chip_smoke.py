#!/usr/bin/env python3
"""Bring-up check: the solve engine and its HTTP front door on a TPU.

    python chip_smoke.py              # one chip: serve, big, kernel
    python chip_smoke.py --chips 4    # four chips: sharded + spanning only

Runs the main path once, in this one process (which owns the chip),
through the entry points a user calls, and checks every result against
the plain solver ``abo_minimize`` run on the same chip:

- ``serve``: the HTTP front door (``repro.serve.Frontend`` over
  ``SolveService(SolveEngine(lanes=8))`` on 127.0.0.1, the code
  ``solve_server --http`` runs) takes 12 jobs over ``POST /submit`` —
  griewank, sphere and rastrigin at n in {1e5, 3e5, 1e6} at the paper's
  sampling, plus three jobs whose solution is off the grid's centre
  column (see ``OFF_CENTRE``) — and each ``/result`` long-poll must equal
  the reference bit for bit: ``fun``, the per-pass ``history`` and the
  bytes of ``x`` (``engine/DESIGN.md`` § Bit-identity);
- ``big``: one off-centre griewank job at n = 1e8 straight through
  ``SolveEngine``, the same check;
- ``kernel``: the Pallas path. ``abo_minimize(..., use_kernel=True)`` at
  n = 1e8 and the paper's sampling, whose ``fun`` must agree with the jnp
  path within ``KERNEL_FUN_ATOL`` (the kernel carries f32 aggregates
  through a pass with no re-sync, so bit-identity is not its contract);
  then every pass of an off-centre solve at n = 1e8, the kernel against
  its jnp oracle ``kernels/coord_sweep/ref.py`` from the same input (see
  ``KERNEL_*`` for what each pass must meet);
- ``--chips 4``: ``SolveEngine(devices=4, span_pages=...)`` with one
  off-centre griewank lane at n = 1e8 striped over the four chips and four
  whole lanes at n = 1e6; every result must equal ``abo_minimize`` on one
  chip under the span config the engine derived, and each chip's peak
  memory must hold at least half its quarter of the striped lane.

Each phase prints one JSON line: device, wall seconds, the seconds and
count of XLA compiles inside the phase (a persistent-cache hit builds
nothing), engine steps, ``peak_bytes_in_use`` (since the process began)
and the verdict. The last line is ``{"ok": ..., "device": {...}}``.
Exits non-zero when JAX finds no TPU, and when any comparison fails.
Uses the compile cache of ``repro.launch.compile_cache``.
"""
from __future__ import annotations

import argparse
import http.client
import json
import pathlib
import sys
import threading
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

PAPER = {"samples_per_pass": 50, "n_passes": 5, "block_size": 4096}
# The benchmark domains are symmetric about their optimum 0, and the
# paper's m = 50 puts 49 grid points on pass 0 with the centre one exactly
# at 0: every coordinate lands on the optimum in pass 0, and passes 1-4
# and the carried aggregates have nothing left to change. m = 51 (50 grid
# points, none at the centre) leaves pass 0 one grid step away, so every
# later pass, and the aggregate-coupled griewank selection, moves x.
OFF_CENTRE = {**PAPER, "samples_per_pass": 51}
SERVE_JOBS = [(o, n, seed, PAPER) for seed, (o, n) in enumerate(
    (o, n) for n in (100_000, 300_000, 1_000_000)
    for o in ("griewank", "sphere", "rastrigin"))] + [
    ("griewank", 100_000, 9, OFF_CENTRE),
    ("griewank", 1_000_000, 10, OFF_CENTRE),
    ("shifted_sphere", 300_000, 11, PAPER)]   # optimum off every grid
BIG_N = 100_000_000
KERNEL_N = BIG_N
# Kernel against the jnp path, both at paper sampling: both land every
# coordinate on 0, so any fun above this is a page the kernel never swept
# or a wrong number it wrote.
KERNEL_FUN_ATOL = 1e-3
# Kernel against ref.py, per pass, from the same input. The two evaluate
# cos/log with different instruction sequences (Mosaic vs XLA), and an
# ulp there can flip a near-tie between two grid points: so at most this
# share of coordinates may differ, none by more than one grid step, and
# each aggregate (S, L, K) by at most KERNEL_AGG_RTOL of the magnitude it
# entered the pass with (the carried sums round at that scale).
KERNEL_X_DIFFER_FRAC = 1e-3
KERNEL_AGG_RTOL = 1e-5
# pages per device before a lane stripes: the 1e8 lane (24,415 pages)
# splits into four shards, one per chip; a 1e6 lane (245 pages) stays whole
SPAN_PAGES = 6104
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    """Count and time XLA compiles, via jax.monitoring."""

    def __init__(self):
        import jax.monitoring
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def mark(self):
        return self.count, self.seconds


def _stat(device, key):
    """One ``memory_stats()`` entry (None where the backend keeps none)."""
    return (device.memory_stats() or {}).get(key)


def device_info(devices):
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def report(phase, devices, log, mark, t0, ok, **extra):
    c0, s0 = mark
    line = {"phase": phase, **device_info(devices),
            "wall_s": time.perf_counter() - t0,
            "compile_s": log.seconds - s0, "executables": log.count - c0,
            "peak_bytes_in_use": _stat(devices[0], "peak_bytes_in_use"),
            "ok": bool(ok), **extra}
    print(json.dumps(line), flush=True)
    return bool(ok)


def compare(fun, x, history, ref) -> dict:
    """An engine result against its ``abo_minimize`` reference: the bits
    of fun, of every pass's objective and of x must match. Where they do
    not, by how much (recorded, not forgiven)."""
    import numpy as np
    x = np.asarray(x, np.float32)
    rx = np.asarray(ref.x, np.float32)
    hist = np.asarray(history, np.float32)
    rhist = np.asarray(ref.history, np.float32)
    same = (fun == float(ref.fun) and hist.tobytes() == rhist.tobytes()
            and x.tobytes() == rx.tobytes())
    out = {"bit_identical": bool(same)}
    if not same:
        out.update(fun=fun, ref_fun=float(ref.fun),
                   history=hist.tolist(), ref_history=rhist.tolist(),
                   coords_differ=int(np.count_nonzero(
                       x.view(np.uint32) != rx.view(np.uint32))),
                   max_abs_dx=float(np.max(np.abs(x - rx))))
    return out


def reference(obj, n, config, seed):
    from repro.core.abo import abo_minimize
    from repro.objectives import OBJECTIVES
    return abo_minimize(OBJECTIVES[obj], n, config=config, seed=seed)


def _http(port, method, path, body=None, timeout=300):
    """One request, as a client should send it: backpressure answers
    (429/503 with Retry-After — e.g. a submit queued behind a step that
    is compiling) are waited out and retried. Returns (status, payload,
    reply bytes)."""
    while True:
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=timeout)
        try:
            conn.request(method, path, body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            raw = resp.read()
            retry = resp.getheader("Retry-After")
        finally:
            conn.close()
        out = json.loads(raw)
        if resp.status in (429, 503) and retry is not None:
            time.sleep(float(retry))
            continue
        return resp.status, out, len(raw)


def phase_serve(devices, log):
    """The HTTP front door over an 8-lane engine, checked per job."""
    from repro.core.abo import ABOConfig
    from repro.engine import SolveEngine, SolveService
    from repro.serve.frontend import Frontend

    mark, t0 = log.mark(), time.perf_counter()
    fe = Frontend(SolveService(SolveEngine(lanes=8)), 0)
    port = fe.httpd.server_address[1]
    server = threading.Thread(target=fe.serve, name="serve", daemon=True)
    server.start()
    ids = []
    for obj, n, seed, config in SERVE_JOBS:
        st, out, _ = _http(port, "POST", "/submit", json.dumps(
            {"objective": obj, "n": n, "seed": seed, "config": config}))
        if st != 200:
            raise RuntimeError(f"submit {obj} n={n}: HTTP {st} {out}")
        ids.append(out["job_id"])
    results, reply_bytes = [], []
    for jid in ids:
        while True:
            st, out, size = _http(port, "GET",
                                  f"/result?job_id={jid}&wait=60")
            if st == 200:
                break
            if st != 202:
                raise RuntimeError(f"result {jid}: HTTP {st} {out}")
        results.append(out)
        reply_bytes.append(size)
    serve_s = time.perf_counter() - t0
    steps = fe.service.engine.step_count
    fe.begin_shutdown("smoke done")
    server.join(timeout=120)
    if fe.engine_error is not None:
        raise RuntimeError("engine failed") from fe.engine_error
    t_ref = time.perf_counter()
    verdicts = [dict(objective=obj, n=n, m=config["samples_per_pass"],
                     **compare(out["fun"], out["x"], out["history"],
                               reference(obj, n, ABOConfig(**config), seed)))
                for (obj, n, seed, config), out in zip(SERVE_JOBS, results)]
    return report("serve", devices, log, mark, t0,
                  all(v["bit_identical"] for v in verdicts), serve_s=serve_s,
                  ref_s=time.perf_counter() - t_ref, steps=steps,
                  jobs=len(SERVE_JOBS),
                  bit_identical=sum(v["bit_identical"] for v in verdicts),
                  funs=[out["fun"] for out in results],
                  result_reply_bytes=reply_bytes,
                  mismatches=[v for v in verdicts
                              if not v["bit_identical"]])


def phase_big(devices, log):
    """One paper-scale griewank job straight through SolveEngine."""
    from repro.core.abo import ABOConfig
    from repro.engine import JobSpec, SolveEngine

    mark, t0 = log.mark(), time.perf_counter()
    cfg, seed = ABOConfig(**OFF_CENTRE), 7
    eng = SolveEngine(lanes=8)
    jid = eng.submit(JobSpec("griewank", BIG_N, cfg, seed=seed))
    eng.run()
    res = eng.result(jid)
    engine_s, steps = time.perf_counter() - t0, eng.step_count
    del eng
    t_ref = time.perf_counter()
    verdict = compare(res.fun, res.x, res.history,
                      reference("griewank", BIG_N, cfg, seed))
    return report("big", devices, log, mark, t0, verdict["bit_identical"],
                  n=BIG_N, engine_s=engine_s,
                  ref_s=time.perf_counter() - t_ref, steps=steps,
                  fun=res.fun, **verdict)


def kernel_vs_ref_passes(seed=5) -> list[dict]:
    """Every pass of an off-centre griewank solve at ``KERNEL_N``: the
    Pallas kernel and ref.py each sweep the same input, and the kernel's
    output carries on to the next pass."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.abo import ABOConfig, seeded_start
    from repro.kernels.coord_sweep.kernel import LANES
    from repro.kernels.coord_sweep.ops import pack_aggs, sweep_pass
    from repro.kernels.coord_sweep.ref import sweep_pass_ref
    from repro.objectives import GRIEWANK

    m, bsz, passes = (OFF_CENTRE[k] for k in
                      ("samples_per_pass", "block_size", "n_passes"))
    lo, hi = GRIEWANK.lower, GRIEWANK.upper
    ref = jax.jit(sweep_pass_ref, static_argnames=(
        "m", "n_valid", "lower", "upper", "half_width", "lam", "is_first"))
    n_pad = -(-KERNEL_N // bsz) * bsz
    # padding holds 0, as in abo_minimize(use_kernel=True)
    x = seeded_start(seed, n_pad, jnp.float32, lo, hi).at[KERNEL_N:].set(0)
    aggs = pack_aggs(GRIEWANK.aggregates(x, KERNEL_N, agg_dtype=jnp.float32))
    shrink = ABOConfig(**OFF_CENTRE).resolved_shrink()
    out = []
    for p in range(passes):
        # abo_minimize_kernel's schedule
        hw = 0.5 * (hi - lo) * shrink ** p
        kw = dict(m=m, n_valid=KERNEL_N, half_width=float(hw),
                  lam=p / (passes - 1), is_first=p == 0)
        xk, ak = sweep_pass(x.reshape(-1, LANES), aggs, block=bsz, **kw)
        xr, ar = ref(x.reshape(-1, bsz), aggs, lower=lo, upper=hi, **kw)
        xk_h = np.asarray(xk).reshape(-1)[:KERNEL_N]
        xr_h = np.asarray(xr).reshape(-1)[:KERNEL_N]
        a_in, ak3, ar3 = (np.asarray(a[0, :3], np.float64)
                          for a in (aggs, ak, ar))
        step = (hi - lo if p == 0 else 2 * hw) / (m - 2)
        differ = int(np.count_nonzero(xk_h != xr_h))
        max_dx = float(np.max(np.abs(xk_h - xr_h)))
        agg_err = float(np.max(np.abs(ak3 - ar3) / np.maximum(
            np.abs(a_in), 1.0)))
        out.append(dict(
            pass_idx=p, coords_differ=differ, max_abs_dx=max_dx,
            grid_step=step, agg_rel_err=agg_err, aggs=ak3.tolist(),
            ref_aggs=ar3.tolist(),
            passed=bool(differ <= KERNEL_X_DIFFER_FRAC * KERNEL_N
                        and max_dx <= step * (1 + 1e-3)
                        and agg_err <= KERNEL_AGG_RTOL)))
        x, aggs = xk.reshape(-1), ak
    return out


def phase_kernel(devices, log):
    """The Pallas sweep kernel against the jnp path and against its
    oracle, on the chip."""
    import numpy as np
    from repro.core.abo import ABOConfig, abo_minimize
    from repro.objectives import GRIEWANK

    mark, t0 = log.mark(), time.perf_counter()
    kern = abo_minimize(GRIEWANK, KERNEL_N, config=ABOConfig(
        **PAPER, use_kernel=True))
    kernel_s = time.perf_counter() - t0
    jnp_path = abo_minimize(GRIEWANK, KERNEL_N, config=ABOConfig(**PAPER))
    dfun = abs(kern.fun - jnp_path.fun)
    dx = float(np.max(np.abs(np.asarray(kern.x) - np.asarray(jnp_path.x))))
    t_passes = time.perf_counter()
    passes = kernel_vs_ref_passes()
    return report("kernel", devices, log, mark, t0,
                  dfun <= KERNEL_FUN_ATOL and all(
                      p["passed"] for p in passes),
                  n=KERNEL_N, kernel_s=kernel_s,
                  fun=kern.fun, jnp_fun=jnp_path.fun, abs_dfun=dfun,
                  fun_atol=KERNEL_FUN_ATOL, max_abs_dx=dx,
                  vs_ref_s=time.perf_counter() - t_passes,
                  vs_ref_passes=passes)


def phase_four_chips(devices, log):
    """Sharded pools + one spanning lane over four chips, each job checked
    against abo_minimize on one chip."""
    from repro.core.abo import ABOConfig
    from repro.engine import JobSpec, SolveEngine

    mark, t0 = log.mark(), time.perf_counter()
    cfg = ABOConfig(**OFF_CENTRE)
    eng = SolveEngine(lanes=8, devices=4, span_pages=SPAN_PAGES)
    specs = [JobSpec("griewank", BIG_N, cfg, seed=11)] + [
        JobSpec("griewank", 1_000_000, cfg, seed=20 + i) for i in range(4)]
    ids = [eng.submit(s) for s in specs]
    eng.run()
    # nothing but the engine has touched the chips yet: each one's peak is
    # its share of the pool pages (the striped lane alone puts a quarter
    # of its pages on every chip)
    peaks = [_stat(d, "peak_bytes_in_use") for d in devices[:4]]
    derived = [eng.jobs[j].spec.config for j in ids]
    results = [eng.result(j) for j in ids]
    engine_s, steps = time.perf_counter() - t0, eng.step_count
    del eng
    t_ref = time.perf_counter()
    verdicts = [compare(r.fun, r.x, r.history,
                        reference("griewank", s.n, c, s.seed))
                for s, c, r in zip(specs, derived, results)]
    share = BIG_N * 4 // 4                    # f32 bytes per chip
    spread = all(p is not None and p >= share // 2 for p in peaks)
    return report("four_chips", devices, log, mark, t0,
                  all(v["bit_identical"] for v in verdicts) and spread,
                  engine_s=engine_s,
                  ref_s=time.perf_counter() - t_ref, steps=steps,
                  span_coords=derived[0].span_coords,
                  verdicts=verdicts,
                  peak_bytes_in_use_per_device=peaks,
                  memory_stats_after=[d.memory_stats() for d in devices[:4]])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded + spanning path and "
                         "its one-chip references")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); this check runs on a chip only",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} chip(s)", file=sys.stderr)
        return 1
    log = CompileLog()
    if args.chips == 4:
        ok = phase_four_chips(devices, log)
    else:
        ok = all([phase_serve(devices, log), phase_big(devices, log),
                  phase_kernel(devices, log)])
    print(json.dumps({"ok": ok, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": args.chips}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
