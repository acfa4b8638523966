"""Solve-service launcher: queue many ABO jobs through the batched engine.

    PYTHONPATH=src python -m repro.launch.solve_server --jobs 32 --lanes 8
    PYTHONPATH=src python -m repro.launch.solve_server --jobs 32 \
        --n 500,1300,2600,6000            # heterogeneous-n workload
    PYTHONPATH=src python -m repro.launch.solve_server --jobs 32 \
        --ckpt-dir results/solve_ckpt --resume

Drives repro.engine end to end: submits a synthetic mix of jobs across
``--objectives`` (and, with a comma list in ``--n``, across problem
sizes), drains the queue with continuous lane refill, and prints jobs/sec
+ probe-FE/sec. With ``--ckpt-dir`` the engine snapshots every
``--ckpt-every`` steps and ``--resume`` picks up in-flight jobs from the
newest committed checkpoint (``--resume`` without ``--ckpt-dir`` is an
error — it would silently start a fresh engine with no checkpointing).

Heterogeneous n rides the block-paged lane pool: a job occupies exactly
``ceil(n / block)`` pages of its family's shared page pool, the
row-compacted sweep touches only occupied block rows, and every n shares
one compiled executable family — no pad rungs, no admission gating, no
padded compute beyond the last block's tail. Per-job results are
bit-identical to standalone ``abo_minimize`` at any lane/page layout.
With ``--devices D`` the page pools shard across the first D JAX devices
(on a TPU host, D chips; the CPU rehearsal launches with
XLA_FLAGS=--xla_force_host_platform_device_count=D so D host devices
exist before jax initializes); lanes place whole per
device, stepping is donated and zero-copy, and results stay bit-identical
at every device count — a snapshot cut on one D resumes on another
(reshard on load). ``--span PAGES`` additionally stripes any lane larger
than PAGES pages across the mesh (spanning lanes): the engine derives a
reduction-tile-aligned ``span_coords`` for the job, the sweep runs
Gauss-Seidel within each shard and Jacobi across shards, and results are
bit-identical to ``abo_minimize`` under that span config at every device
count — this is the path toward the paper's 1e9-variable single-job
headline, where no one device can hold the lane.
``--retain-done N`` bounds the job table: once a result has been
delivered (or a job cancelled), only the N most recent such records are
kept — eviction happens at delivery/cancel time, so ``--retain-done 0``
means "forget a record the moment its client is done with it". Pool
device memory is elastic: drained pools shrink past the
``--pool-high-water`` hysteresis, so a service's footprint tracks live
traffic, not its historical peak. ``--journal-every M`` switches
checkpointing to incremental mode: client inputs append to a journal the
moment they arrive and the whole engine state is snapshotted (and the
journal compacted) only every M steps — resume replays the journal over
the newest base and re-runs post-base passes deterministically, so
results still match an uninterrupted run bit-for-bit.

``--http PORT`` additionally exposes submit/poll/result/cancel as
JSON-over-HTTP on localhost via the hardened serving tier
(repro.serve.frontend — stdlib only). Endpoints:

    POST /submit   {"objective": "griewank", "n": 1000, "seed": 0}
    GET  /poll?job_id=job-000000[&wait=S]      # long-poll to terminal
    GET  /result?job_id=job-000000[&wait=S]    # long-poll to done
    POST /cancel   {"job_id": "job-000000"}
    GET  /stats
    GET  /healthz          # liveness: lock-free, 200 {"status": "ok"}
    GET  /metrics          # Prometheus text, lock-free render

Every non-200 carries the standard envelope (repro.serve.errors):
``{"error": ..., "code": ..., "job_id"?: ..., "status"?: ...}`` —
unknown ids 404 ``unknown_job``, malformed requests schema'd 400s,
terminal-without-result 409 ``conflict``, a /result before completion
202 ``not_done``, handler failures a JSON 500 — never a raw traceback.
Requests are validated at the door (``--max-n`` caps job size), bodies
are capped (``--max-body``; 411/413 past it), ``--auth SPEC`` arms
bearer-token tenants with token-bucket rate limits and job quotas
(401/429), ``--max-inflight`` bounds the request queue and
``--deadline`` each request's engine-access budget (503 ``saturated``
/ ``deadline`` sheds with Retry-After). Admission rejections map to
backpressure codes: ``--max-queue`` overflow answers 429,
``--memory-budget`` shedding 503 — both with a Retry-After derived
from queue depth and recent step time. ``--port-file PATH`` publishes
the bound port (atomic) for supervisors and tests. ``--verbose`` turns
on access logging: one structured JSON line per request (method, path,
status, duration_ms) on stdout — without it the server is silent.

``--workers N`` (with ``--http`` and ``--ckpt-dir``) scales out: the
process becomes a supervisor/router (repro.serve.router) over N engine
worker processes, each owning a journaled checkpoint subdirectory,
health-probed and respawned on crash with fsck --repair + journal
resume — zero acked jobs lost. Submissions route per objective family
(``crc32(objective) % N``) so compiled executables stay hot; job ids
come back prefixed (``w0:job-000123``) and route follow-ups.

Shutdown: SIGTERM/SIGINT cut a final snapshot (with ``--ckpt-dir``),
flush the journal, and exit 0 — in both batch and HTTP modes. A kill
that lands anyway is recoverable: ``python -m repro.checkpoint.fsck``
validates/repairs the base+journal chain and ``--resume`` replays it.

Chaos: ``--inject SPEC`` arms the deterministic fault-injection
registry (repro.engine.faults) — e.g.
``--inject "objective_eval:every=4:seed=7"`` poisons every 4th job's
lane with NaN (quarantined to FAILED at harvest, siblings unharmed),
``--inject "snapshot_write:nth=2:kind=kill"`` kills the process inside
the 2nd snapshot's commit window. Off by default; fault counts surface
as ``engine_faults_injected_total{site=...}``.

Guardrails: ``--sanitize`` runs the engine under the repro.analysis
runtime sanitizers — every ``step()`` executes inside the host-sync
guard (an implicit device->host sync anywhere but the designed
harvest/snapshot points raises ``HostSyncError``) and every fused
dispatch asserts its donated pool buffers actually died.
``--compile-budget N`` additionally wraps the batch drain in
``compile_guard(N)``: the run fails if more than N XLA executables are
built, enforcing one-executable-per-plan-signature end to end. Results
under the sanitizers stay bit-identical to standalone ``abo_minimize``.

Telemetry: ``--trace PATH`` enables the engine's pass-level span tracer
and exports Chrome-trace-event JSON to PATH when the run ends (batch
mode) or the server shuts down (HTTP mode) — load it in
chrome://tracing or https://ui.perfetto.dev. ``--metrics-out PATH``
writes a final Prometheus text snapshot of the metrics registry after a
batch run (what CI uploads as a build artifact).
"""
from __future__ import annotations

import argparse
import signal
import threading
import time

from repro.core.abo import ABOConfig
from repro.engine.jobs import JobSpec
from repro.engine.scheduler import SolveEngine, too_few_devices_message
from repro.engine.service import SolveService


def _mixed_specs(n_jobs, objectives, ns, cfg, seed0=0):
    return [JobSpec(objectives[i % len(objectives)], ns[i % len(ns)], cfg,
                    seed=seed0 + i)
            for i in range(n_jobs)]


def _build_server(service: SolveService, port: int, poll_s: float = 0.01,
                  verbose: bool = False, config=None):
    """Compat shim over :class:`repro.serve.frontend.Frontend`: returns
    ``(httpd, stepper_thread)`` exactly like the old demo builder (tests
    drive ``serve_forever`` from their own thread and ``shutdown()``
    it). The Frontend instance rides along as ``httpd._frontend``; pass
    ``config`` (a FrontendConfig) to harden beyond the defaults."""
    from repro.serve.frontend import Frontend, FrontendConfig
    if config is None:
        config = FrontendConfig(poll_s=poll_s, verbose=verbose)
    fe = Frontend(service, port, config)
    return fe.httpd, fe.stepper_thread


def _install_signal_handlers(on_signal):
    """SIGTERM/SIGINT -> ``on_signal(signum)``; returns the previous
    handlers (signal.signal only works from the main thread — tests
    driving servers from worker threads skip this and kill a subprocess
    instead)."""
    if threading.current_thread() is not threading.main_thread():
        return {}                        # in-process test harness thread
    prev = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        prev[sig] = signal.signal(
            sig, lambda signum, frame: on_signal(signum))
    return prev


def _serve_http(service: SolveService, port: int, poll_s: float = 0.01,
                verbose: bool = False, config=None,
                port_file: str | None = None):
    """Hardened JSON-over-HTTP front-end (repro.serve.frontend); blocks
    until SIGTERM/SIGINT, then lets in-flight replies finish, cuts a
    final snapshot (when checkpointing is on) and returns for a clean
    exit 0."""
    from repro.serve.frontend import Frontend, FrontendConfig
    if config is None:
        config = FrontendConfig(poll_s=poll_s, verbose=verbose)
    fe = Frontend(service, port, config)
    if port_file:
        from repro.serve.worker import _write_port_file
        _write_port_file(port_file, fe.httpd.server_address[1])
    _install_signal_handlers(
        lambda signum: fe.begin_shutdown(f"signal {signum}"))
    fe.serve()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--jobs", type=int, default=32)
    ap.add_argument("--lanes", type=int, default=8)
    ap.add_argument("--devices", type=int, default=None, metavar="D",
                    help="shard each family's page pool across the first "
                         "D JAX devices (lanes place whole onto the least-"
                         "loaded device; results stay bit-identical at any "
                         "D). On a TPU host D counts chips. The CPU "
                         "rehearsal of a mesh forces D host devices with "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=D. On resume, D overrides the snapshot's "
                         "device count (reshard on load)")
    ap.add_argument("--span", type=int, default=None, metavar="PAGES",
                    help="spanning lanes: stripe any lane whose page count "
                         "exceeds PAGES across the device mesh instead of "
                         "placing it whole (requires --devices >= 2; the "
                         "engine derives a tile-aligned span_coords, rows "
                         "run Gauss-Seidel within a shard and Jacobi "
                         "across, and results stay bit-identical to "
                         "abo_minimize with that span config at every D). "
                         "On resume the snapshot's recorded span wins")
    ap.add_argument("--n", default="1000",
                    help="problem size, or a comma list for a "
                         "heterogeneous-n workload (e.g. 500,1300,6000)")
    ap.add_argument("--objectives", default="griewank,sphere,rastrigin")
    ap.add_argument("--samples", type=int, default=50)
    ap.add_argument("--passes", type=int, default=5)
    ap.add_argument("--block", type=int, default=4096)
    ap.add_argument("--retain-done", type=int, default=None, metavar="N",
                    help="evict whole job records of delivered/cancelled "
                         "jobs beyond the N most recent (0 = evict at "
                         "delivery; default: keep all) — bounds snapshot "
                         "aux growth on a churny service")
    ap.add_argument("--pool-high-water", type=float, default=2.0,
                    metavar="X",
                    help="shrink a drained pool's device arrays once its "
                         "capacity exceeds X times the ladder rung "
                         "actually occupied (X >= 1; 0 disables shrinking "
                         "— capacity is retained forever)")
    ap.add_argument("--journal-every", type=int, default=None,
                    metavar="STEPS",
                    help="incremental checkpointing: append client inputs "
                         "to a journal as they happen and cut a whole-"
                         "state base snapshot (compacting the journal) "
                         "only every STEPS steps; requires --ckpt-dir")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=1)
    ap.add_argument("--resume", action="store_true",
                    help="resume in-flight jobs from --ckpt-dir")
    ap.add_argument("--http", type=int, default=None, metavar="PORT",
                    help="serve submit/poll/result over HTTP instead of "
                         "running a synthetic batch (0 = ephemeral "
                         "port; see --port-file)")
    ap.add_argument("--workers", type=int, default=None, metavar="N",
                    help="with --http and --ckpt-dir: become a "
                         "supervisor/router over N engine worker "
                         "processes (repro.serve.router) — per-family "
                         "routing, crash respawn with journal resume")
    ap.add_argument("--auth", default=None, metavar="SPEC",
                    help="bearer-token tenants: token[:key=val]*[;...] "
                         "with keys name, rate (req/s token bucket), "
                         "burst, quota (lifetime job budget); missing/"
                         "unknown tokens answer 401, over-rate 429")
    ap.add_argument("--max-body", type=int, default=1 << 20,
                    metavar="BYTES",
                    help="reject request bodies larger than BYTES with "
                         "413 (Content-Length is required: 411 without "
                         "it, 400 when malformed)")
    ap.add_argument("--max-n", type=int, default=None, metavar="N",
                    help="reject submissions with n > N at the door "
                         "(schema'd 400) — bounds what one request can "
                         "commission before admission control prices it")
    ap.add_argument("--deadline", type=float, default=30.0, metavar="S",
                    help="per-request engine-access budget: a request "
                         "that cannot reach the engine within S seconds "
                         "answers 503 with Retry-After")
    ap.add_argument("--wait-max", type=float, default=60.0, metavar="S",
                    help="cap on ?wait= long-polls (/result, /poll)")
    ap.add_argument("--max-inflight", type=int, default=64, metavar="N",
                    help="bounded request queue: past N concurrent "
                         "requests the front door sheds 503 saturated "
                         "instead of piling up threads")
    ap.add_argument("--port-file", default=None, metavar="PATH",
                    help="write the bound HTTP port to PATH (atomic) "
                         "once listening — supervisors and tests read "
                         "it instead of racing a fixed port")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="enable pass-level span tracing and export "
                         "Chrome-trace-event JSON to PATH when the run "
                         "(or server) ends — load it in Perfetto")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a final Prometheus text snapshot of the "
                         "metrics registry to PATH after a batch run")
    ap.add_argument("--verbose", action="store_true",
                    help="HTTP access logging: one structured JSON line "
                         "per request (method, path, status, duration_ms)")
    ap.add_argument("--sanitize", action="store_true",
                    help="run the engine under the repro.analysis runtime "
                         "sanitizers: every step() under the host-sync "
                         "guard (implicit device->host syncs outside the "
                         "designed harvest/snapshot points raise) and "
                         "every fused dispatch asserts its donated pool "
                         "buffers died")
    ap.add_argument("--compile-budget", type=int, default=None, metavar="N",
                    help="batch mode: fail the run if draining the queue "
                         "builds more than N XLA executables (counted via "
                         "jax.monitoring) — enforces one-executable-per-"
                         "plan-signature end to end")
    ap.add_argument("--inject", default=None, metavar="SPEC",
                    help="arm deterministic fault injection: "
                         "site[:key=val]*[;site...] with sites "
                         "snapshot_write/journal_append/pool_resize/"
                         "fused_step/objective_eval and schedules nth=N, "
                         "every=K, prob=P:seed=S (e.g. "
                         "'objective_eval:every=4:seed=7')")
    ap.add_argument("--max-queue", type=int, default=None, metavar="N",
                    help="bounded admission: reject submissions (HTTP "
                         "429) once N jobs are queued awaiting a lane")
    ap.add_argument("--memory-budget", type=int, default=None,
                    metavar="BYTES",
                    help="shed load (HTTP 503) when projected pool device "
                         "bytes for live + queued + incoming work would "
                         "exceed BYTES")
    args = ap.parse_args(argv)

    if args.retain_done is not None and args.retain_done < 0:
        # must fail at the argparse boundary (usage + exit code 2), not as
        # a ValueError traceback out of the engine constructor
        ap.error(f"--retain-done must be >= 0, got {args.retain_done}")
    high_water = args.pool_high_water
    if high_water == 0:
        high_water = None                # 0 = never shrink
    elif high_water < 1:
        ap.error("--pool-high-water must be >= 1 (or 0 to disable), got "
                 f"{args.pool_high_water}")
    if args.journal_every is not None:
        if args.journal_every < 1:
            ap.error("--journal-every must be >= 1, got "
                     f"{args.journal_every}")
        if not args.ckpt_dir:
            ap.error("--journal-every requires --ckpt-dir (the journal is "
                     "an incremental layer over base snapshots)")
    if args.devices is not None:
        import jax
        if args.devices < 1:
            ap.error(f"--devices must be >= 1, got {args.devices}")
        if args.devices > len(jax.devices()):
            # usage error, not an engine traceback: the fix is the launch
            # environment, not the request
            ap.error("--" + too_few_devices_message(args.devices,
                                                    jax.devices()))
    if args.span is not None:
        if args.span < 1:
            ap.error(f"--span must be >= 1, got {args.span}")
        if (args.devices or 1) < 2:
            ap.error("--span requires --devices >= 2 (a single device has "
                     "no mesh to stripe a lane across)")
    if args.max_queue is not None and args.max_queue < 1:
        ap.error(f"--max-queue must be >= 1, got {args.max_queue}")
    if args.memory_budget is not None and args.memory_budget < 1:
        ap.error(f"--memory-budget must be >= 1, got {args.memory_budget}")
    faults = None
    if args.inject:
        from repro.engine.faults import parse_fault_spec
        try:
            faults = parse_fault_spec(args.inject)
        except ValueError as e:
            ap.error(f"--inject: {e}")
    if args.max_body < 1:
        ap.error(f"--max-body must be >= 1, got {args.max_body}")
    if args.deadline <= 0:
        ap.error(f"--deadline must be > 0, got {args.deadline}")
    if args.wait_max < 0:
        ap.error(f"--wait-max must be >= 0, got {args.wait_max}")
    if args.max_inflight < 1:
        ap.error(f"--max-inflight must be >= 1, got {args.max_inflight}")
    if args.max_n is not None and args.max_n < 1:
        ap.error(f"--max-n must be >= 1, got {args.max_n}")
    tenants = None
    if args.auth:
        from repro.serve.limits import TenantTable
        try:
            tenants = TenantTable.from_spec(args.auth)
        except ValueError as e:
            ap.error(f"--auth: {e}")
    if args.workers is not None:
        # router mode: this process supervises N worker processes and
        # never builds an engine of its own
        if args.workers < 1:
            ap.error(f"--workers must be >= 1, got {args.workers}")
        if args.http is None:
            ap.error("--workers requires --http (the router IS an HTTP "
                     "front door)")
        if not args.ckpt_dir:
            ap.error("--workers requires --ckpt-dir (each worker owns a "
                     "journaled subdirectory; without one a worker "
                     "crash would lose acked jobs)")
        if args.inject:
            ap.error("--inject with --workers is ambiguous; use "
                     "python -m repro.serve.router --inject-worker "
                     "IDX:SPEC to arm one worker")
        from repro.serve.router import serve_router
        worker_args = ["--lanes", str(args.lanes),
                       "--journal-every", str(args.journal_every or 8)]
        if args.retain_done is not None:
            worker_args += ["--retain-done", str(args.retain_done)]
        if args.max_queue is not None:
            worker_args += ["--max-queue", str(args.max_queue)]
        if args.memory_budget is not None:
            worker_args += ["--memory-budget", str(args.memory_budget)]
        if args.sanitize:
            worker_args += ["--sanitize"]
        if args.verbose:
            worker_args += ["--verbose"]
        serve_router(args.workers, args.http, args.ckpt_dir,
                     worker_args=worker_args, tenants=tenants,
                     max_body_bytes=args.max_body,
                     port_file=args.port_file, verbose=args.verbose)
        return None                      # returns only on interrupt
    if args.resume:
        if not args.ckpt_dir:
            ap.error("--resume requires --ckpt-dir (without it there is no "
                     "checkpoint to resume from and nothing would be saved)")
        # flags only shape a FRESH engine (empty ckpt dir); a found
        # checkpoint's recorded lanes/retain_done win so the resumed run
        # can't diverge from the uninterrupted one (faults/sanitize are
        # observation, re-armed per life)
        engine = SolveEngine.resume(args.ckpt_dir, ckpt_every=args.ckpt_every,
                                    lanes=args.lanes,
                                    retain_done=args.retain_done,
                                    pool_high_water=high_water,
                                    journal_every=args.journal_every,
                                    max_queue=args.max_queue,
                                    memory_budget_bytes=args.memory_budget,
                                    devices=args.devices,
                                    span_pages=args.span,
                                    sanitize=args.sanitize,
                                    faults=faults)
    else:
        engine = SolveEngine(lanes=args.lanes, checkpoint_dir=args.ckpt_dir,
                             ckpt_every=args.ckpt_every,
                             retain_done=args.retain_done,
                             pool_high_water=high_water,
                             journal_every=args.journal_every,
                             max_queue=args.max_queue,
                             memory_budget_bytes=args.memory_budget,
                             devices=args.devices,
                             span_pages=args.span,
                             sanitize=args.sanitize,
                             faults=faults)
    service = SolveService(engine)
    if args.trace:
        engine.trace(args.trace)

    if args.http is not None:
        from repro.serve.frontend import FrontendConfig
        cfg = FrontendConfig(verbose=args.verbose,
                             max_body_bytes=args.max_body,
                             deadline_s=args.deadline,
                             wait_max_s=args.wait_max,
                             max_inflight=args.max_inflight,
                             max_n=args.max_n, tenants=tenants)
        _serve_http(service, args.http, config=cfg,
                    port_file=args.port_file)
        return None                      # returns only on interrupt

    cfg = ABOConfig(samples_per_pass=args.samples, n_passes=args.passes,
                    block_size=args.block)
    objectives = [o for o in args.objectives.split(",") if o]
    try:
        ns = [int(v) for v in str(args.n).split(",") if v.strip()]
    except ValueError:
        ns = []
    if not ns:
        ap.error(f"--n must be an int or comma list of ints, got {args.n!r}")
    if not args.resume:
        engine.submit_many(_mixed_specs(args.jobs, objectives, ns, cfg))
        if args.ckpt_dir:
            engine.snapshot()    # a kill during warmup can't lose the queue
    done_before = {j for j, r in engine.jobs.items() if r.status == "done"}
    # SIGTERM/SIGINT stop the drain at the next step boundary; the final
    # snapshot below then lands a consistent image and we exit 0 — a
    # KeyboardInterrupt traceback would skip it and lose the tail
    stop_flag = threading.Event()

    def on_signal(signum):
        print(f"[solve_server] signal {signum}: stopping after this step",
              flush=True)
        stop_flag.set()

    _install_signal_handlers(on_signal)
    t0 = time.time()
    if args.compile_budget is not None:
        from repro.analysis import compile_guard
        with compile_guard(args.compile_budget, "solve_server drain") as cg:
            done = engine.run(stop=stop_flag.is_set)
        print(f"[solve_server] compile_guard: {cg.count} executable(s) "
              f"built (budget {args.compile_budget})", flush=True)
    else:
        done = engine.run(stop=stop_flag.is_set)
    dt = max(time.time() - t0, 1e-9)
    if args.ckpt_dir:
        # a final base: in journal mode the last generation's results may
        # postdate the last in-run base, and a batch CLI never "fetches"
        # them — without this, a --resume after clean completion would
        # re-derive the tail instead of finding it done
        engine.snapshot()
    # FE from the specs of jobs THIS run finished (on --resume they may
    # differ from this invocation's CLI defaults)
    fe = sum(r.spec.config.n_passes * r.spec.config.samples_per_pass
             * r.spec.n for j, r in engine.jobs.items()
             if r.status == "done" and j not in done_before)
    waste = engine.pad_stats()["swept_waste"]
    stats = {"done": done, "steps": engine.step_count, "dt_s": dt,
             "jobs_per_s": done / dt, "fe_per_s": fe / dt,
             "families": len(engine.pools),
             "families_created": len(engine.family_keys_seen),
             "devices": engine.n_dev, "sanitize": engine.sanitize,
             "span_pages": engine.span_pages,
             "span_lanes": engine.stats().get("engine_span_lanes", 0),
             "swept_waste": waste, **engine.memory_stats()}
    if args.compile_budget is not None:
        stats["compiles"] = cg.count
        stats["compile_budget"] = args.compile_budget
    if stop_flag.is_set():
        stats["interrupted"] = True      # drained partially, snapshot cut
    if engine.ckpt is not None and engine.journal_every is not None:
        stats["journal"] = engine.ckpt.journal_stats()
    print(f"[solve_server] {done} jobs in {dt:.2f}s over "
          f"{engine.step_count} steps "
          f"({stats['families_created']} executable families, "
          f"{0.0 if waste is None else waste:.1%} swept-row waste): "
          f"{stats['jobs_per_s']:.1f} jobs/s, {stats['fe_per_s']:.3g} "
          "probe-FE/s", flush=True)
    if args.trace:
        print(f"[solve_server] trace -> {engine.trace_export()}",
              flush=True)
    if args.metrics_out:
        with open(args.metrics_out, "w") as fh:
            fh.write(engine.render_prometheus())
        print(f"[solve_server] metrics -> {args.metrics_out}", flush=True)
    return stats


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
