"""Observability: metrics registry, span tracer, engine telemetry.

Three layers under test:

* ``repro.obs`` units — registry create-or-get semantics, cumulative
  histogram buckets, Prometheus text rendering, null-span tracing and
  Chrome-trace export, the analytic roofline model;
* the engine integration — every step phase emits a span, the lifecycle
  histograms see every job, the gauges agree with the legacy
  ``memory_stats``/``pad_stats`` aliases, and (the invariant that makes
  telemetry safe to leave on) per-job fun/x stay bit-identical to
  ``abo_minimize`` with tracing enabled;
* the HTTP surface — ``/metrics`` serves the text exposition and
  ``--verbose`` emits one structured JSON access-log line per request.
"""
import http.client
import json
import threading

import numpy as np
import pytest

from repro.core import ABOConfig, abo_minimize
from repro.engine.jobs import JobSpec
from repro.engine.scheduler import SolveEngine
from repro.engine.service import SolveService
from repro.objectives import OBJECTIVES
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NULL_SPAN, Tracer

CFG = ABOConfig(samples_per_pass=5, n_passes=3, block_size=8)

PHASES = {"refill", "plan_build", "fused_sweep", "harvest"}
HARVEST_KIDS = ("finalize", "device_wait", "readback")


def _drained_engine(tracing=False, jobs=3, **kw):
    eng = SolveEngine(lanes=2, **kw)
    if tracing:
        eng.trace()
    ids = eng.submit_many([JobSpec("sphere", 20 + 9 * i, CFG, seed=i)
                           for i in range(jobs)])
    eng.run()
    return eng, ids


# ------------------------------------------------------------ registry units
def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("jobs_total", "help text")
    c.inc()
    c.inc(2.5)
    assert reg.counter("jobs_total") is c        # create-or-get, cacheable
    g = reg.gauge("depth")
    g.set(7)
    g.inc(-2)
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["jobs_total"] == 3.5
    assert snap["depth"] == 5.0
    assert snap["lat_seconds_count"] == 4
    assert snap["lat_seconds_sum"] == pytest.approx(55.55)
    assert snap["lat_seconds_avg"] == pytest.approx(55.55 / 4)
    # Prometheus semantics: bucket i counts observations <= bounds[i]
    assert h.bucket_counts == [1, 2, 3]


def test_registry_labels_and_kind_mismatch():
    reg = MetricsRegistry()
    a = reg.counter("http_requests_total", endpoint="/poll", status=200)
    b = reg.counter("http_requests_total", endpoint="/poll", status=404)
    assert a is not b
    a.inc(3)
    b.inc()
    snap = reg.snapshot()
    assert snap['http_requests_total{endpoint="/poll",status="200"}'] == 3.0
    assert snap['http_requests_total{endpoint="/poll",status="404"}'] == 1.0
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("http_requests_total", endpoint="/poll", status=200)


def test_prometheus_rendering():
    reg = MetricsRegistry()
    reg.counter("steps_total", "engine steps").inc(4)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.5, 2.0))
    for v in (0.1, 1.0, 9.0):
        h.observe(v)
    text = reg.render_prometheus()
    assert "# HELP steps_total engine steps" in text
    assert "# TYPE steps_total counter" in text
    assert "steps_total 4.0" in text
    assert "# TYPE lat_seconds histogram" in text
    assert 'lat_seconds_bucket{le="0.5"} 1' in text
    assert 'lat_seconds_bucket{le="2"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert "lat_seconds_sum 10.1" in text
    assert "lat_seconds_count 3" in text
    assert text.endswith("\n")


# ------------------------------------------------------------- tracer units
def test_tracer_disabled_is_null_span():
    tr = Tracer()
    assert tr.span("anything", k=1) is NULL_SPAN   # no per-call allocation
    with tr.span("x") as sp:
        sp.set(a=2)
    assert tr.events == []


def test_tracer_records_and_exports(tmp_path):
    tr = Tracer()
    tr.enable()
    with tr.span("outer", step=0):
        with tr.span("inner") as sp:
            sp.set(found=3)
    assert tr.counts() == {"outer": 1, "inner": 1}
    path = tmp_path / "trace.json"
    tr.export(str(path))
    doc = json.loads(path.read_text())
    evs = {e["name"]: e for e in doc["traceEvents"]}
    inner, outer = evs["inner"], evs["outer"]
    assert inner["args"]["found"] == 3
    # positional nesting: inner's [ts, ts+dur] inside outer's
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    for e in doc["traceEvents"]:
        assert e["ph"] == "X" and e["dur"] >= 0 and "tid" in e


def test_tracer_event_cap_and_missing_path():
    tr = Tracer(max_events=3)
    tr.enable()
    for i in range(10):
        with tr.span("e", i=i):
            pass
    assert len(tr.events) == 3
    with pytest.raises(ValueError, match="no trace path"):
        tr.export()


def test_tracer_annotation_hook_opens_and_closes_in_order():
    log = []

    class Ann:
        def __init__(self, name, **args):
            self.name = name
            log.append(("new", name, args))

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    tr = Tracer()
    assert tr.span("off", k=1) is NULL_SPAN
    tr.enable(annotate=Ann)
    with tr.span("outer", step=0):
        with tr.span("inner", passes=5, jobs="j1 j2") as sp:
            sp.set(late=1)                 # reaches the JSON only
    assert log == [("new", "engine.outer", {"step": 0}),
                   ("enter", "engine.outer"),
                   ("new", "engine.inner", {"passes": 5, "jobs": "j1 j2"}),
                   ("enter", "engine.inner"),
                   ("exit", "engine.inner"), ("exit", "engine.outer")]
    assert tr.events[0]["args"] == {"passes": 5, "jobs": "j1 j2", "late": 1}
    tr.disable()
    assert tr.span("off") is NULL_SPAN
    with tr.span("off"):
        pass
    assert len(log) == 6 and len(tr.events) == 2


# ------------------------------------------------------- engine integration
def test_engine_spans_and_bit_identity(tmp_path):
    eng, ids = _drained_engine(tracing=True)
    assert PHASES | {"step"} <= set(eng.tracer.counts())
    # the invariant that makes tracing safe to leave on: per-job fun/x
    # bit-identical to standalone abo_minimize
    for i, jid in enumerate(ids):
        r = eng.result(jid)
        ref = abo_minimize(OBJECTIVES["sphere"], 20 + 9 * i, config=CFG,
                           seed=i)
        assert r.fun == ref.fun
        assert np.asarray(r.x).tobytes() == np.asarray(ref.x).tobytes()
    # exported trace is valid Chrome-trace JSON with phases nested in steps
    path = eng.trace_export(str(tmp_path / "t.json"))
    doc = json.loads(open(path).read())
    evs = doc["traceEvents"]
    steps = [e for e in evs if e["name"] == "step"]
    nested = PHASES | {"resize", "snapshot"} | set(HARVEST_KIDS)
    inner = [e for e in evs if e["name"] in nested]
    assert steps and inner
    for e in inner:
        assert any(s["ts"] <= e["ts"]
                   and e["ts"] + e["dur"] <= s["ts"] + s["dur"] + 1e-3
                   for s in steps), f"{e['name']} span not nested in a step"


def _inside(e, outer):
    return (outer["ts"] <= e["ts"]
            and e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1e-3)


def test_engine_harvest_children_carry_job_ids():
    """A finishing harvest holds its finalize dispatch, the wait for the
    device and the read-back, in that order; refill and harvest name the
    jobs they placed and finished."""
    eng, ids = _drained_engine(tracing=True)
    evs = eng.tracer.events
    harvests = [e for e in evs if e["name"] == "harvest"
                and e["args"]["finished"]]
    assert harvests
    for h in harvests:
        kids = [e for e in evs if e["name"] in HARVEST_KIDS
                and _inside(e, h)]
        assert [e["name"] for e in kids] == list(HARVEST_KIDS)
        assert kids[-1]["args"]["bytes"] > 0
        assert len(h["args"]["jobs"].split()) == h["args"]["finished"]
    finished = [j for h in harvests for j in h["args"]["jobs"].split()]
    placed = [j for e in evs if e["name"] == "refill"
              for j in e["args"]["jobs"].split()]
    assert sorted(finished) == sorted(placed) == sorted(ids)
    # a harvest that finishes no job opens no child: no sync
    n_kids = sum(e["name"] in HARVEST_KIDS for e in evs)
    assert n_kids == len(HARVEST_KIDS) * len(harvests)


def test_engine_spans_reach_the_profiler_trace(tmp_path):
    """With a profiler running, each engine span is an ``engine.<name>``
    host event on the profiler's clock, its open-time args as stats."""
    import glob

    import jax
    from jax.profiler import ProfileData
    eng = SolveEngine(lanes=2)
    eng.trace()
    jax.profiler.start_trace(str(tmp_path))
    try:
        jid = eng.submit(JobSpec("sphere", 20, CFG, seed=0))
        eng.run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    host = [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("engine.")]
    names = [n for n, _ in host]
    for span in ("step", "refill", "fused_sweep", "harvest") + HARVEST_KIDS:
        assert "engine." + span in names
    assert dict(host)["engine.harvest"]["jobs"] == jid
    assert dict(host)["engine.readback"]["bytes"] > 0
    assert dict(host)["engine.fused_sweep"]["passes"] == CFG.n_passes
    assert dict(host)["engine.fused_sweep"]["agg_bytes"] == 4   # float32


def test_engine_trace_default_path(tmp_path):
    path = str(tmp_path / "default.json")
    eng = SolveEngine(lanes=2)
    eng.trace(path)                      # path remembered by the tracer
    eng.submit_many([JobSpec("sphere", 16, CFG, seed=0)])
    eng.run()
    assert eng.trace_export() == path
    assert json.loads(open(path).read())["traceEvents"]


def test_engine_metrics_counters_and_histograms():
    eng, ids = _drained_engine(jobs=4)
    # telemetry-off default: the step loop recorded zero trace events
    assert not eng.tracer.enabled and eng.tracer.events == []
    for jid in ids:
        eng.result(jid)
    snap = eng.stats()
    assert snap["engine_jobs_submitted_total"] == 4
    assert snap["engine_jobs_done_total"] == 4
    assert snap["engine_steps_total"] >= 1
    assert snap["engine_passes_total"] >= CFG.n_passes
    assert snap["engine_plan_builds_total"] >= 1
    assert snap["engine_pages_allocated_total"] > 0
    assert snap["engine_est_bytes_moved_total"] > 0
    # lifecycle histograms saw every job through every transition
    for h in ("queued", "run", "total", "fetch"):
        assert snap[f"engine_job_{h}_seconds_count"] == 4, h
    assert snap["engine_job_total_seconds_sum"] >= \
        snap["engine_job_run_seconds_sum"]
    # drained: occupancy gauges back at zero, census gauges = legacy alias
    assert snap["engine_active_lanes"] == 0
    assert snap["engine_queue_depth"] == 0
    ms = eng.memory_stats()
    assert snap["engine_pool_device_bytes"] == ms["pool_device_bytes"]
    assert snap["engine_pool_pages"] == ms["pool_pages"]
    assert snap['engine_device_bytes{device="0"}'] == ms["pool_device_bytes"]


def test_service_stats_aliases_match_registry():
    eng, ids = _drained_engine(jobs=2)
    out = SolveService(eng).stats()
    snap = out["metrics"]
    assert out["active_lanes"] == int(snap["engine_active_lanes"])
    assert out["queued"] == int(snap["engine_queue_depth"])
    assert out["families"] == int(snap["engine_families"])
    assert out["families_created"] == int(snap["engine_families_created"])
    assert out["executables"] == int(snap["engine_executables"])
    assert out["pool_device_bytes"] == snap["engine_pool_device_bytes"]
    assert out["steps"] == eng.step_count == snap["engine_steps_total"]
    for k in ("jobs", "fill_ratio", "pad_waste", "swept_rows",
              "swept_rows_live", "swept_waste", "retain_done"):
        assert k in out, k


def test_checkpoint_metrics(tmp_path):
    eng = SolveEngine(lanes=2, checkpoint_dir=str(tmp_path),
                      journal_every=2)
    ids = eng.submit_many([JobSpec("sphere", 24, CFG, seed=i)
                           for i in range(3)])
    eng.run()
    for jid in ids:
        eng.result(jid)
    snap = eng.stats()
    assert snap["ckpt_snapshots_total"] >= 1
    assert snap["ckpt_snapshot_seconds_count"] == \
        snap["ckpt_snapshots_total"]
    assert snap["ckpt_journal_records_total"] >= 3   # >= the submits
    jst = eng.ckpt.journal_stats()
    assert snap["ckpt_journal_segments"] == jst["segments"]
    assert snap["ckpt_journal_lag_records"] == jst["records"]
    assert snap["ckpt_journal_bytes"] == jst["bytes"]


# ------------------------------------------------------------- HTTP surface
def test_http_metrics_endpoint_and_access_log(capsys):
    from repro.launch.solve_server import _build_server

    svc = SolveService(SolveEngine(lanes=2))
    httpd, _stepper = _build_server(svc, port=0, verbose=True)
    server = threading.Thread(target=httpd.serve_forever, daemon=True)
    server.start()
    try:
        port = httpd.server_address[1]
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        spec = {"objective": "sphere", "n": 24, "seed": 0,
                "config": {"samples_per_pass": 5, "n_passes": 3,
                           "block_size": 8}}
        conn.request("POST", "/submit", json.dumps(spec))
        sub = json.loads(conn.getresponse().read())
        assert sub["job_id"]
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        text = resp.read().decode()
        assert resp.status == 200
        assert resp.getheader("Content-Type").startswith("text/plain")
        assert "# TYPE engine_steps_total counter" in text
        assert "engine_jobs_submitted_total 1.0" in text
        assert 'http_requests_total{endpoint="/submit",status="200"} 1.0' \
            in text
        conn.request("GET", "/poll?job_id=nope")
        missing = conn.getresponse()
        missing.read()
        assert missing.status == 404
    finally:
        httpd.shutdown()
        httpd.server_close()
    logs = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]
    by_path = {ln["path"]: ln for ln in logs}
    assert by_path["/submit"]["method"] == "POST"
    assert by_path["/submit"]["status"] == 200
    assert by_path["/metrics"]["status"] == 200
    assert by_path["/poll?job_id=nope"]["status"] == 404
    assert all(ln["duration_ms"] >= 0 for ln in logs)


# ----------------------------------------------------------------- roofline
def test_plan_pass_bytes_matches_manual():
    import jax.numpy as jnp

    from repro.engine import batched
    from repro.obs.roofline import plan_pass_bytes

    assert plan_pass_bytes(None, 8, 4) == 0
    eng = SolveEngine(lanes=2, max_fuse=1)
    eng.submit_many([JobSpec("sphere", 40, CFG, seed=0),
                     JobSpec("sphere", 17, CFG, seed=1)])
    eng.step()
    pool = next(iter(eng.pools.values()))
    plan = pool.plan
    bsz = batched.key_config(pool.key).block_size
    item = jnp.dtype(pool.key[2]).itemsize
    sync_rows = int(np.prod(plan.sync.pages.shape))
    want = (2 * plan.swept_slots + sync_rows) * bsz * item
    assert plan.pass_bytes == want == plan_pass_bytes(plan, bsz, item) > 0
    # one step at max_fuse=1 dispatched exactly one pass of this plan
    assert eng.stats()["engine_est_bytes_moved_total"] == plan.pass_bytes


def test_measured_peak_bandwidth_small():
    from repro.obs.roofline import measured_peak_bandwidth

    assert measured_peak_bandwidth(nbytes=1 << 22, repeats=2) > 0


def test_hlo_bytes_accessed_order_of_magnitude():
    import jax
    import jax.numpy as jnp

    from repro.obs.roofline import hlo_bytes_accessed

    f = jax.jit(lambda x: x * 2.0)
    got = hlo_bytes_accessed(f, jnp.zeros((1024,), jnp.float32))
    # None when the backend hides cost analysis; otherwise at least the
    # read+write footprint's order of magnitude
    assert got is None or got >= 1024 * 4
