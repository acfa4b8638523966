"""Batched multi-tenant solve engine: batched-vs-sequential equivalence,
continuous lane refill at depth, submit/poll/cancel lifecycle, service
front-end, and kill/resume determinism through the checkpoint snapshot."""
import numpy as np
import pytest

from repro.core import ABOConfig, abo_minimize
from repro.engine import (CANCELLED, DONE, QUEUED, RUNNING, JobSpec,
                          SolveEngine, SolveService)
from repro.objectives import OBJECTIVES

# small/fast shapes reused across tests so the module-level compile cache
# amortizes jit time over the whole file
CFG = ABOConfig(samples_per_pass=12, n_passes=3)
SHAPES = [("griewank", 64), ("sphere", 96), ("rastrigin", 80)]


def _mixed_specs(count, seed0=0):
    return [JobSpec(*SHAPES[i % len(SHAPES)], CFG, seed=seed0 + i)
            for i in range(count)]


def _solo_fun(spec):
    return abo_minimize(OBJECTIVES[spec.objective], spec.n,
                        config=spec.config, seed=spec.seed).fun


def test_batched_matches_sequential():
    """K engine jobs == K independent abo_minimize calls (same init, same
    per-pass math, same exact final re-eval)."""
    specs = _mixed_specs(6)
    eng = SolveEngine(lanes=3)
    ids = eng.submit_many(specs)
    assert eng.run() == len(specs)
    for spec, jid in zip(specs, ids):
        r = eng.result(jid)
        assert abs(r.fun - _solo_fun(spec)) < 1e-5, (spec.objective, r.fun)
        assert r.n == spec.n and r.x.shape == (spec.n,)
        assert len(np.asarray(r.history)) == CFG.n_passes


def test_32_jobs_through_8_lanes_continuous_refill():
    """The acceptance workload: >=32 queued jobs, <=8 lanes, every lane
    refilled from the queue the step its job finishes."""
    specs = _mixed_specs(32, seed0=100)
    eng = SolveEngine(lanes=8)
    ids = eng.submit_many(specs)
    assert eng.run() == 32
    # 32 jobs x 3 passes over <= 8 lanes needs > n_passes generations:
    # proof that lanes were reused, not widened
    assert eng.step_count > CFG.n_passes
    assert eng.active_lanes == 0 and not eng.pending()
    for spec, jid in zip(specs, ids):
        assert abs(eng.result(jid).fun - _solo_fun(spec)) < 1e-5


def test_mixed_n_shares_family_pool():
    """Jobs with ANY mix of true n ride one family pool and one executable
    set (host page tables + per-lane n_valid), and still match their
    standalone runs."""
    from repro.engine.batched import family_key
    cfg = ABOConfig(samples_per_pass=12, n_passes=3, block_size=64)
    na, nb = 130, 430            # > 128 keeps the Jacobi block: 3 vs 7 pages
    assert family_key("sphere", na, cfg) == family_key("sphere", nb, cfg)
    specs = [JobSpec("sphere", na, cfg, seed=7),
             JobSpec("sphere", nb, cfg, seed=8)]
    eng = SolveEngine(lanes=2)
    ids = eng.submit_many(specs)
    eng.run()
    assert len(eng.pools) == 1
    for spec, jid in zip(specs, ids):
        assert abs(eng.result(jid).fun - _solo_fun(spec)) < 1e-5


@pytest.mark.parametrize("obj,n,m", [("sphere", 4000, 51),
                                     ("shifted_sphere", 30000, 50)])
def test_padded_lane_bit_identical_off_grid(obj, n, m):
    """A lane whose last block is part padding, on a trajectory that stays
    off the grid's centre: abo_minimize pads with its seeded start, the
    engine with zeros, and a padding coordinate's t(x) - t(x) (not 0 once
    contracted into an FMA) must not decide the guarded commit — fun, the
    per-pass history and x equal bit for bit."""
    cfg = ABOConfig(samples_per_pass=m, n_passes=5, block_size=4096)
    spec = JobSpec(obj, n, cfg, seed=11)
    eng = SolveEngine(lanes=2)
    jid = eng.submit(spec)
    eng.run()
    got = eng.result(jid)
    ref = abo_minimize(OBJECTIVES[obj], n, config=cfg, seed=11)
    assert got.fun == ref.fun
    np.testing.assert_array_equal(np.asarray(got.history, np.float32),
                                  np.asarray(ref.history))
    np.testing.assert_array_equal(np.asarray(got.x), np.asarray(ref.x))


@pytest.mark.parametrize("block,m", [(2, 5), (4, 7), (8, 7)])
def test_small_block_loop_bit_identical(block, m):
    """At blocks this small XLA:CPU compiles abo_minimize's block loop as
    one kernel and may contract the candidate grid's ``center + w * offs``
    into a fused multiply-add there, while the engine's row loop rounds
    the product first: the grid must round it in every program, or x
    comes apart by ulps."""
    cfg = ABOConfig(samples_per_pass=m, n_passes=4, block_size=block)
    specs = [JobSpec("sphere", 40 + 17 * i, cfg, seed=i) for i in range(7)]
    eng = SolveEngine(lanes=3)
    ids = eng.submit_many(specs)
    eng.run()
    for spec, jid in zip(specs, ids):
        got = eng.result(jid)
        ref = abo_minimize(OBJECTIVES["sphere"], spec.n, config=cfg,
                           seed=spec.seed)
        assert got.fun == ref.fun, spec.n
        np.testing.assert_array_equal(np.asarray(got.x), np.asarray(ref.x))


def test_submit_poll_cancel_lifecycle():
    # max_fuse=1: strict pass-per-step, so a job is observably RUNNING
    eng = SolveEngine(lanes=1, max_fuse=1)
    ids = eng.submit_many(_mixed_specs(3))
    assert all(eng.poll(j)["status"] == QUEUED for j in ids)
    assert eng.cancel(ids[1])                 # cancel while queued
    eng.step()
    assert eng.poll(ids[0])["status"] == RUNNING
    assert eng.poll(ids[0])["passes_done"] == 1
    eng.run()
    assert eng.poll(ids[0])["status"] == DONE
    assert eng.poll(ids[1])["status"] == CANCELLED
    assert eng.poll(ids[2])["status"] == DONE
    with pytest.raises(RuntimeError):
        eng.result(ids[1])
    assert not eng.cancel(ids[0])             # can't cancel a DONE job


def test_cancel_running_frees_lane():
    eng = SolveEngine(lanes=1, max_fuse=1)
    ids = eng.submit_many(_mixed_specs(2))
    eng.step()
    assert eng.poll(ids[0])["status"] == RUNNING
    assert eng.cancel(ids[0])
    assert eng.active_lanes == 0
    eng.run()
    assert eng.poll(ids[1])["status"] == DONE


def test_unknown_objective_rejected():
    eng = SolveEngine(lanes=1)
    with pytest.raises(KeyError):
        eng.submit(JobSpec("no_such_objective", 10, CFG))


def test_service_dict_roundtrip():
    svc = SolveService(lanes=2)
    reply = svc.submit({"objective": "griewank", "n": 64,
                        "config": {"samples_per_pass": 12, "n_passes": 3},
                        "seed": 0, "tag": "t"})
    jid = reply["job_id"]
    assert svc.result(jid)["error"] == "not done"
    svc.drain()
    out = svc.result(jid)
    assert out["status"] == DONE and len(out["x"]) == 64
    assert abs(out["fun"] - _solo_fun(JobSpec("griewank", 64, CFG, seed=0))) \
        < 1e-5
    assert svc.poll("nope")["error"] == "unknown job"
    assert svc.stats()["jobs"] == {DONE: 1}


def test_kill_resume_determinism(tmp_path):
    """Killing the engine mid-solve and resuming from the checkpoint
    reproduces an uninterrupted run's final objectives exactly. The
    reference engine runs with full generation fusion while the
    interrupted one steps pass-by-pass — so this also proves fused and
    unfused stepping are bit-identical."""
    specs = _mixed_specs(7, seed0=40)

    ref = SolveEngine(lanes=2)
    ref_ids = ref.submit_many(specs)
    ref.run()

    eng = SolveEngine(lanes=2, checkpoint_dir=tmp_path, ckpt_every=1,
                      max_fuse=1)
    ids = eng.submit_many(specs)
    for _ in range(4):                 # some jobs done, some mid-solve
        eng.step()
    del eng                            # "kill" — no further writes

    res = SolveEngine.resume(tmp_path)
    assert res.step_count == 4
    assert res.max_fuse == 1           # runtime knobs survive the kill
    assert res.active_lanes == 2       # mid-solve lanes came back
    res.run()
    for a, b in zip(ref_ids, ids):
        assert ref.result(a).fun == res.result(b).fun
        np.testing.assert_array_equal(ref.result(a).x, res.result(b).x)


def test_resume_empty_dir_gives_fresh_engine(tmp_path):
    eng = SolveEngine.resume(tmp_path)
    assert eng.step_count == 0 and not eng.pending()
    # engine knobs must reach the fresh-engine fallback, not be dropped
    eng = SolveEngine.resume(tmp_path, lanes=2, retain_done=5)
    assert eng.lanes == 2 and eng.retain_done == 5


# ---- PR 2 regression sweep -------------------------------------------------
def test_stats_queued_ignores_stale_cancelled_ids():
    """Cancelled-while-queued jobs must not surface as phantom queued work
    — neither live (cancel purges the deque) nor after a resume restores a
    stale queue that still carries them."""
    eng = SolveEngine(lanes=1, max_fuse=1)
    svc = SolveService(eng)
    ids = eng.submit_many(_mixed_specs(3))
    eng.step()                           # ids[0] running
    assert eng.cancel(ids[1])
    assert ids[1] not in eng.queue       # purged immediately
    assert svc.stats()["queued"] == 1
    # a queue restored from an old checkpoint can still hold stale ids:
    # counting must skip them even without the purge
    eng.queue.append(ids[1])
    assert svc.stats()["queued"] == 1
    eng.run()
    assert svc.stats()["queued"] == 0
    assert eng.poll(ids[2])["status"] == DONE


def test_seeds_beyond_int32_run_and_match_solo():
    """Seeds >= 2**31 used to raise OverflowError in _refill's int32 lane
    array; abo_minimize accepts them (PRNGKey folds to 32 bits), so the
    engine must too — with identical bits."""
    spec = JobSpec("rastrigin", 64, CFG, seed=2 ** 31 + 5)
    eng = SolveEngine(lanes=1)
    jid = eng.submit(spec)
    eng.run()
    r = eng.result(jid)
    solo = _solo_fun(spec)
    assert r.fun == solo or abs(r.fun - solo) < 1e-6


def test_negative_seed_matches_solo():
    # PRNGKey folds negative seeds; the engine's fold must mirror it
    spec = JobSpec("rastrigin", 64, CFG, seed=-3)
    eng = SolveEngine(lanes=1)
    jid = eng.submit(spec)
    eng.run()
    assert eng.result(jid).fun == _solo_fun(spec)


def test_result_mark_fetched_flag():
    """A wire front-end defers the fetched mark until its reply actually
    went out; only then do snapshots drop the solution vector."""
    svc = SolveService(lanes=1)
    jid = svc.submit({"objective": "sphere", "n": 8,
                      "config": {"samples_per_pass": 12, "n_passes": 2}}
                     )["job_id"]
    svc.drain()
    rec = svc.engine.jobs[jid]
    assert "x" in svc.result(jid, mark_fetched=False)
    assert not rec.fetched               # reply not confirmed yet
    svc.mark_fetched(jid)
    assert rec.fetched
    assert "x" in svc.result(jid)        # still in memory, only snapshots
    #                                      stop carrying it


def test_solve_server_rejects_malformed_n():
    from repro.launch import solve_server
    for bad in (",", "400x", ""):
        with pytest.raises(SystemExit):
            solve_server.main(["--n", bad])


def test_bad_seeds_rejected_at_submit():
    with pytest.raises(ValueError):
        JobSpec("sphere", 8, CFG, seed=2 ** 63)      # PRNGKey would raise
    with pytest.raises(ValueError):
        JobSpec("sphere", 8, CFG, seed="not-an-int")
    with pytest.raises(ValueError):
        JobSpec("sphere", 8, CFG, seed=True)


def test_snapshot_evicts_fetched_solution(tmp_path):
    """Once a result has been delivered, later snapshots stop carrying its
    solution vector (bounded aux growth); unfetched results keep theirs."""
    eng = SolveEngine(lanes=2, checkpoint_dir=tmp_path)
    ids = eng.submit_many(_mixed_specs(2))
    eng.run()
    eng.result(ids[0])                   # fetch -> evict from snapshots
    eng.snapshot()
    aux = eng.ckpt.aux(eng.ckpt.latest_step())
    assert "x" not in aux["jobs"][ids[0]] and aux["jobs"][ids[0]]["fetched"]
    assert "x" in aux["jobs"][ids[1]]

    res = SolveEngine.resume(tmp_path)
    assert res.jobs[ids[0]].x is None and res.jobs[ids[0]].fetched
    assert res.jobs[ids[1]].x is not None
    # fun/history survive eviction; only the vector is gone
    assert res.result(ids[0]).fun == eng.jobs[ids[0]].fun
    assert res.result(ids[0]).x is None
    np.testing.assert_array_equal(res.result(ids[1]).x, eng.jobs[ids[1]].x)
    svc = SolveService(res)
    out = svc.result(ids[0])
    assert out["status"] == DONE and "x" not in out


def test_retain_done_evicts_whole_records():
    """With a retention window, delivered (fetched DONE) and cancelled
    records past the N most recent are evicted outright; queued, running,
    and undelivered DONE jobs are never touched."""
    eng = SolveEngine(lanes=2, retain_done=2)
    ids = eng.submit_many(_mixed_specs(6, seed0=300))
    eng.run()
    for jid in ids[:4]:                  # deliver 4 of 6 results
        eng.result(jid)
    eng.step()                           # GC runs at step boundaries
    assert ids[0] not in eng.jobs and ids[1] not in eng.jobs
    assert ids[2] in eng.jobs and ids[3] in eng.jobs   # newest 2 delivered
    assert ids[4] in eng.jobs and ids[5] in eng.jobs   # undelivered: kept
    svc = SolveService(eng)
    assert svc.poll(ids[0])["error"] == "unknown job"
    assert svc.result(ids[4])["status"] == DONE        # still fetchable


def test_retain_done_bounds_snapshot_aux(tmp_path):
    """A churny fetch-everything workload must not grow the snapshot job
    table: with retain_done, aux size plateaus instead of accumulating
    every record ever finished."""
    import json

    eng = SolveEngine(lanes=2, retain_done=3, checkpoint_dir=tmp_path)
    sizes = []
    for round_ in range(4):
        ids = eng.submit_many(_mixed_specs(4, seed0=500 + 10 * round_))
        eng.run()
        for jid in ids:
            eng.result(jid)
        eng.step()                       # fold GC into a snapshot
        aux = eng.ckpt.aux(eng.ckpt.latest_step())
        sizes.append(len(json.dumps(aux)))
        assert len(aux["jobs"]) <= 3 + eng.lanes
    assert len(eng.jobs) <= 3
    # plateau: later rounds add jobs but not snapshot bytes (id strings
    # grow by a char at most — allow 1% drift, not another round's worth)
    assert sizes[-1] <= sizes[1] * 1.01


def test_retain_done_zero_evicts_at_delivery_and_cancel():
    """retain_done=0 means "forget a record the moment its client is done
    with it": eviction fires inside result()/cancel() themselves — a
    drained engine never steps again, so waiting for the next step would
    keep the records forever."""
    eng = SolveEngine(lanes=2, retain_done=0)
    ids = eng.submit_many(_mixed_specs(3, seed0=900))
    assert eng.cancel(ids[2])            # cancelled while queued
    assert ids[2] not in eng.jobs        # gone immediately, no step needed
    eng.run()
    assert ids[0] in eng.jobs            # undelivered results are safe
    r = eng.result(ids[0])
    assert r.fun is not None
    assert ids[0] not in eng.jobs        # evicted the moment it delivered
    svc = SolveService(eng)
    out = svc.result(ids[1])             # the service fetch path too
    assert out["status"] == DONE
    assert ids[1] not in eng.jobs
    assert svc.poll(ids[0])["error"] == "unknown job"


def test_retain_done_zero_cancel_via_service():
    # the service reply must survive the record being evicted inside the
    # cancel call itself
    svc = SolveService(lanes=1, retain_done=0)
    jid = svc.submit({"objective": "sphere", "n": 8,
                      "config": {"samples_per_pass": 12, "n_passes": 2}}
                     )["job_id"]
    out = svc.cancel(jid)
    assert out["cancelled"] and out["status"] == CANCELLED
    assert jid not in svc.engine.jobs


def test_retain_done_tolerates_legacy_records_without_done_seq():
    """Records restored from pre-done_seq snapshots carry done_seq=None;
    two of them in the evictable set used to TypeError the retention
    sort. They count as oldest (unknowable finish order) and evict
    first."""
    eng = SolveEngine(lanes=1, retain_done=0)
    from repro.engine.jobs import JobState
    for i in (1, 2):
        rec = JobState(job_id=f"job-x{i}", spec=JobSpec("sphere", 8, CFG),
                       status=CANCELLED)
        eng.jobs[rec.job_id] = rec
    eng._gc_jobs()
    assert not eng.jobs


def test_solve_server_rejects_negative_retain_done():
    from repro.launch import solve_server
    with pytest.raises(SystemExit):     # argparse error, not a traceback
        solve_server.main(["--retain-done", "-1"])
    with pytest.raises(SystemExit):     # same boundary for the new knobs
        solve_server.main(["--pool-high-water", "0.5"])
    with pytest.raises(SystemExit):     # journal needs a checkpoint dir
        solve_server.main(["--journal-every", "4"])


def test_solve_server_resume_requires_ckpt_dir():
    from repro.launch import solve_server
    with pytest.raises(SystemExit):
        solve_server.main(["--resume"])


def test_http_front_end_hardening():
    """GET handlers answer JSON for every outcome: 404 for unknown job
    ids and endpoints (not 200-with-error-field), 400 for malformed
    payloads — and never a raw traceback page."""
    import http.client
    import json
    import threading

    from repro.launch.solve_server import _build_server

    svc = SolveService(lanes=1)
    httpd, _stepper = _build_server(svc, 0)   # ephemeral port, no stepper:
    port = httpd.server_address[1]            # the test drains explicitly
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        def req(method, path, body=None):
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
            conn.request(method, path, body=body)
            resp = conn.getresponse()
            payload = json.loads(resp.read().decode())
            conn.close()
            return resp.status, payload

        status, out = req("POST", "/submit", json.dumps(
            {"objective": "sphere", "n": 64, "seed": 0,
             "config": {"samples_per_pass": 12, "n_passes": 3}}))
        assert status == 200
        jid = out["job_id"]
        # a /result before completion is the 202 not_done envelope
        status, out = req("GET", f"/result?job_id={jid}")
        assert status == 202 and out["code"] == "not_done"
        svc.drain()
        status, out = req("GET", f"/result?job_id={jid}")
        assert status == 200 and len(out["x"]) == 64

        assert req("GET", "/poll?job_id=nope") == \
            (404, {"job_id": "nope", "status": "unknown",
                   "error": "unknown job", "code": "unknown_job"})
        assert req("GET", "/result?job_id=nope")[0] == 404
        assert req("GET", "/poll")[0] == 404                  # missing id
        assert req("GET", "/nosuch")[0] == 404
        assert req("GET", "/stats")[0] == 200
        assert req("POST", "/cancel", json.dumps({"job_id": "nope"}))[0] \
            == 404
        assert req("POST", "/submit", "{not json")[0] == 400
        status, out = req("POST", "/submit", json.dumps(
            {"objective": "sphere", "n": 64, "seed": 2 ** 63}))
        assert status == 400 and "seed" in out["error"]
    finally:
        httpd.shutdown()
        httpd.server_close()
