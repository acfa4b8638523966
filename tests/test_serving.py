"""Serving tier: hardened front door, limits, envelope, shutdown.

Covers the repro.serve stack — token buckets / tenants / schema
validation as pure units, then the Frontend over a live engine:
standardized error envelope across every status class (202/400/401/
404/409/411/413/429/503), body caps, lock-free /healthz + /metrics
while the engine lock is held, condvar wake-on-submit (no poll_s
latency cliff), long-poll delivery, http_reply / slow_client chaos,
and SIGTERM with an in-flight request (subprocess: reply completes,
final snapshot lands, resume is bit-exact).
"""
import http.client
import json
import os
import pathlib
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.core import ABOConfig, abo_minimize
from repro.engine import SolveService
from repro.objectives import OBJECTIVES
from repro.serve.errors import ApiError, CODE_STATUS, status_for
from repro.serve.limits import TenantTable, TokenBucket
from repro.serve.validate import validate_cancel, validate_submit

REPO = pathlib.Path(__file__).resolve().parent.parent
CFG = {"samples_per_pass": 12, "n_passes": 3}


# ------------------------------------------------------------ limits units
def test_token_bucket_burst_then_rate():
    clock = [0.0]
    b = TokenBucket(rate=2.0, burst=3, clock=lambda: clock[0])
    assert [b.take() for _ in range(3)] == [0.0, 0.0, 0.0]  # burst free
    wait = b.take()
    assert wait > 0                       # empty: wait for the refill
    clock[0] += wait
    assert b.take() == 0.0                # exactly one token landed
    clock[0] += 100.0
    assert [b.take() for _ in range(3)] == [0.0, 0.0, 0.0]  # re-capped
    assert b.take() > 0                   # burst cap held at 3


def test_token_bucket_disabled_and_validation():
    assert TokenBucket(rate=0).take() == 0.0
    assert TokenBucket(rate=None).take() == 0.0
    with pytest.raises(ValueError):
        TokenBucket(rate=-1)
    with pytest.raises(ValueError):
        TokenBucket(rate=1, burst=0)


def test_tenant_table_spec_and_auth():
    tt = TenantTable.from_spec(
        "s3cret:name=alice:rate=5:burst=10:quota=100;guest:rate=0.5")
    assert len(tt) == 2
    alice = tt.authenticate("Bearer s3cret")
    assert alice.name == "alice" and alice.quota_jobs == 100
    assert tt.authenticate("Bearer guest").name == "tenant-1"
    for bad in (None, "", "Bearer nope", "Basic s3cret", "s3cret"):
        with pytest.raises(ApiError) as ei:
            tt.authenticate(bad)
        assert ei.value.http_status == 401
        assert ei.value.code == "unauthorized"


def test_tenant_table_spec_errors():
    for bad in ("", ";;", "tok:rate", "tok:zzz=1",
                "tok:name=a;tok:name=b",          # duplicate token
                "a:name=x;b:name=x"):             # duplicate name
        with pytest.raises(ValueError):
            TenantTable.from_spec(bad)


def test_tenant_rate_and_quota():
    clock = [0.0]
    tt = TenantTable.from_spec("tok:name=t:rate=1:burst=1:quota=2",
                               clock=lambda: clock[0])
    t = tt.authenticate("Bearer tok")
    tt.check_rate(t, now=0.0)
    with pytest.raises(ApiError) as ei:
        tt.check_rate(t, now=0.0)
    assert ei.value.http_status == 429 and ei.value.code == "rate_limited"
    assert ei.value.retry_after and ei.value.retry_after > 0
    tt.check_quota(t)
    tt.charge_job(t)
    tt.check_quota(t)
    tt.charge_job(t)
    with pytest.raises(ApiError) as ei:
        tt.check_quota(t)                 # quota spent BEFORE the engine
    assert ei.value.code == "quota_exceeded"


# -------------------------------------------------------- validation units
def test_validate_submit_shapes():
    ok = {"objective": "sphere", "n": 64, "seed": 3,
          "config": {"samples_per_pass": 5}, "x0": [0.0] * 64,
          "tag": "t", "ttl_s": 9.5}
    assert validate_submit(ok) is ok
    cases = [
        ([1, 2], "JSON object"),
        ({"n": 4}, "objective"),
        ({"objective": 7, "n": 4}, "objective"),
        ({"objective": "sphere"}, "'n'"),
        ({"objective": "sphere", "n": True}, "integer"),
        ({"objective": "sphere", "n": 0}, ">= 1"),
        ({"objective": "sphere", "n": 4, "zzz": 1}, "unknown field"),
        ({"objective": "sphere", "n": 4, "seed": 1.5}, "integer"),
        ({"objective": "sphere", "n": 4, "tag": 9}, "string"),
        ({"objective": "sphere", "n": 4, "ttl_s": 0}, "> 0"),
        ({"objective": "sphere", "n": 4, "x0": "abc"}, "list"),
        ({"objective": "sphere", "n": 4, "x0": [0.0] * 3}, "3 entries"),
        ({"objective": "sphere", "n": 4, "x0": [0.0] * 3 + [None]},
         "number"),
        ({"objective": "sphere", "n": 4, "config": 5}, "object"),
        ({"objective": "sphere", "n": 4, "config": {"zz": 1}},
         "unknown key"),
        ({"objective": "sphere", "n": 4,
          "config": {"samples_per_pass": [5]}}, "scalar"),
    ]
    for req, needle in cases:
        with pytest.raises(ApiError) as ei:
            validate_submit(req)
        assert ei.value.http_status == 400, req
        assert needle in ei.value.message, (req, ei.value.message)
    with pytest.raises(ApiError) as ei:
        validate_submit({"objective": "sphere", "n": 10_000}, max_n=500)
    assert "limit of 500" in ei.value.message


def test_validate_cancel():
    assert validate_cancel({"job_id": "job-7"}) == "job-7"
    for bad in ("nope", {}, {"job_id": ""}, {"job_id": 7}):
        with pytest.raises(ApiError):
            validate_cancel(bad)


def test_status_for_mapping():
    assert status_for({"code": "unknown_job"}) == 404
    assert status_for({"code": "not_done"}) == 202
    assert status_for({"code": "conflict"}) == 409
    assert status_for({"job_id": "x"}) == 200
    assert status_for("not-a-dict") == 200
    assert set(CODE_STATUS.values()) == {404, 202, 409}


# -------------------------------------------------- in-process front door
def _start(svc, cfg=None):
    from repro.serve.frontend import Frontend, FrontendConfig
    fe = Frontend(svc, 0, cfg or FrontendConfig(poll_s=0.005))
    threading.Thread(target=fe.httpd.serve_forever, daemon=True).start()
    return fe


def _stop(fe):
    fe.httpd.shutdown()
    fe._stop_stepper.set()
    with fe._wake:
        fe._wake.notify_all()
    fe.httpd.server_close()


def _req(port, method, path, body=None, headers=None, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path, body=body, headers=headers or {})
        resp = conn.getresponse()
        raw = resp.read()
        hdrs = dict(resp.getheaders())
        try:
            payload = json.loads(raw)
        except json.JSONDecodeError:
            payload = raw.decode()
        return resp.status, payload, hdrs
    finally:
        conn.close()


def _submit_body(seed=0, n=64, objective="sphere"):
    return json.dumps({"objective": objective, "n": n, "seed": seed,
                       "config": CFG})


def test_error_envelope_every_status_class():
    """One decoder suffices: every non-200 is {error, code, ...} with
    the documented code <-> HTTP status pairing (the satellite's
    400/404/409/413/429/503 sweep, plus 202/401/411)."""
    svc = SolveService(lanes=1, max_queue=2)
    from repro.serve.frontend import FrontendConfig
    fe = _start(svc, FrontendConfig(max_body_bytes=512,
                                    tenants=TenantTable.from_spec(
                                        "tok:name=t:quota=1")))
    port = fe.httpd.server_address[1]
    auth = {"Authorization": "Bearer tok"}
    try:
        seen = {}

        def expect(status, code, method, path, body=None, headers=auth):
            got, payload, hdrs = _req(port, method, path, body, headers)
            assert got == status, (path, got, payload)
            assert payload["code"] == code, (path, payload)
            assert isinstance(payload["error"], str) and payload["error"]
            seen[status] = payload
            return payload, hdrs

        expect(400, "bad_json", "POST", "/submit", "{not json")
        expect(400, "bad_request", "POST", "/submit",
               json.dumps({"objective": "sphere"}))
        expect(401, "unauthorized", "POST", "/submit", _submit_body(),
               headers={})
        # unknown-job payloads carry a status field alongside the code
        p, _ = expect(404, "unknown_job", "GET", "/poll?job_id=nope")
        assert p["status"] == "unknown" and p["job_id"] == "nope"
        expect(404, "unknown_endpoint", "GET", "/nosuch")
        expect(413, "body_too_large", "POST", "/submit",
               json.dumps({"objective": "x" * 600, "n": 4}))

        st, sub, _ = _req(port, "POST", "/submit", _submit_body(),
                          auth)
        assert st == 200
        jid = sub["job_id"]
        p, _ = expect(202, "not_done", "GET", f"/result?job_id={jid}")
        assert p["status"] == "queued" and p["job_id"] == jid

        # tenant quota spent (1 accepted job) -> 429 before the engine
        expect(429, "quota_exceeded", "POST", "/submit",
               _submit_body(1))
        # engine queue full -> 429 with Retry-After
        tt2 = TenantTable.from_spec("tok:name=t")
        fe.cfg.tenants = tt2
        st2, _, _ = _req(port, "POST", "/submit", _submit_body(2), auth)
        assert st2 == 200                  # fills max_queue=2
        p, hdrs = expect(429, "queue_full", "POST", "/submit",
                         _submit_body(3))
        assert int(hdrs["Retry-After"]) >= 1

        st, _, _ = _req(port, "POST", "/cancel",
                        json.dumps({"job_id": jid}), auth)
        assert st == 200
        p, _ = expect(409, "conflict", "GET", f"/result?job_id={jid}")
        assert p["status"] == "cancelled"

        fe._stopping = True                # shutdown shed, no teardown
        p, hdrs = expect(503, "shutting_down", "POST", "/submit",
                         _submit_body(4))
        assert "Retry-After" in hdrs
        fe._stopping = False
        assert set(seen) == {202, 400, 401, 404, 409, 413, 429, 503}
    finally:
        _stop(fe)


def test_memory_budget_maps_to_503_with_retry_after():
    svc = SolveService(lanes=1, memory_budget_bytes=1)
    fe = _start(svc)
    port = fe.httpd.server_address[1]
    try:
        st, payload, hdrs = _req(port, "POST", "/submit", _submit_body())
        assert st == 503 and payload["code"] == "memory_budget"
        assert int(hdrs["Retry-After"]) >= 1
    finally:
        _stop(fe)


def test_body_caps_raw_socket():
    """411 on missing Content-Length, 400 on malformed/negative — via a
    raw socket (http.client always sets the header)."""
    svc = SolveService(lanes=1)
    fe = _start(svc)
    port = fe.httpd.server_address[1]

    def raw(headers):
        with socket.create_connection(("127.0.0.1", port),
                                      timeout=10) as s:
            s.sendall((f"POST /submit HTTP/1.1\r\n"
                       f"Host: x\r\n{headers}\r\n").encode())
            s.settimeout(10)
            chunks = []
            while chunk := s.recv(65536):   # server closes -> EOF
                chunks.append(chunk)
            data = b"".join(chunks).decode()
        status = int(data.split(" ", 2)[1])
        body = json.loads(data.rsplit("\r\n\r\n", 1)[1])
        return status, body, data

    try:
        st, body, head = raw("")                      # no Content-Length
        assert st == 411 and body["code"] == "length_required"
        assert "Connection: close" in head
        st, body, _ = raw("Content-Length: -5\r\n")
        assert st == 400 and body["code"] == "bad_length"
        st, body, _ = raw("Content-Length: zz\r\n")
        assert st == 400 and body["code"] == "bad_length"
    finally:
        _stop(fe)


def test_oversized_body_413_closes_connection():
    svc = SolveService(lanes=1)
    from repro.serve.frontend import FrontendConfig
    fe = _start(svc, FrontendConfig(max_body_bytes=100))
    port = fe.httpd.server_address[1]
    try:
        st, payload, hdrs = _req(port, "POST", "/submit", "x" * 200)
        assert st == 413 and payload["code"] == "body_too_large"
        assert hdrs.get("Connection") == "close"
    finally:
        _stop(fe)


def test_healthz_and_metrics_lock_free_while_engine_busy():
    """The liveness endpoints answer while the engine lock is HELD (a
    long fused step in real life) — the satellite's lock-free
    requirement, falsified by any handler that waits on the engine."""
    svc = SolveService(lanes=1)
    fe = _start(svc)
    port = fe.httpd.server_address[1]
    try:
        assert fe._engine_lock.acquire(timeout=5)
        try:
            t0 = time.perf_counter()
            st, payload, _ = _req(port, "GET", "/healthz", timeout=5)
            assert st == 200 and payload["status"] == "ok"
            st, text, _ = _req(port, "GET", "/metrics", timeout=5)
            assert st == 200 and "engine_steps_total" in text
            # registry renders even when gauges can't refresh
            assert "serve_request_seconds" in text
            assert time.perf_counter() - t0 < 3.0
            # engine-touching endpoints DO shed on the deadline instead
            # of hanging: a short-deadline probe answers 503
            fe.cfg.deadline_s, saved = 0.2, fe.cfg.deadline_s
            st, payload, hdrs = _req(port, "GET", "/stats", timeout=10)
            assert st == 503 and payload["code"] == "deadline"
            assert "Retry-After" in hdrs
            fe.cfg.deadline_s = saved
        finally:
            fe._engine_lock.release()
    finally:
        _stop(fe)


def test_saturation_sheds_503():
    svc = SolveService(lanes=1)
    from repro.serve.frontend import FrontendConfig
    fe = _start(svc, FrontendConfig(max_inflight=1, deadline_s=5.0))
    port = fe.httpd.server_address[1]
    try:
        assert fe._engine_lock.acquire(timeout=5)
        try:
            # one request occupies the single slot (blocked on the lock)
            blocked = threading.Thread(
                target=_req, args=(port, "GET", "/stats"), daemon=True)
            blocked.start()
            deadline = time.monotonic() + 5
            while fe._inflight < 1 and time.monotonic() < deadline:
                time.sleep(0.01)
            st, payload, hdrs = _req(port, "GET", "/stats", timeout=10)
            assert st == 503 and payload["code"] == "saturated"
            assert "Retry-After" in hdrs
        finally:
            fe._engine_lock.release()
        blocked.join(timeout=10)
    finally:
        _stop(fe)


def test_condvar_stepper_wakes_on_submit():
    """With poll_s=5 a busy-wait stepper would add ~5s of latency; the
    condvar stepper must finish a submitted job far faster."""
    svc = SolveService(lanes=1)
    from repro.serve.frontend import FrontendConfig
    fe = _start(svc, FrontendConfig(poll_s=5.0, idle_max_s=5.0))
    fe.stepper_thread.start()
    port = fe.httpd.server_address[1]
    try:
        # warm-up solve: pay the jit compile OUTSIDE the timed window
        st, sub, _ = _req(port, "POST", "/submit", _submit_body(7))
        st, out, _ = _req(port, "GET",
                          f"/result?job_id={sub['job_id']}&wait=30")
        assert st == 200 and out["status"] == "done"
        # let the stepper park on the condvar (worst case for wake-up)
        time.sleep(0.3)
        t0 = time.perf_counter()
        st, sub, _ = _req(port, "POST", "/submit", _submit_body())
        assert st == 200
        st, out, _ = _req(port, "GET",
                          f"/result?job_id={sub['job_id']}&wait=10")
        dt = time.perf_counter() - t0
        assert st == 200 and out["status"] == "done"
        assert dt < 3.0, f"stepper slept through the submit ({dt:.1f}s)"
        snap = svc.engine.metrics.snapshot()
        assert snap.get("serve_stepper_wakeups_total", 0) >= 1
    finally:
        _stop(fe)


def test_long_poll_result_delivers_and_times_out():
    svc = SolveService(lanes=1)
    fe = _start(svc)
    fe.stepper_thread.start()
    port = fe.httpd.server_address[1]
    try:
        st, sub, _ = _req(port, "POST", "/submit", _submit_body())
        st, out, _ = _req(port, "GET",
                          f"/result?job_id={sub['job_id']}&wait=30")
        assert st == 200 and out["status"] == "done"
        assert len(out["x"]) == 64
        ref = abo_minimize(OBJECTIVES["sphere"], 64,
                           config=ABOConfig(**CFG), seed=0)
        assert out["fun"] == float(ref.fun)
        assert np.asarray(out["x"], np.float64).tobytes() == \
            np.asarray(ref.x, np.float64).tobytes()
        # a wait on a job that cannot finish times out as 202 not_done
        fe._stop_stepper.set()
        with fe._wake:
            fe._wake.notify_all()
        fe.stepper_thread.join(timeout=10)
        st2, sub2, _ = _req(port, "POST", "/submit", _submit_body(9))
        t0 = time.perf_counter()
        st, out, _ = _req(port, "GET",
                          f"/result?job_id={sub2['job_id']}&wait=0.4")
        assert st == 202 and out["code"] == "not_done"
        assert 0.3 < time.perf_counter() - t0 < 5.0
        # malformed wait is a schema'd 400
        st, out, _ = _req(port, "GET",
                          f"/result?job_id={sub2['job_id']}&wait=zz")
        assert st == 400 and out["code"] == "bad_request"
    finally:
        _stop(fe)


def test_http_reply_fault_tears_reply_without_losing_result():
    """An injected torn reply (connection dropped before any byte) must
    not mark the result fetched — the retry succeeds and the solution
    is intact. This is the delivery-after-write contract under chaos."""
    svc = SolveService(lanes=1, faults="http_reply:nth=2")
    fe = _start(svc)
    port = fe.httpd.server_address[1]
    try:
        st, sub, _ = _req(port, "POST", "/submit", _submit_body())  # hit 1
        assert st == 200
        svc.drain()
        jid = sub["job_id"]
        with pytest.raises((http.client.BadStatusLine,
                            http.client.RemoteDisconnected,
                            ConnectionResetError)):
            _req(port, "GET", f"/result?job_id={jid}")   # hit 2: torn
        # the record still holds x: the torn reply was not a delivery
        st, out, _ = _req(port, "GET", f"/result?job_id={jid}")
        assert st == 200 and len(out["x"]) == 64
        snap = svc.engine.metrics.snapshot()
        assert snap['engine_faults_injected_total{site="http_reply"}'] \
            == 1
    finally:
        _stop(fe)


def test_slow_client_fault_does_not_stall_others():
    """A delayed body read sleeps in its own connection thread; the
    liveness endpoints answer meanwhile."""
    svc = SolveService(lanes=1, faults="slow_client:nth=1:delay_s=1.0")
    fe = _start(svc)
    port = fe.httpd.server_address[1]
    try:
        t0 = time.perf_counter()
        slow = threading.Thread(
            target=_req, args=(port, "POST", "/submit", _submit_body()),
            daemon=True)
        slow.start()
        time.sleep(0.1)                   # let the slow POST hit the nap
        st, payload, _ = _req(port, "GET", "/healthz", timeout=5)
        dt = time.perf_counter() - t0
        assert st == 200 and dt < 0.9, \
            f"healthz waited on the slow client ({dt:.2f}s)"
        slow.join(timeout=10)
        assert time.perf_counter() - t0 >= 1.0   # the nap really ran
    finally:
        _stop(fe)


def test_submit_rejects_unknown_objective_as_400():
    svc = SolveService(lanes=1)
    fe = _start(svc)
    port = fe.httpd.server_address[1]
    try:
        st, out, _ = _req(port, "POST", "/submit",
                          _submit_body(objective="nope"))
        assert st == 400 and out["code"] == "bad_request"
        assert "nope" in out["error"]
    finally:
        _stop(fe)


def test_dead_stepper_fails_healthz_and_requests():
    """A step that raises kills the stepper: /healthz answers 503
    engine_failed, and so does every engine request — a live listener
    never fronts a dead engine that would ack work and never run it."""
    from repro.engine.faults import parse_fault_spec
    svc = SolveService(lanes=1, faults=parse_fault_spec("fused_step:nth=1"))
    fe = _start(svc)
    port = fe.httpd.server_address[1]
    fe.stepper_thread.start()
    try:
        st, sub, _ = _req(port, "POST", "/submit", _submit_body())
        assert st == 200
        fe.stepper_thread.join(timeout=60)
        assert not fe.stepper_thread.is_alive()
        st, out, _ = _req(port, "GET", "/healthz")
        assert st == 503 and out["status"] == "engine_failed", out
        assert "fused_step" in out["error"]
        st, out, _ = _req(port, "GET",
                          f"/result?job_id={sub['job_id']}&wait=5")
        assert st == 503 and out["code"] == "engine_failed", out
        st, out, _ = _req(port, "POST", "/submit", _submit_body(seed=1))
        assert st == 503 and out["code"] == "engine_failed", out
    finally:
        _stop(fe)


def test_dead_stepper_http_process_exits_nonzero(tmp_path):
    """``solve_server --http``: an engine failure ends the process with a
    non-zero status (a supervisor sees a crash, not a healthy zombie)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env.setdefault("JAX_PLATFORMS", "cpu")
    port_file = tmp_path / "port"
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.solve_server",
         "--http", "0", "--port-file", str(port_file), "--lanes", "1",
         "--inject", "fused_step:nth=1"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    try:
        deadline = time.monotonic() + 120
        while not port_file.exists() and time.monotonic() < deadline:
            assert proc.poll() is None, proc.communicate()[1][-3000:]
            time.sleep(0.1)
        st, _, _ = _req(int(port_file.read_text()), "POST", "/submit",
                        _submit_body())
        assert st == 200
        out, err = proc.communicate(timeout=120)
        assert proc.returncode not in (0, None), (out, err[-3000:])
        assert "engine failed" in out
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()


# ---------------------------------------------------------- shutdown path
def test_sigterm_with_inflight_request_then_bitexact_resume(tmp_path):
    """SIGTERM while a long-poll /result is parked: the reply completes
    (result or a clean 503 shutting_down), the final snapshot lands,
    the process exits 0, and a resume re-derives the job bit-exactly."""
    ck = str(tmp_path / "ck")
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env.setdefault("JAX_PLATFORMS", "cpu")
    port_file = tmp_path / "port"
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.solve_server",
         "--http", "0", "--port-file", str(port_file),
         "--ckpt-dir", ck, "--journal-every", "4", "--lanes", "2"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    try:
        deadline = time.monotonic() + 120
        while not port_file.exists() and time.monotonic() < deadline:
            assert proc.poll() is None, proc.communicate()[1][-3000:]
            time.sleep(0.1)
        port = int(port_file.read_text())

        st, sub, _ = _req(port, "POST", "/submit", _submit_body())
        assert st == 200
        jid = sub["job_id"]

        inflight: dict = {}

        def long_poll():
            # /poll, not /result: the reply must never mark the job
            # fetched, or the final snapshot legitimately drops x and
            # the bit-exactness check below has nothing to compare
            try:
                inflight["reply"] = _req(
                    port, "GET", f"/poll?job_id={jid}&wait=30",
                    timeout=60)
            except Exception as e:        # noqa: BLE001 — recorded
                inflight["error"] = e

        t = threading.Thread(target=long_poll, daemon=True)
        t.start()
        time.sleep(1.0)                   # the poll is parked in-flight
        proc.send_signal(signal.SIGTERM)
        t.join(timeout=90)
        out, err = proc.communicate(timeout=120)
        assert proc.returncode == 0, err[-3000:]
        assert "final snapshot cut" in out

        # the in-flight request got a real HTTP answer, not a dropped
        # connection: the result, or the enveloped shutdown 503
        assert "reply" in inflight, inflight.get("error")
        st, payload, _ = inflight["reply"]
        assert st in (200, 503), payload
        if st == 503:
            assert payload["code"] == "shutting_down"

        from repro.checkpoint.fsck import fsck
        assert fsck(ck)["ok"]
        from repro.engine import SolveEngine
        eng = SolveEngine.resume(ck)
        eng.run()
        rec = eng.jobs[jid]
        ref = abo_minimize(OBJECTIVES["sphere"], 64,
                           config=ABOConfig(**CFG), seed=0)
        assert rec.fun == float(ref.fun)
        assert np.asarray(rec.x, np.float64).tobytes() == \
            np.asarray(ref.x, np.float64).tobytes()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
