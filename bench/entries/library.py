"""Entry ``library``: one user calling the engine as a library.

``SolveEngine.submit`` -> ``run`` -> ``result``, one job after another
(a closed loop of ``clients: 1``). The result comes back as the
library returns it; nothing goes over HTTP. The traced run traces the
first ``trace.jobs`` jobs of the window, whole. Of the answers, only
those of the jobs the check samples (``traffic.checked``, drawn before
the window) and of the first job, the sample of a run too short for
them, are kept.
"""
from __future__ import annotations

import time

import numpy as np

import traffic as gen


def run(r) -> dict:
    from repro.core.abo import ABOConfig
    from repro.engine import JobSpec, SolveEngine

    tr = r.traffic
    if tr["loop"] != "closed" or tr["clients"] != 1:
        raise ValueError("the library entry drives one closed-loop client")
    eng = SolveEngine(**r.config["engine"])

    def solve(job: dict):
        spec = JobSpec(job["objective"], int(job["n"]), ABOConfig(
            samples_per_pass=int(job["samples_per_pass"]),
            n_passes=int(job["n_passes"]),
            block_size=int(job["block_size"])), seed=int(job["seed"]))
        with r.span("bench.solve"):
            jid = eng.submit(spec)
            eng.run()
            return jid, eng.result(jid)

    # set-up: the same shapes as the window, from a seed of their own
    warm = gen.ClosedStream(tr, r.config["job"], tr["warmup"]["seed"])
    for _ in range(tr["warmup"]["jobs"]):
        solve(warm.next())

    stream = gen.ClosedStream(tr, r.config["job"], r.seed)
    sampled = set(gen.checked(tr, r.seed))
    if r.trace:
        eng.trace()
    jobs, answers = [], {}
    c0 = (eng.swept_slots, eng.swept_slots_live, eng.step_count)
    with r.window() as (t0, _):
        end = t0 + r.seconds
        while not jobs or time.perf_counter() < end:
            job = stream.next()
            traced = r.trace and job["index"] < tr["trace"]["jobs"]
            if traced:
                r.trace_begin()
            job["t_due"] = job["t_sent"] = time.time()
            jid, res = solve(job)
            job["t_recv"] = time.time()
            if traced and job["index"] + 1 == tr["trace"]["jobs"]:
                r.trace_end()
            rec = eng.jobs[jid]
            job.update(delivered=True, traced=traced, status=rec.status,
                       t_submit=rec.t_submit, t_place=rec.t_place,
                       t_done=rec.t_done)
            jobs.append(job)
            if job["index"] in sampled or job["index"] == 0:
                answers[job["index"]] = {"fun": float(res.fun),
                                         "history": np.asarray(res.history),
                                         "x": np.asarray(res.x)}
    r.trace_end()
    counters = {"swept_slots": eng.swept_slots - c0[0],
                "swept_slots_live": eng.swept_slots_live - c0[1],
                "steps": eng.step_count - c0[2]}
    spans = _spans(eng.tracer)
    sample = ([(j, answers[j["index"]]) for j in jobs
               if j["index"] in sampled] or [(jobs[0], answers[0])])
    del eng, answers
    return {"jobs": jobs, "sample": sample, "attempted": len(jobs),
            "failed": 0, "counters": counters, "spans": spans}


def _spans(tracer) -> list[dict]:
    """The engine's own spans, on the perf_counter clock (seconds)."""
    base = tracer.t0_ns / 1e9
    return [{"name": e["name"], "start": base + e["ts"] / 1e6,
             "dur": e["dur"] / 1e6} for e in tracer.events]
