#!/usr/bin/env python3
"""From a profiler trace to the engine's own numbers: the fused step's
time per block row, the read-back, and the device's idle time split by
what the engine's host code was doing.

The engine's tracer, enabled by ``SolveEngine.trace()``, opens a
``jax.profiler.TraceAnnotation`` named ``engine.<span>`` around each of
its spans, with the span's args as stats, so the spans sit on the
profiler's host plane on the device trace's own clock. The engine's
executables are named for what they do, so the device plane's module
line (``XLA Modules``) shows the fused step as ``jit_fused_step(<id>)``.

The reduction, over the window that the ``bench.traced`` span covers in
the same file:

- ``sweep_row_us``: the device time of the ``fused_step`` modules (each
  device's, averaged over the devices), in microseconds, over the block
  rows those steps swept: the sum of ``passes`` x ``swept_rows`` over the
  ``engine.fused_sweep`` spans that open in the window;
- ``readback_ms``: the summed length of the ``engine.readback`` spans
  (the copy of the finishers' outputs to the host, after
  ``engine.device_wait`` has waited for the device), with the bytes they
  read (``readback_bytes``) and the rate;
- the idle split: each stretch of the window in which device 0 runs no
  op goes to the innermost engine span that covers it (a span's self
  time, not the largest span's), and the stretches add up by that span:
  ``outside`` (no engine span), ``readback``, ``device_wait``, and
  ``sched_idle_ms``, all the others (refill, placement, plan build,
  dispatch, finalize, the harvest's own bookkeeping, the step between
  them). The four add up to the window's idle time on device 0.

Each number is None where the trace holds nothing for it: no window, no
device op, no engine span (a program whose tracer does not annotate),
no ``fused_step`` module (a program whose executables are all ``run``).

    python3 bench/enginetrace.py [TRACE_DIR]

prints the reduction of the newest trace under ``TRACE_DIR`` (by
default the harness's) as one JSON object.
"""
from __future__ import annotations

import glob
import json
import os
import pathlib
import re
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import devtrace  # noqa: E402
import harness  # noqa: E402

WINDOW = "bench.traced"
ENGINE = "engine."
MODULES_LINE = "XLA Modules"
FUSED_STEP = re.compile(r"^jit_fused_step(_sharded)?\(")
WAITS = ("readback", "device_wait")


def newest(trace_dir) -> str | None:
    """The newest ``.xplane.pb`` under a profiler output directory."""
    paths = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    return events_of(ProfileData.from_file(path))


def events_of(profile) -> dict:
    """``{"devices": {plane: {"ops": [...], "modules": [...]}},
    "host": [...]}``: device events ``(start_ns, end_ns, name)``; host
    events ``(start_ns, end_ns, name, stats)`` of the window span and
    the engine's spans."""
    devices, host = {}, []
    for plane in profile.planes:
        if devtrace.DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            devices[plane.name] = {
                key: [(e.start_ns, e.start_ns + e.duration_ns,
                       devtrace.op_name(e.name))
                      for e in lines[name].events] if name in lines else []
                for key, name in (("ops", devtrace.OPS_LINE),
                                  ("modules", MODULES_LINE))}
        elif plane.name.startswith("/host:"):
            host += [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                      dict(e.stats))
                     for ln in plane.lines for e in ln.events
                     if e.name == WINDOW or e.name.startswith(ENGINE)]
    return {"devices": devices, "host": host}


def segments(spans, lo: float, hi: float) -> list[tuple]:
    """[lo, hi] cut into ``(start, end, name)`` stretches, each named for
    the innermost span covering it (None where none does). ``spans`` are
    ``(start, end, name)`` of one thread, so they nest."""
    out, stack, t = [], [], lo

    def upto(x, name):
        nonlocal t
        if x > t:
            out.append((t, x, name))
            t = x

    for s, e, n in sorted(spans, key=lambda sp: (sp[0], -sp[1])):
        while stack and stack[-1][0] <= s:
            upto(*stack.pop())
        upto(s, stack[-1][1] if stack else None)
        stack.append((e, n))
    while stack:
        upto(*stack.pop())
    upto(hi, None)
    return out


def split(idle, segs) -> dict:
    """The idle stretches' nanoseconds by the name of the segment that
    holds them (both lists sorted and disjoint)."""
    out: dict = {}
    i = 0
    for a, b in idle:
        while i < len(segs) and segs[i][1] <= a:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < b:
            s, e, n = segs[j]
            ov = min(b, e) - max(a, s)
            if ov > 0:
                out[n] = out.get(n, 0.0) + ov
            j += 1
    return out


def reduce(events: dict, window_name: str = WINDOW) -> dict | None:
    """The numbers in the module docstring; None where the trace holds no
    window span or no device op."""
    wins = [h for h in events["host"] if h[2] == window_name]
    devs = [d for _, d in sorted(events["devices"].items()) if d["ops"]]
    if not wins or not devs:
        return None
    lo, hi = min(h[0] for h in wins), max(h[1] for h in wins)
    spans = [(max(s, lo), min(e, hi), n[len(ENGINE):], st)
             for s, e, n, st in events["host"]
             if n.startswith(ENGINE) and min(e, hi) > max(s, lo)]

    fused_ns = sum(min(e, hi) - max(s, lo) for d in devs
                   for s, e, n in d["modules"]
                   if FUSED_STEP.match(n) and min(e, hi) > max(s, lo)
                   ) / len(devs)
    rows = sum(int(st.get("passes", 0)) * int(st.get("swept_rows", 0))
               for s, _, n, st in events["host"]
               if n == ENGINE + "fused_sweep" and lo <= s < hi)
    reads = [(e - s, int(st.get("bytes", 0))) for s, e, n, st in spans
             if n == "readback"]
    read_ns = sum(t for t, _ in reads)
    read_bytes = sum(b for _, b in reads)

    idle = devtrace.gaps(devtrace.merge(devs[0]["ops"], lo, hi), lo, hi)
    by_span = split(idle, segments([sp[:3] for sp in spans], lo, hi))
    outside = by_span.pop(None, 0.0)
    waits = {w: by_span.get(w, 0.0) for w in WAITS}
    sched = sum(t for n, t in by_span.items() if n not in WAITS)
    ms = 1e-6
    return {
        "window_s": (hi - lo) / 1e9,
        "idle_ms": sum(b - a for a, b in idle) * ms,
        "fused_step_s": fused_ns / 1e9,
        "swept_rows": rows,
        "sweep_row_us": fused_ns / 1e3 / rows if fused_ns and rows else None,
        "readback_ms": read_ns * ms if reads else None,
        "readback_bytes": read_bytes,
        "readback_gb_s": read_bytes / read_ns if read_ns else None,
        "sched_idle_ms": sched * ms if spans else None,
        "idle_split_ms": {"outside": outside * ms,
                          **{w: t * ms for w, t in waits.items()},
                          "sched": sched * ms},
        "idle_by_span_ms": {n: t * ms for n, t in sorted(by_span.items())},
    }


def of(record: dict) -> dict | None:
    """The reduction of the run's trace (the newest under the harness's
    trace directory), parsed once per run: it is kept in the record, which
    every reader of the run is handed; None where the run took no device
    trace."""
    if record.get("device_trace") is None:
        return None
    if "engine_trace" not in record:
        path = newest(harness.TRACE_DIR)
        record["engine_trace"] = reduce(load(path)) if path else None
    return record["engine_trace"]


def read(record: dict, name: str):
    """One number of the reduction, for a metric's reader."""
    out = of(record)
    return None if out is None else out[name]


if __name__ == "__main__":
    path = newest(sys.argv[1] if len(sys.argv) > 1 else harness.TRACE_DIR)
    print(json.dumps(reduce(load(path)) if path else None))
