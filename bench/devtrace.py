"""From a profiler trace to the device's busy time, top ops and idle gaps.

``jax.profiler`` writes an XSpace (``*.xplane.pb``). Its device planes
(``/device:TPU:<i>``) carry a line of XLA ops, each event a (name,
start, duration) in nanoseconds; host planes carry
the host threads' events, the benchmark's own ``TraceAnnotation`` spans
(``bench.*``) among them, on the same clock.

The reduction, over the window that the ``bench.traced`` span covers:

- busy time: the union of the op intervals of each device, clipped to
  the window, averaged over the devices;
- top ops: each op name's summed time, over all devices, divided by the
  device count;
- idle gaps: the stretches of the window in which device 0 runs no op,
  longest first, each labelled by the host span that overlaps it most
  (the benchmark's ``bench.*`` spans, and the engine's own tracer spans
  as ``engine.<name>`` once ``with_spans`` has put them on the trace's
  clock), or ``unattributed``.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
BENCH_PREFIX = "bench."
TOP = 10


def load(path: str) -> dict:
    """The events of one ``.xplane.pb`` that the reduction reads:
    ``{"devices": {plane: {"ops": [...]}}, "host": [...]}``, each event
    ``(start_ns, end_ns, name)``."""
    from jax.profiler import ProfileData
    return events_of(ProfileData.from_file(path))


def op_name(text: str) -> str:
    """An op's name: the TPU trace names it by its whole HLO instruction
    (``%while.51 = (s32[], ...) while(...)``); keep ``%while.51``."""
    return text.split(" = ", 1)[0]


def events_of(profile) -> dict:
    devices, host = {}, []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            devices[plane.name] = {"ops": [
                (e.start_ns, e.start_ns + e.duration_ns, op_name(e.name))
                for ln in plane.lines if ln.name == OPS_LINE
                for e in ln.events]}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in ln.events if e.name.startswith(BENCH_PREFIX)]
    return {"devices": devices, "host": host}


def merge(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of ``(start, end, ...)`` intervals clipped to [lo, hi],
    as sorted disjoint pairs."""
    out: list[list[float]] = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float):
    """The stretches of [lo, hi] that ``busy`` leaves uncovered."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap, host, window_name: str) -> str:
    """The host span overlapping a gap most (the shorter one on a tie,
    so a phase wins over the step that holds it)."""
    best, name = (0.0, 0.0), "unattributed"
    for s, e, n in host:
        if n == window_name:
            continue
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > 0 and (ov, -(e - s)) > best:
            best, name = (ov, -(e - s)), n
    return name


def reduce(events: dict, window_name: str = "bench.traced") -> dict | None:
    """The numbers above, in seconds; None where the trace holds no
    window span or no device op."""
    spans = [h for h in events["host"] if h[2] == window_name]
    devs = [d for _, d in sorted(events["devices"].items()) if d["ops"]]
    if not spans or not devs:
        return None
    lo, hi = min(s for s, _, _ in spans), max(e for _, e, _ in spans)
    busy = [merge(d["ops"], lo, hi) for d in devs]
    busy_ns = sum(e - s for b in busy for s, e in b) / len(devs)
    by_op: dict[str, float] = {}
    for d in devs:
        for s, e, n in d["ops"]:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                by_op[n] = by_op.get(n, 0.0) + (e - s)
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    idle = sorted(gaps(busy[0], lo, hi), key=lambda g: g[0] - g[1])[:TOP]
    k = len(devs)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9,
        "device_ops": [[n, t / k / 1e9] for n, t in top],
        "idle_gaps": [[label(g, events["host"], window_name),
                       (g[1] - g[0]) / 1e9] for g in idle],
    }


def with_spans(events: dict, spans: list[dict], window_name: str,
               window_start_ns: int) -> dict:
    """Add spans timed on the host's ``perf_counter`` (seconds) to the
    host events, shifted onto the trace's clock: ``window_start_ns`` is
    the ``perf_counter`` reading (ns) at which the window span opened."""
    starts = [s for s, _, n in events["host"] if n == window_name]
    if not starts:
        return events
    shift = min(starts) - window_start_ns
    extra = [(sp["start"] * 1e9 + shift, (sp["start"] + sp["dur"]) * 1e9
              + shift, "engine." + sp["name"]) for sp in spans]
    return {**events, "host": events["host"] + extra}


def reduce_dir(trace_dir, window_name: str = "bench.traced",
               spans: list[dict] = (), window_start_ns: int = 0
               ) -> dict | None:
    """Reduce the newest ``.xplane.pb`` under a profiler output dir, with
    the engine's spans (see :func:`with_spans`) to label idle gaps."""
    paths = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    if not paths:
        return None
    events = load(max(paths, key=os.path.getmtime))
    return reduce(with_spans(events, list(spans), window_name,
                             window_start_ns), window_name)
