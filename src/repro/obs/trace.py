"""Low-overhead span tracer with Chrome-trace-event JSON export.

Disabled is the default and costs one attribute check per ``span()``
call: the tracer hands back a module-level null span whose enter/exit
are no-ops — no allocation, no clock read, no list append, no
annotation. Enabled, a span is two ``perf_counter_ns`` reads and one
dict append; events are buffered in memory (capped at ``max_events``)
and exported on demand as the Chrome trace event format::

    {"traceEvents": [{"name", "ph": "X", "ts", "dur", "pid", "tid",
                      "args"}, ...]}

which chrome://tracing and https://ui.perfetto.dev load directly —
``ts``/``dur`` are microseconds relative to ``enable()``.

Profiler-clock mode: ``enable(annotate=factory)`` also opens
``factory("engine." + name, **args)`` around every span, with the args
the span was opened with (args added later by ``set`` stay in the JSON
only). ``SolveEngine.trace`` passes ``jax.profiler.TraceAnnotation``,
so each span lands on the profiler's host plane, on the device trace's
clock, with its args as stats. The factory is injected, never imported
here: this module stays stdlib-only.

Span nesting is positional, not structural: a complete ("X") event whose
``[ts, ts+dur]`` interval contains another's is its parent in the
viewer. The engine emits ``step`` as the parent span with the phase
spans (``refill``, ``plan_build``, ``fused_sweep``, ``harvest``, ...)
inside it, all on the stepping thread's ``tid``; ``harvest`` holds
``finalize``, ``device_wait`` and ``readback`` (engine/DESIGN.md
"Observability").
"""
# repro: gauge-path — stdlib-only by invariant: observing must never sync the device
from __future__ import annotations

import json
import os
import threading
import time


class _NullSpan:
    """The disabled path: a reusable no-op context manager."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args):
        pass


NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("tracer", "name", "args", "t0", "ann")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self.tracer = tracer
        self.name = name
        self.args = args
        self.t0 = 0
        self.ann = None

    def set(self, **args):
        """Attach/update args mid-span (shown in the viewer's detail
        pane) — e.g. the number of jobs a harvest finished."""
        self.args.update(args)

    def __enter__(self):
        annotate = self.tracer.annotate
        if annotate is not None:
            self.ann = annotate("engine." + self.name, **self.args)
            self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        tr = self.tracer
        if tr.enabled and len(tr.events) < tr.max_events:
            tr.events.append({
                "name": self.name, "ph": "X",
                "ts": (self.t0 - tr.t0_ns) / 1000.0,
                "dur": (t1 - self.t0) / 1000.0,
                "pid": tr.pid, "tid": threading.get_ident() & 0xFFFF,
                "args": self.args,
            })
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False


class Tracer:
    """Span buffer; ``enabled=False`` until :meth:`enable` is called."""

    def __init__(self, max_events: int = 200_000):
        self.enabled = False
        self.max_events = max_events
        self.events: list[dict] = []
        self.t0_ns = 0
        self.pid = os.getpid()
        self.default_path: str | None = None
        self.annotate = None

    def enable(self, path: str | None = None, annotate=None):
        """Start recording; ``path`` (optional) becomes the default
        export target for :meth:`export`. ``annotate`` (optional) is a
        context-manager factory opened as ``annotate("engine." + name,
        **args)`` around every span (see the module docstring)."""
        self.enabled = True
        self.default_path = path or self.default_path
        self.annotate = annotate or self.annotate
        if not self.t0_ns:
            self.t0_ns = time.perf_counter_ns()

    def disable(self):
        self.enabled = False

    def span(self, name: str, **args):
        if not self.enabled:
            return NULL_SPAN
        return _Span(self, name, args)

    def counts(self) -> dict[str, int]:
        """Events recorded so far, by span name."""
        out: dict[str, int] = {}
        for ev in self.events:
            out[ev["name"]] = out.get(ev["name"], 0) + 1
        return out

    def to_dict(self) -> dict:
        return {"traceEvents": list(self.events),
                "displayTimeUnit": "ms"}

    def export(self, path: str | None = None) -> str:
        """Write the Chrome trace JSON; returns the path written."""
        path = path or self.default_path
        if path is None:
            raise ValueError("no trace path: pass one or enable(path=...)")
        with open(path, "w") as fh:
            json.dump(self.to_dict(), fh)
        return path
