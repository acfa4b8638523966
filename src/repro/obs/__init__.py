"""Engine telemetry: metrics registry, span tracer, roofline accounting.

Three pieces, all dependency-free beyond jax (which only
:mod:`.roofline` touches):

* :mod:`.metrics` — counters / gauges / histograms in a
  :class:`MetricsRegistry`; ``snapshot()`` is the canonical flat dict
  behind ``SolveService.stats()``, ``render_prometheus()`` the
  ``/metrics`` endpoint's text format.
* :mod:`.trace` — a :class:`Tracer` whose spans cost nothing when
  disabled and export Chrome-trace-event JSON (Perfetto-loadable) when
  enabled via ``SolveEngine.trace(path)`` / ``solve_server --trace``.
  Enabled by the engine, each span also opens a profiler annotation
  ``engine.<span>`` (an injected factory, ``jax.profiler.TraceAnnotation``),
  so under a running profiler the engine's phases sit on the device
  trace's clock beside its executables (``jit_fused_step``,
  ``jit_place``, ``jit_finalize``, ...; engine/DESIGN.md
  "Observability").
* :mod:`.roofline` — the analytic bytes-moved-per-pass model for sweep
  plans, an XLA ``cost_analysis`` cross-check, and a measured-stream
  peak-bandwidth probe; the ``engine_roofline`` bench scenario reports
  achieved vs. peak from these.

Overhead policy (see engine/DESIGN.md "Observability"): disabled tracing
returns a shared null span; counters/gauges are cached plain-attribute
adds; nothing on the step hot path reads device memory — device-derived
gauges refresh only at stats/scrape boundaries.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram,  # noqa: F401
                               MetricsRegistry)
from repro.obs.trace import NULL_SPAN, Tracer  # noqa: F401

# roofline is the one jax-touching module here; resolve its names
# lazily (PEP 562) so jax-free consumers — the serving router, the
# lint gate — can import repro.obs.metrics without paying for jax
_ROOFLINE = ("hlo_bytes_accessed", "measured_peak_bandwidth",
             "plan_pass_bytes")


def __getattr__(name):
    if name in _ROOFLINE:
        from repro.obs import roofline
        return getattr(roofline, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
