"""What every cell's run shares: the chip, the clocks, the window, the
profiler trace, the metric readers and the check.

``run.py`` is the command; it finds the cell's configuration and
traffic by name and hands a :class:`Run` to the configuration's entry
(``bench/entries/<entry>.py``), which builds the system under test,
warms it, drives the window through :meth:`Run.window` and returns what
it saw (see ``entries/__init__.py``). This module then reads the peak
memory, runs the check against the reference, and hands the run's record
to the metric readers (``bench/metrics/<name>.py``).
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import pathlib
import shutil
import sys
import threading
import time

import check
import peaks as peaks_table
import devtrace

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
TRACE_DIR = ROOT / ".bench_trace"       # profiler output, inside the checkout
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class CompileLog:
    """Counts executables built: ``loads`` counts every one (compiled or
    read from the persistent cache), ``hits`` those the cache served;
    ``count`` is the difference, the XLA compiles."""

    def __init__(self):
        import jax.monitoring
        self.loads = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.loads += 1

    def _on_event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.hits += 1

    @property
    def count(self) -> int:
        return self.loads - self.hits


def setup_jax(config: dict, chips: int, require_tpu: bool = True):
    """Precision flags, the compile cache and the chips. Raises
    :class:`NoChip` where JAX finds no TPU or too few of them. With
    ``require_tpu=False`` (the CPU tests) the persistent compile cache
    stays off, as the repository's in-process tests keep it."""
    import jax
    jax.config.update("jax_enable_x64",
                      config["precision"]["aggregates"] == "float64")
    if require_tpu:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        # every executable goes to the persistent cache, however fast it
        # compiled, so that no later run of the cell compiles it again
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chip(s), JAX sees "
                     f"{len(devices)}")
    return devices[:chips]


class Run:
    """One run of one cell: what an entry needs from the harness."""

    def __init__(self, *, cell: dict, config: dict, traffic: dict,
                 seed: int, seconds: float, trace: bool, devices,
                 t_start: float):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices = devices
        self.t_start = t_start            # perf_counter at process start
        self.compiles = CompileLog()
        self.setup_s = None
        self.t0 = self.t1 = None          # the window, perf_counter
        self.wall0 = self.wall1 = None    # the window, time.time
        self.compiles_in_window = None
        self.loads_in_window = None
        self._trace_on = False
        self._trace_lock = threading.Lock()
        self.trace_wall = None            # (start, end) of the traced span

    # ---- the window -------------------------------------------------------
    @contextlib.contextmanager
    def window(self):
        """The measured window: set-up ends where it opens; yields the
        (perf_counter, time.time) instant it opened."""
        self.t0 = time.perf_counter()
        self.wall0 = time.time()
        self.setup_s = self.t0 - self.t_start
        c0, l0 = self.compiles.count, self.compiles.loads
        try:
            yield self.t0, self.wall0
        finally:
            self.t1 = time.perf_counter()
            self.wall1 = time.time()
            self.compiles_in_window = self.compiles.count - c0
            self.loads_in_window = self.compiles.loads - l0

    # ---- the profiler trace -----------------------------------------------
    def trace_begin(self):
        """Start the device trace (in a ``--trace 1`` run only)."""
        import jax
        with self._trace_lock:
            if not self.trace or self._trace_on or self.trace_wall:
                return
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            TRACE_DIR.mkdir(parents=True)
            # host spans at the user level, no Python call tracing,
            # which would flood the trace
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            self._trace_on = True
            self._trace_ann = self.span("bench.traced")
            self._trace_pc0 = time.perf_counter_ns()
            self._trace_ann.__enter__()
            self.trace_wall = (time.time(), None)

    def trace_end(self):
        """Stop the device trace (once; safe from any thread)."""
        import jax
        with self._trace_lock:
            if not self._trace_on:
                return
            self._trace_ann.__exit__(None, None, None)
            self.trace_wall = (self.trace_wall[0], time.time())
            jax.profiler.stop_trace()
            self._trace_on = False

    @staticmethod
    def span(name: str):
        """A host span of the benchmark's own, on the profiler's clock
        (a no-op when no trace is being taken)."""
        import jax
        return jax.profiler.TraceAnnotation(name)

    def device_trace(self, spans: list[dict] = ()) -> dict | None:
        """The reduced device trace of the traced span (see devtrace.py);
        ``spans``, on the perf_counter clock, label its idle gaps."""
        if not self.trace or self.trace_wall is None:
            return None
        return devtrace.reduce_dir(TRACE_DIR, "bench.traced", spans,
                                   self._trace_pc0)


def memory_peak(devices) -> int | None:
    """Peak bytes in use on the fullest chip since the process began."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_check(config: dict, sample: list[tuple[dict, dict]], failed: int,
              pad_to: int) -> tuple[bool, dict, list[dict]]:
    """Reference solves of the sampled jobs, the gaps, the verdict.
    Every reference solve is compiled at ``pad_to`` coordinates (the
    mix's largest job), so one program per objective serves them all."""
    readings = []
    for job, got in sample:
        ref = check.reference_for(job, config, pad_to=pad_to)
        readings.append({"index": job["index"], "objective": job["objective"],
                         "n": job["n"], **check.gaps(job["objective"], got,
                                                     ref)})
    ok, held = check.verdict(check.worst(readings), config["limits"], failed)
    return ok and bool(sample), held, readings


def metric_specs(benchmark: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics this cell reports in a run of this kind."""
    e2e = [m for m in benchmark["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not trace:
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in benchmark["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


def read_metrics(specs: list[dict], record: dict) -> dict:
    """Each metric's reader over the run's record; a reader that finds
    nothing to read returns None, and the metric is left out."""
    out = {}
    for spec in specs:
        value = load_module("metrics", spec["name"]).read(record)
        if value is not None:
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return out


def peaks_for(devices) -> dict:
    return peaks_table.peaks(devices[0].device_kind)


def eprint(*a):
    print(*a, file=sys.stderr, flush=True)
