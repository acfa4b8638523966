"""The engine-span reduction (``bench/enginetrace.py``) and the three
metrics that read it, on the CPU: a synthetic event set, a program that
does not annotate, and an excerpt of a trace recorded on the chip."""
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH / "tests"))

import devtrace  # noqa: E402
import enginetrace  # noqa: E402
import harness  # noqa: E402
import readers  # noqa: E402
import record_trace_excerpt  # noqa: E402

RECORDED = BENCH / "tests" / "data" / "engine_trace_excerpt.textproto"
METRICS = {"sweep_row_us.solve": "sweep_row_us",
           "readback_ms.solve": "readback_ms",
           "sched_idle_ms.solve": "sched_idle_ms"}


def _synthetic():
    """One traced solve, in nanoseconds. Idle on the device: [0, 120],
    [140, 170], [670, 680], [690, 950] (spanning device_wait, readback,
    the harvest's own time, the step's and none) and [960, 1000]."""
    dev = {"ops": [(120, 140, "%place"), (170, 670, "%while.1"),
                   (680, 690, "%fusion.2"), (950, 960, "%copy.3")],
           "modules": [(120, 140, "jit_place(1)"),
                       (170, 670, "jit_fused_step(2)"),
                       (680, 690, "jit_finalize(3)")]}
    host = [(0, 1000, "bench.traced", {}),
            (-100, -50, "engine.fused_sweep",
             {"passes": 9, "swept_rows": 99}),       # before the window
            (100, 900, "engine.step", {"step": 0}),
            (110, 150, "engine.refill", {}),
            (160, 170, "engine.fused_sweep", {"passes": 5, "swept_rows": 10}),
            (600, 880, "engine.harvest", {"jobs": "j0"}),
            (610, 620, "engine.finalize", {}),
            (620, 700, "engine.device_wait", {}),
            (700, 800, "engine.readback", {"bytes": 4000})]
    return {"devices": {"/device:TPU:0": dev}, "host": host}


def test_reduce_synthetic_splits_idle_by_innermost_span():
    out = enginetrace.reduce(_synthetic())
    assert out["swept_rows"] == 50
    assert out["sweep_row_us"] == pytest.approx(500 / 1e3 / 50)
    assert out["readback_ms"] == pytest.approx(100e-6)
    assert out["readback_gb_s"] == pytest.approx(40.0)
    assert out["idle_by_span_ms"] == pytest.approx(
        {"step": 40e-6, "refill": 20e-6, "fused_sweep": 10e-6,
         "harvest": 80e-6, "device_wait": 20e-6, "readback": 100e-6})
    assert out["idle_split_ms"] == pytest.approx(
        {"outside": 190e-6, "readback": 100e-6, "device_wait": 20e-6,
         "sched": 150e-6})
    assert out["sched_idle_ms"] == pytest.approx(150e-6)
    assert sum(out["idle_split_ms"].values()) == pytest.approx(
        out["idle_ms"]) == pytest.approx(460e-6)


def test_segments_name_the_innermost_span():
    segs = enginetrace.segments(
        [(0, 10, "a"), (2, 4, "b"), (4, 6, "c"), (8, 12, "d")], -1, 14)
    assert segs == [(-1, 0, None), (0, 2, "a"), (2, 4, "b"), (4, 6, "c"),
                    (6, 8, "a"), (8, 12, "d"), (12, 14, None)]


def test_reduce_without_engine_spans_or_names_reads_nothing():
    """A program whose tracer does not annotate and whose executables
    are all ``run``: every metric finds nothing, and nothing raises."""
    ev = _synthetic()
    ev["host"] = [h for h in ev["host"] if not h[2].startswith("engine.")]
    ev["devices"]["/device:TPU:0"]["modules"] = [
        (s, e, "jit_run(7)") for s, e, _ in
        ev["devices"]["/device:TPU:0"]["modules"]]
    out = enginetrace.reduce(ev)
    assert [out[k] for k in METRICS.values()] == [None, None, None]
    assert out["idle_split_ms"]["outside"] == pytest.approx(out["idle_ms"])
    assert enginetrace.reduce({"devices": {}, "host": []}) is None


def test_metrics_parse_the_trace_once(tmp_path, monkeypatch):
    trace = tmp_path / "plugins" / "profile" / "1"
    trace.mkdir(parents=True)
    (trace / "h.xplane.pb").write_bytes(b"")
    loads = []
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(enginetrace, "load",
                        lambda path: loads.append(path) or _synthetic())
    want = enginetrace.reduce(_synthetic())
    record = {"device_trace": {"busy_s": 1}}
    for name, key in METRICS.items():
        read = harness.load_module("metrics", name).read
        assert read({"device_trace": None}) is None
        assert read(record) == want[key]
    assert len(loads) == 1


# ---- the recorded excerpt ---------------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(RECORDED.read_text())


def test_recorded_trace_names_the_fused_step(recorded):
    ev = enginetrace.events_of(recorded)
    (dev,) = ev["devices"].values()
    modules = {n.split("(")[0] for _, _, n in dev["modules"]}
    assert "jit_fused_step" in modules
    assert not any(m == "jit_run" for m in modules)
    harvests = [st["jobs"] for _, _, n, st in ev["host"]
                if n == "engine.harvest" and st["jobs"]]
    assert len(harvests) == 2 and len(set(harvests)) == 2


def test_recorded_trace_readers_and_idle_sum(recorded):
    """The three readers are non-null, and the four parts of the idle
    time add up to the idle share times the window that the device
    trace's own reduction reads, within 1 %."""
    out = enginetrace.reduce(enginetrace.events_of(recorded))
    for key in METRICS.values():
        assert out[key] is not None and out[key] > 0
    dt = devtrace.reduce(devtrace.events_of(recorded))
    idle_ms = readers.idle_pct({"device_trace": dt}) / 100 \
        * dt["window_s"] * 1e3
    assert sum(out["idle_split_ms"].values()) == pytest.approx(
        idle_ms, rel=0.01)
    assert out["idle_split_ms"]["readback"] > 0
    assert out["idle_split_ms"]["device_wait"] >= 0
    assert out["window_s"] == pytest.approx(dt["window_s"])
    # the rows: every engine.fused_sweep span's passes x swept_rows
    ev = enginetrace.events_of(recorded)
    rows = sum(st["passes"] * st["swept_rows"] for _, _, n, st in ev["host"]
               if n == "engine.fused_sweep")
    fused = sum(e - s for _, d in ev["devices"].items()
                for s, e, n in d["modules"] if n.startswith("jit_fused_step("))
    assert out["sweep_row_us"] == pytest.approx(fused / 1e3 / rows)


def test_excerpt_writer_round_trips(recorded):
    from jax.profiler import ProfileData
    again = ProfileData.from_text_proto(
        record_trace_excerpt.excerpt(recorded, "again"))
    assert enginetrace.events_of(again) == enginetrace.events_of(recorded)
