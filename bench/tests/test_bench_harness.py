"""The benchmark's harness on the CPU: the trace reduction, the traffic
generator, the peaks table, the refusal to run off the chip, and that
every name in BENCHMARK.json finds its files."""
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402
import harness  # noqa: E402
import peaks  # noqa: E402
import traffic as gen  # noqa: E402

BENCHMARK = harness.load_json(ROOT / "BENCHMARK.json")
RECORDED = BENCH / "tests" / "data" / "tpu_trace_excerpt.textproto"


# ---- trace reduction ------------------------------------------------------
def _brute_busy(ops, lo, hi, step=1):
    """Busy nanoseconds by marking every covered tick (slow, plain)."""
    ticks = np.zeros(int(hi - lo), bool)
    for s, e, _ in ops:
        a, b = int(max(s, lo) - lo), int(min(e, hi) - lo)
        if b > a:
            ticks[a:b] = True
    return int(ticks.sum())


def test_reduce_synthetic():
    events = {"devices": {"/device:TPU:0": {
        "ops": [(10, 20, "a"), (15, 30, "b"), (50, 60, "a"), (95, 130, "c")]}},
        "host": [(0, 100, "bench.traced"), (30, 50, "bench.solve"),
                 (60, 70, "bench.result")]}
    out = devtrace.reduce(events)
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["busy_s"] == pytest.approx(35e-9)       # 10-30, 50-60, 95-100
    assert dict(out["device_ops"]) == pytest.approx(
        {"a": 20e-9, "b": 15e-9, "c": 5e-9})
    gaps = out["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([35e-9, 20e-9, 10e-9])
    assert [g[0] for g in gaps] == ["bench.result", "bench.solve",
                                    "unattributed"]


def test_engine_spans_label_gaps_on_the_trace_clock():
    """Engine spans (perf_counter seconds) are shifted so that the
    window span's start matches the perf_counter reading taken as it
    opened; a phase wins a gap over the step that holds it."""
    events = {"devices": {"/device:TPU:0": {"ops": [(1000, 1010, "a")]}},
              "host": [(1000, 1100, "bench.traced")]}
    spans = [{"name": "step", "start": 4.99e-6, "dur": 200e-9},
             {"name": "harvest", "start": 5e-6, "dur": 90e-9}]
    moved = devtrace.with_spans(events, spans, "bench.traced", 4990)
    assert (1010.0, 1100.0, "engine.harvest") in moved["host"]
    out = devtrace.reduce(moved)
    assert out["idle_gaps"] == [["engine.harvest", pytest.approx(90e-9)]]


def test_reduce_needs_window_and_ops():
    assert devtrace.reduce({"devices": {}, "host": [(0, 1, "bench.traced")]}) \
        is None
    assert devtrace.reduce({"devices": {"/device:TPU:0": {
        "ops": [(0, 1, "a")]}}, "host": []}) is None


def test_reduce_recorded_tpu_trace():
    """An excerpt of a trace recorded on a TPU v5e, read through the
    profiler's own parser, against a plain tick count."""
    from jax.profiler import ProfileData
    events = devtrace.events_of(ProfileData.from_text_proto(
        RECORDED.read_text()))
    dev = events["devices"]
    assert list(dev) == ["/device:TPU:0"] and dev["/device:TPU:0"]["ops"]
    (lo, hi, _), = [h for h in events["host"] if h[2] == "bench.traced"]
    out = devtrace.reduce(events)
    ops = dev["/device:TPU:0"]["ops"]
    assert out["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert out["busy_s"] == pytest.approx(
        _brute_busy(ops, lo, hi) / 1e9, abs=2e-9 * len(ops))
    total = sum(t for _, t in out["device_ops"])
    assert total >= out["busy_s"] * 0.5 and out["busy_s"] <= out["window_s"]
    assert sum(t for _, t in out["idle_gaps"]) <= \
        out["window_s"] - out["busy_s"] + 1e-9


# ---- traffic ----------------------------------------------------------------
# every traffic mix under bench/traffic, with a configuration it runs on
MIX_CONFIG = {"one_user_loop": "abo_griewank_paper"}


def test_every_mix_is_tested():
    assert sorted(MIX_CONFIG) == sorted(
        p.stem for p in (BENCH / "traffic").glob("*.json"))


@pytest.mark.parametrize("name", sorted(MIX_CONFIG))
def test_traffic_same_seed_same_jobs(name):
    config = harness.load_json(BENCH / "configs" / f"{MIX_CONFIG[name]}.json")
    tr = gen.load(name)
    seed = 2 ** 31 + 11
    s1, s2 = (gen.ClosedStream(tr, config["job"], seed) for _ in "ab")
    a = [s1.next() for _ in range(300)]
    assert a == [s2.next() for _ in range(300)]
    other = gen.ClosedStream(tr, config["job"], seed + 1)
    assert a != [other.next() for _ in range(300)]
    for job in a:
        assert 0 <= job["seed"] < 2 ** 31
        assert {"objective", "n", "samples_per_pass", "n_passes",
                "block_size"} <= set(job)


@pytest.mark.parametrize("name", sorted(MIX_CONFIG))
def test_check_sample_is_fixed_by_the_seed(name):
    tr = gen.load(name)
    seed = 2 ** 31 + 11
    a = gen.checked(tr, seed)
    assert a == gen.checked(tr, seed) and a == sorted(set(a))
    assert len(a) == tr["check"]["jobs"] and a[-1] < tr["check"]["among"]
    assert any(gen.checked(tr, seed + k) != a for k in range(1, 9))


def test_log_points_are_the_mix_sizes():
    assert gen.sizes({"log_points": [1e4, 1e6, 16]}, {})[::5] == \
        [10000, 46416, 215443, 1000000]
    assert gen.sizes(None, {"n": 7}) == [7]


# ---- peaks, chip refusal, names -------------------------------------------
def test_peaks_reject_unknown_device():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v99")


def test_run_exits_nonzero_without_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         BENCHMARK["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout
    assert "TPU" in proc.stderr


def test_every_name_finds_its_files():
    names = {c["name"] for c in BENCHMARK["configs"]}
    for c in BENCHMARK["configs"]:
        cfg = harness.load_json(ROOT / c["file"])
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert {"entry", "precision", "control", "job", "limits"} <= set(cfg)
        assert (BENCH / "entries" / f"{cfg['entry']}.py").exists()
    for w in BENCHMARK["workloads"]:
        assert w["config"] in names
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)
    for w in BENCHMARK["workloads"]:
        e2e = harness.metric_specs(BENCHMARK, w["name"], False)
        per_layer = harness.metric_specs(BENCHMARK, w["name"], True)
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert per_layer
