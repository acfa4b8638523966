"""The check of the float64-aggregate configuration
(``abo_griewank_paper_f64``), on the CPU at a small size: a sound run
comes out correct, and its control, the reference with float32
aggregates, does not.

The harness turns on ``jax_enable_x64`` for this configuration, and x64
is process-wide, so each case runs in a child process of its own; the
limits are the configuration's own.
"""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

BENCH = pathlib.Path(__file__).resolve().parents[1]
CONFIG = "abo_griewank_paper_f64"
SEED = 2 ** 31 + 7                       # larger than 32 signed bits

_SMALL_CELL = f"""
    import copy
    import json
    import sys
    sys.path.insert(0, {str(BENCH)!r})
    sys.path.insert(1, {str(BENCH.parent / "src")!r})
    import jax
    import control
    import harness
    import run as bench_run
    import traffic as gen

    config = harness.load_json(harness.HERE / "configs" / "{CONFIG}.json")
    tr = copy.deepcopy(gen.load("one_user_loop"))
    config["job"]["n"] = 20_000
    tr["warmup"]["jobs"] = 1
    tr["check"] = {{"jobs": 3, "among": 4}}
    cell = {{"name": "f64", "config": "{CONFIG}",
             "traffic": "one_user_loop", "chips": 1}}
"""


def _child(body: str) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_SMALL_CELL)
         + textwrap.dedent(body)],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_f64_sound_run_is_correct():
    out = _child("""
        out = bench_run.measure_cell(cell, config, tr, [], %d, 1.5, False,
                                     require_tpu=False)
        assert jax.config.jax_enable_x64
        print(json.dumps({"correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "compared": out["compared"]}))
    """ % SEED)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] >= 1


def test_f64_control_is_not_correct():
    """The reference with float32 aggregates fails the limits."""
    out = _child("""
        harness.setup_jax(config, 1, require_tpu=False)
        assert jax.config.jax_enable_x64
        jobs = control.jobs_checked(config, tr, %d)
        pad = max(gen.sizes(tr.get("n"), config["job"]))
        print(json.dumps(control.readings(config, jobs, pad)))
    """ % SEED)
    assert not out["correct"], out["held"]
