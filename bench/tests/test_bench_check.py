"""The check that decides ``correct``, on the CPU at small sizes.

A sound run of each configuration comes out correct; the control (the
reference one step lower in precision) and each fault the cells can
have, planted under a run whose chip check is skipped, come out not
correct. The sizes are cut so that a test run holds them; the limits
are the configurations' own.
"""
import copy
import dataclasses
import json
import pathlib
import sys

import jax.numpy as jnp
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import control  # noqa: E402
import harness  # noqa: E402
import run as bench_run  # noqa: E402
import traffic as gen  # noqa: E402

from repro.engine import batched  # noqa: E402

SEED = 2 ** 31 + 7                       # larger than 32 signed bits


# (configuration, traffic mix) of each kind of cell: the library entry
MIXES = {"library": ("abo_griewank_paper", "one_user_loop")}


def small_cell(kind: str):
    """A cell's configuration and mix at test size: smaller jobs, a
    short warm-up, and the sample drawn among the few jobs a short
    window completes."""
    config_name, traffic_name = MIXES[kind]
    config = harness.load_json(BENCH / "configs" / f"{config_name}.json")
    tr = copy.deepcopy(gen.load(traffic_name))
    config["job"]["n"] = 20_000
    tr["warmup"]["jobs"] = 1
    tr["check"] = {"jobs": 3, "among": 4}
    cell = {"name": kind, "config": config_name, "traffic": traffic_name,
            "chips": 1}
    return cell, config, tr


CELLS = sorted(MIXES)


def measure(name: str) -> dict:
    cell, config, tr = small_cell(name)
    return bench_run.measure_cell(cell, config, tr, [], SEED, 1.5, False,
                                  require_tpu=False)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    out = measure(name)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] >= 1


@pytest.mark.parametrize("name", CELLS)
def test_run_checks_the_controls_jobs(name, capsys):
    """A run checks the stream jobs drawn before its window, the same
    jobs the control solves."""
    cell, config, tr = small_cell(name)
    out = bench_run.measure_cell(cell, config, tr, [], SEED, 3.0, False,
                                 require_tpu=False)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    checked = next(ln["checked"] for ln in lines if "checked" in ln)
    want = [j["index"] for j in control.jobs_checked(config, tr, SEED)]
    assert out["attempted"] >= tr["check"]["among"]
    assert [c["index"] for c in checked] == want


def _unchanged(orig):
    def fused_step(self, bands, sync, span=None):
        def step(state, n_fused, shard_rows, *arrs):
            return state
        return step
    return fused_step


def _half_left_out(orig):
    """Every second page of each swept lane keeps its old coordinates."""
    def fused_step(self, bands, sync, span=None):
        fn = orig(self, bands, sync, span)

        def step(state, n_fused, shard_rows, *arrs):
            pages = arrs[-1][:, 1::2].reshape(-1)       # the sync table
            before = jnp.array(state.pool[pages])
            new = fn(state, n_fused, shard_rows, *arrs)
            return dataclasses.replace(
                new, pool=new.pool.at[pages].set(before))
        return step
    return fused_step


def _answer_altered(orig):
    """One coordinate in a hundred of each answer moved to the bound."""
    def finalize(self, g, v):
        fn = orig(self, g, v)

        def out(state, lanes, pages):
            f, x, hist = fn(state, lanes, pages)
            return f, x.at[:, ::100].set(self.obj.lower), hist
        return out
    return finalize


FAULTS = {"state_unchanged": ("fused_step", _unchanged),
          "half_left_out": ("fused_step", _half_left_out),
          "answer_altered": ("finalize", _answer_altered)}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_planted_fault_is_not_correct(name, fault, monkeypatch):
    attr, make = FAULTS[fault]
    monkeypatch.setattr(batched.PoolOps, attr,
                        make(getattr(batched.PoolOps, attr)))
    out = measure(name)
    assert not out["correct"], out["compared"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    """The reference one step lower in precision fails the limits."""
    _, config, tr = small_cell(name)
    jobs = control.jobs_checked(config, tr, SEED)
    pad = max(gen.sizes(tr.get("n"), config["job"]))
    assert not control.readings(config, jobs, pad)["correct"]
