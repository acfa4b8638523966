"""The float64 probe's ``expm1`` from float32 pairs
(``repro.objectives.pair_expm1``) against numpy's float64 ``expm1``.

x64 is process-global, so the measurement runs in a child process with
``JAX_ENABLE_X64=1`` (this file run as a script), once per module. The
child evaluates the routine under ``jit`` alone, fused into a jitted
float64 Griewank ``combine_relaxed`` (where the probe runs it, so the
compiler sees it in that context), and op by op with jit disabled, where
no compiler can contract or fold across ops; all three must give the
same bits. Run on a TPU (``PYTHONPATH=src python
tests/test_pair_expm1.py``), the same script prints the routine's worst
error beside ``jnp.expm1``'s own float64 error on the chip.
"""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
WORST = 2.0 ** -45          # worst relative error allowed against numpy
DENSE = {                   # dense samples: uniform draws and a linspace
    "pm_1e-12": (-1e-12, 1e-12),
    "pm_1e-3": (-1e-3, 1e-3),
    "pm_1": (-1.0, 1.0),
    "m88_0": (-88.0, 0.0),
    "m1e6_m88": (-1e6, -88.0),
}
SETS = (*DENSE, "special", "solve_1e5")


def _dense(lo, hi, seed):
    rng = np.random.default_rng(seed)
    return np.concatenate([rng.uniform(lo, hi, 1_000_000),
                           np.linspace(lo, hi, 200_001)])


def _solve_log_products():
    """Griewank's float64 log-product ``L`` at every candidate a seeded
    n = 1e5 solve probes in its first two blocks: from the seeded start
    with pass 0's grid, and from the answer with each pass's window."""
    import jax.numpy as jnp

    from repro.core.abo import (ABOConfig, _candidate_grid, abo_minimize,
                                pass_schedule, seeded_start)
    from repro.objectives import GRIEWANK

    n, cfg = 100_000, ABOConfig(samples_per_pass=51)
    x0 = seeded_start(3, n, jnp.float32, GRIEWANK.lower, GRIEWANK.upper)
    x1 = abo_minimize(GRIEWANK, n, config=cfg, seed=3).x
    idx = jnp.arange(2 * cfg.block_size)
    out = []
    for x, passes in ((x0, [0]), (x1, range(cfg.n_passes))):
        aggs = GRIEWANK.aggregates(x)
        xb = x[idx]
        for p in passes:
            hw, _ = pass_schedule(cfg, p, jnp.float64)
            cands = _candidate_grid(xb, GRIEWANK.lower, GRIEWANK.upper, hw,
                                    cfg.samples_per_pass, p == 0)
            delta = GRIEWANK.term_delta(idx, xb, cands)
            out.append(np.asarray(aggs[1] + delta[..., 1]).ravel())
    return np.concatenate(out)


def samples(name: str) -> np.ndarray:
    if name in DENSE:
        return _dense(*DENSE[name], seed=SETS.index(name))
    if name == "special":
        return np.array([0.0, -0.0, np.nan, -np.inf, 1e-300, -1e-300,
                         -2.0 ** -61, 2.0 ** -59, -88.7, -1e6])
    return _solve_log_products()


def _worst(got, ref) -> float:
    """log2 of the worst relative error; -inf when every value is
    exact, +inf when a zero, a NaN or a sign is wrong."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    if not np.array_equal(np.isnan(got), np.isnan(ref)):
        return float("inf")
    ok = ~np.isnan(ref)
    got, ref = got[ok], ref[ok]
    if np.any((ref == 0) & (got != 0)):
        return float("inf")
    nz = ref != 0
    err = np.abs(got[nz] - ref[nz]) / np.abs(ref[nz])
    return float(np.log2(err.max())) if err.size and err.max() > 0 \
        else float("-inf")


def measure(with_jnp: bool = False) -> dict:
    """Per sample set: the routine's worst error alone under jit and fused
    into ``combine_relaxed``, whether those two and the op-by-op result
    are the same bits, and (on request) ``jnp.expm1``'s own error."""
    import jax
    import jax.numpy as jnp

    from repro.objectives.griewank import _combine_relaxed
    from repro.objectives.pair_expm1 import expm1_f64

    assert jax.config.jax_enable_x64
    alone = jax.jit(expm1_f64)

    @jax.jit
    def fused(s, log_p, k, lam):
        # s = 0, K = 0 and λ = 1 are arguments, so nothing folds: f = -q
        return -_combine_relaxed(jnp.stack([s, log_p, k], -1), lam)

    out = {"device": jax.devices()[0].device_kind}
    for name in SETS:
        x = samples(name)
        xd = jnp.asarray(x)
        zeros = jnp.zeros_like(xd)
        q = np.asarray(alone(xd))
        qf = np.asarray(fused(zeros, xd, zeros, jnp.asarray(1.0)))
        sub = xd[: 4096]
        with jax.disable_jit():
            q_ops = np.asarray(expm1_f64(sub))
        # the input as the device holds it: a TPU's float64 is a pair of
        # float32s, with float32's exponent range
        ref = np.expm1(np.asarray(xd))
        row = {"n": int(x.size), "alone": _worst(q, ref),
               "fused": _worst(qf, ref),
               # equal values, NaN for NaN: f = -(0 - q) turns q = +0 to -0
               "same_bits": bool(np.array_equal(q, qf, equal_nan=True)
                                 and np.array_equal(q[: 4096], q_ops,
                                                    equal_nan=True))}
        if with_jnp:
            row["jnp_expm1"] = _worst(np.asarray(jax.jit(jnp.expm1)(xd)), ref)
        out[name] = row
    out["checks"] = _combine_checks()
    return out


def _combine_checks() -> dict:
    """Float64 Griewank: ``combine_relaxed(a, 1)`` is ``combine(a)`` bit
    for bit over both signs of the product term, and neither lowers to
    an ``exp`` or ``expm1`` primitive."""
    import jax
    import jax.numpy as jnp

    from repro.objectives import GRIEWANK

    rng = np.random.default_rng(7)
    n = 200_000
    aggs = jnp.asarray(np.stack([rng.uniform(0, 2, n),
                                 -rng.exponential(1e-2, n),
                                 rng.integers(0, 4, n).astype(float)], -1))
    exact = np.asarray(jax.jit(GRIEWANK.combine)(aggs))
    relaxed = np.asarray(jax.jit(GRIEWANK.combine_relaxed)(
        aggs, jnp.asarray(1.0)))
    prims = {str(e.primitive) for f in (GRIEWANK.combine,
                                        lambda a: GRIEWANK.combine_relaxed(
                                            a, jnp.asarray(0.5)))
             for e in jax.make_jaxpr(f)(aggs).jaxpr.eqns}
    return {"relaxed_at_1_is_combine": exact.tobytes() == relaxed.tobytes(),
            "no_exp_primitive": not prims & {"exp", "expm1"}}


@pytest.fixture(scope="module")
def measured():
    env = {**os.environ, "JAX_ENABLE_X64": "1", "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(REPO / "src")}
    proc = subprocess.run([sys.executable, __file__, "--json-only"],
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", SETS)
def test_pair_expm1_matches_numpy_float64(measured, name):
    row = measured[name]
    assert row["alone"] <= np.log2(WORST), row
    assert row["fused"] <= np.log2(WORST), row
    assert row["same_bits"], row


@pytest.mark.parametrize("check", ["relaxed_at_1_is_combine",
                                   "no_exp_primitive"])
def test_float64_griewank_combine(measured, check):
    assert measured["checks"][check]


def test_float32_griewank_keeps_native_expm1():
    """Float32 aggregates take ``jnp.expm1``/``jnp.exp`` as before: the
    pair routine's barriers, bit casts and table appear nowhere."""
    import jax
    import jax.numpy as jnp

    from repro.objectives import GRIEWANK

    aggs = jnp.zeros((4096, 51, 3), jnp.float32)
    for f in (GRIEWANK.combine,
              lambda a: GRIEWANK.combine_relaxed(a, jnp.float32(0.5))):
        jaxpr = jax.make_jaxpr(f)(aggs).jaxpr
        prims = [str(e.primitive) for e in jaxpr.eqns]
        assert "expm1" in prims
        assert not {"optimization_barrier", "bitcast_convert_type",
                    "round"} & set(prims), prims
        assert all(v.aval.dtype == jnp.float32 for e in jaxpr.eqns
                   for v in e.outvars if v.aval.dtype.kind == "f")


if __name__ == "__main__":
    import jax
    jax.config.update("jax_enable_x64", True)
    result = measure(with_jnp="--json-only" not in sys.argv)
    if "--json-only" not in sys.argv:
        for key, row in result.items():
            print(key, row)
    print(json.dumps(result))
