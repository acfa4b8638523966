"""jit'd wrapper for the coord_sweep kernel + full ABO driver on top of it.

``abo_minimize_kernel`` is the kernel-path equivalent of
:func:`repro.core.abo.abo_minimize` for the Griewank objective: the pass
loop is unrolled in Python (each pass is one statically-specialized
pallas_call) and everything else — init, padding, FE accounting, exact final
re-evaluation — matches the jnp path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.abo import ABOConfig, ABOResult
from repro.kernels.coord_sweep.kernel import (AGG_LANES, LANES,
                                              sweep_pass_kernel)
from repro.objectives.griewank import GRIEWANK


def pack_aggs(aggs3: jnp.ndarray) -> jnp.ndarray:
    """(3,) float aggregates -> (1, AGG_LANES) kernel i/o vector."""
    out = jnp.zeros((1, AGG_LANES), jnp.float32)
    return out.at[0, :3].set(aggs3.astype(jnp.float32))


@functools.partial(jax.jit,
                   static_argnames=("block", "m", "n_valid", "half_width",
                                    "lam", "is_first", "interpret"))
def sweep_pass(x2d, aggs, *, block, m, n_valid, half_width, lam, is_first,
               interpret=False):
    """One kernel pass over the lane-dense ``(n_pad / LANES, LANES)``
    solution, ``block`` coordinates per Jacobi tile."""
    return sweep_pass_kernel(
        x2d, aggs, block=block, m=m, n_valid=n_valid, lower=GRIEWANK.lower,
        upper=GRIEWANK.upper, half_width=half_width, lam=lam,
        is_first=is_first, interpret=interpret)


def abo_minimize_kernel(
    n: int,
    *,
    config: ABOConfig | None = None,
    x0: jnp.ndarray | None = None,
    dtype=jnp.float32,
    interpret: bool = False,
) -> ABOResult:
    """Griewank ABO with the Pallas sweep kernel. ``interpret=True`` runs
    the kernel through the Pallas interpreter (any backend, tests); the
    default compiles it for the TPU and fails anywhere else."""
    cfg = config or ABOConfig()
    bsz, m = cfg.block_size, cfg.samples_per_pass
    n_pad = -(-n // bsz) * bsz
    if x0 is None:
        x = jnp.full((n_pad,), GRIEWANK.lower
                     + 0.6180339887 * (GRIEWANK.upper - GRIEWANK.lower), dtype)
    else:
        x = jnp.zeros((n_pad,), dtype).at[:n].set(jnp.asarray(x0, dtype))
    x2d = x.reshape(-1, LANES)
    aggs = pack_aggs(GRIEWANK.aggregates(x, n, agg_dtype=jnp.float32))

    shrink = cfg.resolved_shrink()
    w0 = 0.5 * (GRIEWANK.upper - GRIEWANK.lower)
    hist = []
    for p in range(cfg.n_passes):
        lam = (p / (cfg.n_passes - 1)
               if cfg.coupling_schedule == "linear" and cfg.n_passes > 1
               else 1.0)
        x2d, aggs = sweep_pass(
            x2d, aggs, block=bsz, m=m, n_valid=n,
            half_width=float(w0 * shrink ** p), lam=float(lam),
            is_first=(p == 0), interpret=interpret)
        hist.append(GRIEWANK.combine(aggs[0, :3]))

    x = x2d.reshape(-1)[:n]
    f_exact = float(GRIEWANK.value(x))
    return ABOResult(x=x, fun=f_exact, fe=cfg.n_passes * m * n,
                     history=jnp.stack(hist), n=n, config=cfg)
