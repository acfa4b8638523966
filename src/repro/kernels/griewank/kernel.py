"""Fused streaming Griewank evaluation — Pallas TPU kernel.

Computes the three aggregates [S, L, K] of a length-N vector in ONE pass:
the vector is laid out lane-dense as ``(N_pad / 128, 128)``, the grid
streams ``(chunk / 128, 128)`` tiles HBM→VMEM, and the running sums ride
lanes 0..2 of the resident ``(1, 128)`` output row across the sequential
grid (zero intermediate HBM traffic, no scalar memory). This is the
memory-roofline-optimal form: N·itemsize bytes read, ~10 flops/element
— arithmetic intensity ≈ 2.5 flop/byte, firmly memory-bound (§Roofline).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.coord_sweep.kernel import (AGG_LANES, LANES,
                                              _griewank_planes, lane_index,
                                              pack_aggs_row, tile_sum)


def _eval_kernel(x_ref, out_ref, *, n_valid):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros((1, AGG_LANES), jnp.float32)

    xc = x_ref[...]                                        # (rows, 128)
    idx = lane_index(xc.shape[0], i)
    s, log_abs, k = _griewank_planes(idx, xc)
    valid = idx < n_valid
    out_ref[...] += pack_aggs_row(*(tile_sum(jnp.where(valid, t, 0.0))
                                    for t in (s, log_abs, k)))


def griewank_aggregates_kernel(
    x2d: jnp.ndarray,              # (N_pad / LANES, LANES)
    *,
    chunk: int,
    n_valid: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns (1, AGG_LANES) with [S, L, K] in lanes 0..2. ``chunk`` (a
    multiple of LANES; of 1024 on the TPU unless one chunk spans the
    vector) coordinates are reduced per grid step."""
    if chunk % LANES:
        raise ValueError(f"chunk ({chunk}) must be a multiple of {LANES}")
    rows = chunk // LANES
    if x2d.shape[0] % rows:
        raise ValueError(f"{x2d.shape[0]} rows are not whole chunks of "
                         f"{rows}")
    kern = functools.partial(_eval_kernel, n_valid=n_valid)
    return pl.pallas_call(
        kern,
        grid=(x2d.shape[0] // rows,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, AGG_LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, AGG_LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x2d)
