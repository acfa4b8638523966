"""jit'd public wrapper for the fused Griewank evaluation kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.coord_sweep.kernel import LANES
from repro.kernels.griewank.kernel import griewank_aggregates_kernel
from repro.objectives.griewank import GRIEWANK


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def griewank_eval(x: jnp.ndarray, *, chunk: int = 4096,
                  interpret: bool = False) -> jnp.ndarray:
    """Scalar Griewank value of a flat vector via the streaming kernel."""
    n = x.shape[0]
    n_pad = -(-n // chunk) * chunk
    x2d = jnp.zeros((n_pad,), x.dtype).at[:n].set(x).reshape(-1, LANES)
    aggs = griewank_aggregates_kernel(x2d, chunk=chunk, n_valid=n,
                                      interpret=interpret)
    return GRIEWANK.combine(aggs[0, :3])
