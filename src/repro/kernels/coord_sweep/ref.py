"""Pure-jnp oracle for the coord_sweep kernel — identical semantics.

Gauss-Seidel across blocks (lax.scan), Jacobi within a block, guarded
commits, incumbent candidate column, frozen padding — the same algorithm
as kernel.py, expressed with plain jnp so interpret-mode kernel runs can
be checked against it. The grid (a float64 linspace rounded once) and the
block-commit sums are built here on their own, not from the kernel's
helpers; the sums follow the association the kernel documents — halve
the ``(block / LANES, LANES)`` tile's rows, then its lanes, an odd last
row carried along — because the aggregates of a pass that collapses onto
the optimum are all cancellation, and only the same association gives
the same bits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.coord_sweep.kernel import LANES, _combine, _griewank_planes


def _halving_sum(a):
    """Sum over axis 0: add the second half onto the first (an odd last
    entry carried to the next round), until one entry is left."""
    while a.shape[0] > 1:
        h = a.shape[0] // 2
        a = jnp.concatenate([a[:h] + a[h:2 * h], a[2 * h:]])
    return a[0]


def _block_sum(d):
    """(block,) -> scalar: the tile's rows halved, then its lanes."""
    return _halving_sum(_halving_sum(d.reshape(-1, LANES)))


def sweep_pass_ref(
    x2d: jnp.ndarray,
    aggs: jnp.ndarray,          # (1, AGG_LANES)
    *,
    m: int,
    n_valid: int,
    lower: float,
    upper: float,
    half_width: float,
    lam: float,
    is_first: bool,
):
    n_blocks, block = x2d.shape
    dt = x2d.dtype
    s0, l0, k0 = aggs[0, 0], aggs[0, 1], aggs[0, 2]
    hw = 0.5 * (upper - lower) if is_first else half_width
    offs = jnp.asarray(np.linspace(-hw, hw, m - 1).astype(dt))   # (m-1,)

    def body(carry, blk):
        x2d, s0, l0, k0 = carry
        xb = x2d[blk]
        jlane = jnp.broadcast_to(jnp.arange(m)[None, :], (block, m))
        bidx = blk * block + jnp.broadcast_to(jnp.arange(block)[:, None], (block, m))

        center = (jnp.full((block,), 0.5 * (lower + upper), dt) if is_first
                  else xb)
        grid = jnp.clip(center[:, None] + offs[None, :], lower, upper)
        cands = jnp.concatenate([grid, xb[:, None]], axis=1)
        valid = bidx < n_valid
        cands = jnp.where(valid, cands, xb[:, None])

        s_new, l_new, k_new = _griewank_planes(bidx, cands)
        s_old, l_old, k_old = _griewank_planes(bidx[:, 0], xb)
        ds = s_new - s_old[:, None]
        dl = l_new - l_old[:, None]
        dk = k_new - k_old[:, None]
        f = _combine(s0 + ds, l0 + dl, k0 + dk, lam)

        sel = jnp.argmin(f, axis=1)
        onehot = (jlane == sel[:, None]).astype(dt)
        x_sel = jnp.sum(cands * onehot, axis=1)
        s1 = s0 + _block_sum(jnp.sum(ds * onehot, axis=1))
        l1 = l0 + _block_sum(jnp.sum(dl * onehot, axis=1))
        k1 = k0 + _block_sum(jnp.sum(dk * onehot, axis=1))
        accept = _combine(s1, l1, k1, lam) <= _combine(s0, l0, k0, lam)

        x2d = x2d.at[blk].set(jnp.where(accept, x_sel, xb))
        s0 = jnp.where(accept, s1, s0)
        l0 = jnp.where(accept, l1, l0)
        k0 = jnp.where(accept, k1, k0)
        return (x2d, s0, l0, k0), None

    (x2d, s0, l0, k0), _ = jax.lax.scan(
        body, (x2d, s0, l0, k0), jnp.arange(n_blocks))
    aggs_out = jnp.zeros_like(aggs).at[0, 0].set(s0).at[0, 1].set(l0) \
        .at[0, 2].set(k0)
    return x2d, aggs_out
