"""ABO coordinate-sweep Pallas TPU kernel (the paper's inner loop).

One `pallas_call` executes a FULL ABO pass over the solution vector:

  * the solution is laid out lane-dense as ``(n_pad / 128, 128)`` and the
    grid ``(n_blocks,)`` streams one ``(block / 128, 128)`` tile per step,
    executed **sequentially** on the TensorCore ("arbitrary" dimension
    semantics) — the tile shape is what the TPU compiler accepts: its last
    two dimensions are multiples of (8, 128) (or span the whole array);
  * the three Griewank aggregates (S, L, K) ride lanes 0..2 of one
    ``(1, 128)`` vector row. The aggregates output block has the same index
    at every step, so it stays resident in VMEM across the grid and doubles
    as the running state: the sweep is Gauss-Seidel across blocks exactly
    like the pure-jnp reference, with zero HBM traffic for the state and no
    scalar memory traffic at all;
  * candidates are *generated in VMEM* from the incumbent tile, one
    candidate column at a time (a ``fori_loop`` over the m - 1 grid points,
    whose offsets sit in a small SMEM table, then the incumbent) —
    candidates never exist in HBM, which is the kernel-level realization
    of the paper's "zero additional RAM";
  * each candidate is an O(1) aggregate-update probe over the whole tile,
    folded into a running first-minimum (``argmin``'s tie rule), followed
    by the guarded block commit.

Static specialization: pass-level constants (window, λ, first-pass flag,
n_valid) are compile-time Python values — ABO re-specializes the kernel per
pass (5 passes ⇒ 5 kernels), the standard TPU trade of recompilation for
zero scalar traffic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# aggregate lanes: [S, L, K] padded to one 128-lane vector for the HBM i/o
AGG_LANES = 128
# minor (lane) dimension of the solution layout
LANES = 128


def _griewank_planes(idx, x):
    """Unstacked Griewank term planes (s, l, k) for any-shaped idx/x."""
    dt = x.dtype
    i1 = (idx + 1).astype(dt)
    u = x * jax.lax.rsqrt(i1)
    c = jnp.cos(u)
    s2 = jnp.square(jnp.sin(u))
    log_abs = jnp.where(
        s2 < 0.5,
        0.5 * jnp.log1p(-jnp.minimum(s2, 0.999999)),
        jnp.log(jnp.maximum(jnp.abs(c), 1e-38)),
    )
    return x * x * (1.0 / 4000.0), log_abs, (c < 0).astype(dt)


def _expm1(x):
    """exp(x) - 1 to a few ulp from exp and log alone (Kahan's form): the
    TPU kernel compiler has no expm1, and the plain ``exp(x) - 1`` loses
    every bit the near-optimum objective lives in."""
    u = jnp.exp(x)
    return jnp.where(u == 1.0, x, jnp.where(
        u == 0.0, -1.0, (u - 1.0) * x / jnp.log(u)))


def grid_deltas(half_width: float, m: int, dtype) -> np.ndarray:
    """(m - 1,) offsets of the linear candidate grid from its centre,
    ``half_width · (2j - (m - 2)) / (m - 2)``, evaluated on the host in
    float64 and rounded once: the grid is symmetric, its centre column is
    exactly 0, and a candidate is one add (centre + offset) that no
    compiler contraction (FMA) can round differently in the kernel and in
    ref.py."""
    j = np.arange(m - 1, dtype=np.float64)
    return (half_width * (2 * j - (m - 2)) / (m - 2)).astype(dtype)


def _combine(s, log_abs, k, lam):
    positive = jnp.mod(k, 2.0) < 0.5
    return jnp.where(positive, s - lam * _expm1(log_abs),
                     s + lam * (jnp.exp(log_abs) + 1.0))


def lane_index(rows: int, step):
    """(rows, LANES) global coordinate index of grid step ``step``'s tile."""
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    return step * (rows * LANES) + row * LANES + lane


def _tree_sum(a, axis):
    """Sum over ``axis`` (kept, size 1) by an explicit balanced tree of
    elementwise adds — halve, add, repeat; an odd leftover rides along —
    so the association is fixed by the code, not by a backend's reduction
    strategy: the kernel (compiled or interpreted) and ref.py round alike."""
    while a.shape[axis] > 1:
        k = a.shape[axis] // 2
        head = (jax.lax.slice_in_dim(a, 0, k, axis=axis)
                + jax.lax.slice_in_dim(a, k, 2 * k, axis=axis))
        a = head if a.shape[axis] == 2 * k else jnp.concatenate(
            [head, jax.lax.slice_in_dim(a, 2 * k, a.shape[axis], axis=axis)],
            axis=axis)
    return a


# Scalars inside the kernels are "splat rows": (1, LANES) vectors holding
# one value in every lane. The TPU compiler broadcasts a row down sublanes
# or a column across lanes, never a (1, 1) value both ways at once.
def _splat(v):
    """(1, 1) -> (1, LANES) splat row."""
    return jnp.broadcast_to(v, (1, LANES))


def tile_sum(a):
    """(rows, LANES) -> splat row of the sum (rows, then lanes)."""
    return _splat(_tree_sum(_tree_sum(a, 0), 1))


def unpack_aggs(row):
    """(1, AGG_LANES) aggregate row -> (S, L, K) splat rows."""
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return tuple(_splat(jnp.sum(jnp.where(lane == a, row, 0.0), axis=1,
                                keepdims=True)) for a in range(3))


def pack_aggs_row(s, l, k):
    """S, L, K splat rows -> (1, AGG_LANES) row with zeros past lane 2."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, AGG_LANES), 1)
    return jnp.where(lane == 0, s, jnp.where(
        lane == 1, l, jnp.where(lane == 2, k, 0.0)))


def _sweep_kernel(deltas_ref, x_ref, aggs_ref, x_out_ref, aggs_out_ref, *,
                  m, n_valid, lower, upper, lam, is_first):
    i = pl.program_id(0)
    rows = x_ref.shape[0]
    dt = x_ref.dtype

    @pl.when(i == 0)
    def _init():
        aggs_out_ref[...] = aggs_ref[...]

    agg = aggs_out_ref[...]
    s0, l0, k0 = unpack_aggs(agg)                            # splat rows
    xb = x_ref[...]                                          # (rows, 128)
    idx = lane_index(rows, i)
    valid = idx < n_valid
    s_old, l_old, k_old = _griewank_planes(idx, xb)
    center = (jnp.full(xb.shape, 0.5 * (lower + upper), dt) if is_first
              else xb)

    def probe(j):
        """Candidate column j of the linear grid and its O(1) probe."""
        c = jnp.clip(center + deltas_ref[j], lower, upper)
        c = jnp.where(valid, c, xb)                          # freeze padding
        s_new, l_new, k_new = _griewank_planes(idx, c)
        ds, dl, dk = s_new - s_old, l_new - l_old, k_new - k_old
        return _combine(s0 + ds, l0 + dl, k0 + dk, lam), c, ds, dl, dk

    def keep_first_min(j, best):
        cand = probe(j)
        better = cand[0] < best[0]
        return tuple(jnp.where(better, a, b) for a, b in zip(cand, best))

    best = jax.lax.fori_loop(1, m - 1, keep_first_min, probe(0))
    # the incumbent is candidate column m - 1: zero deltas, f at the
    # carried aggregates; it wins only on a strict improvement (argmin
    # returns the first minimum)
    f0 = _combine(s0, l0, k0, lam)
    zero = jnp.zeros_like(xb)
    better = f0 < best[0]
    _, x_sel, ds, dl, dk = (jnp.where(better, a, b) for a, b in
                            zip((f0, xb, zero, zero, zero), best))

    s1, l1, k1 = s0 + tile_sum(ds), l0 + tile_sum(dl), k0 + tile_sum(dk)
    accept = _combine(s1, l1, k1, lam) <= f0                 # splat row
    x_out_ref[...] = jnp.where(accept, x_sel, xb)
    aggs_out_ref[...] = jnp.where(accept, pack_aggs_row(s1, l1, k1), agg)


def sweep_pass_kernel(
    x2d: jnp.ndarray,          # (n_pad / LANES, LANES) padded solution
    aggs: jnp.ndarray,         # (1, AGG_LANES) with [S, L, K] in lanes 0..2
    *,
    block: int,
    m: int,
    n_valid: int,
    lower: float,
    upper: float,
    half_width: float,
    lam: float,
    is_first: bool,
    interpret: bool = False,
):
    """One full ABO pass (all blocks, Gauss-Seidel) in a single pallas_call.

    ``block`` (a multiple of LANES) coordinates form one Jacobi tile of
    ``block / LANES`` rows; on the TPU those rows must be a multiple of 8
    (block a multiple of 1024) unless one tile spans the whole vector."""
    if block % LANES:
        raise ValueError(f"block ({block}) must be a multiple of {LANES}")
    rows = block // LANES
    n_rows = x2d.shape[0]
    if n_rows % rows:
        raise ValueError(f"{n_rows} rows are not whole blocks of {rows}")
    kern = functools.partial(
        _sweep_kernel, m=m, n_valid=n_valid, lower=lower, upper=upper,
        lam=lam, is_first=is_first)
    if is_first:
        half_width = 0.5 * (upper - lower)
    deltas = jnp.asarray(grid_deltas(half_width, m, x2d.dtype))
    return pl.pallas_call(
        kern,
        grid=(n_rows // rows,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, AGG_LANES), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, AGG_LANES), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
            jax.ShapeDtypeStruct((1, AGG_LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(deltas, x2d, aggs)
