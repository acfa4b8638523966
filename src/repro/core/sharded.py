"""Mesh-sharded ABO — the paper's parallel claim (Eq. 7: E_cp = O(m)) on a pod.

Layout: the solution vector is sharded over *every* mesh axis (flattened);
each device Jacobi-sweeps its own coordinate shard against its local view of
the scalar aggregates, then one `psum` of the aggregate deltas re-syncs the
global view. Communication per pass is **n_aggs scalars per device** — the
O(1) traffic that makes the coordinate sweep embarrassingly parallel, vs. the
O(N) exchanges a population method would need.

Semantics: block commits are Gauss-Seidel *within* a device (its local view
advances) and Jacobi *across* devices (views are stale until the pass-end
psum). The commit guard therefore runs per local block against the local
view, and once globally per pass after the sync.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.abo import ABOConfig, _candidate_grid, _default_probe_tile
from repro.objectives.base import SeparableObjective


def axis_linear_index(axes: Sequence[str]):
    """Flattened linear device index over ``axes`` (row-major), traced
    inside a shard_map'd program. The single-axis case is the engine's
    sharded page pool ("which pool shard am I"); the multi-axis case is
    :func:`make_sharded_abo`'s coordinate offset on an N-d mesh."""
    dev = jnp.zeros((), jnp.int32)
    for ax in axes:
        dev = dev * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    return dev


def owner_select(x: jnp.ndarray, owner: jnp.ndarray, my, axis: str):
    """Replicate per-row state whose row ``i`` is authoritative only on
    device ``owner[i]``: every device keeps its own rows and takes every
    other row from that row's owner, in ONE ``psum`` — the O(n_aggs)-
    scalars-per-device traffic of the paper's Eq. 7, applied to the
    engine's per-slot aggregate table.

    Bit-exactness is non-negotiable (the engine's results must equal
    ``abo_minimize``'s at every device count), and a float ``sum`` with
    zeros is NOT the identity for every bit pattern (-0.0 + 0.0 = +0.0).
    So the select reduces *bit patterns*: values are reinterpreted as
    unsigned words, non-owned rows zeroed, psum'd (integer addition of
    disjoint nonzeros == bitwise OR == exact transfer), and cast back.
    NaN payloads, signed zeros, and denormals all round-trip untouched.

    ``owner`` is int32 of any shape that is a leading prefix of ``x``'s —
    ``(rows,)`` against ``(rows, ...)`` per-slot tables, or ``(v, g)``
    against the ``(v, g, block)`` page gather of a striped spanning lane
    (engine harvest); ``x`` is any fixed-width dtype; ``my`` is this
    device's :func:`axis_linear_index`.
    """
    mask = owner == my
    mask = mask.reshape(mask.shape + (1,) * (x.ndim - mask.ndim))
    if jnp.issubdtype(x.dtype, jnp.integer):
        picked = jnp.where(mask, x, jnp.zeros_like(x))
        return jax.lax.psum(picked, axis)
    bits_dt = jnp.dtype(f"uint{x.dtype.itemsize * 8}")
    bits = jax.lax.bitcast_convert_type(x, bits_dt)
    bits = jnp.where(mask, bits, jnp.zeros_like(bits))
    return jax.lax.bitcast_convert_type(jax.lax.psum(bits, axis), x.dtype)


def _local_pass(obj, cfg, probe_tile, x_loc, aggs, half_width, pass_idx, lam,
                global_offset, n_valid):
    """Sweep this device's coordinate shard; return (x_loc, local agg delta)."""
    bsz, m = cfg.block_size, cfg.samples_per_pass
    n_blocks = x_loc.shape[0] // bsz
    aggs0 = aggs

    def block_body(carry, blk):
        x_loc, aggs = carry
        start = blk * bsz
        xb = jax.lax.dynamic_slice(x_loc, (start,), (bsz,))
        idx = global_offset + start + jnp.arange(bsz)
        valid = idx < n_valid
        cands = _candidate_grid(xb, obj.lower, obj.upper, half_width, m,
                                pass_idx == 0)
        cands = jnp.where(valid[:, None], cands, xb[:, None])
        f_cand, delta = probe_tile(aggs, idx, xb, cands, lam)
        sel = jnp.argmin(f_cand, axis=1)
        x_sel = jnp.take_along_axis(cands, sel[:, None], axis=1)[:, 0]
        d_sel = jnp.take_along_axis(delta, sel[:, None, None], axis=1)[:, 0, :]
        aggs_new = aggs + d_sel.sum(axis=0).astype(aggs.dtype)
        if cfg.guard_commits:
            accept = obj.combine_at(aggs_new, lam) <= obj.combine_at(aggs, lam)
            x_sel = jnp.where(accept, x_sel, xb)
            aggs = jnp.where(accept, aggs_new, aggs)
        else:
            aggs = aggs_new
        x_loc = jax.lax.dynamic_update_slice(x_loc, x_sel, (start,))
        return (x_loc, aggs), None

    (x_loc, aggs), _ = jax.lax.scan(block_body, (x_loc, aggs),
                                    jnp.arange(n_blocks))
    return x_loc, aggs - aggs0


def make_sharded_abo(
    obj: SeparableObjective,
    n: int,
    mesh: Mesh,
    *,
    config: ABOConfig | None = None,
    dtype=jnp.float32,
):
    """Build (step_fn, x_sharding, aggs_sharding) for one ABO pass on ``mesh``.

    ``step_fn(x, aggs, pass_idx) -> (x, aggs)`` is shard_map'd over all mesh
    axes; ``x`` must be length ``pad(n)`` divisible by devices × block_size.
    Used by both the real distributed run and the multi-pod dry-run.
    """
    cfg = config or ABOConfig()
    axes: Sequence[str] = tuple(mesh.axis_names)
    n_dev = mesh.devices.size
    shard = -(-n // (n_dev * cfg.block_size)) * cfg.block_size
    n_pad = shard * n_dev
    probe_tile = _default_probe_tile(obj)

    def step(x_loc, aggs, pass_idx):
        dev = axis_linear_index(axes)
        offset = dev.astype(jnp.int64 if jax.config.jax_enable_x64 else
                            jnp.int32) * shard
        if cfg.coupling_schedule == "linear" and cfg.n_passes > 1:
            lam = (pass_idx / (cfg.n_passes - 1)).astype(aggs.dtype)
        else:
            lam = jnp.ones((), aggs.dtype)
        half_width = 0.5 * cfg.resolved_shrink() ** pass_idx  # fractional
        # aggs enters replicated; local commits make it device-varying
        aggs_v = jax.lax.pcast(aggs, axes, to="varying")
        x_loc, d_aggs = _local_pass(obj, cfg, probe_tile, x_loc, aggs_v,
                                    half_width, pass_idx, lam, offset, n)
        # O(1) traffic: one all-reduce of the n_aggs scalar deltas.
        for ax in axes:
            d_aggs = jax.lax.psum(d_aggs, ax)
        return x_loc, aggs + d_aggs

    step_sm = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(axes), P(), P()),
        out_specs=(P(axes), P()),
    )
    x_sharding = NamedSharding(mesh, P(axes))
    aggs_sharding = NamedSharding(mesh, P())
    return jax.jit(step_sm, donate_argnums=(0,)), x_sharding, aggs_sharding, n_pad


def input_specs(obj: SeparableObjective, n: int, mesh: Mesh,
                *, config: ABOConfig | None = None, dtype=jnp.float32):
    """ShapeDtypeStructs for the dry-run (no allocation)."""
    cfg = config or ABOConfig()
    n_dev = mesh.devices.size
    shard = -(-n // (n_dev * cfg.block_size)) * cfg.block_size
    n_pad = shard * n_dev
    agg_dt = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    return {
        "x": jax.ShapeDtypeStruct((n_pad,), dtype),
        "aggs": jax.ShapeDtypeStruct((obj.n_aggs,), agg_dt),
        "pass_idx": jax.ShapeDtypeStruct((), jnp.int32),
    }
