"""Per-kernel shape/dtype sweeps, interpret-mode vs pure-jnp oracle."""
import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import ABOConfig, abo_minimize
from repro.kernels.coord_sweep.ops import (abo_minimize_kernel, pack_aggs,
                                           sweep_pass)
from repro.kernels.coord_sweep.ref import sweep_pass_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import (attention_ref,
                                               attention_ref_chunked)
from repro.kernels.griewank.ops import griewank_eval
from repro.kernels.griewank.ref import griewank_aggregates_ref
from repro.kernels.griewank.kernel import griewank_aggregates_kernel
from repro.objectives import GRIEWANK, griewank


# ---------------------------------------------------------------------------
# coord_sweep
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_blocks,block,m", [(1, 128, 16), (4, 256, 64),
                                              (3, 512, 128), (2, 128, 33),
                                              (2, 384, 24)])
@pytest.mark.parametrize("lam,is_first", [(0.0, True), (0.5, False),
                                          (1.0, False)])
def test_coord_sweep_vs_ref(n_blocks, block, m, lam, is_first, rng):
    n = n_blocks * block - 17              # force padding coords
    x2d = jnp.asarray(
        rng.uniform(-600, 600, (n_blocks, block)).astype(np.float32))
    aggs = pack_aggs(GRIEWANK.aggregates(x2d.reshape(-1), n,
                                         agg_dtype=jnp.float32))
    kw = dict(m=m, n_valid=n, half_width=37.5, lam=lam, is_first=is_first)
    xk, ak = sweep_pass(x2d.reshape(-1, 128), aggs, block=block,
                        interpret=True, **kw)
    xr, ar = sweep_pass_ref(x2d, aggs, lower=-600.0, upper=600.0, **kw)
    np.testing.assert_allclose(np.asarray(xk).reshape(n_blocks, block),
                               np.asarray(xr), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ak[0, :3]), np.asarray(ar[0, :3]),
                               rtol=2e-4, atol=1e-5)


def test_coord_sweep_padding_frozen(rng):
    n_blocks, block, n = 2, 128, 200       # 56 padded coords
    x2d = jnp.asarray(rng.uniform(-600, 600,
                                  (n_blocks, block)).astype(np.float32))
    aggs = pack_aggs(GRIEWANK.aggregates(x2d.reshape(-1), n,
                                         agg_dtype=jnp.float32))
    xk, _ = sweep_pass(x2d.reshape(-1, 128), aggs, block=block, m=16,
                       n_valid=n, half_width=50.0, lam=1.0, is_first=False,
                       interpret=True)
    np.testing.assert_array_equal(np.asarray(xk).reshape(-1)[n:],
                                  np.asarray(x2d).reshape(-1)[n:])


def test_kernel_abo_end_to_end():
    r = abo_minimize_kernel(
        4096, config=ABOConfig(block_size=512, samples_per_pass=64),
        interpret=True)
    assert r.fun < 1e-6


def test_kernel_path_never_picks_interpret_itself():
    """Off the TPU the kernel path fails loudly unless the caller asks for
    the interpreter — it never falls back to it on its own, and neither
    does ``abo_minimize(use_kernel=True)`` above it."""
    cfg = ABOConfig(block_size=1024, samples_per_pass=8, n_passes=2)
    with pytest.raises(ValueError, match="interpret"):
        abo_minimize_kernel(2048, config=cfg)
    with pytest.raises(ValueError, match="interpret"):
        abo_minimize(GRIEWANK, 2048, config=dataclasses.replace(
            cfg, use_kernel=True))


# ---------------------------------------------------------------------------
# griewank eval kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,chunk", [(100, 128), (4096, 512), (5000, 1024)])
def test_griewank_kernel_vs_ref(n, chunk, rng):
    x = jnp.asarray(rng.uniform(-600, 600, n).astype(np.float32))
    got = float(griewank_eval(x, chunk=chunk, interpret=True))
    want = float(griewank(x))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_griewank_kernel_aggs_vs_ref(rng):
    x2d = jnp.asarray(rng.uniform(-600, 600, (4, 256)).astype(np.float32))
    got = griewank_aggregates_kernel(x2d.reshape(-1, 128), chunk=256,
                                     n_valid=1000, interpret=True)
    want = griewank_aggregates_ref(x2d, n_valid=1000)
    np.testing.assert_allclose(np.asarray(got[0, :3]),
                               np.asarray(want[0, :3]), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
SHAPE_SWEEP = [
    # (b, hq, hkv, sq, d, window, causal)
    (2, 4, 4, 256, 64, None, True),
    (1, 8, 2, 384, 128, None, True),      # GQA
    (2, 4, 1, 256, 64, None, True),       # MQA
    (2, 4, 4, 256, 64, 128, True),        # SWA
    (1, 2, 2, 128, 64, None, False),      # encoder (non-causal)
]


@pytest.mark.parametrize("b,hq,hkv,sq,d,win,causal", SHAPE_SWEEP)
def test_flash_kernel_vs_ref(b, hq, hkv, sq, d, win, causal, rng):
    q = jnp.asarray(rng.normal(size=(b, hq, sq, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, hkv, sq, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, hkv, sq, d)).astype(np.float32))
    o1 = flash_attention(q, k, v, causal=causal, window=win,
                         impl="interpret")
    o2 = flash_attention(q, k, v, causal=causal, window=win, impl="ref")
    assert float(jnp.max(jnp.abs(o1 - o2))) < 2e-3


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_kernel_dtypes(dtype, rng):
    q = jnp.asarray(rng.normal(size=(1, 2, 128, 64))).astype(dtype)
    k = jnp.asarray(rng.normal(size=(1, 2, 128, 64))).astype(dtype)
    v = jnp.asarray(rng.normal(size=(1, 2, 128, 64))).astype(dtype)
    o1 = flash_attention(q, k, v, impl="interpret").astype(jnp.float32)
    o2 = flash_attention(q, k, v, impl="ref").astype(jnp.float32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    assert float(jnp.max(jnp.abs(o1 - o2))) < tol


def test_flash_non_divisible_seq(rng):
    q = jnp.asarray(rng.normal(size=(1, 2, 200, 64)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 2, 200, 64)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 2, 200, 64)).astype(np.float32))
    o1 = flash_attention(q, k, v, impl="interpret")
    o2 = flash_attention(q, k, v, impl="ref")
    assert float(jnp.max(jnp.abs(o1 - o2))) < 2e-3


def test_chunked_matches_dense_property(rng):
    for _ in range(3):
        sq = int(rng.randint(16, 300))
        sk = int(rng.randint(16, 300))
        win = int(rng.randint(8, 64)) if rng.rand() < 0.5 else None
        q = jnp.asarray(rng.normal(size=(1, 2, sq, 32)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(1, 2, sk, 32)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(1, 2, sk, 32)).astype(np.float32))
        a = attention_ref(q, k, v, causal=True, window=win)
        b = attention_ref_chunked(q, k, v, causal=True, window=win,
                                  block_k=64)
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5
