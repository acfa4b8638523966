"""Griewank benchmark (paper Eq. 3) — full, streaming, and separable forms.

    f(x) = Σ x_i²/4000 − Π cos(x_i/√i) + 1,   i = 1..d (1-based),
    domain x_i ∈ [-600, 600], global optimum f(0) = 0.

The product term is carried in log-magnitude + sign-parity space so that the
separable (incremental) algebra of :mod:`repro.objectives.base` applies and
so the full evaluation stays stable at d ~ 1e9 (a naive Π underflows).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.objectives.base import SeparableObjective
from repro.objectives.pair_expm1 import expm1_f64

# |cos| is clamped before the log so that removing a term (agg - log|cos|)
# never produces inf - inf. exp(-103) == 0 in fp32 anyway, so the clamp is
# invisible to the objective value.
_LOG_TINY = {jnp.dtype("float32"): 1e-38, jnp.dtype("float64"): 1e-300}


def _terms(idx: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Per-coordinate aggregate contributions: [x²/4000, log|cos|, 1{cos<0}].

    log|cos(u)| is computed as ½·log1p(−sin²u) where |cos| is large — exact
    to 1 ulp near the optimum (u→0), where the naive log(cos) loses all the
    bits that the paper's ~1e-13 best-objective values live in.
    """
    dt = x.dtype
    i1 = (idx + 1).astype(dt)  # Griewank's i is 1-based
    u = x * jax.lax.rsqrt(i1)
    c = jnp.cos(u)
    s2 = jnp.square(jnp.sin(u))
    tiny = _LOG_TINY.get(jnp.dtype(dt), 1e-38)
    log_abs = jnp.where(
        s2 < 0.5,
        0.5 * jnp.log1p(-jnp.minimum(s2, 0.999999)),
        jnp.log(jnp.maximum(jnp.abs(c), tiny)),
    )
    neg = (c < 0).astype(dt)
    return jnp.stack([x * x * (1.0 / 4000.0), log_abs, neg], axis=-1)


def _combine_f64(s, log_p, positive, lam) -> jnp.ndarray:
    """f_λ from float64 aggregates, both signs from one ``q = expm1(L)``
    computed in float32 pairs (:mod:`repro.objectives.pair_expm1`): a
    TPU v5e has no float64 unit, and XLA's emulated float64 ``expm1``
    cost more than the rest of the probe together."""
    q = expm1_f64(log_p)
    return jnp.where(positive, s - lam * q, s + lam * (q + 2.0))


def _combine(aggs: jnp.ndarray) -> jnp.ndarray:
    """f = S − (−1)^K · exp(L) + 1 from aggs = [S, L, K].

    The +1 / −exp(L) cancellation is the whole objective near the optimum
    (f → 0 while both terms → 1), so the positive-sign branch uses expm1.
    """
    s, log_p, k = aggs[..., 0], aggs[..., 1], aggs[..., 2]
    positive = jnp.mod(k, 2.0) < 0.5
    if log_p.dtype == jnp.float64:
        return _combine_f64(s, log_p, positive, 1.0)
    return jnp.where(positive,
                     s - jnp.expm1(log_p),
                     s + jnp.exp(log_p) + 1.0)


def _combine_relaxed(aggs: jnp.ndarray, lam) -> jnp.ndarray:
    """Homotopy f_λ = S − λ·(−1)^K·exp(L) + λ:  f_0 = S (separable),
    f_1 = f exactly, and f_λ(x*) = 0 for every λ."""
    s, log_p, k = aggs[..., 0], aggs[..., 1], aggs[..., 2]
    positive = jnp.mod(k, 2.0) < 0.5
    if log_p.dtype == jnp.float64:
        return _combine_f64(s, log_p, positive, lam)
    return jnp.where(positive,
                     s - lam * jnp.expm1(log_p),
                     s + lam * (jnp.exp(log_p) + 1.0))


GRIEWANK = SeparableObjective(
    name="griewank",
    n_aggs=3,
    terms=_terms,
    combine=_combine,
    lower=-600.0,
    upper=600.0,
    combine_relaxed=_combine_relaxed,
)


def griewank_naive(x: jnp.ndarray) -> jnp.ndarray:
    """Textbook direct evaluation (unstable for large d; test oracle only)."""
    i1 = jnp.arange(1, x.shape[-1] + 1, dtype=x.dtype)
    return (jnp.sum(x * x, axis=-1) / 4000.0
            - jnp.prod(jnp.cos(x / jnp.sqrt(i1)), axis=-1) + 1.0)


def griewank(x: jnp.ndarray, n_valid: int | None = None, **kw) -> jnp.ndarray:
    """Stable full evaluation via the aggregate form (streams in chunks)."""
    return GRIEWANK.value(x, n_valid, **kw)
