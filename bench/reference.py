"""Plain reference ABO solver: the yardstick that decides ``correct``.

Written from the algorithm's description, not from the program: it
imports nothing of ``repro`` and takes nothing the program has made.
One solve is

- a start drawn per coordinate from the job's seed (coordinate ``i``
  from ``fold_in(PRNGKey(seed), i)``, uniform over the domain);
- ``n_passes`` passes. Pass ``p`` sweeps the coordinates in blocks of
  ``block_size``, one block after another. Every coordinate of a block
  is probed at ``m - 1`` points of a linear grid plus its incumbent:
  over the whole domain in pass 0, over ``x +- w_p`` later, with
  ``w_p = 0.5 * shrink**p`` of the domain and
  ``shrink = 2 * safety / (m - 2)``. A probe's value is the objective
  with that one coordinate changed, from running aggregates (the
  objective is a function of sums of per-coordinate terms). Each
  coordinate takes its best probe (the first on ties); the block's
  moves commit together, and only if they do not worsen the objective.
  The aggregates then carry on to the next block;
- the probes of pass ``p`` judge the relaxed objective, whose coupling
  weight ``lam`` rises linearly from 0 in pass 0 to 1 in the last pass;
- after each pass the aggregates are summed afresh from ``x``, and the
  history records the exact objective.

Problems of at most 128 coordinates use blocks of one. Coordinates past
``n`` in the last block are frozen and left out of every sum.

``x_dtype`` is the solution's precision and ``agg_dtype`` the
aggregates'. The control of ``check.py`` runs this same code one step
lower in precision.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

SMALL_N = 128        # at or below this many coordinates, blocks of one


# ---- objectives: per-coordinate terms and how their sums combine --------
def _griewank_terms(i, x):
    """[x^2 / 4000, log|cos(x / sqrt(i + 1))|, 1 if that cos < 0]."""
    u = x / jnp.sqrt((i + 1).astype(x.dtype))
    c = jnp.cos(u)
    s2 = jnp.sin(u) ** 2
    # log|cos u| = log(1 - sin^2 u) / 2, accurate where cos is near 1
    log_abs = jnp.where(s2 < 0.5, 0.5 * jnp.log1p(-jnp.minimum(s2, 0.999999)),
                        jnp.log(jnp.maximum(jnp.abs(c), 1e-38)))
    return jnp.stack([x * x / 4000.0, log_abs, (c < 0).astype(x.dtype)], -1)


def _griewank_value(a, lam):
    """f = S - lam * (-1)^K * exp(L) + lam; lam = 1 is Griewank itself."""
    s, log_p, k = a[..., 0], a[..., 1], a[..., 2]
    even = jnp.mod(k, 2.0) < 0.5
    return jnp.where(even, s - lam * jnp.expm1(log_p),
                     s + lam * (jnp.exp(log_p) + 1.0))


def _shifted_sphere_terms(i, x):
    """(x - 3 sin(i + 1))^2: the optimum lies off every symmetric grid."""
    d = x - 3.0 * jnp.sin((i + 1).astype(x.dtype))
    return (d * d)[..., None]


def _rastrigin_terms(i, x):
    """[x^2 - 10 cos(2 pi x), 1]: the count carries the 10 n offset."""
    two_pi = jnp.asarray(2.0 * math.pi, x.dtype)
    return jnp.stack([x * x - 10.0 * jnp.cos(two_pi * x),
                      jnp.ones_like(x)], -1)


def _sum_value(a, lam):
    return a[..., 0]


def _rastrigin_value(a, lam):
    return a[..., 0] + 10.0 * a[..., 1]


# name -> (terms, value(aggregates, lam), lower, upper)
OBJECTIVES = {
    "griewank": (_griewank_terms, _griewank_value, -600.0, 600.0),
    "shifted_sphere": (_shifted_sphere_terms, _sum_value, -100.0, 100.0),
    "rastrigin": (_rastrigin_terms, _rastrigin_value, -5.12, 5.12),
}


# ---- the solve ------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("dtype", "lo", "hi"))
def _draw(seed, idx, *, dtype, lo, hi):
    key = jax.random.PRNGKey(seed)
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(idx)
    return jax.vmap(lambda k: jax.random.uniform(k, (), dtype, lo, hi))(keys)


def start(seed: int, n: int, dtype, lo: float, hi: float,
          chunk: int = 1 << 20) -> jnp.ndarray:
    """The seeded start: coordinate i uniform from fold_in(key, i)."""
    seed = jnp.asarray(seed, jnp.uint32)
    parts = []
    for s in range(0, n, chunk):
        idx = jnp.arange(s, s + chunk, dtype=jnp.uint32)
        parts.append(_draw(seed, idx, dtype=jnp.dtype(dtype), lo=lo, hi=hi))
    return jnp.concatenate(parts)[:n]


@functools.partial(jax.jit, static_argnames=(
    "objective", "m", "n_passes", "block", "safety", "agg_dtype"))
def _solve(x, n, *, objective, m, n_passes, block, safety, agg_dtype):
    """``x`` is the start padded to any whole number of blocks; ``n``
    (traced) is the true size, so one program serves every n that fits."""
    terms, value, lo, hi = OBJECTIVES[objective]
    xdt = x.dtype
    n_blocks = (n + block - 1) // block
    shrink = 2.0 * safety / max(m - 2, 1)
    offs = jnp.linspace(-1.0, 1.0, m - 1, dtype=xdt)

    def agg_terms(i, v):
        return terms(i, v).astype(agg_dtype)

    def aggregates(x):
        i = jnp.arange(x.shape[0])
        t = agg_terms(i, x) * (i < n)[:, None].astype(agg_dtype)
        return t.sum(0)

    def one_pass(p, carry):
        x, aggs, hist = carry
        first = p == 0
        half = jnp.asarray(0.5 * shrink ** np.arange(n_passes),
                           agg_dtype)[p]
        lam = (jnp.asarray(np.arange(n_passes) / (n_passes - 1), agg_dtype)[p]
               if n_passes > 1 else jnp.ones((), agg_dtype))

        def one_block(b, carry):
            x, aggs = carry
            i = b * block + jnp.arange(block)
            xb = jax.lax.dynamic_slice(x, (b * block,), (block,))
            valid = i < n
            span = jnp.asarray(hi - lo, xdt)
            centre = jnp.where(first, jnp.asarray(0.5 * (lo + hi), xdt), xb)
            width = jnp.where(first, 0.5 * span, half.astype(xdt) * span)
            grid = jnp.clip(centre[:, None] + width * offs[None, :], lo, hi)
            cands = jnp.concatenate([grid, xb[:, None]], 1)      # (B, m)
            cands = jnp.where(valid[:, None], cands, xb[:, None])
            t_old = agg_terms(i, xb)                             # (B, A)
            t_new = agg_terms(i[:, None], cands)                 # (B, m, A)
            delta = t_new - t_old[:, None, :]
            f = value(aggs + delta, lam)                         # (B, m)
            pick = jnp.argmin(f, axis=1)
            x_new = jnp.take_along_axis(cands, pick[:, None], 1)[:, 0]
            d = jnp.take_along_axis(delta, pick[:, None, None], 1)[:, 0]
            d = jnp.where(valid[:, None], d, 0.0)
            aggs_new = aggs + d.sum(0)
            keep = value(aggs_new, lam) <= value(aggs, lam)
            x_new = jnp.where(keep, x_new, xb)
            aggs = jnp.where(keep, aggs_new, aggs)
            return jax.lax.dynamic_update_slice(x, x_new, (b * block,)), aggs

        x, _ = jax.lax.fori_loop(0, n_blocks, one_block, (x, aggs))
        aggs = aggregates(x)
        return x, aggs, hist.at[p].set(value(aggs, 1.0))

    x, aggs, hist = jax.lax.fori_loop(
        0, n_passes, one_pass,
        (x, aggregates(x), jnp.zeros((n_passes,), agg_dtype)))
    return x, value(aggs, 1.0), hist


def solve(objective: str, n: int, *, m: int, n_passes: int, block: int,
          seed: int, safety: float = 2.0, x_dtype=jnp.float32,
          agg_dtype=jnp.float32, pad_to: int | None = None) -> dict:
    """One reference solve; returns host ``x`` (float32), ``fun`` and
    ``history`` (float64). ``pad_to`` (at least ``n``) fixes the compiled
    length, so solves of many sizes share one program."""
    _, _, lo, hi = OBJECTIVES[objective]
    block = 1 if n <= SMALL_N else block
    length = max(n, pad_to or n)
    n_pad = -(-length // block) * block
    x = start(seed, n, jnp.float32, lo, hi).astype(x_dtype)
    x = jnp.concatenate([x, jnp.zeros((n_pad - n,), x_dtype)])
    x, fun, hist = _solve(x, jnp.asarray(n, jnp.int32), objective=objective,
                          m=m, n_passes=n_passes, block=block, safety=safety,
                          agg_dtype=jnp.dtype(agg_dtype))
    return {"x": np.asarray(x[:n].astype(jnp.float32)),
            "fun": float(fun), "history": np.asarray(hist, np.float64)}


def value64(objective: str, x: np.ndarray) -> float:
    """The objective of a host vector, summed in float64 on the host."""
    terms, value, _, _ = OBJECTIVES[objective]
    total = None
    step = 1 << 22
    for s in range(0, len(x), step):
        xs = np.asarray(x[s: s + step], np.float64)
        i = np.arange(s, s + len(xs))
        t = np.asarray(_np_terms(objective, i, xs)).sum(0)
        total = t if total is None else total + t
    return float(_np_value(objective, total))


def _np_terms(objective, i, x):
    if objective == "griewank":
        u = x / np.sqrt(i + 1.0)
        c = np.cos(u)
        s2 = np.sin(u) ** 2
        log_abs = np.where(s2 < 0.5, 0.5 * np.log1p(-np.minimum(s2, 1 - 1e-15)),
                           np.log(np.maximum(np.abs(c), 1e-300)))
        return np.stack([x * x / 4000.0, log_abs, (c < 0) * 1.0], -1)
    if objective == "shifted_sphere":
        return ((x - 3.0 * np.sin(i + 1.0)) ** 2)[:, None]
    if objective == "rastrigin":
        return np.stack([x * x - 10.0 * np.cos(2 * np.pi * x),
                         np.ones_like(x)], -1)
    raise KeyError(objective)


def _np_value(objective, a):
    if objective == "griewank":
        s, log_p, k = a
        return s - np.expm1(log_p) if k % 2 < 0.5 else s + np.exp(log_p) + 1.0
    if objective == "rastrigin":
        return a[0] + 10.0 * a[1]
    return a[0]
