"""Separable-objective algebra: the incremental O(1)-probe interface ABO exploits.

The paper's Table 3 reports ~3.9M function evaluations per second single
threaded at N=1e9 — only possible if an "FE" is an O(1) *probe* computed from
running aggregates rather than an O(N) re-evaluation (DESIGN.md §1.1). This
module formalizes that: an objective is *separable* when

    f(x) = combine( Σ_i terms(i, x_i) )

with ``terms(i, ·) -> R^{n_aggs}``. Probing a coordinate change x_i -> c then
costs O(1):

    f' = combine( aggs - terms(i, x_i) + terms(i, c) )

Products (Griewank's Π cos) are folded into the sum algebra via
log-magnitude + sign-parity aggregates.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp


def _default_agg_dtype() -> jnp.dtype:
    # Aggregates accumulate N terms; keep them in f64 when x64 is enabled so
    # that fp32 solution storage (the paper's "single precision" rows) does
    # not lose the running sums at N ~ 1e9.
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


def tree_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Sum over axis 0 with an EXPLICIT balanced association tree.

    ``x.sum(axis=0)`` leaves the accumulation order to the backend, which
    picks it per program: XLA:CPU varies it with the physical length and
    the surrounding program, and XLA:TPU lays a vmapped ``(v, tile,
    n_aggs)`` batch out differently from one ``(tile, n_aggs)`` tile. So
    the same logical sum can round differently in the dense solver's scan
    and the engine's vmapped or sharded programs. Here the tree is spelled
    out as elementwise adds (halve, add, repeat; an odd leftover rides
    along unmodified), which the compiler cannot reassociate, so any two
    programs summing the same values get the same bits. Cost is the same
    ~len(x) adds a native reduce performs.
    """
    while x.shape[0] > 1:
        k = x.shape[0] // 2
        head = x[:k] + x[k: 2 * k]
        x = head if x.shape[0] == 2 * k else \
            jnp.concatenate([head, x[2 * k:]], axis=0)
    return x[0]


@dataclasses.dataclass(frozen=True)
class SeparableObjective:
    """A sum-decomposable objective with O(1) incremental probes.

    Attributes:
      name: identifier used by benchmarks/configs.
      n_aggs: number of scalar running aggregates.
      terms: ``terms(idx, x) -> (..., n_aggs)``; ``idx`` is the 0-based global
        coordinate index, broadcastable against ``x``.
      combine: ``combine(aggs) -> f`` mapping (..., n_aggs) -> (...).
      lower/upper: uniform feasible bounds (paper's best case, s=1).
    """

    name: str
    n_aggs: int
    terms: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]
    combine: Callable[[jnp.ndarray], jnp.ndarray]
    lower: float
    upper: float
    # Optional homotopy: combine_relaxed(aggs, lam) with lam ∈ [0, 1] must
    # satisfy combine_relaxed(a, 1) == combine(a) and should decouple the
    # cross-coordinate interaction at lam=0 (e.g. Griewank's Π term).
    # ABO's continuation schedule (beyond-paper, DESIGN.md §2) anneals lam
    # over passes to escape paired-coordinate local minima that pure
    # coordinate descent provably cannot leave.
    combine_relaxed: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray] | None = None

    # ---- full evaluations ------------------------------------------------
    # Fixed reduction tile: every aggregate sum is computed as a sequential
    # accumulation of (REDUCE_TILE, n_aggs) partial sums over tiles anchored
    # at multiples of REDUCE_TILE, the last tile zero-padded to full width.
    # Because every tile reduce has the same compiled shape and tiles are
    # combined in index order, the floating-point result depends only on the
    # masked content — NOT on the physical vector length, the number of
    # trailing zeros, or whether the call is vmapped. XLA:CPU's reduction
    # grouping is length-dependent (appending even one zero to a ~3e5
    # vector changes low bits), so this invariance cannot be left to the
    # backend; the engine's bit-identity contract (gathered lane views at
    # ladder-padded widths == the dense solver's padded vector) rests on it.
    # For the same reason each tile sums through :func:`tree_sum`, not a
    # native reduce, whose order the backend may choose per layout.
    REDUCE_TILE = 4096

    def aggregates(
        self,
        x: jnp.ndarray,
        n_valid: int | None = None,
        *,
        chunk_size: int | None = None,
        agg_dtype=None,
    ) -> jnp.ndarray:
        """Masked Σ_i terms(i, x_i), streamed tile-by-tile.

        Memory is O(REDUCE_TILE) beyond the input (dynamic_slice windows —
        never a padded O(N) copy, which the paper's zero-RAM claim
        forbids). ``chunk_size`` is accepted for backward compatibility and
        ignored: the reduction tile must be one global constant or results
        would depend on the caller's chunking (see REDUCE_TILE)."""
        del chunk_size
        agg_dtype = agg_dtype or _default_agg_dtype()
        tile = self.REDUCE_TILE
        n = x.shape[0]
        n_valid = n if n_valid is None else n_valid

        def tile_sum(xc, start):
            idx = start + jnp.arange(tile)
            t = self.terms(idx, xc).astype(agg_dtype)
            mask = (idx < n_valid)[:, None].astype(agg_dtype)
            return tree_sum(t * mask)

        n_full, tail = divmod(n, tile)
        acc = jnp.zeros((self.n_aggs,), agg_dtype)
        if n_full:
            def body(acc, cid):
                start = cid * tile
                xc = jax.lax.dynamic_slice(x, (start,), (tile,))
                return acc + tile_sum(xc, start), None

            acc, _ = jax.lax.scan(body, acc, jnp.arange(n_full))
        if tail:
            xt = jnp.zeros((tile,), x.dtype).at[:tail].set(
                jax.lax.dynamic_slice(x, (n_full * tile,), (tail,)))
            acc = acc + tile_sum(xt, n_full * tile)
        return acc

    def tile_partial(self, xc, tile_idx, n_valid, *, agg_dtype=None):
        """Masked partial sum of ONE fixed-origin reduction tile.

        ``xc`` is the (REDUCE_TILE,) slice of the solution anchored at
        global coordinate ``tile_idx * REDUCE_TILE`` — content beyond the
        physical vector must be zeros (terms of masked indices are still
        *evaluated* before masking, exactly as :meth:`aggregates` does for
        its zero-padded tail). Emits the identical ops as the tile reduce
        inside :meth:`aggregates`, so folding these partials in index order
        (:meth:`fold_tile_partials`) reproduces ``aggregates`` bit-for-bit.
        The engine's spanning resync computes these per owning device and
        bit-pattern-psums the disjoint results (engine/DESIGN.md
        § Spanning lanes)."""
        agg_dtype = agg_dtype or _default_agg_dtype()
        tile = self.REDUCE_TILE
        idx = tile_idx * tile + jnp.arange(tile)
        t = self.terms(idx, xc).astype(agg_dtype)
        mask = (idx < n_valid)[:, None].astype(agg_dtype)
        return tree_sum(t * mask)

    def fold_tile_partials(self, partials, n_tiles, *, agg_dtype=None):
        """Left-fold fixed-origin tile partials in index order.

        ``partials`` is (T_pad, n_aggs) with row t holding
        ``tile_partial`` of tile t (rows at/beyond ``n_tiles`` are
        ignored); ``n_tiles`` may be traced. The fold is where-guarded —
        NOT a masked add — because adding a +0.0 row would flip a -0.0
        accumulator bit. Matches the sequential tile accumulation inside
        :meth:`aggregates` add-for-add, so the result is bit-identical to
        ``aggregates`` over the same masked content."""
        agg_dtype = agg_dtype or _default_agg_dtype()
        acc0 = jnp.zeros((self.n_aggs,), agg_dtype)

        def body(t, acc):
            return jnp.where(t < n_tiles, acc + partials[t], acc)

        return jax.lax.fori_loop(0, partials.shape[0], body, acc0)

    def value(self, x: jnp.ndarray, n_valid: int | None = None, **kw) -> jnp.ndarray:
        return self.combine(self.aggregates(x, n_valid, **kw))

    def combine_at(self, aggs: jnp.ndarray, lam) -> jnp.ndarray:
        """combine under coupling weight lam (falls back to exact combine)."""
        if self.combine_relaxed is None:
            return self.combine(aggs)
        return self.combine_relaxed(aggs, lam)

    # ---- the O(1) probe --------------------------------------------------
    def probe(
        self,
        aggs: jnp.ndarray,
        idx: jnp.ndarray,
        old: jnp.ndarray,
        new: jnp.ndarray,
    ) -> jnp.ndarray:
        """Objective after x[idx]: old -> new, other coordinates frozen.

        Broadcasts: ``idx``/``old`` of shape (B,), ``new`` of shape (B, m)
        probes every candidate of every coordinate in the block at once
        (the Jacobi tile the coord_sweep Pallas kernel computes in VMEM).
        """
        delta = self.term_delta(idx, old, new)
        return self.combine(aggs + delta)

    def term_delta(self, idx, old, new) -> jnp.ndarray:
        """terms(idx, new) - terms(idx, old), broadcast to new's shape.

        ``terms(old)`` is evaluated once per coordinate and broadcast as a
        *result* — never recomputed per candidate (m× transcendental waste).
        """
        agg_dtype = _default_agg_dtype()
        idx_b = jnp.reshape(idx, idx.shape + (1,) * (new.ndim - idx.ndim))
        t_new = self.terms(jnp.broadcast_to(idx_b, new.shape), new).astype(agg_dtype)
        t_old = self.terms(idx, old).astype(agg_dtype)          # (..., n_aggs)
        t_old = jnp.reshape(
            t_old, old.shape + (1,) * (new.ndim - old.ndim) + (self.n_aggs,))
        return t_new - t_old
