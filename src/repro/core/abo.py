"""Amo-Boateng Optimization (ABO) — the paper's core algorithm, in JAX.

Faithful structure (DESIGN.md §1):
  * every pass samples each parameter space **linearly** (a deterministic
    candidate grid per coordinate — the paper's Fig. 1 arrows),
  * probes are O(1) via the separable-aggregate algebra (the only reading of
    Table 3 consistent with 3.9M FE/s single-threaded at N=1e9),
  * memory = the solution vector + O(block·m) scratch + n_aggs scalars —
    the paper's "zero additional RAM",
  * compute = O(m·N) with m = passes × samples_per_pass (paper Eq. 5;
    Table 3 shows m ≈ 250).

Beyond-paper adaptations (DESIGN.md §3): coordinates are swept in blocks of
``block_size`` with Jacobi commits (all coordinates of a block move at once
against frozen aggregates), guarded so the committed objective never
regresses. This is what makes the sweep a dense (B, m) tile — VPU/MXU-shaped
on TPU (see kernels/coord_sweep) — instead of a scalar loop.
"""
# repro: hot-path — the per-pass sweep; every host sync below is a designed one
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.objectives.base import SeparableObjective, tree_sum


@dataclasses.dataclass(frozen=True)
class ABOConfig:
    """Sampling-rate schedule. Defaults reproduce the paper's m ≈ 250·N FE."""

    samples_per_pass: int = 50   # candidates per coordinate per pass (incl. incumbent)
    n_passes: int = 5            # total probes/coordinate m = 5 × 50 = 250
    block_size: int = 4096      # coordinates swept per Jacobi tile
    shrink: float | None = None  # window factor per pass; None -> 2·safety/(m-2)
    safety: float = 2.0          # window covers ± safety × previous grid spacing
    guard_commits: bool = True   # reject a block commit that worsens f (monotone)
    use_kernel: bool = False     # route the probe tile through the Pallas kernel
    # Spanning decomposition: when set, the lane is divided into fixed
    # contiguous shards of ``span_coords`` coordinates. Blocks run
    # Gauss-Seidel WITHIN a shard (carried aggregates, as always) but
    # Jacobi ACROSS shards: at each shard's first block the carried
    # aggregates reset to the pass-entry snapshot, so every shard sweeps
    # against the same frozen cross-shard state. This is a *math* knob —
    # it changes the trajectory deterministically and applies identically
    # at every device count — which is exactly what lets the engine stripe
    # one lane's pages across the mesh and still match the dense solver
    # bit-for-bit (see engine/DESIGN.md § Spanning lanes).
    span_coords: int | None = None
    # "linear": anneal the cross-coordinate coupling weight λ from 0 to 1
    # over passes (continuation; escapes paired local minima — DESIGN.md §2).
    # "none": the paper-pure exact objective in every pass.
    coupling_schedule: str = "linear"

    def __post_init__(self):
        if self.samples_per_pass < 3:
            raise ValueError(
                f"samples_per_pass must be >= 3, got {self.samples_per_pass}: "
                "m=2 degenerates the candidate grid's linspace to a single "
                "point (the incumbent plus one fixed probe), so the window "
                "never refines")
        if self.n_passes < 1:
            raise ValueError(
                f"n_passes must be >= 1, got {self.n_passes}: ABO needs at "
                "least the full-interval pass 0")
        if self.block_size < 1:
            raise ValueError(
                f"block_size must be >= 1, got {self.block_size}: each Jacobi "
                "tile must hold at least one coordinate")
        if self.span_coords is not None:
            if self.span_coords < 1:
                raise ValueError(
                    f"span_coords must be >= 1, got {self.span_coords}")
            if self.span_coords % self.block_size != 0:
                raise ValueError(
                    f"span_coords ({self.span_coords}) must be a multiple of "
                    f"block_size ({self.block_size}): a shard boundary inside "
                    "a Jacobi tile would split one block commit across two "
                    "aggregate snapshots")

    def resolved_shrink(self) -> float:
        if self.shrink is not None:
            return self.shrink
        return 2.0 * self.safety / max(self.samples_per_pass - 2, 1)


@dataclasses.dataclass
class ABOResult:
    x: jnp.ndarray           # (n,) solution (unpadded)
    fun: float               # objective at x
    fe: int                  # probe-FE count (paper's FE semantics)
    history: jnp.ndarray     # (n_passes,) objective after each pass
    n: int
    config: ABOConfig


def _candidate_grid(xb, lo, hi, half_width, m, is_first_pass):
    """(B, m) linear sampling grid; incumbent is always candidate column m-1.

    Pass 0 ignores the incumbent position and sweeps the full feasible
    interval (the paper's "sampling each parameter space linearly"); later
    passes sweep a shrinking window centred on the incumbent.

    ``lo``/``hi`` may be scalars (uniform bounds — the paper's s=1 best
    case) or (B,) arrays (per-coordinate parameter spaces — the s=3 worst
    case of Eq. 6, costing exactly the extra O(N) bound vectors the paper
    predicts). ``half_width`` is a fraction of the full range in [0, 0.5].
    """
    dt = xb.dtype
    lo = jnp.broadcast_to(jnp.asarray(lo, dt), xb.shape)[:, None]   # (B, 1)
    hi = jnp.broadcast_to(jnp.asarray(hi, dt), xb.shape)[:, None]
    span = hi - lo
    center = jnp.where(is_first_pass, 0.5 * (lo + hi), xb[:, None])
    w = jnp.where(is_first_pass, 0.5 * span,
                  jnp.asarray(half_width, dt) * span)
    offs = jnp.linspace(-1.0, 1.0, m - 1, dtype=dt)          # (m-1,)
    grid = jnp.clip(center + _rounded(w * offs[None, :]), lo, hi)
    return jnp.concatenate([grid, xb[:, None]], axis=1)       # (B, m)


def _rounded(p):
    """``p``, rounded on its own before the caller adds it to anything.

    A compiler may contract ``c + a * b`` into one fused multiply-add,
    which skips the product's rounding, and whether it does depends on
    the program around it: XLA:CPU compiles a dense block loop of small
    blocks as one kernel and contracts the candidate grid there, but not
    in the engine's row loop, which puts the two a few ulps apart. The
    product goes through its integer bits, xored with ``p != p`` (zero
    unless ``p`` is NaN, which stays NaN), which no compiler can fold
    away or contract through."""
    bits = jax.lax.bitcast_convert_type(
        p, jnp.dtype(f"int{8 * p.dtype.itemsize}"))
    return jax.lax.bitcast_convert_type(
        bits ^ (p != p).astype(bits.dtype), p.dtype)


def _first_min(op, acc):
    """``jnp.argmin``'s reducer (``lax._ArgMinMaxReducer(lt)``) over
    ``(value, index, *payload)``: the value moves on ``lt | isnan``, the
    index and every payload move together on that or on an equal value at
    a lower index."""
    (v, i, *p), (av, ai, *ap) = op, acc
    pick_val = jax.lax.lt(v, av) | jax.lax.ne(v, v)
    pick_idx = pick_val | (jax.lax.eq(v, av) & jax.lax.lt(i, ai))
    return (jax.lax.select(pick_val, v, av),
            jax.lax.select(pick_idx, i, ai),
            *(jax.lax.select(pick_idx, a, b) for a, b in zip(p, ap)))


def _select_first_min(f_cand, cands, delta):
    """``(x_sel, d_sel)`` at ``jnp.argmin(f_cand, 1)``, picked inside the
    argmin's own reduce instead of gathered after it: bit-equal to
    ``take_along_axis`` of ``cands`` (B, m) and ``delta`` (B, m, A) at
    that index (first minimum, a NaN first). Every operand is a (B, m)
    array; a gather of ``delta`` made the TPU compiler relay the (B, m, A)
    tile out with A minor, padded from A to 128 lanes, on every block.
    The identity's index is m, above every real one, so a real
    element wins each tie with it: a row of +inf selects its column 0,
    never the identity's zero payload."""
    b, m = f_cand.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (b, m), 1)
    deltas = [delta[..., a] for a in range(delta.shape[-1])]
    # XLA refuses a reduce over floats of two widths: a candidate narrower
    # than the values (float32 x beside float64 aggregates) rides as its
    # bit pattern and is cast back after the reduce
    narrow = cands.dtype != f_cand.dtype
    payload = (jax.lax.bitcast_convert_type(
        cands, jnp.dtype(f"int{8 * cands.dtype.itemsize}")) if narrow
        else cands)
    operands = (f_cand, iota, payload, *deltas)
    init = (jnp.array(jnp.inf, f_cand.dtype), jnp.array(m, jnp.int32),
            *(jnp.zeros((), o.dtype) for o in operands[2:]))
    _, _, x_sel, *d_sel = jax.lax.reduce(operands, init, _first_min, (1,))
    if narrow:
        x_sel = jax.lax.bitcast_convert_type(x_sel, cands.dtype)
    return x_sel, jnp.stack(d_sel, axis=-1)


def _block_step(obj, cfg, probe_tile, xb, aggs, idx, valid, half_width,
                is_first_pass, lam, lo, hi):
    """Probe-and-commit one Jacobi block: the (B, m) candidate tile, the
    first-minimum selection, and the guarded aggregate commit.

    The selection is one variadic reduce (:func:`_select_first_min`) that
    carries the candidate and its per-aggregate deltas beside argmin's
    (value, index) pair, bit-equal to argmin plus two gathers. On a TPU
    v5e the gathers took two thirds of the engine's row loop: the delta
    gather wanted a layout copy of the (B, m, A) tile, padded 42x.

    This is the single block-level primitive BOTH sweep layouts execute:
    :func:`_sweep_pass` scans it over a dense padded vector (abo_minimize),
    and the engine's row-compacted page sweep (repro.engine.batched) vmaps
    it over gathered lane rows — sharing the code path is what makes the
    two layouts bit-identical per lane.
    """
    m = cfg.samples_per_pass
    agg_dt = aggs.dtype
    cands = _candidate_grid(xb, lo, hi, half_width, m, is_first_pass)
    # Padding coordinates are frozen: their only candidate is themselves.
    cands = jnp.where(valid[:, None], cands, xb[:, None])

    f_cand, delta = probe_tile(aggs, idx, xb, cands, lam)  # (B, m), (B, m, A)
    x_sel, d_sel = _select_first_min(f_cand, cands, delta)  # (B,), (B, A)
    # a frozen padding coordinate's delta is t(x) - t(x), which is not 0
    # once the compiler contracts it into an FMA (the rounding error of
    # t(x)): zero it, or padding noise decides the guarded commit
    d_sel = jnp.where(valid[:, None], d_sel, jnp.zeros_like(d_sel))
    # tree_sum, not d_sel.sum(0): the commit reduction must round the
    # same way in the dense scan and the engine's vmapped sweep
    aggs_new = aggs + tree_sum(d_sel).astype(agg_dt)

    if cfg.guard_commits:
        accept = obj.combine_at(aggs_new, lam) <= obj.combine_at(aggs, lam)
        x_sel = jnp.where(accept, x_sel, xb)
        aggs_new = jnp.where(accept, aggs_new, aggs)
    return x_sel, aggs_new


def pass_schedule(cfg: ABOConfig, pass_idx, agg_dtype):
    """(half_width, lam) for a pass index — the shrink/continuation
    schedule of :func:`abo_pass_step`, factored out so the engine's row
    sweep computes the identical per-lane values. ``pass_idx`` may be a
    scalar or a traced array (per-lane schedules under vmap).

    Both values are host-precomputed tables indexed by ``pass_idx``, NOT
    on-device ``shrink ** p`` arithmetic: a traced-exponent pow lowers
    through exp/log whose bits can differ between compilation contexts
    (the dense solver's scan vs the engine's vmapped row sweep), and a
    one-ulp half_width difference shifts every candidate grid — the
    avalanche that breaks engine-vs-abo_minimize bit-identity the moment
    aggregates are large enough for probe ties. A table lookup is the
    same bits everywhere (and exact, being evaluated in float64). OOB
    indices clip: the engine's scratch lane keeps incrementing its
    pass_idx past n_passes and must stay inert, not out-of-range."""
    ps = np.arange(cfg.n_passes, dtype=np.float64)
    hw_tab = jnp.asarray(0.5 * cfg.resolved_shrink() ** ps, agg_dtype)
    half_width = jnp.take(hw_tab, pass_idx, mode="clip")
    if cfg.coupling_schedule == "linear" and cfg.n_passes > 1:
        lam_tab = jnp.asarray(ps / (cfg.n_passes - 1), agg_dtype)
        lam = jnp.take(lam_tab, pass_idx, mode="clip")
    else:
        # match pass_idx's shape (not a bare scalar): the engine computes
        # the schedule for a whole gathered row at once and vmaps the
        # block step over it, so lam must be mappable alongside half_width
        lam = jnp.broadcast_to(jnp.ones((), agg_dtype),
                               jnp.shape(pass_idx))
    return half_width, lam


def _sweep_pass(obj, x, aggs, n_valid, half_width, pass_idx, lam, cfg,
                probe_tile, bounds=None):
    """One full pass: scan Jacobi block sweeps over the (padded) solution.

    The :func:`_block_step` call is fenced with ``optimization_barrier``
    (inputs and outputs), and the engine's row sweep fences its vmapped
    call the same way — including inside the sharded engine's shard_map
    partition, a third compilation context (the barrier composes inside
    shard_map; it has no vmap batching rule, so it always wraps OUTSIDE
    the vmap). The fences pin the probe/commit math into a
    self-contained fusion region with identical content in every program,
    so XLA cannot specialize its instruction selection (FMA contraction,
    loop-context vectorization) differently per surrounding program —
    which it otherwise does: the same block step compiled inside the
    engine's dynamic row loop rounds differently from this scan, flipping
    argmin picks wherever two candidates probe within an ulp. That broke
    engine-vs-abo_minimize bit-identity in any regime where trajectories
    don't collapse onto exact grid points.
    """
    n_pad = x.shape[0]
    bsz = cfg.block_size
    n_blocks = n_pad // bsz
    first = pass_idx == 0
    # Spanning decomposition: shards of span_coords coordinates run
    # Gauss-Seidel within, Jacobi across — at every shard's first block the
    # carried aggregates reset to the pass-entry snapshot ``aggs0``, so each
    # shard's sweep sees only the previous pass's cross-shard state. The
    # reset makes shard sweeps within a pass provably independent (another
    # shard's current-pass x enters a block step only through the carried
    # aggregates), which is what lets the engine run them device-parallel
    # and still reproduce THIS dense scan bit-for-bit. Codegen is emitted
    # only when span_coords is set: the span-free program is untouched.
    rows_per_shard = (cfg.span_coords // bsz
                      if cfg.span_coords is not None else None)
    aggs0 = aggs

    def block_body(carry, blk):
        x, aggs = carry
        if rows_per_shard is not None:
            # At blk == 0 this is a bitwise no-op (carried == pass-entry).
            aggs = jnp.where(blk % rows_per_shard == 0, aggs0, aggs)
        start = blk * bsz
        xb = jax.lax.dynamic_slice(x, (start,), (bsz,))
        idx = start + jnp.arange(bsz)
        valid = idx < n_valid

        if bounds is not None:       # per-coordinate spaces (paper's s=3)
            lo = jax.lax.dynamic_slice(bounds[0], (start,), (bsz,))
            hi = jax.lax.dynamic_slice(bounds[1], (start,), (bsz,))
            xb, ag, idx, valid, hw, fst, lm, lo, hi = \
                jax.lax.optimization_barrier(
                    (xb, aggs, idx, valid, half_width, first, lam, lo, hi))
        else:
            lo, hi = obj.lower, obj.upper
            xb, ag, idx, valid, hw, fst, lm = \
                jax.lax.optimization_barrier(
                    (xb, aggs, idx, valid, half_width, first, lam))
        x_sel, aggs = jax.lax.optimization_barrier(_block_step(
            obj, cfg, probe_tile, xb, ag, idx, valid, hw, fst, lm, lo, hi))
        x = jax.lax.dynamic_update_slice(x, x_sel, (start,))
        return (x, aggs), None

    (x, aggs), _ = jax.lax.scan(block_body, (x, aggs), jnp.arange(n_blocks))
    return x, aggs


@functools.lru_cache(maxsize=None)
def _default_probe_tile(obj):
    # lru_cache keeps the closure's identity stable per objective so jitted
    # callers (abo_minimize, the engine's compile cache) hit their caches
    # across calls instead of recompiling per solve.
    def probe_tile(aggs, idx, xb, cands, lam):
        delta = obj.term_delta(idx, xb, cands)        # (B, m, A)
        return obj.combine_at(aggs + delta, lam), delta
    return probe_tile


# --------------------------------------------------------------------------
# Reentrant pass-level API. ``abo_init`` builds an ABOState; one call to
# ``abo_pass_step`` advances it by exactly one pass. ``abo_minimize`` is a
# fori_loop over the same step; the batched engine (repro.engine) vmaps it
# across solve lanes — both paths execute identical per-pass math.
# --------------------------------------------------------------------------
@dataclasses.dataclass
class ABOState:
    """Complete in-flight solver state at a pass boundary (a JAX pytree).

    Everything ABO needs to continue — and everything a checkpoint needs to
    capture — lives here: the (padded) solution, the running aggregates, the
    per-pass objective history, the next pass index, and the true coordinate
    count (traced, so same-padded-n jobs can share a compiled executable).
    """

    x: jnp.ndarray          # (n_pad,) padded solution vector
    aggs: jnp.ndarray       # (n_aggs,) running aggregates
    hist: jnp.ndarray       # (n_passes,) objective after each pass
    pass_idx: jnp.ndarray   # () int32, next pass to run
    n_valid: jnp.ndarray    # () int32, true n (padding coords are frozen)


jax.tree_util.register_dataclass(
    ABOState,
    data_fields=["x", "aggs", "hist", "pass_idx", "n_valid"],
    meta_fields=[],
)


def effective_config(cfg: ABOConfig, n: int) -> ABOConfig:
    """The block size actually used for an n-dimensional solve.

    Tiny problems get exact Gauss-Seidel coordinate descent (block=1):
    sequential commits resolve the product-term coupling that Jacobi tiles
    can miscoordinate on when a block spans most of the problem. At scale,
    Jacobi tiles are the paper's parallel variant (Eq. 7) and the coupling
    per block is O(block/N) — negligible.
    """
    bsz = 1 if n <= 128 else cfg.block_size
    if bsz != cfg.block_size:
        cfg = dataclasses.replace(cfg, block_size=bsz)
    # A span covering the whole problem is exactly the span-free program
    # (the reset fires only at block 0, where it is a bitwise no-op) —
    # normalize it away so family keys, plan signatures and codegen agree.
    if cfg.span_coords is not None and cfg.span_coords >= n:
        cfg = dataclasses.replace(cfg, span_coords=None)
    return cfg


def abo_make_state(obj: SeparableObjective, x: jnp.ndarray, n_valid,
                   cfg: ABOConfig) -> ABOState:
    """Pass-0 state from a (padded) start vector. Traceable — the engine
    builds lane states inside its jitted place op with this."""
    aggs = obj.aggregates(x, n_valid)
    return ABOState(
        x=x,
        aggs=aggs,
        hist=jnp.zeros((cfg.n_passes,), aggs.dtype),
        pass_idx=jnp.zeros((), jnp.int32),
        n_valid=jnp.asarray(n_valid, jnp.int32),
    )


def seeded_start(seed, n_pad, dtype, lo, hi, chunk=1 << 20):
    """Pad-invariant random feasible start over ``(n_pad,)``.

    Coordinate ``i`` is drawn from its own counter-derived key
    (``fold_in(PRNGKey(seed), i)``), so its value depends only on
    ``(seed, i)`` — never on the padded length. One seeded job therefore
    starts from bit-identical coordinates whichever canonical pad size the
    engine's ladder buckets it into (a plain ``uniform(key, (n_pad,))``
    draw does NOT have this property: threefry splits the counter array in
    half, coupling every element's bits to the total length).

    Large n is drawn in ``chunk``-sized segments (same per-coordinate
    bits) so live scratch stays O(chunk) keys beyond the output vector —
    the zero-RAM contract's init must not allocate a 2x-output key array
    at the paper's n ~ 1e9.

    Traceable: ``seed`` may be a Python int or a traced unsigned scalar
    (the engine's batched lane placement) — both reach the same PRNG key.
    """
    key = jax.random.PRNGKey(seed)

    def draw(idx):
        ks = jax.vmap(lambda i: jax.random.fold_in(key, i))(idx)
        return jax.vmap(
            lambda k: jax.random.uniform(k, (), dtype, lo, hi))(ks)

    if n_pad <= chunk:
        return draw(jnp.arange(n_pad, dtype=jnp.uint32))
    n_chunks = -(-n_pad // chunk)
    out = jax.lax.map(
        lambda c: draw(c * chunk + jnp.arange(chunk, dtype=jnp.uint32)),
        jnp.arange(n_chunks, dtype=jnp.uint32))
    return out.reshape(n_chunks * chunk)[:n_pad]


def seeded_at(seed, idx, dtype, lo, hi):
    """:func:`seeded_start`'s per-coordinate draw at arbitrary global
    indices: the identical ``(seed, i) -> value`` map (same fold_in, same
    uniform), exposed for layouts holding a non-contiguous coordinate
    subset — the engine's striped spanning pages, where each device seeds
    only the coordinates of the pages it owns. ``idx`` is a (k,) uint32
    array of global coordinate indices."""
    key = jax.random.PRNGKey(seed)
    ks = jax.vmap(lambda i: jax.random.fold_in(key, i))(idx)
    return jax.vmap(lambda k: jax.random.uniform(k, (), dtype, lo, hi))(ks)


def _init_x(obj, n, n_pad, x0, dtype, seed, bounds):
    """The start vector + padded bounds (host-side, a handful of ops)."""
    bnds = None
    if bounds is not None:
        # the paper's s=3 case: two extra O(N) vectors, nothing else
        lo = jnp.full((n_pad,), obj.lower, dtype).at[:n].set(
            jnp.asarray(bounds[0], dtype))
        hi = jnp.full((n_pad,), obj.upper, dtype).at[:n].set(
            jnp.asarray(bounds[1], dtype))
        bnds = (lo, hi)
    if x0 is not None:
        x = jnp.zeros((n_pad,), dtype).at[:n].set(jnp.asarray(x0, dtype))
    elif seed is not None:
        # pad-invariant per-coordinate draw — bit-identical start whichever
        # canonical pad size serves this n (engine ladder bucketing)
        x = seeded_start(seed, n_pad, dtype, obj.lower, obj.upper)
        if bnds is not None:
            x = bnds[0] + (bnds[1] - bnds[0]) * (x - obj.lower) \
                / (obj.upper - obj.lower)
    else:
        # Deterministic off-centre start (golden-section point) — midpoint
        # would coincide with the optimum of symmetric benchmark domains.
        if bnds is not None:
            x = bnds[0] + 0.6180339887 * (bnds[1] - bnds[0])
        else:
            x = jnp.full((n_pad,), obj.lower
                         + 0.6180339887 * (obj.upper - obj.lower), dtype)
    return x, bnds


def abo_init(
    obj: SeparableObjective,
    n: int,
    *,
    config: ABOConfig | None = None,
    x0: jnp.ndarray | None = None,
    dtype: Any = jnp.float32,
    seed: int | None = None,
    bounds: tuple[jnp.ndarray, jnp.ndarray] | None = None,
) -> tuple[ABOState, ABOConfig, tuple[jnp.ndarray, jnp.ndarray] | None]:
    """Build the pass-0 state for a solve.

    Returns ``(state, cfg, padded_bounds)`` where ``cfg`` is the effective
    (block-size-resolved) config — callers must thread that same cfg into
    every ``abo_pass_step``.
    """
    cfg = effective_config(config or ABOConfig(), n)
    n_pad = -(-n // cfg.block_size) * cfg.block_size
    x, bnds = _init_x(obj, n, n_pad, x0, dtype, seed, bounds)
    return abo_make_state(obj, x, n, cfg), cfg, bnds


def abo_pass_step(
    obj: SeparableObjective,
    state: ABOState,
    *,
    config: ABOConfig,
    probe_tile=None,
    bounds: tuple[jnp.ndarray, jnp.ndarray] | None = None,
) -> ABOState:
    """Advance a solve by exactly one pass. Pure and traceable: safe under
    jit, vmap (the engine's (K, B, m) batched tile), scan, and fori_loop.

    ``state.pass_idx`` drives the shrink/continuation schedule, so lanes at
    different passes can share one vmapped executable.
    """
    cfg = config
    probe_tile = probe_tile or _default_probe_tile(obj)
    p = state.pass_idx
    # fractional window after pass p-1 shrinks geometrically from the
    # full range (0.5 = whole interval)
    half_width, lam = pass_schedule(cfg, p, state.aggs.dtype)
    x, aggs = _sweep_pass(obj, state.x, state.aggs, state.n_valid, half_width,
                          p, lam, cfg, probe_tile, bounds)
    # re-sync aggregates exactly once per pass: kills accumulated-delta
    # drift (one O(N) streaming scan per pass — amortized over m·N probes)
    aggs = obj.aggregates(x, state.n_valid)
    hist = state.hist.at[p].set(obj.combine(aggs))
    return ABOState(x=x, aggs=aggs, hist=hist, pass_idx=p + 1,
                    n_valid=state.n_valid)


@functools.partial(
    jax.jit,
    static_argnames=("obj", "n", "cfg", "probe_tile"),
    donate_argnums=(0,),
)
def _abo_jit(x, obj, n, cfg, probe_tile, bounds=None):
    state = abo_make_state(obj, x, n, cfg)

    def pass_body(_, s):
        return abo_pass_step(obj, s, config=cfg, probe_tile=probe_tile,
                             bounds=bounds)

    state = jax.lax.fori_loop(0, cfg.n_passes, pass_body, state)
    # One exact O(N) re-evaluation so the reported optimum carries no
    # accumulated-delta rounding (drift itself is asserted small in tests).
    f_exact = obj.combine(
        obj.aggregates(state.x, state.n_valid))
    return state, f_exact


def abo_minimize(
    obj: SeparableObjective,
    n: int,
    *,
    config: ABOConfig | None = None,
    x0: jnp.ndarray | None = None,
    dtype: Any = jnp.float32,
    seed: int | None = None,
    bounds: tuple[jnp.ndarray, jnp.ndarray] | None = None,
) -> ABOResult:
    """Minimize a separable objective with ABO.

    Total live memory is one (padded) solution vector of ``n`` ``dtype``
    elements plus an O(block_size × samples_per_pass) probe tile.

    Init is the deterministic domain midpoint (the paper's determinism: pass
    0 sweeps the full interval linearly regardless, so x0 only seeds the
    incumbent column). Pass ``seed`` for a random feasible start — the
    multimodality-robustness benchmarks use both (EXPERIMENTS.md).
    """
    cfg = effective_config(config or ABOConfig(), n)
    n_pad = -(-n // cfg.block_size) * cfg.block_size
    x, bnds = _init_x(obj, n, n_pad, x0, dtype, seed, bounds)

    if cfg.use_kernel:
        # the Pallas path implements the whole pass in-kernel (Gauss-Seidel
        # across blocks with SMEM-carried aggregates) — Griewank only
        if obj.name != "griewank" or bounds is not None:
            raise NotImplementedError(
                "use_kernel supports the uniform-bounds Griewank benchmark; "
                "use the jnp path for other objectives")
        if cfg.span_coords is not None:
            raise NotImplementedError(
                "use_kernel does not implement the spanning decomposition "
                "(span_coords): the kernel carries aggregates in SMEM across "
                "the whole pass with no shard-boundary reset; use the jnp "
                "path for spanning solves")
        from repro.kernels.coord_sweep.ops import abo_minimize_kernel
        return abo_minimize_kernel(n, config=cfg, x0=x[:n], dtype=dtype)

    probe_tile = _default_probe_tile(obj)
    state, fun = _abo_jit(x, obj, n, cfg, probe_tile, bnds)
    fe = cfg.n_passes * cfg.samples_per_pass * n
    # repro: allow[RPR001] solve is complete; returning fun to the caller is
    # the designed end-of-run sync
    return ABOResult(x=state.x[:n], fun=float(fun), fe=fe, history=state.hist,
                     n=n, config=cfg)


# --------------------------------------------------------------------------
# Black-box (non-separable) fallback — the general-purpose mode the paper
# advertises. Probes cost O(N) each; memory stays O(N) (lax.map, no (m, N)
# candidate matrix).
# --------------------------------------------------------------------------
def abo_minimize_blackbox(
    fun,
    n: int,
    lower: float,
    upper: float,
    *,
    config: ABOConfig | None = None,
    x0: jnp.ndarray | None = None,
    dtype: Any = jnp.float32,
) -> ABOResult:
    cfg = config or ABOConfig(block_size=1)
    m = cfg.samples_per_pass
    x = (jnp.full((n,), 0.5 * (lower + upper), dtype)
         if x0 is None else jnp.asarray(x0, dtype))

    @jax.jit
    def run(x):
        shrink = cfg.resolved_shrink()

        def coord_body(i, carry):
            x, f_cur, half_width, p = carry
            xi = x[i]
            cands = _candidate_grid(xi[None], lower, upper, half_width, m,
                                    p == 0)[0]                    # (m,)
            f_c = jax.lax.map(lambda c: fun(x.at[i].set(c)), cands)
            j = jnp.argmin(f_c)
            better = f_c[j] <= f_cur
            x = x.at[i].set(jnp.where(better, cands[j], xi))
            return x, jnp.minimum(f_c[j], f_cur), half_width, p

        def pass_body(p, carry):
            x, f_cur, hist = carry
            hw = 0.5 * shrink ** p           # fractional window
            x, f_cur, _, _ = jax.lax.fori_loop(
                0, n, coord_body, (x, f_cur, hw, p))
            return x, f_cur, hist.at[p].set(f_cur)

        f0 = fun(x)
        hist = jnp.zeros((cfg.n_passes,), f0.dtype)
        return jax.lax.fori_loop(0, cfg.n_passes, pass_body, (x, f0, hist))

    x, f, hist = run(x)
    # repro: allow[RPR001] solve is complete; end-of-run sync (blackbox path)
    return ABOResult(x=x, fun=float(f), fe=cfg.n_passes * m * n,
                     history=hist, n=n, config=cfg)
