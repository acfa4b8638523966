"""Block-paged lane pool + row-compacted sweep: pay-for-n batched stepping.

Layout. Every solve *family* — (objective, effective config, dtype), the
things that shape compiled code — owns one :class:`PoolState`: a shared
``(P, block_size)`` page pool holding every lane's coordinate blocks, plus
per-lane-slot scalar state (aggregates, history, pass index, true n). Which
pages belong to which lane lives host-side in the scheduler's page tables;
the device never sees a lane as a contiguous (n_pad,) vector except through
explicit gathers. A lane with true n occupies exactly ``ceil(n / block)``
pages, so jobs of wildly different n share one pool, one set of compiled
executables, and — crucially — the engine's compute is proportional to
``Σ_i ceil(n_i / block)``, not ``K × n_pad``: padding blocks and idle lanes
simply do not exist to be swept.

Row-compacted sweep. A pass is an outer loop over block *rows* (row r of a
lane covers coordinates ``[r·block, (r+1)·block)``). At each row the step
gathers only the lanes actually occupying that row, runs the shared
(W, block, m) probe tile — the same :func:`repro.core.abo._block_step`
primitive ``abo_minimize`` scans, vmapped over the gathered lanes — and
scatters the committed blocks back into the pool. Because the number of
lanes occupying a row shrinks as r grows past the short lanes' depth, the
gather width W is padded onto the small :func:`pad_ladder` {1, 1.5}×pow2
rung ladder (the pad ladder of the old dense layout, shrunk to a row-width
ladder), so the whole width range compiles a handful of row-step
executables and row padding wastes at most 1/3 — in practice a few percent
— of swept block rows. Rows execute in ascending-row order per lane
(descending width), preserving the Gauss-Seidel block ordering of the
dense sweep.

Bit-identity. Per-lane math is exactly ``abo_minimize``'s: the row sweep
vmaps the identical block primitive with the identical pass schedule, and
every whole-lane reduction (end-of-pass aggregate re-sync, placement init,
final exact re-eval) runs over a *gathered contiguous row view* — the
lane's pages concatenated in order, length padded onto a page-count rung.
``SeparableObjective.aggregates`` reduces in fixed REDUCE_TILE tiles
accumulated in index order, so its bits depend only on the masked content,
never on the physical length of the view — gathered rungs, the dense
solver's exact pad, and any n (including past the old 1 MiB chunk
boundary) all reduce identically. Seeded starts stay pad-invariant
(per-coordinate counter draws), so a job's fun/x are bit-identical
whichever pool, slot, page assignment, or lane mix serves it.

Everything per-job-hot is jitted and cached per compiled shape in
:class:`PoolOps`: row sweeps keyed (width rung, row-count rung), lane
syncs / placements / finalizes keyed (page-count rung, lane-batch rung).
The scheduler tracks progress host-side and never syncs the device
mid-flight; successive row sweeps pipeline through JAX's async dispatch.

Sharded pools. With a ``mesh`` (a 1-axis ``"pool"`` device mesh) the page
dimension carries a ``NamedSharding``: device d owns local pages
``[d·cap_loc, (d+1)·cap_loc)`` of the global ``(n_dev·cap_loc, block)``
pool, each with its own all-zero local scratch page 0, while the per-slot
scalars stay replicated. Every pool op becomes one ``shard_map``'d
executable consuming *per-device* index tables (leading device axis,
sharded along it): each device sweeps only its resident lanes' bands —
Gauss-Seidel within a device, Jacobi across, exactly
``repro.core.sharded``'s semantics — and the per-slot tables are
re-replicated by ONE owner-selected ``psum`` per pass
(:func:`repro.core.sharded.owner_select`, which transfers bit patterns,
not float sums, so replicas agree to the bit). Lanes are placed wholly on
one device, so the psum moves each slot's n_aggs scalars from its single
writer — the paper's Eq. 7 communication bound — and per-lane math stays
bit-identical to ``abo_minimize`` at every device count. The
``optimization_barrier`` fences still wrap the vmapped block step (the
barrier composes inside shard_map; it has no vmap rule, so it must stay
outside the vmap), pinning the probe math against XLA's per-partition
respecialization. All state arguments are donated, sharded buffers
included, so steady-state stepping updates every shard in place.
"""
# repro: hot-path — fused pool sweep; zero host syncs by construction
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.abo import (ABOConfig, _block_step, _default_probe_tile,
                            effective_config, pass_schedule, seeded_at,
                            seeded_start)
from repro.core.sharded import axis_linear_index, owner_select
from repro.objectives.base import SeparableObjective, _default_agg_dtype

# (family key, lanes, pages, n_dev) -> PoolOps bundle of jitted functions
_POOL_OPS_CACHE: dict[tuple, "PoolOps"] = {}

# (device ids, target dims, state shapes) -> jitted sharded resize
_RESIZE_CACHE: dict[tuple, Callable] = {}

# Padding-waste ceiling for ladder quantization: the {1, 1.5} x pow2
# ladder's intrinsic worst case is 1/3, so at the default every count rides
# a canonical rung; 0 disables quantization (exact sizes).
DEFAULT_MAX_PAD_WASTE = 0.35

# Page id 0 and the last lane-slot row (one past the pool's current slot
# count) are reserved scratch targets for ladder padding entries in
# gathers/scatters: scratch page content is all-zeros by construction and
# the scratch lane has n_valid = 0, so padded work is inert and padded
# reads are exact zeros. Sharded pools reserve LOCAL page 0 on every
# device (per-device tables hold local ids, so the same constant applies
# shard-by-shard); the shared scratch lane-slot row is owned by device 0
# for replication purposes.
SCRATCH_PAGE = 0

# Sentinel rows-per-shard for lanes WITHOUT a spanning decomposition: the
# shard-boundary aggregate reset in the band body fires at rows where
# ``row % shard_rows[slot] == 0`` — with this sentinel that is only row 0,
# where the reset is a bitwise no-op (the carried aggregates equal the
# pass-entry snapshot before a lane's first row), so span-free lanes sweep
# the identical trajectory as before.
SPAN_NONE_ROWS = 1 << 30

# The pass-end aggregate re-sync's name scope: every op it compiles to
# carries ``engine.resync`` in its metadata's op name, which a TPU
# profile keeps as each op's ``tf_op``, so a profile tells the re-sync's
# device time apart from the row sweep's inside the one fused step.
RESYNC_SCOPE = "engine.resync"


def pad_ladder(n: int, block: int,
               max_pad_waste: float = DEFAULT_MAX_PAD_WASTE) -> int:
    """Canonical padded size for a count of ``n`` in units of ``block``.

    Rungs are {1, 1.5} x powers of two in units of ``block``
    (block x {1, 2, 3, 4, 6, 8, 12, ...}) — a geometric ladder, so the
    whole [1, 1e9] range needs only ~2 log2(range) distinct sizes and
    padding waste ``(n_pad - n) / n_pad`` never exceeds 1/3. If the
    smallest rung >= n still wastes more than ``max_pad_waste`` (possible
    only for bounds tighter than the ladder's 1/3), the count keeps its
    exact ``ceil(n/block)*block`` size.

    In the paged layout this quantizes *counts*, not coordinate padding:
    row widths (lanes gathered per block row), page-count rungs (gathered
    row views), lane-batch widths, and pool capacities all ride it with
    ``block=1``.
    """
    exact = -(-n // block) * block
    if max_pad_waste <= 0.0:
        return exact
    mult = exact // block
    rung = 1
    while rung < mult:
        if rung & (rung - 1) == 0 and rung >= 2:   # 2^j -> 3*2^(j-1)
            rung = rung * 3 // 2
        elif rung == 1:
            rung = 2
        else:                                      # 3*2^(j-1) -> 2^(j+1)
            rung = rung // 3 * 4
    n_pad = rung * block
    if (n_pad - n) / n_pad <= max_pad_waste:
        return n_pad
    return exact


def family_key(obj_name: str, n: int, cfg: ABOConfig,
               dtype=jnp.float32) -> tuple:
    """Compile-sharing key for an n-dimensional job: everything that shapes
    compiled executables EXCEPT any padded size. Jobs of every n whose
    effective config matches share one pool and one executable set (n only
    enters through the block-size resolution of tiny problems)."""
    eff = effective_config(cfg, n)
    return (obj_name, eff, jnp.dtype(dtype).name)


def key_config(key: tuple) -> ABOConfig:
    return key[1]


def pages_for(n: int, block: int) -> int:
    """Pages a lane with true n occupies — its real footprint."""
    return -(-n // block)


@dataclasses.dataclass
class PoolState:
    """One family's device state: the shared page pool + per-slot scalars.

    ``pool[0]`` is the reserved all-zero scratch page and slot ``lanes``
    (the last row of the per-slot arrays) the scratch lane — ladder padding
    entries in gathers/scatters target them. Page ownership is host-side
    (the scheduler's page tables); nothing here says which lane a page
    belongs to.
    """

    pool: jnp.ndarray       # (P, block) coordinate pages
    aggs: jnp.ndarray       # (lanes+1, n_aggs) running aggregates per slot
    hist: jnp.ndarray       # (lanes+1, n_passes) objective after each pass
    pass_idx: jnp.ndarray   # (lanes+1,) int32, next pass per slot
    n_valid: jnp.ndarray    # (lanes+1,) int32, true n per slot (0 = idle)


jax.tree_util.register_dataclass(
    PoolState,
    data_fields=["pool", "aggs", "hist", "pass_idx", "n_valid"],
    meta_fields=[],
)


def state_sharding(mesh: Mesh) -> PoolState:
    """The NamedSharding pytree of a sharded PoolState: pages split over
    the mesh's ``"pool"`` axis, per-slot scalars replicated."""
    return PoolState(
        pool=NamedSharding(mesh, P("pool", None)),
        aggs=NamedSharding(mesh, P()),
        hist=NamedSharding(mesh, P()),
        pass_idx=NamedSharding(mesh, P()),
        n_valid=NamedSharding(mesh, P()),
    )


def _state_specs() -> PoolState:
    """shard_map in/out specs matching :func:`state_sharding`."""
    return PoolState(pool=P("pool", None), aggs=P(), hist=P(),
                     pass_idx=P(), n_valid=P())


def zeros_pool_state(obj: SeparableObjective, key: tuple, lanes: int,
                     pages: int, mesh: Mesh | None = None) -> PoolState:
    """An all-idle pool (also the checkpoint-restore ``like`` tree).
    Idle and scratch slots hold n_valid=0, so they are never swept and any
    ladder-padding work routed at them is frozen. With ``mesh``, ``pages``
    is the GLOBAL page count (``n_dev × cap_loc``) and the pool lands
    sharded over the page dimension."""
    _, cfg, dtype = key
    agg_dt = _default_agg_dtype()
    state = PoolState(
        pool=jnp.zeros((pages, cfg.block_size), jnp.dtype(dtype)),
        aggs=jnp.zeros((lanes + 1, obj.n_aggs), agg_dt),
        hist=jnp.zeros((lanes + 1, cfg.n_passes), agg_dt),
        pass_idx=jnp.zeros((lanes + 1,), jnp.int32),
        n_valid=jnp.zeros((lanes + 1,), jnp.int32),
    )
    if mesh is not None:
        state = jax.device_put(state, state_sharding(mesh))
    return state


def write_pages(pool, pages, rows):
    """``pool[pages[i]] = rows[i]`` for each page entry, in order.

    Placement tables pad with the scratch page, so ``pages`` repeats it.
    XLA leaves the order of a scatter's duplicate updates to the backend,
    and on a TPU v5e a scatter of one lane's 256 page rows (245 real, 11
    scratch) wrote the scratch rows' zeros over real pages 224-245. One
    ``dynamic_update_slice`` per row means the same on every backend."""
    pages = pages.reshape(-1)
    rows = rows.reshape(pages.shape[0], pool.shape[1]).astype(pool.dtype)

    def body(i, pool):
        return jax.lax.dynamic_update_slice(
            pool, rows[i][None], (pages[i], jnp.zeros((), pages.dtype)))

    return jax.lax.fori_loop(0, pages.shape[0], body, pool)


def resize_pool_state(state: PoolState, lanes: int, pages: int,
                      mesh: Mesh | None = None) -> PoolState:
    """Re-shape a pool's device state to ``lanes`` slots and ``pages``
    capacity, growing or shrinking either dimension.

    Surviving pages keep their ids and content (new pages are zero;
    callers must only shrink past all-free tails). Surviving lane slots
    keep their scalars; the scratch slot — always the LAST row — is
    rebuilt as zeros at its new index, which also launders the junk that
    ladder-padded syncs accumulate in it (its pass_idx increments every
    plan step). Host-rare either way: both dimensions ride the count
    ladder with a drain-side hysteresis, so resizes happen O(log traffic)
    times per family, not per admission.

    Sharded pools resize *per shard*: ``pages`` is the new global count
    (``n_dev × cap_loc'``) and each device pads/trims its own local page
    tail — page ids are (device, local), so a global-row copy would move
    pages across devices when the shard height changes."""
    p0 = state.pool.shape[0]
    s0 = state.aggs.shape[0] - 1
    if pages == p0 and lanes == s0:
        return state
    keep = min(s0, lanes)

    def resize_slots(a):
        out = jnp.zeros((lanes + 1,) + a.shape[1:], a.dtype)
        return out.at[:keep].set(a[:keep])

    if mesh is not None:
        n_dev = mesh.devices.size
        loc_new = pages // n_dev
        loc_old = p0 // n_dev
        # cache the jitted resize per (topology, shape transition): an
        # unjitted shard_map re-traces every call, and drain/regrow
        # cycles resize on the same few ladder rungs over and over
        ck = (tuple(d.id for d in mesh.devices.flat), lanes, pages,
              tuple((leaf.shape, str(leaf.dtype))
                    for leaf in (state.pool, state.aggs, state.hist,
                                 state.pass_idx, state.n_valid)))
        fn = _RESIZE_CACHE.get(ck)
        if fn is None:

            def resize_sharded(pool, aggs, hist, pass_idx, n_valid):
                if loc_new > loc_old:
                    pool = jnp.zeros((loc_new, pool.shape[1]),
                                     pool.dtype).at[:loc_old].set(pool)
                elif loc_new < loc_old:
                    pool = pool[:loc_new]
                if lanes != s0:
                    aggs, hist = resize_slots(aggs), resize_slots(hist)
                    pass_idx, n_valid = (resize_slots(pass_idx),
                                         resize_slots(n_valid))
                return pool, aggs, hist, pass_idx, n_valid

            fn = jax.jit(jax.shard_map(
                resize_sharded, mesh=mesh, check_vma=False,
                in_specs=(P("pool", None), P(), P(), P(), P()),
                out_specs=(P("pool", None), P(), P(), P(), P())),
                donate_argnums=(0, 1, 2, 3, 4))
            _RESIZE_CACHE[ck] = fn
        out = fn(state.pool, state.aggs, state.hist, state.pass_idx,
                 state.n_valid)
        return PoolState(*out)

    # unsharded: same cached-jit policy as the sharded branch above. The
    # old eager .at[].set()/slice path dispatched ~8 one-op executables
    # per shape transition (each a fresh compile the first time a
    # drain/regrow cycle hit that rung) and COPIED the pool instead of
    # donating it — the sanitizers flagged both.
    ck = (None, lanes, pages,
          tuple((leaf.shape, str(leaf.dtype))
                for leaf in (state.pool, state.aggs, state.hist,
                             state.pass_idx, state.n_valid)))
    fn = _RESIZE_CACHE.get(ck)
    if fn is None:

        def host_resize(pool, aggs, hist, pass_idx, n_valid):
            if pages > p0:
                pool = jnp.zeros((pages, pool.shape[1]),
                                 pool.dtype).at[:p0].set(pool)
            elif pages < p0:
                pool = pool[:pages]
            if lanes != s0:
                aggs, hist = resize_slots(aggs), resize_slots(hist)
                pass_idx, n_valid = (resize_slots(pass_idx),
                                     resize_slots(n_valid))
            return pool, aggs, hist, pass_idx, n_valid

        # donate exactly the arguments whose shapes survive the
        # transition: those alias in place; the rest can't alias anyway
        # (XLA would warn and copy), and their old buffers die when the
        # caller swaps in the new state
        donate = []
        if pages == p0:
            donate.append(0)
        if lanes == s0:
            donate.extend((1, 2, 3, 4))
        fn = jax.jit(host_resize, donate_argnums=tuple(donate))
        _RESIZE_CACHE[ck] = fn
    out = fn(state.pool, state.aggs, state.hist, state.pass_idx,
             state.n_valid)
    return PoolState(*out)


class PoolOps:
    """Jitted per-family operations over a :class:`PoolState`.

    Each method returns a cached jitted callable for one compiled shape:

    * ``fused_step(bands, sync)`` — a whole sweep-plan step: every width
      band's row loop plus the end-of-pass lane sync, wrapped in a
      dynamic-count pass loop, in ONE executable. The compile key is the
      plan *signature* (band and sync shape rungs only), so steady-state
      traffic reuses one program and per-pass dispatch overhead — the
      dominant cost of narrow mixed-n bands — is paid once per fused
      generation instead of once per band per pass.
    * ``place(g, v)`` / ``place_x(g)`` — initialize freshly admitted lanes
      (seeded / golden-section / explicit x0 starts) into their pages.
    * ``finalize(g, v)`` — exact final re-eval + row-view gather for ONLY
      the finishing lanes (idle/running lanes cost nothing at harvest).

    All state arguments are donated: the scheduler threads one PoolState
    through, so buffers update in place.

    Each executable's HLO module is named for what it does (the profiler
    shows ``jit_<name>(<hash>)``): ``fused_step``, ``place``, ``place_x``,
    ``finalize``, with a ``_sharded`` suffix on a mesh, and the mesh-only
    ``place_span`` and ``finalize_span``; pool resizes are
    ``host_resize`` / ``resize_sharded``. A trace reduction finds the
    fused step's device time by that name.

    With a ``mesh`` the same methods return shard_map'd executables over
    *per-device* tables (leading device axis, local page ids) plus an
    ``owner`` slot→device table; see the module docstring for the layout
    and the per-pass owner-selected psum that keeps the replicated slot
    arrays in agreement.
    """

    def __init__(self, obj: SeparableObjective, key: tuple, lanes: int,
                 pages: int, mesh: Mesh | None = None):
        self.obj = obj
        self.key = key
        self.lanes = lanes
        self.pages = pages
        self.mesh = mesh
        self.n_dev = mesh.devices.size if mesh is not None else 1
        self.cfg: ABOConfig = key_config(key)
        self.dtype = jnp.dtype(key[2])
        self.probe_tile = _default_probe_tile(obj)
        self._cache: dict[tuple, Callable] = {}

    def compiled_count(self) -> int:
        return len(self._cache)

    # ----------------------------------------------------- traced sub-steps
    def _band_body(self, state: PoolState, lanes, pages, rows, n_rows,
                   shard_rows, aggs0):
        """Sweep one width band: rows [0, n_rows) of the (r_cap, w) plan
        arrays, in order. Each row gathers the w lanes' blocks, runs the
        shared (w, block, m) probe tile — the identical per-lane schedule
        + block primitive as abo_pass_step — and scatters blocks +
        aggregates back. Ladder-padding entries point at the scratch
        lane/page and are frozen no-ops; planned rows past n_rows cost
        nothing (dynamic loop count).

        The vmapped block step is fenced with ``optimization_barrier``
        exactly like the dense solver's scan (see core.abo._sweep_pass):
        without the fence, XLA specializes the probe math to THIS
        program's dynamic loops (different FMA/vectorization choices than
        the dense scan) and argmin picks flip wherever candidates probe
        within an ulp — the reason per-lane bits are identical to
        abo_minimize at any layout.

        ``shard_rows`` is the (slots+1,) per-slot spanning decomposition
        (rows per shard; SPAN_NONE_ROWS for span-free lanes) and ``aggs0``
        the pass-entry aggregate snapshot: at a shard's first row the
        gathered aggregates reset to ``aggs0`` — core.abo._sweep_pass's
        Jacobi-across-shards reset, expressed per gathered entry so every
        device sweeps its resident shards against the same frozen
        cross-shard state."""
        obj, cfg, probe_tile = self.obj, self.cfg, self.probe_tile
        bsz = cfg.block_size

        def core_step(xb, ag, idx, valid, half_width, first, lam):
            return _block_step(obj, cfg, probe_tile, xb, ag, idx, valid,
                               half_width, first, lam,
                               obj.lower, obj.upper)

        def body(j, carry):
            pool, aggs = carry
            ln, pg, rw = lanes[j], pages[j], rows[j]
            p = state.pass_idx[ln]               # (w,)
            half_width, lam = pass_schedule(cfg, p, aggs.dtype)
            idx = rw[:, None] * bsz + jnp.arange(bsz)[None, :]
            valid = idx < state.n_valid[ln][:, None]
            # shard-boundary Jacobi reset (bitwise no-op at a lane's row 0)
            ag = jnp.where((rw % shard_rows[ln] == 0)[:, None],
                           aggs0[ln], aggs[ln])
            args = jax.lax.optimization_barrier(
                (pool[pg], ag, idx, valid, half_width, p == 0, lam))
            if ln.shape[0] == 1:
                # one lane runs unbatched: vmapped, its size-1 lane axis
                # lands second-minor in XLA:TPU's layout of the probe
                # tile, whose (1, 128) tiles fill one sublane in eight
                xb2, ag2 = jax.lax.optimization_barrier(jax.tree.map(
                    lambda r: r[None], core_step(*(a[0] for a in args))))
            else:
                xb2, ag2 = jax.lax.optimization_barrier(
                    jax.vmap(core_step)(*args))
            return pool.at[pg].set(xb2), aggs.at[ln].set(ag2)

        pool, aggs = jax.lax.fori_loop(
            0, n_rows, body, (state.pool, state.aggs))
        return dataclasses.replace(state, pool=pool, aggs=aggs)

    def _gather_rows(self, state: PoolState, pages):
        """(v, g) page ids -> (v, g*block) contiguous row views. Pages past
        a lane's true count are scratch (exact zeros), and the tile-fixed
        aggregate reduction is length-invariant, so masked whole-row
        reductions bit-match the dense solver's padded vector at ANY rung
        width — including views crossing the reduction-tile boundary."""
        v, g = pages.shape
        return state.pool[pages].reshape(v, g * self.cfg.block_size)

    def _sync_body(self, state: PoolState, lanes, pages):
        """End-of-pass bookkeeping of abo_pass_step for the gathered
        lanes: exact aggregate re-sync over the contiguous row view (kills
        accumulated-delta drift), history entry, pass_idx advance."""
        obj = self.obj
        xrow = self._gather_rows(state, pages)
        nv = state.n_valid[lanes]
        p = state.pass_idx[lanes]
        # Clamp the history column: identity for real lanes (they sync at
        # most n_passes times before harvest), but ladder-padding entries
        # keep incrementing the scratch slot's pass_idx across plans —
        # without the clamp their scatter index outruns the hist width and
        # we'd silently depend on drop-out-of-bounds scatter semantics.
        p_hist = jnp.minimum(p, self.cfg.n_passes - 1)
        aggs = jax.vmap(lambda xr, n: obj.aggregates(
            xr, n))(xrow, nv)
        f = jax.vmap(obj.combine)(aggs)
        return dataclasses.replace(
            state,
            aggs=state.aggs.at[lanes].set(aggs.astype(state.aggs.dtype)),
            hist=state.hist.at[lanes, p_hist].set(
                f.astype(state.hist.dtype)),
            pass_idx=state.pass_idx.at[lanes].add(1),
        )

    def _span_partial_aggs(self, st: PoolState, vs: int, t_pad: int,
                           sp_lanes, sp_ntiles, tile_slot, tile_idx,
                           tile_pages, tile_off):
        """(vs, n_aggs) exact aggregates for striped lanes, reconstructed
        from per-device fixed-origin tile partials: masked ``tile_partial``
        per owned tile, disjoint scatter into a zeros table, ONE
        bit-pattern psum (exactly-one-writer cells, so the integer sum IS
        the bit transfer), replicated in-order fold."""
        obj = self.obj
        agg_dt = st.aggs.dtype
        # (vs+1,) n_valid per table row; the dump row masks to zero terms
        nv_rows = jnp.concatenate(
            [st.n_valid[sp_lanes], jnp.zeros((1,), jnp.int32)])

        def one_tile(slot, t, pgs, off):
            xr = st.pool[pgs].reshape(-1)            # (ppt*block,)
            xc = jax.lax.dynamic_slice(
                xr, (off,), (obj.REDUCE_TILE,))
            return obj.tile_partial(xc, t, nv_rows[slot], agg_dtype=agg_dt)

        parts = jax.vmap(one_tile)(tile_slot, tile_idx, tile_pages,
                                   tile_off)          # (ts, n_aggs)
        table = jnp.zeros((vs + 1, t_pad + 1, obj.n_aggs),
                          agg_dt).at[tile_slot, tile_idx].set(parts)
        bits_dt = jnp.dtype(f"uint{table.dtype.itemsize * 8}")
        table = jax.lax.bitcast_convert_type(
            jax.lax.psum(jax.lax.bitcast_convert_type(table, bits_dt),
                         "pool"), table.dtype)
        return jax.vmap(lambda pr, nt: obj.fold_tile_partials(
            pr, nt, agg_dtype=agg_dt))(table[:vs], sp_ntiles)

    def _span_sync(self, st: PoolState, vs: int, t_pad: int,
                   sp_lanes, sp_ntiles, tile_slot, tile_idx, tile_pages,
                   tile_off):
        """End-of-pass re-sync for STRIPED spanning lanes: the distributed
        reconstruction of ``obj.aggregates`` over a lane whose pages live
        on several devices.

        Each device computes the masked fixed-origin partial of every
        REDUCE_TILE tile it owns (``obj.tile_partial`` — the identical ops
        as the tile reduce inside ``aggregates``), scatters them into a
        zeros ``(vs+1, t_pad+1, n_aggs)`` table (row vs / column t_pad are
        the dump targets for ladder padding), and the tables are combined
        by ONE bit-pattern psum: tile ownership is disjoint, so every cell
        has exactly one non-zero writer and the integer sum transfers its
        bit pattern exactly — no owner map needed. The replicated fold
        (``obj.fold_tile_partials``) then accumulates the partials in
        global tile order, add-for-add the sequence ``aggregates`` runs —
        so the synced aggregates are bit-identical to the dense solver's
        exact re-sync at every device count."""
        obj, cfg = self.obj, self.cfg
        aggs = self._span_partial_aggs(st, vs, t_pad, sp_lanes, sp_ntiles,
                                       tile_slot, tile_idx, tile_pages,
                                       tile_off)
        f = jax.vmap(obj.combine)(aggs)
        p = st.pass_idx[sp_lanes]
        p_hist = jnp.minimum(p, cfg.n_passes - 1)
        return dataclasses.replace(
            st,
            aggs=st.aggs.at[sp_lanes].set(aggs.astype(st.aggs.dtype)),
            hist=st.hist.at[sp_lanes, p_hist].set(
                f.astype(st.hist.dtype)),
            pass_idx=st.pass_idx.at[sp_lanes].add(1),
        )

    # ----------------------------------------------------------- fused step
    def fused_step(self, bands: tuple, sync: tuple,
                   span: tuple | None = None) -> Callable:
        """One executable for a whole sweep-plan step.

        ``bands`` is the plan signature ``((w, r_cap), ...)`` and ``sync``
        the lane-sync shape ``(g, v)``. The returned callable takes
        ``(state, n_fused, shard_rows, lanes_0, pages_0, rows_0,
        n_rows_0, ..., sync_lanes, sync_pages)`` and runs ``n_fused``
        complete passes — every band in ascending-row order (preserving
        per-lane Gauss-Seidel block ordering), then the per-lane re-sync —
        inside one dynamic fori_loop. ``shard_rows`` is the (slots+1,)
        spanning decomposition (SPAN_NONE_ROWS for span-free lanes). Both
        the pass count and the per-band row counts are traced scalars, so
        one compiled program serves any fuse depth and any partial band
        fill of the same signature.

        Sharded pools take ``(state, n_fused, owner, shard_rows,
        *per_device_arrs)`` where every table carries a leading device
        axis (band lanes/pages/rows ``(D, r_cap, w)``, band row counts
        ``(D,)``, sync tables ``(D, v)`` / ``(D, v, g)``) and ``owner``
        maps slot→device. Each device runs ITS band schedule and lane sync
        per pass, then the slot arrays are re-replicated by one
        owner-selected psum — the pass-end Jacobi exchange of
        ``core.sharded``, n_aggs scalars per slot from its one writer.

        ``span`` (sharded only) is the striped-lane signature
        ``(vs, t_pad, ts, ppt)``; when set, six extra tables follow the
        sync tables — ``sp_lanes (vs,)`` / ``sp_ntiles (vs,)``
        (replicated) and per-device ``tile_slot/tile_idx/tile_off
        (D, ts)`` / ``tile_pages (D, ts, ppt)`` — and each pass ends with
        the distributed span re-sync (:meth:`_span_sync`) before the
        owner psum. Striped slots carry owner 0: their scalars are already
        replica-identical after the span sync, so the select is a no-op.
        """
        ck = ("step", bands, sync, span)
        fn = self._cache.get(ck)
        if fn is not None:
            return fn
        n_bands = len(bands)
        if self.mesh is None:
            assert span is None, "striped spanning lanes need a mesh"

            def fused_step(state: PoolState, n_fused, shard_rows, *arrs):
                band_args = [arrs[4 * i: 4 * i + 4] for i in range(n_bands)]
                sync_args = arrs[4 * n_bands: 4 * n_bands + 2]

                def one_pass(_, st):
                    aggs0 = st.aggs
                    for ba in band_args:
                        st = self._band_body(st, *ba, shard_rows, aggs0)
                    with jax.named_scope(RESYNC_SCOPE):
                        return self._sync_body(st, *sync_args)

                return jax.lax.fori_loop(0, n_fused, one_pass, state)

            fn = jax.jit(fused_step, donate_argnums=(0,))
        else:

            def fused_step_sharded(state: PoolState, n_fused, owner,
                                   shard_rows, *arrs):
                my = axis_linear_index(("pool",))
                band_args = [tuple(a[0] for a in arrs[4 * i: 4 * i + 3])
                             + (arrs[4 * i + 3][0],) for i in range(n_bands)]
                sync_args = tuple(a[0] for a in
                                  arrs[4 * n_bands: 4 * n_bands + 2])
                if span is not None:
                    vs, t_pad, _, _ = span
                    base = 4 * n_bands + 2
                    sp_lanes, sp_ntiles = arrs[base], arrs[base + 1]
                    tile_slot, tile_idx = (arrs[base + 2][0],
                                           arrs[base + 3][0])
                    tile_pages, tile_off = (arrs[base + 4][0],
                                            arrs[base + 5][0])

                def one_pass(_, st):
                    aggs0 = st.aggs
                    for ba in band_args:
                        st = self._band_body(st, *ba, shard_rows, aggs0)
                    with jax.named_scope(RESYNC_SCOPE):
                        st = self._sync_body(st, *sync_args)
                        if span is not None:
                            st = self._span_sync(
                                st, vs, t_pad, sp_lanes, sp_ntiles,
                                tile_slot, tile_idx, tile_pages, tile_off)
                    # ONE exchange per pass: every slot's scalars from
                    # their single writer (bit patterns, not float sums)
                    return dataclasses.replace(
                        st,
                        aggs=owner_select(st.aggs, owner, my, "pool"),
                        hist=owner_select(st.hist, owner, my, "pool"),
                        pass_idx=owner_select(st.pass_idx, owner, my,
                                              "pool"))

                return jax.lax.fori_loop(0, n_fused, one_pass, state)

            band_specs = (P("pool", None, None),) * 3 + (P("pool"),)
            span_specs = () if span is None else (
                P(), P(), P("pool", None), P("pool", None),
                P("pool", None, None), P("pool", None))
            fn = jax.jit(jax.shard_map(
                fused_step_sharded, mesh=self.mesh, check_vma=False,
                in_specs=(_state_specs(), P(), P(), P())
                + band_specs * n_bands
                + (P("pool", None), P("pool", None, None))
                + span_specs,
                out_specs=_state_specs()), donate_argnums=(0,))
        self._cache[ck] = fn
        return fn

    # ------------------------------------------------------------ placement
    def place(self, g: int, v: int) -> Callable:
        """(state, lanes (v,), pages (v, g), seeded (v,), seeds (v,),
        n_valid (v,)) -> state. Start vectors + exact init aggregates for
        freshly admitted lanes, scattered into their pages — one dispatch
        for the whole refill batch. Seeded starts are per-coordinate
        counter draws (bit-identical to abo_minimize's at any layout);
        coordinates past a lane's true n are zeroed so scratch-page writes
        from ladder padding keep the scratch page exactly zero."""
        ck = ("place", g, v)
        fn = self._cache.get(ck)
        if fn is not None:
            return fn
        obj, cfg, dt = self.obj, self.cfg, self.dtype
        bsz = cfg.block_size
        width = g * bsz

        def init_row(seed, is_seeded, nv):
            xs = seeded_start(seed, width, dt, obj.lower, obj.upper)
            xg = jnp.full((width,), obj.lower + 0.6180339887
                          * (obj.upper - obj.lower), dt)
            xr = jnp.where(is_seeded, xs, xg)
            xr = jnp.where(jnp.arange(width) < nv, xr,
                           jnp.zeros((), dt))
            ag = obj.aggregates(xr, nv)
            return xr, ag

        if self.mesh is None:

            def place(state: PoolState, lanes, pages, seeded, seeds, n_valid):
                xr, ag = jax.vmap(init_row)(seeds, seeded, n_valid)
                return self._write_lanes(state, lanes, pages, xr, ag,
                                         n_valid)

            fn = jax.jit(place, donate_argnums=(0,))
        else:
            # sharded: per-device tables; every device computes the whole
            # v-batch of start rows (v is a refill batch, tiny next to a
            # sweep) but only ITS lanes' rows are real — the rest target
            # its local scratch slot/page and the owner psum restores one
            # authoritative value per slot across replicas
            def place_sharded(state: PoolState, owner, lanes, pages,
                              seeded, seeds, n_valid):
                my = axis_linear_index(("pool",))
                lanes, pages = lanes[0], pages[0]
                seeded, seeds, n_valid = seeded[0], seeds[0], n_valid[0]
                xr, ag = jax.vmap(init_row)(seeds, seeded, n_valid)
                st = self._write_lanes(state, lanes, pages, xr, ag, n_valid)
                return self._reconcile_slots(st, owner, my)

            fn = jax.jit(jax.shard_map(
                place_sharded, mesh=self.mesh, check_vma=False,
                in_specs=(_state_specs(), P(), P("pool", None),
                          P("pool", None, None), P("pool", None),
                          P("pool", None), P("pool", None)),
                out_specs=_state_specs()), donate_argnums=(0,))
        self._cache[ck] = fn
        return fn

    def _reconcile_slots(self, st: PoolState, owner, my) -> PoolState:
        """Re-replicate every per-slot array from its owner device (one
        bit-exact psum each; see core.sharded.owner_select)."""
        return dataclasses.replace(
            st,
            aggs=owner_select(st.aggs, owner, my, "pool"),
            hist=owner_select(st.hist, owner, my, "pool"),
            pass_idx=owner_select(st.pass_idx, owner, my, "pool"),
            n_valid=owner_select(st.n_valid, owner, my, "pool"))

    def place_x(self, g: int) -> Callable:
        """(state, lane (), pages (g,), xrow (g*block,), n_valid ()) ->
        state. Explicit-x0 placement for one lane (rare; xrow is built
        host-side with zeros past n)."""
        ck = ("place_x", g)
        fn = self._cache.get(ck)
        if fn is not None:
            return fn
        obj = self.obj
        if self.mesh is None:

            def place_x(state: PoolState, lane, pages, xrow, n_valid):
                ag = obj.aggregates(xrow, n_valid)
                return self._write_lanes(
                    state, lane[None], pages[None], xrow[None], ag[None],
                    n_valid[None])

            fn = jax.jit(place_x, donate_argnums=(0,))
        else:

            def place_x_sharded(state: PoolState, owner, lane, pages,
                                xrow, n_valid):
                my = axis_linear_index(("pool",))
                lane, pages, xrow, n_valid = (lane[0], pages[0], xrow[0],
                                              n_valid[0])
                ag = obj.aggregates(xrow, n_valid)
                st = self._write_lanes(
                    state, lane[None], pages[None], xrow[None], ag[None],
                    n_valid[None])
                return self._reconcile_slots(st, owner, my)

            fn = jax.jit(jax.shard_map(
                place_x_sharded, mesh=self.mesh, check_vma=False,
                in_specs=(_state_specs(), P(), P("pool"),
                          P("pool", None), P("pool", None), P("pool")),
                out_specs=_state_specs()), donate_argnums=(0,))
        self._cache[ck] = fn
        return fn

    def place_span(self, gl: int, ts: int, ppt: int, t_pad: int) -> Callable:
        """Placement for ONE striped spanning lane (sharded pools only).

        ``(state, lane (), n_valid (), seed (), seeded (), poison (),
        n_tiles (), pg_tbl (D, gl), gpage_tbl (D, gl), tile_idx (D, ts),
        tile_pages (D, ts, ppt), tile_off (D, ts)) -> state``.

        Each device writes only its resident pages: page entry j holds the
        LOCAL page id and the lane's GLOBAL page index (padding entries are
        local scratch 0 / gpage -1 and write exact zeros). Seeded starts
        use the per-coordinate counter draw (``core.abo.seeded_at``) so a
        striped lane starts from bit-identical coordinates as the dense
        solver's ``seeded_start``; golden starts are the same constant.
        ``poison`` NaNs global coordinate 0 on whichever device owns page
        0 (the engine's fault-injection hook). Init aggregates come from
        the same tile-partial psum + in-order fold as the span re-sync, so
        they are bit-identical to ``obj.aggregates`` over the dense start
        vector. All slot scalars land replica-identical — no owner psum
        needed."""
        ck = ("place_span", gl, ts, ppt, t_pad)
        fn = self._cache.get(ck)
        if fn is not None:
            return fn
        assert self.mesh is not None, "place_span requires a sharded pool"
        obj, cfg, dt = self.obj, self.cfg, self.dtype
        bsz = cfg.block_size

        def place_span(state: PoolState, lane, n_valid, seed, seeded,
                       poison, n_tiles, pg_tbl, gpage_tbl, tile_idx,
                       tile_pages, tile_off):
            lane, n_valid = lane[0], n_valid[0]
            seed, seeded, poison = seed[0], seeded[0], poison[0]
            n_tiles = n_tiles[0]
            pg_tbl, gpage_tbl = pg_tbl[0], gpage_tbl[0]
            tile_idx = tile_idx[0]
            tile_pages, tile_off = tile_pages[0], tile_off[0]

            def write_one(gpage):
                idx = gpage * bsz + jnp.arange(bsz)
                xs = seeded_at(seed, idx.astype(jnp.uint32), dt,
                               obj.lower, obj.upper)
                xg = jnp.full((bsz,), obj.lower + 0.6180339887
                              * (obj.upper - obj.lower), dt)
                xr = jnp.where(seeded, xs, xg)
                xr = jnp.where(poison & (idx == 0),
                               jnp.full((), jnp.nan, dt), xr)
                ok = (gpage >= 0) & (idx < n_valid)
                return jnp.where(ok, xr, jnp.zeros((), dt))

            vals = jax.vmap(write_one)(gpage_tbl)     # (gl, block)
            st = dataclasses.replace(
                state, pool=write_pages(state.pool, pg_tbl, vals))
            st = dataclasses.replace(
                st,
                hist=st.hist.at[lane].set(
                    jnp.zeros((cfg.n_passes,), st.hist.dtype)),
                pass_idx=st.pass_idx.at[lane].set(
                    jnp.zeros((), jnp.int32)),
                n_valid=st.n_valid.at[lane].set(
                    n_valid.astype(jnp.int32)),
            )
            # row 0 is the one real lane; dump tiles (idx == t_pad) route
            # to the dump row
            tile_slot = jnp.where(tile_idx < t_pad, 0, 1).astype(jnp.int32)
            ag = self._span_partial_aggs(
                st, 1, t_pad, lane[None], n_tiles[None], tile_slot,
                tile_idx, tile_pages, tile_off)
            return dataclasses.replace(
                st, aggs=st.aggs.at[lane].set(ag[0].astype(st.aggs.dtype)))

        fn = jax.jit(jax.shard_map(
            place_span, mesh=self.mesh, check_vma=False,
            in_specs=(_state_specs(), P(), P(), P(), P(), P(), P(),
                      P("pool", None), P("pool", None),
                      P("pool", None), P("pool", None, None),
                      P("pool", None)),
            out_specs=_state_specs()), donate_argnums=(0,))
        self._cache[ck] = fn
        return fn

    def _write_lanes(self, state, lanes, pages, xrow, aggs, n_valid):
        v = pages.shape[0]
        return dataclasses.replace(
            state,
            pool=write_pages(state.pool, pages, xrow),
            aggs=state.aggs.at[lanes].set(aggs.astype(state.aggs.dtype)),
            hist=state.hist.at[lanes].set(
                jnp.zeros((v, self.cfg.n_passes), state.hist.dtype)),
            pass_idx=state.pass_idx.at[lanes].set(
                jnp.zeros((v,), jnp.int32)),
            n_valid=state.n_valid.at[lanes].set(
                n_valid.astype(jnp.int32)),
        )

    # ------------------------------------------------------------- finalize
    def finalize(self, g: int, v: int) -> Callable:
        """(state, lanes (v,), pages (v, g)) -> (f (v,), x (v, g*block),
        hist (v, n_passes)). Exact O(n) re-eval + solution gather for ONLY
        the finishing lanes — the dense layout re-evaluated every lane in
        the group on every harvest; here turnover costs the finishers'
        pages and nothing else. Same dispatch economics (one call per
        harvest batch), a fraction of the compute."""
        ck = ("final", g, v)
        fn = self._cache.get(ck)
        if fn is not None:
            return fn
        obj = self.obj
        if self.mesh is None:

            def finalize(state: PoolState, lanes, pages):
                xrow = self._gather_rows(state, pages)
                nv = state.n_valid[lanes]
                f = jax.vmap(lambda xr, n: obj.combine(obj.aggregates(
                    xr, n)))(xrow, nv)
                return f, xrow, state.hist[lanes]

            # repro: allow[RPR005] finalize reads pool state the next step
            # still owns — donating would free live pages; no static args
            fn = jax.jit(finalize)
        else:
            # sharded: finisher i's row in each output is computed by its
            # resident device (row_dev[i]) from its local pages; the other
            # devices produce scratch garbage in that row, which the
            # owner-selected psum discards — outputs land replicated, so
            # the host reads exact per-lane values once
            def finalize_sharded(state: PoolState, row_dev, lanes, pages):
                my = axis_linear_index(("pool",))
                lanes, pages = lanes[0], pages[0]
                xrow = self._gather_rows(state, pages)
                nv = state.n_valid[lanes]
                f = jax.vmap(lambda xr, n: obj.combine(obj.aggregates(
                    xr, n)))(xrow, nv)
                return (owner_select(f, row_dev, my, "pool"),
                        owner_select(xrow, row_dev, my, "pool"),
                        owner_select(state.hist[lanes], row_dev, my,
                                     "pool"))

            # repro: allow[RPR005] sharded finalize: same read-only contract
            # as the unsharded branch — state must stay live for stepping
            fn = jax.jit(jax.shard_map(
                finalize_sharded, mesh=self.mesh, check_vma=False,
                in_specs=(_state_specs(), P(), P("pool", None),
                          P("pool", None, None)),
                out_specs=(P(), P(), P())))
        self._cache[ck] = fn
        return fn

    def finalize_span(self, g: int, v: int) -> Callable:
        """Harvest for STRIPED spanning lanes (sharded pools only).

        ``(state, page_dev (v, g), lanes (v,), pages (D, v, g)) ->
        (f (v,), x (v, g*block), hist (v, n_passes))``. No single device
        holds a striped lane's row view, so the gather is selected
        per-PAGE: device d gathers its local pages (scratch elsewhere) and
        one owner_select over the (v, g) page→device map stitches the
        global view. ``f`` comes from ``combine(state.aggs[lane])`` — at
        harvest the lane's last action was its span re-sync, whose
        aggregates are bit-identical to the exact re-eval the unsharded
        finalize computes (and to ``abo_minimize``'s final ``f_exact``)."""
        ck = ("final_span", g, v)
        fn = self._cache.get(ck)
        if fn is not None:
            return fn
        assert self.mesh is not None, "finalize_span requires a sharded pool"
        obj, bsz = self.obj, self.cfg.block_size

        def finalize_span(state: PoolState, page_dev, lanes, pages):
            my = axis_linear_index(("pool",))
            pages = pages[0]                          # (v, g) local ids
            xpg = state.pool[pages]                   # (v, g, block)
            xpg = owner_select(xpg, page_dev, my, "pool")
            xrow = xpg.reshape(v, g * bsz)
            f = jax.vmap(obj.combine)(state.aggs[lanes])
            return f, xrow, state.hist[lanes]

        # repro: allow[RPR005] read-only like finalize: the pool must stay
        # live for stepping, so no donation
        fn = jax.jit(jax.shard_map(
            finalize_span, mesh=self.mesh, check_vma=False,
            in_specs=(_state_specs(), P(), P(),
                      P("pool", None, None)),
            out_specs=(P(), P(), P())))
        self._cache[ck] = fn
        return fn


def get_pool_ops(obj: SeparableObjective, key: tuple, lanes: int,
                 pages: int, mesh: Mesh | None = None) -> PoolOps:
    ck = (key, lanes, pages, mesh.devices.size if mesh is not None else 1)
    ops = _POOL_OPS_CACHE.get(ck)
    if ops is None:
        ops = PoolOps(obj, key, lanes, pages, mesh)
        _POOL_OPS_CACHE[ck] = ops
    return ops


def compiled_executable_count(families: set | None = None) -> int:
    """Distinct jitted executables built for pool operations (each cache
    entry is one compiled shape). With ``families`` (a set of family
    keys, e.g. an engine's ``family_keys_seen``), counts only executables
    those families own — the per-engine number stats report; without it,
    the process-wide total."""
    return sum(ops.compiled_count() for (key, _, _, _), ops
               in _POOL_OPS_CACHE.items()
               if families is None or key in families)
