"""Slot-based continuous batching of ABO solve lanes over paged pools.

The engine owns a budget of ``lanes`` concurrent solves. Jobs are
grouped by compiled *family* (objective, effective config, dtype — see
batched.family_key); each family gets one :class:`LanePool` whose lane
coordinate blocks live in a shared page pool with host-side page tables.
Between steps, lanes whose job has run all its passes are finalized via a
compact gather of just those lanes and immediately refilled from the
queue — the swap-finished-jobs-between-steps pattern of
``launch/serve.py``, at pass granularity instead of token granularity.

Pool memory is *elastic*: a pool's lane-slot count starts at observed
demand and rides the count ladder up to the engine budget (a family that
only ever sees two concurrent jobs sizes its per-slot arrays for two, not
``lanes``), and on drain both dimensions shrink — free pages and empty
slots past a ``pool_high_water`` hysteresis of the ladder rung actually
needed are released from the device (``batched.resize_pool_state``).
Page/slot ids are stable, so only all-free *tails* can be released; the
low-id-first free-list policy steers occupancy toward low ids so drains
strand little. A long-lived service's footprint therefore tracks live
traffic instead of its historical peak — the zero-RAM contract applied to
the engine itself.

Heterogeneous n costs what it costs: a lane occupies ``ceil(n / block)``
pages and the row-compacted sweep touches exactly the occupied rows, so
admission needs no fill-ratio gate, no canonical pad rungs, and no
sibling-group fusion — a queued job lands in its family's pool whenever a
lane slot is free, and jobs of every n share that family's executables.
The only ladder left is on *counts* (row widths, gathered-view sizes,
pool capacity), which bounds compiled shapes while wasting at most 1/3 —
in practice a few percent — of swept block rows (``pad_stats`` reports
the realized fraction).

Every lane advances whole passes per step, so job progress is tracked
host-side (``JobState.passes_done``) and the step loop never reads device
memory: row sweeps pipeline through JAX's async dispatch, and the engine
only syncs when a job finishes (its exact final objective) or a
checkpoint is cut. Steady-state dispatch re-sends the plan's cached
device-resident tables and a cached fused-pass-count constant — no
per-step host wraps or transfers.

With ``devices=D`` each family's page pool is sharded across a 1-axis
device mesh: lanes place whole onto the least-loaded device (host page
tables map lane→(device, local page)), each device sweeps only its
resident lanes' bands inside one shard_map'd fused executable, and one
owner-selected psum per pass re-replicates the per-slot scalars — the
Gauss-Seidel-within / Jacobi-across semantics of ``core/sharded.py`` at
the pool layer, with per-job fun/x still bit-identical to abo_minimize
at every device count (see engine/DESIGN.md "Sharded pools & donation").

Fault tolerance: with a ``checkpoint_dir``, the engine snapshots every
``ckpt_every`` steps — the pool states as array leaves, and the job
table / queue / page tables as the manifest's aux JSON — in one atomic
CheckpointManager commit. ``SolveEngine.resume(dir)`` rebuilds the whole
engine mid-solve; because snapshots land on pass boundaries and every pass
is deterministic, a killed-and-resumed engine reproduces an uninterrupted
run's results exactly. With ``retain_done=N``, whole job records of
delivered (fetched DONE) or cancelled jobs beyond the N most recent are
evicted from the table, so a long-lived service's snapshot aux stays
bounded no matter how many jobs churn through.

With ``journal_every=M`` the whole-state snapshot becomes a rare *base*
(cut every M steps) and the gaps are covered by an append-only journal of
client inputs — submit / cancel / fetched records appended the moment
they happen (see jobs.J_*). Resume restores the newest base, then replays
journal records past the base's ``journal_seq``: replayed submissions
re-queue, replayed cancels/fetches re-apply, and every solve past the
base re-runs deterministically from its base state — so per-job fun/x are
bit-identical to the uninterrupted run while steady-state checkpoint I/O
is O(client events), not O(job table). Each base snapshot truncates the
journal segments it covers (compaction).
"""
# repro: hot-path — engine step loop; harvest/snapshot are the designed sync points
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from typing import Any, Iterable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.analysis import sanitize as _sanitize
from repro.checkpoint.manager import CheckpointManager
from repro.obs.metrics import MetricsRegistry
from repro.obs.roofline import plan_pass_bytes
from repro.obs.trace import Tracer
from repro.core.abo import ABOConfig
from repro.engine import batched
from repro.engine.faults import resolve_faults
from repro.engine.jobs import (CANCELLED, DONE, FAILED, J_CANCEL, J_EXPIRE,
                               J_FETCHED, J_SUBMIT, QUEUED, RUNNING, JobSpec,
                               JobState, next_job_id)
from repro.objectives import OBJECTIVES
from repro.objectives.base import SeparableObjective

# shared no-op context: sanitize-mode hooks cost one attribute check and
# this reusable nullcontext when the mode is off — no allocation per step
_NULL = contextlib.nullcontext()


def _job_ids(pairs) -> str:
    """The ids of (slot, job) pairs as one span arg: space-separated,
    since the profiler's annotation encoding cuts a value at a comma."""
    return " ".join(rec.job_id for _, rec in pairs)


class AdmissionError(RuntimeError):
    """Typed submit() rejection (backpressure, not malformed input — a
    RuntimeError subclass so wire front-ends can keep mapping ValueError
    to 400 while these map to 429/503)."""


class QueueFullError(AdmissionError):
    """submit() rejected: the bounded queue is at max_queue."""


class MemoryBudgetError(AdmissionError):
    """submit() rejected: admitting the job would push projected pool
    device bytes past memory_budget_bytes."""


@dataclasses.dataclass
class _SweepRun:
    """One contiguous band of block rows sharing a width rung: the plan
    arrays one band loop of the fused-step executable consumes. Sharded
    plans carry a leading device axis on every array (``(D, r_cap, w)``
    tables sharded over the mesh, per-device row counts ``(D,)``) — the
    same signature rungs, one schedule per device."""

    w: int                   # width rung (lanes gathered per row)
    r_cap: int               # row-count rung (array length)
    n_rows: jnp.ndarray      # () int32 — rows actually executed (<= r_cap)
    lanes: jnp.ndarray       # (r_cap, w) lane-slot ids (scratch-padded)
    pages: jnp.ndarray       # (r_cap, w) page ids (scratch-padded)
    rows: jnp.ndarray        # (r_cap, w) global block-row numbers
    live_slots: int          # true (lane, row) pairs in the band
    swept_slots: int         # executed slots incl. width-rung padding


@dataclasses.dataclass
class _SyncGroup:
    """All active lanes gathered at one page-count rung: the end-of-pass
    lane sync inside the fused step (finalize at harvest reuses the same
    gather shape for just the finishing lanes). Sharded plans carry a
    leading device axis (each device syncs its resident lanes)."""

    g: int                   # page-count rung (gathered row view, pages)
    v: int                   # lane-batch rung
    lanes: jnp.ndarray       # (v,) lane-slot ids (scratch-padded)
    pages: jnp.ndarray       # (v, g) page ids (scratch-padded)


@dataclasses.dataclass
class _Plan:
    runs: list[_SweepRun]
    sync: _SyncGroup | None
    live_slots: int          # per-pass true block rows
    swept_slots: int         # per-pass executed block rows
    # the dispatch-ready argument list (band tables, sync tables — owner
    # table first when sharded), built ONCE at plan time: steady-state
    # stepping re-sends the same device-resident arrays every fused
    # dispatch instead of re-wrapping host indices per step
    args: list = dataclasses.field(default_factory=list)
    # analytic DRAM bytes one pass of this plan moves (obs.roofline):
    # computed once from plan shapes at build time, accumulated into
    # engine_est_bytes_moved_total per dispatch — never a device read
    pass_bytes: int = 0
    # striped-spanning-lane signature (vs, t_pad, ts, ppt) — None when no
    # lane in this plan stripes across the mesh (see _build_plan_sharded)
    span: tuple | None = None
    # analytic bytes the per-pass span re-sync moves (tile gathers + the
    # bit-pattern psum of the partial table); obs.roofline adds this term
    # to pass_bytes
    span_psum_bytes: int = 0

    def signature(self) -> tuple:
        """The compiled shape of this plan: band + sync + span rungs
        only. Plans sharing a signature share one fused-step
        executable."""
        return (tuple((r.w, r.r_cap) for r in self.runs),
                (self.sync.g, self.sync.v), self.span)


def _gather_tables(entries: list[tuple[int, list[int]]], scratch_lane: int):
    """Scratch-padded gather tables for a batch of lanes.

    ``entries`` is ``[(slot, page_ids), ...]``. Returns the page-count
    rung ``g`` (the deepest member's), the lane-batch rung ``v``, and the
    (v,) / (v, g) lane/page index arrays — ladder padding targets the
    scratch slot/page, so sync, placement, and finalize all share one
    padding convention."""
    g = batched.pad_ladder(max(len(pt) for _, pt in entries), 1)
    v = batched.pad_ladder(len(entries), 1)
    lanes_np = np.full((v,), scratch_lane, np.int32)
    pages_np = np.full((v, g), batched.SCRATCH_PAGE, np.int32)
    for i, (slot, pt) in enumerate(entries):
        lanes_np[i] = slot
        pages_np[i, : len(pt)] = pt
    return g, v, lanes_np, pages_np


@dataclasses.dataclass
class LanePool:
    """One family's lanes: shared page pool + host-side page tables.

    ``slots`` (the per-slot array height) is sized to this family's
    observed concurrency, not the engine budget: it starts at zero, grows
    on the count ladder as admissions demand (capped at ``lanes``), and
    shrinks back on drain past the ``high_water`` hysteresis — as does the
    page capacity. ``high_water=None`` disables shrinking (capacity is
    retained forever, the pre-elastic behavior).

    With a ``mesh`` the pool pages are sharded: the global capacity is
    ``n_dev × cap_loc``, page ids in :attr:`page_table` are LOCAL to the
    lane's device, and ``lane_dev[slot]`` records which device hosts each
    lane (the lane→(device, page) mapping of the page tables). Lanes are
    placed whole onto the least-loaded device, so per-lane sweeps stay
    single-device Gauss-Seidel and results stay bit-identical to the
    unsharded engine; devices balance at lane granularity."""

    key: tuple
    obj: SeparableObjective
    lanes: int                                   # engine budget = slot cap
    slots: int = 0                               # current lane-slot count
    high_water: float | None = 2.0               # shrink hysteresis factor
    state: batched.PoolState | None = None       # materialized on first use
    capacity: int = 1                            # GLOBAL pages incl. the
    #                                              per-device scratch page 0
    mesh: Mesh | None = None                     # None = unsharded
    n_dev: int = 1
    job_ids: list[str | None] = dataclasses.field(default_factory=list)
    page_table: list[list[int] | None] = dataclasses.field(
        default_factory=list)
    lane_dev: list[int | None] = dataclasses.field(default_factory=list)
    # per-device free lists of LOCAL page ids (index 0 = device 0, ...)
    free_pages: list[list[int]] = dataclasses.field(default_factory=list)
    plan: _Plan | None = None                    # rebuilt when lanes change

    def __post_init__(self):
        if not self.job_ids:
            self.job_ids = [None] * self.slots
        if not self.page_table:
            self.page_table = [None] * self.slots
        if not self.lane_dev:
            self.lane_dev = [None] * self.slots
        if not self.free_pages:
            self.free_pages = [[] for _ in range(self.n_dev)]
        if self.capacity < self.n_dev:       # one scratch page per device
            self.capacity = self.n_dev

    @property
    def cap_loc(self) -> int:
        """Per-device page capacity (== ``capacity`` when unsharded)."""
        return self.capacity // self.n_dev

    @property
    def active(self) -> int:
        return sum(j is not None for j in self.job_ids)

    def free_slot(self) -> int | None:
        for i, j in enumerate(self.job_ids):
            if j is None:
                return i
        return None

    def take_slot(self) -> int:
        """A free slot, growing the ladder-sized slot plan when all are
        occupied (the device arrays resize lazily in :meth:`materialize`).
        Callers gate admission on the engine-wide lane budget, so growth
        never exceeds ``lanes``."""
        slot = self.free_slot()
        if slot is not None:
            return slot
        new = min(batched.pad_ladder(self.slots + 1, 1), self.lanes)
        assert new > self.slots, "slot budget exhausted"
        self.job_ids += [None] * (new - self.slots)
        self.page_table += [None] * (new - self.slots)
        self.lane_dev += [None] * (new - self.slots)
        self.slots = new
        self.plan = None
        return self.free_slot()

    def pick_device(self) -> int:
        """The least-loaded device (fewest live pages; ties go low) — the
        deterministic placement rule for a new lane. Bit-identity does not
        depend on it (any placement gives the same per-lane bits); balance
        does."""
        if self.n_dev == 1:
            return 0
        live = [0] * self.n_dev
        for jid, pt, dev in zip(self.job_ids, self.page_table,
                                self.lane_dev):
            if jid is not None and pt:
                if isinstance(dev, list):    # striped: count page-wise
                    for d in dev:
                        live[d] += 1
                else:
                    live[dev] += len(pt)
        return min(range(self.n_dev), key=lambda d: (live[d], d))

    # repro: allow[RPR001] striped page allocation is host bookkeeping:
    # numpy over host free lists, never live device buffers
    def alloc_span_pages(self, count: int, rps_pages: int
                         ) -> tuple[list[int], list[int]]:
        """Striped allocation for one spanning lane: ``count`` pages in
        fixed contiguous shards of ``rps_pages``, shard k resident on
        device ``k % n_dev`` (round-robin — re-derivable at any device
        count, which is what lets kill/resume reshard a striped lane
        deterministically). Returns the per-page (LOCAL id, device)
        columns of the lane's page table in global page order."""
        shard_of = ((np.arange(count) // rps_pages)
                    % self.n_dev).astype(np.int64)
        locs = np.zeros((count,), np.int64)
        for d in range(self.n_dev):
            idx = np.flatnonzero(shard_of == d)
            if len(idx):
                locs[idx] = self.alloc_pages(len(idx), d)
        return locs.tolist(), shard_of.tolist()

    def alloc_pages(self, count: int, dev: int = 0) -> list[int]:
        """Take ``count`` LOCAL page ids on device ``dev``, growing the
        per-device capacity plan onto the next ladder rung when that
        device's free list runs short (every device's shard grows in
        lockstep — the pool is one sharded array; the device arrays
        resize lazily in :meth:`materialize`)."""
        free = self.free_pages[dev]
        if len(free) < count:
            need = count - len(free)
            new_loc = batched.pad_ladder(self.cap_loc + need, 1)
            for d in range(self.n_dev):
                self.free_pages[d].extend(range(self.cap_loc, new_loc))
            self.capacity = new_loc * self.n_dev
            free = self.free_pages[dev]
        pages, self.free_pages[dev] = free[:count], free[count:]
        return pages

    def release_pages(self, pages: list[int], dev: int = 0):
        self.free_pages[dev].extend(pages)
        self.free_pages[dev].sort()          # deterministic reassignment

    def materialize(self) -> bool:
        """Reconcile the device state to the host plan (slots, capacity)
        — growing OR shrinking; a no-op when shapes already match.
        Returns True when the device arrays actually changed (the engine
        counts these as pool resizes)."""
        if self.state is None:
            self.state = batched.zeros_pool_state(
                self.obj, self.key, self.slots, self.capacity, self.mesh)
            return True
        new = batched.resize_pool_state(
            self.state, self.slots, self.capacity, self.mesh)
        changed = new is not self.state
        self.state = new
        return changed

    def shrink_to_fit(self):
        """Release free capacity past the high-water hysteresis. Called
        after lanes drain: if the current slot count / page capacity
        exceeds ``high_water ×`` the ladder rung covering the highest
        occupied slot / used page, the all-free tail is cut and the device
        arrays resized immediately — that is the moment the memory
        actually returns. Only tails can go (ids are stable); interior
        free pages wait for the lanes pinning higher ids to drain.
        Sharded pools cut every shard to the ladder rung covering the
        deepest-loaded device (shards stay equal-height). Returns True
        when device arrays were actually resized."""
        if self.high_water is None or self.state is None:
            return False
        top = max((i for i, j in enumerate(self.job_ids) if j is not None),
                  default=-1)
        slot_target = min(batched.pad_ladder(max(top + 1, 1), 1), self.lanes)
        if slot_target < self.slots and self.slots > self.high_water \
                * slot_target:
            del self.job_ids[slot_target:]
            del self.page_table[slot_target:]
            del self.lane_dev[slot_target:]
            self.slots = slot_target
            self.plan = None
        used_top = batched.SCRATCH_PAGE
        for jid, pt in zip(self.job_ids, self.page_table):
            if jid is not None and pt:
                used_top = max(used_top, max(pt))
        loc_target = batched.pad_ladder(used_top + 1, 1)
        if loc_target < self.cap_loc and self.cap_loc > self.high_water \
                * loc_target:
            self.capacity = loc_target * self.n_dev
            self.free_pages = [[p for p in fp if p < loc_target]
                               for fp in self.free_pages]
            self.plan = None
        return self.materialize()

    def _slot_bytes(self) -> int:
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in (self.state.aggs, self.state.hist,
                                self.state.pass_idx, self.state.n_valid))

    def device_bytes(self) -> int:
        """Physical bytes the device arrays hold across all devices (0 if
        unmaterialized). Sharded pools count the replicated per-slot
        arrays once per device — that is what actually sits in device
        memory."""
        if self.state is None:
            return 0
        pool_b = self.state.pool.size * self.state.pool.dtype.itemsize
        return pool_b + self._slot_bytes() * self.n_dev

    def per_device_stats(self) -> list[dict]:
        """Per-device resident footprint: local pages, slots, bytes."""
        if self.state is None:
            return [{"pages": 0, "slots": 0, "bytes": 0}
                    for _ in range(self.n_dev)]
        bsz = self.state.pool.shape[1]
        shard_b = self.cap_loc * bsz * self.state.pool.dtype.itemsize
        slot_b = self._slot_bytes()
        return [{"pages": self.cap_loc,
                 "slots": self.state.aggs.shape[0] - 1,
                 "bytes": shard_b + slot_b} for _ in range(self.n_dev)]

    # ------------------------------------------------------------- planning
    @staticmethod
    def _bands_np(active, scratch: int):
        """Numpy band tables for one device's (or the unsharded pool's)
        active lanes: a list of ``{w, nb, lanes, pages, rows, live}``
        dicts with ``(nb, w)`` arrays, width already on its rung, rows
        NOT yet padded to a row-count rung (callers pad — the unsharded
        plan to each band's own rung, the sharded plan to the rung
        unified across devices).

        ``active`` entries are ``(slot, pages, rows)``: ``rows`` holds
        each page's GLOBAL block-row number inside its lane — ``None``
        means the contiguous ``0..len(pages)-1`` of a whole lane, while a
        striped spanning lane's per-device entry carries just its
        resident shards' pages with their true global rows, so the probe
        index math and the shard-boundary Jacobi reset see the same
        coordinates at every device count. Entries' rows must ascend:
        the band loop executes entry position r before r+1, which is the
        Gauss-Seidel order within each (shard of a) lane.

        Construction is array-at-once: lanes sort by depth (descending,
        slot-ascending ties), so the lanes occupying row r are exactly the
        first ``count(r)`` of that order and every band's plan arrays are
        numpy slices of one (lane, row) page matrix — no host loop over
        block rows. A paper-scale lane (1e9 coords ≈ 244k rows) plans in
        milliseconds; the old per-row Python loop scaled with pool size.
        Entry order within a row is a permutation of the old planner's —
        harmless, since row entries touch disjoint (lane, page) pairs.
        """
        if not active:
            return []
        n_act = len(active)
        depths = np.fromiter((len(e[1]) for e in active), np.int64, n_act)
        order = np.lexsort((np.arange(n_act), -depths))
        slots_arr = np.fromiter((e[0] for e in active), np.int32,
                                n_act)[order]
        max_rows = int(depths.max())
        pages_mat = np.full((n_act, max_rows), batched.SCRATCH_PAGE,
                            np.int32)
        rows_mat = np.zeros((n_act, max_rows), np.int32)
        for i, oi in enumerate(order):
            _, pt, rws = active[oi]
            pages_mat[i, : len(pt)] = pt
            rows_mat[i, : len(pt)] = (np.arange(len(pt), dtype=np.int32)
                                      if rws is None else rws)

        # lanes occupying row r (non-increasing), its width rung, and the
        # maximal contiguous runs of equal rung = the bands
        rows_idx = np.arange(max_rows)
        counts = n_act - np.searchsorted(np.sort(depths), rows_idx,
                                         side="right")
        rung_lut = np.array([0] + [batched.pad_ladder(c, 1)
                                   for c in range(1, n_act + 1)], np.int64)
        rungs = rung_lut[counts]
        starts = np.concatenate(
            [[0], np.flatnonzero(np.diff(rungs)) + 1, [max_rows]])

        bands = []
        for r0, r1 in zip(starts[:-1], starts[1:]):
            r0, r1 = int(r0), int(r1)
            w_rung = int(rungs[r0])
            nb = r1 - r0
            cmax = int(counts[r0])           # counts peak at the band head
            colmask = np.arange(cmax)[None, :] < counts[r0:r1, None]
            lanes_np = np.full((nb, w_rung), scratch, np.int32)
            pages_np = np.full((nb, w_rung), batched.SCRATCH_PAGE,
                               np.int32)
            rows_np = np.zeros((nb, w_rung), np.int32)
            lanes_np[:, :cmax] = np.where(
                colmask, slots_arr[None, :cmax], scratch)
            pages_np[:, :cmax] = np.where(
                colmask, pages_mat[:cmax, r0:r1].T, batched.SCRATCH_PAGE)
            rows_np[:, :cmax] = np.where(colmask, rows_mat[:cmax, r0:r1].T, 0)
            bands.append({"w": w_rung, "nb": nb, "lanes": lanes_np,
                          "pages": pages_np, "rows": rows_np,
                          "live": int(counts[r0:r1].sum())})
        return bands

    def build_plan(self) -> _Plan:
        """Row-compacted sweep plan for the current lane occupancy.

        Band structure: the number of lanes occupying row r is
        non-increasing in r, so rows sharing a width rung are contiguous;
        the bands run in ascending-row order (descending width) inside
        the fused-step executable, preserving the Gauss-Seidel block
        ordering within every lane. Ladder padding (width and row-count
        rungs) points at the scratch lane/page.

        Sharded pools build one band schedule PER DEVICE (each over that
        device's resident lanes, local page ids) and unify the shapes —
        band i compiles at the max (width, row-count) rung any device
        needs, devices with less work ride scratch padding and a smaller
        dynamic row count. The unified rungs are the plan signature, so
        the one-executable-per-signature contract is unchanged; the
        stacked ``(D, ...)`` tables are device_put sharded once here and
        re-sent verbatim every step.
        """
        active = [(slot, pt) for slot, (jid, pt)
                  in enumerate(zip(self.job_ids, self.page_table))
                  if jid is not None]
        if not active:
            return _Plan([], None, 0, 0)
        scratch = self.slots
        # per-slot spanning decomposition (rows per shard): uniform over
        # a pool — span_coords is part of the family config — so every
        # active slot carries the same value; SPAN_NONE_ROWS elsewhere
        # makes the in-sweep reset fire only at row 0 (a bitwise no-op)
        cfg = batched.key_config(self.key)
        rps = (cfg.span_coords // cfg.block_size
               if cfg.span_coords is not None else batched.SPAN_NONE_ROWS)
        shard_rows = np.full((self.slots + 1,), batched.SPAN_NONE_ROWS,
                             np.int32)
        for slot, _ in active:
            shard_rows[slot] = rps
        if self.mesh is None:
            runs = []
            live = swept = 0
            for b in self._bands_np([(s, pt, None) for s, pt in active],
                                    scratch):
                nb, w_rung = b["nb"], b["w"]
                r_cap = batched.pad_ladder(nb, 1)

                def pad(a, fill):
                    out = np.full((r_cap, w_rung), fill, np.int32)
                    out[:nb] = a
                    return out

                live += b["live"]
                swept += nb * w_rung
                runs.append(_SweepRun(
                    w=w_rung, r_cap=r_cap,
                    n_rows=jnp.asarray(nb, jnp.int32),
                    lanes=jnp.asarray(pad(b["lanes"], scratch)),
                    pages=jnp.asarray(pad(b["pages"],
                                          batched.SCRATCH_PAGE)),
                    rows=jnp.asarray(pad(b["rows"], 0)),
                    live_slots=b["live"],
                    swept_slots=nb * w_rung))

            # one gather shape for every active lane: the deepest lane's
            # page-count rung (short lanes read scratch zeros past their
            # pages — masked out, and a 1/m-cost side dish vs the sweep)
            g, v, lanes_np, pages_np = _gather_tables(active, scratch)
            sync = _SyncGroup(g=g, v=v, lanes=jnp.asarray(lanes_np),
                              pages=jnp.asarray(pages_np))
            plan = _Plan(runs, sync, live, swept)
            plan.args = [jnp.asarray(shard_rows)]
            for r in plan.runs:
                plan.args += [r.lanes, r.pages, r.rows, r.n_rows]
            plan.args += [sync.lanes, sync.pages]
            plan.pass_bytes = plan_pass_bytes(
                plan, batched.key_config(self.key).block_size,
                jnp.dtype(self.key[2]).itemsize)
            return plan
        return self._build_plan_sharded(active, scratch, shard_rows)

    # repro: allow[RPR001] plan building is host metadata work: numpy
    # over host page tables / device maps, never live device buffers
    def _build_plan_sharded(self, active, scratch, shard_rows) -> _Plan:
        D = self.n_dev
        mesh = self.mesh
        cfg = batched.key_config(self.key)
        bsz = cfg.block_size
        whole = [(s, pt) for s, pt in active
                 if not isinstance(self.lane_dev[s], list)]
        span = [(s, pt) for s, pt in active
                if isinstance(self.lane_dev[s], list)]
        per_dev = [[(s, pt) for s, pt in whole if self.lane_dev[s] == d]
                   for d in range(D)]
        # band schedules: whole lanes contribute their full contiguous
        # runs; a striped lane contributes, per device, just its resident
        # shards' pages with TRUE global rows (ascending, so the device
        # sweeps its shards in Gauss-Seidel order and the shard-boundary
        # reset in _band_body fires exactly at each shard's first row)
        band_dev = [[(s, pt, None) for s, pt in act] for act in per_dev]
        for s, pt in span:
            devs = np.asarray(self.lane_dev[s], np.int32)
            pt_np = np.asarray(pt, np.int32)
            rows = np.arange(len(pt), dtype=np.int32)
            for d in range(D):
                m = devs == d
                if m.any():
                    band_dev[d].append((s, pt_np[m], rows[m]))
        bands_d = [self._bands_np(act, scratch) for act in band_dev]
        n_bands = max(len(b) for b in bands_d)
        sh_tab = NamedSharding(mesh, PartitionSpec("pool", None, None))
        sh_vec = NamedSharding(mesh, PartitionSpec("pool"))
        sh_mat = NamedSharding(mesh, PartitionSpec("pool", None))
        sh_rep = NamedSharding(mesh, PartitionSpec())

        runs = []
        live = swept = 0
        for i in range(n_bands):
            devs = [b[i] if i < len(b) else None for b in bands_d]
            w = max((b["w"] for b in devs if b), default=1)
            r_cap = batched.pad_ladder(
                max((b["nb"] for b in devs if b), default=1), 1)
            lanes_np = np.full((D, r_cap, w), scratch, np.int32)
            pages_np = np.full((D, r_cap, w), batched.SCRATCH_PAGE,
                               np.int32)
            rows_np = np.zeros((D, r_cap, w), np.int32)
            n_rows_np = np.zeros((D,), np.int32)
            band_live = band_swept = 0
            for d, b in enumerate(devs):
                if b is None:
                    continue
                nb, wd = b["nb"], b["w"]
                lanes_np[d, :nb, :wd] = b["lanes"]
                pages_np[d, :nb, :wd] = b["pages"]
                rows_np[d, :nb, :wd] = b["rows"]
                n_rows_np[d] = nb
                band_live += b["live"]
                band_swept += nb * w
            live += band_live
            swept += band_swept
            runs.append(_SweepRun(
                w=w, r_cap=r_cap,
                n_rows=jax.device_put(jnp.asarray(n_rows_np), sh_vec),
                lanes=jax.device_put(jnp.asarray(lanes_np), sh_tab),
                pages=jax.device_put(jnp.asarray(pages_np), sh_tab),
                rows=jax.device_put(jnp.asarray(rows_np), sh_tab),
                live_slots=band_live,
                swept_slots=band_swept))

        # per-device lane sync at rungs unified across devices — WHOLE
        # lanes only: a striped lane has no single-device row view, its
        # re-sync is the distributed span sync below
        g = max((batched.pad_ladder(max(len(pt) for _, pt in act), 1)
                 for act in per_dev if act), default=1)
        v = max((batched.pad_ladder(len(act), 1)
                 for act in per_dev if act), default=1)
        lanes_np = np.full((D, v), scratch, np.int32)
        pages_np = np.full((D, v, g), batched.SCRATCH_PAGE, np.int32)
        for d, act in enumerate(per_dev):
            for i, (slot, pt) in enumerate(act):
                lanes_np[d, i] = slot
                pages_np[d, i, : len(pt)] = pt
        sync = _SyncGroup(
            g=g, v=v,
            lanes=jax.device_put(jnp.asarray(lanes_np), sh_mat),
            pages=jax.device_put(jnp.asarray(pages_np), sh_tab))

        # striped slots keep owner 0: after the span sync their scalars
        # are replica-identical, so the owner select is a no-op for them
        owner_np = np.zeros((self.slots + 1,), np.int32)
        for slot, _ in whole:
            owner_np[slot] = self.lane_dev[slot]

        span_sig = None
        span_args: list = []
        span_bytes = 0
        if span:
            span_sig, span_args, span_bytes = self._span_tables(
                span, scratch, sh_rep, sh_mat, sh_tab)
        plan = _Plan(runs, sync, live, swept, span=span_sig,
                     span_psum_bytes=span_bytes)
        plan.args = [jax.device_put(jnp.asarray(owner_np), sh_rep),
                     jax.device_put(jnp.asarray(shard_rows), sh_rep)]
        for r in plan.runs:
            plan.args += [r.lanes, r.pages, r.rows, r.n_rows]
        plan.args += [sync.lanes, sync.pages]
        plan.args += span_args
        plan.pass_bytes = plan_pass_bytes(
            plan, batched.key_config(self.key).block_size,
            jnp.dtype(self.key[2]).itemsize)
        return plan

    # repro: allow[RPR001] plan building is host metadata work: numpy
    # over host page tables / device maps, never live device buffers
    def _span_tables(self, span, scratch, sh_rep, sh_mat, sh_tab):
        """Plan tables for the per-pass distributed span re-sync: for
        every striped lane, each device's owned fixed-origin REDUCE_TILE
        tiles — (table row, global tile, gather pages, in-window offset)
        — plus the replicated (lane, tile-count) vectors. All numpy
        array-at-once: a paper-scale lane (1e9 coords ≈ 244k tiles)
        builds in well under a second, no pool state touched."""
        D = self.n_dev
        cfg = batched.key_config(self.key)
        bsz = cfg.block_size
        tile = self.obj.REDUCE_TILE
        ppt = (tile + bsz - 1) // bsz + 1
        vs = batched.pad_ladder(len(span), 1)
        ntiles = [(len(pt) * bsz + tile - 1) // tile for _, pt in span]
        t_pad = batched.pad_ladder(max(ntiles), 1)
        sp_lanes_np = np.full((vs,), scratch, np.int32)
        sp_ntiles_np = np.zeros((vs,), np.int32)
        per_d: list[list[tuple]] = [[] for _ in range(D)]
        for i, (s, pt) in enumerate(span):
            sp_lanes_np[i] = s
            sp_ntiles_np[i] = ntiles[i]
            tt = np.arange(ntiles[i], dtype=np.int64)
            dev = ((tt * tile) // cfg.span_coords) % D
            p0 = (tt * tile) // bsz
            off = (tt * tile - p0 * bsz).astype(np.int32)
            pt_np = np.asarray(pt, np.int32)
            for d in range(D):
                m = dev == d
                if m.any():
                    per_d[d].append((i, tt[m], p0[m], off[m], pt_np))
        ts = batched.pad_ladder(
            max((sum(len(e[1]) for e in lst) for lst in per_d if lst),
                default=1), 1)
        tile_slot_np = np.full((D, ts), vs, np.int32)       # dump row
        tile_idx_np = np.full((D, ts), t_pad, np.int32)     # dump col
        tile_pages_np = np.zeros((D, ts, ppt), np.int32)    # local scratch
        tile_off_np = np.zeros((D, ts), np.int32)
        for d in range(D):
            j = 0
            for i, tt, p0, off, pt_np in per_d[d]:
                k = len(tt)
                tile_slot_np[d, j:j + k] = i
                tile_idx_np[d, j:j + k] = tt
                tile_off_np[d, j:j + k] = off
                for q in range(ppt):
                    pg = p0 + q
                    # only pages intersecting the tile gather real rows;
                    # the conservative window's trailing page and pages
                    # past the lane's last ride the local scratch zeros
                    ok = (pg < len(pt_np)) & (pg * bsz < (tt + 1) * tile)
                    tile_pages_np[d, j:j + k, q] = np.where(
                        ok, pt_np[np.minimum(pg, len(pt_np) - 1)],
                        batched.SCRATCH_PAGE)
                j += k
        span_args = [
            jax.device_put(jnp.asarray(sp_lanes_np), sh_rep),
            jax.device_put(jnp.asarray(sp_ntiles_np), sh_rep),
            jax.device_put(jnp.asarray(tile_slot_np), sh_mat),
            jax.device_put(jnp.asarray(tile_idx_np), sh_mat),
            jax.device_put(jnp.asarray(tile_pages_np), sh_tab),
            jax.device_put(jnp.asarray(tile_off_np), sh_mat)]
        # psum term: the (vs+1, t_pad+1, n_aggs) partial table crosses
        # the mesh once per pass (read + write per device), plus the
        # owned-tile page gathers feeding it
        agg_item = 8 if jax.config.jax_enable_x64 else 4
        itemsize = jnp.dtype(self.key[2]).itemsize
        span_bytes = (2 * D * (vs + 1) * (t_pad + 1)
                      * self.obj.n_aggs * agg_item
                      + D * ts * ppt * bsz * itemsize)
        return (vs, t_pad, ts, ppt), span_args, span_bytes


def too_few_devices_message(wanted: int, devices) -> str:
    """Why ``devices=wanted`` cannot be met. On a TPU, D counts chips; a
    D-device mesh on the CPU is the rehearsal, with forced host devices."""
    head = f"devices={wanted} but JAX sees {len(devices)} " \
           f"{devices[0].platform} device(s)"
    if devices[0].platform == "cpu":
        return (f"{head}: this is the CPU rehearsal of a mesh — force "
                f"{wanted} host devices with XLA_FLAGS=--xla_force_host_"
                f"platform_device_count={wanted}, set before jax "
                "initializes; on a TPU host, devices counts chips")
    return (f"{head}: devices counts this host's chips; run on a host "
            f"with {wanted} or more")


class SolveEngine:
    """Serve many concurrent ABO jobs through shared jitted sweeps.

    Usage::

        eng = SolveEngine(lanes=8)
        jid = eng.submit(JobSpec("griewank", 1000, seed=0))
        eng.run()                  # or step() from your own loop
        res = eng.result(jid)      # an ABOResult, same as abo_minimize's
    """

    def __init__(self, *, lanes: int = 8, dtype: Any = jnp.float32,
                 objectives: dict[str, SeparableObjective] | None = None,
                 checkpoint_dir: str | None = None, ckpt_every: int = 1,
                 keep: int = 3, max_fuse: int | None = None,
                 retain_done: int | None = None,
                 pool_high_water: float | None = 2.0,
                 journal_every: int | None = None,
                 devices: int | None = None,
                 sanitize: bool = False,
                 faults=None,
                 max_queue: int | None = None,
                 memory_budget_bytes: int | None = None,
                 span_pages: int | None = None):
        if lanes < 1:
            raise ValueError(f"lanes must be >= 1, got {lanes}")
        if span_pages is not None and span_pages < 1:
            raise ValueError(
                f"span_pages must be >= 1, got {span_pages}")
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        if memory_budget_bytes is not None and memory_budget_bytes < 1:
            raise ValueError("memory_budget_bytes must be >= 1, got "
                             f"{memory_budget_bytes}")
        if devices is not None and devices < 1:
            raise ValueError(f"devices must be >= 1, got {devices}")
        self.n_dev = int(devices or 1)
        if self.n_dev > 1:
            avail = jax.devices()
            if len(avail) < self.n_dev:
                raise ValueError(too_few_devices_message(self.n_dev, avail))
            self.mesh = Mesh(np.array(avail[:self.n_dev]), ("pool",))
        else:
            self.mesh = None
        if retain_done is not None and retain_done < 0:
            raise ValueError(
                f"retain_done must be >= 0 or None, got {retain_done}")
        if pool_high_water is not None and pool_high_water < 1.0:
            raise ValueError(
                "pool_high_water must be >= 1 or None (never shrink), got "
                f"{pool_high_water}: shrinking below the rung actually "
                "needed would thrash resize/recompile every admission")
        if journal_every is not None:
            if journal_every < 1:
                raise ValueError(
                    f"journal_every must be >= 1, got {journal_every}")
            if checkpoint_dir is None:
                raise ValueError(
                    "journal_every needs a checkpoint_dir: the journal is "
                    "an incremental layer over base snapshots, not a "
                    "replacement for them")
        self.lanes = lanes
        # cap on passes fused into one stretch of dispatches per step (None
        # = fuse whole generations); 1 restores strict pass-per-step
        # stepping, which is also the finest checkpoint/refill granularity
        self.max_fuse = max_fuse
        # keep at most this many delivered/cancelled job records; None
        # keeps everything (see _gc_jobs)
        self.retain_done = retain_done
        # elastic-pool shrink hysteresis (None = retain capacity forever)
        self.pool_high_water = pool_high_water
        # base-snapshot cadence in journal mode (None = legacy whole-state
        # snapshots every ckpt_every steps)
        self.journal_every = journal_every
        # suppresses re-journaling while replaying journal records
        self._replaying = False
        # runtime sanitizer mode (repro.analysis.sanitize): step() runs
        # under sync_guard (any implicit device->host sync outside a
        # declared point raises), harvest/snapshot declare themselves via
        # allowed_sync, and every fused dispatch asserts its donated
        # input buffers actually died (single-copy pool discipline)
        self.sanitize = bool(sanitize)
        # fault injection (repro.engine.faults): off by default, the null
        # registry — every failpoint costs one dict .get miss, same
        # zero-overhead-when-disabled discipline as the obs tracer
        self.faults = resolve_faults(faults)
        # admission control: bounded queue + projected-memory shedding
        # (None = unbounded, the pre-admission behavior)
        self.max_queue = max_queue
        self.memory_budget_bytes = memory_budget_bytes
        # per-device page budget for spanning: a submitted job needing
        # more pages than this derives a span_coords decomposition at
        # submit time and its lane stripes across the mesh (None = every
        # lane places whole, the pre-spanning behavior; ignored on
        # single-device engines)
        self.span_pages = span_pages
        # projected per-job pool bytes, cached by (family key, pages) —
        # jax.eval_shape is host-only but not free, and admission runs
        # per submit
        self._job_bytes_cache: dict[tuple, int] = {}
        self.dtype = dtype
        self.objectives = dict(objectives or OBJECTIVES)
        self.jobs: dict[str, JobState] = {}
        self.queue: deque[str] = deque()
        self.pools: dict[tuple, LanePool] = {}
        # every family this engine ever opened a pool for — the number of
        # distinct executable families compiled on its behalf
        self.family_keys_seen: set[tuple] = set()
        self.step_count = 0
        # cumulative row-sweep slot accounting (see pad_stats)
        self.swept_slots = 0
        self.swept_slots_live = 0
        # fused pass counts as device-resident constants, keyed by r: the
        # fused dispatch re-sends the same committed scalar instead of
        # re-wrapping a host int (a host->device transfer) every step
        self._r_cache: dict[int, jnp.ndarray] = {}
        self._next = 0
        self._done_seq = 0
        # telemetry (obs/): registry + tracer are always present; the
        # tracer is disabled (null spans) until trace()/--trace enables
        # it, and every hot-path instrument is cached as an attribute so
        # a step pays attribute-add cost, never name resolution
        self.metrics = MetricsRegistry()
        self.tracer = Tracer()
        m = self.metrics
        self._c_steps = m.counter(
            "engine_steps_total", "engine step() calls")
        self._c_passes = m.counter(
            "engine_passes_total", "fused ABO passes dispatched, summed "
            "over pools (r per dispatch)")
        self._c_submitted = m.counter(
            "engine_jobs_submitted_total", "jobs accepted by submit()")
        self._c_done = m.counter(
            "engine_jobs_done_total", "jobs finished")
        self._c_cancelled = m.counter(
            "engine_jobs_cancelled_total", "jobs cancelled")
        self._c_failed = m.counter(
            "engine_jobs_failed_total", "jobs terminally FAILED "
            "(quarantined non-finite results, TTL expiry)")
        self._c_rej_queue = m.counter(
            "engine_admission_rejected_total", "submissions rejected by "
            "admission control", reason="queue_full")
        self._c_rej_mem = m.counter(
            "engine_admission_rejected_total", "submissions rejected by "
            "admission control", reason="memory_budget")
        self._c_plan_builds = m.counter(
            "engine_plan_builds_total", "sweep-plan rebuilds (occupancy "
            "changes)")
        self._c_resizes = m.counter(
            "engine_pool_resizes_total", "device-array pool resizes "
            "(grow or shrink)")
        self._c_pages_alloc = m.counter(
            "engine_pages_allocated_total", "pool pages bound to lanes")
        self._c_pages_freed = m.counter(
            "engine_pages_released_total", "pool pages returned to the "
            "free lists")
        self._c_est_bytes = m.counter(
            "engine_est_bytes_moved_total", "analytic DRAM bytes moved "
            "by dispatched sweeps (obs.roofline model)")
        self._h_queued = m.histogram(
            "engine_job_queued_seconds", "submit -> placed on a lane")
        self._h_run = m.histogram(
            "engine_job_run_seconds", "placed -> done")
        self._h_total = m.histogram(
            "engine_job_total_seconds", "submit -> done")
        self._h_fetch = m.histogram(
            "engine_job_fetch_seconds", "done -> first result fetch")
        self.faults.bind_metrics(self.metrics)
        self.ckpt = (CheckpointManager(checkpoint_dir, keep=keep,
                                       metrics=self.metrics,
                                       faults=self.faults)
                     if checkpoint_dir else None)
        self.ckpt_every = max(ckpt_every, 1)

    # ------------------------------------------------------------- client API
    def _journal(self, kind: str, job_id: str, **fields):
        """Append a client-input record to the checkpoint journal (no-op
        outside journal mode, and while replaying — a replayed event is
        already durable in the segments being replayed)."""
        if self.ckpt is not None and self.journal_every is not None \
                and not self._replaying:
            self.ckpt.journal_append([{"t": kind, "job_id": job_id,
                                       **fields}])

    def _projected_job_bytes(self, spec: JobSpec) -> int:
        """Device bytes one lane of this spec adds to its family pool
        (pages + one slot row), from abstract shapes only — admission
        must not allocate or compile anything."""
        key = batched.family_key(spec.objective, spec.n, spec.config,
                                 self.dtype)
        cfg = batched.key_config(key)
        pages = batched.pages_for(spec.n, cfg.block_size)
        ck = (key, pages)
        cached = self._job_bytes_cache.get(ck)
        if cached is None:
            obj = self.objectives[spec.objective]
            with_lane = jax.eval_shape(
                lambda: batched.zeros_pool_state(obj, key, 1, pages + 1))
            empty = jax.eval_shape(
                lambda: batched.zeros_pool_state(obj, key, 0, 1))
            size = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(with_lane))
            base = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(empty))
            cached = self._job_bytes_cache[ck] = max(size - base, 0)
        return cached

    def _admit(self, spec: JobSpec):
        """Backpressure gate: raises a typed AdmissionError instead of
        letting an overloaded engine queue without bound. QUEUED depth is
        counted like the engine_queue_depth gauge (stale ids in the deque
        don't count against clients)."""
        if self.max_queue is not None:
            depth = sum(j in self.jobs and self.jobs[j].status == QUEUED
                        for j in self.queue)
            if depth >= self.max_queue:
                self._c_rej_queue.inc()
                raise QueueFullError(
                    f"queue full: {depth} queued jobs >= max_queue="
                    f"{self.max_queue}")
        if self.memory_budget_bytes is not None:
            # project the whole admitted-but-unplaced backlog, not just
            # the live pools: admission is the only gate — by refill time
            # the work is already accepted
            projected = self.memory_stats()["pool_device_bytes"]
            for j in self.queue:
                rec = self.jobs.get(j)
                if rec is not None and rec.status == QUEUED:
                    projected += self._projected_job_bytes(rec.spec)
            projected += self._projected_job_bytes(spec)
            if projected > self.memory_budget_bytes:
                self._c_rej_mem.inc()
                raise MemoryBudgetError(
                    f"memory budget: projected pool bytes {projected} > "
                    f"memory_budget_bytes={self.memory_budget_bytes}")

    def _derive_span(self, spec: JobSpec) -> JobSpec:
        """Attach a derived spanning decomposition to a job that exceeds
        the per-device page budget: span_coords = the largest
        lcm(block, REDUCE_TILE)-aligned width within ``span_pages``
        pages (alignment keeps every fixed-origin reduction tile whole
        inside one shard, so the distributed re-sync owns tiles
        disjointly). The derived config replaces the spec BEFORE
        admission and journaling — J_SUBMIT carries it, so a replayed
        life re-derives nothing and solves the identical family."""
        if (self.span_pages is None or self.n_dev == 1
                or spec.config.span_coords is not None
                or spec.x0 is not None):
            return spec                  # user span_coords / x0 win;
        #                                  x0 lanes place whole (the
        #                                  explicit-x0 row is host data)
        cfg = spec.config
        if batched.pages_for(spec.n, cfg.block_size) <= self.span_pages:
            return spec
        chunk = int(np.lcm(cfg.block_size,
                           SeparableObjective.REDUCE_TILE))
        derived = max(chunk,
                      self.span_pages * cfg.block_size // chunk * chunk)
        if derived >= spec.n:
            return spec                  # one aligned shard covers it
        return dataclasses.replace(
            spec, config=dataclasses.replace(cfg, span_coords=derived))

    def submit(self, spec: JobSpec) -> str:
        if spec.objective not in self.objectives:
            raise KeyError(
                f"unknown objective {spec.objective!r}; registered: "
                f"{sorted(self.objectives)}")
        if spec.config.use_kernel:
            raise ValueError(
                "use_kernel=True is not supported by the engine: lane "
                "pools sweep through the jnp fused-step path only (the "
                "Pallas kernel carries SMEM-resident aggregates that "
                "cannot follow paged pool lanes); run kernel configs "
                "through abo_minimize directly")
        spec = self._derive_span(spec)
        self._admit(spec)
        job_id = next_job_id(self._next)
        self._next += 1
        self.jobs[job_id] = JobState(job_id=job_id, spec=spec,
                                     t_submit=time.time())
        self.queue.append(job_id)
        self._c_submitted.inc()
        self._journal(J_SUBMIT, job_id, spec=spec.to_dict())
        return job_id

    def poll(self, job_id: str) -> dict:
        return self.jobs[job_id].poll_dict()

    def result(self, job_id: str):
        rec = self.jobs[job_id]
        first = rec.status == DONE and not rec.fetched
        out = rec.result()               # raises unless DONE; marks fetched
        if first:
            self._mark_fetch_time(rec)
            self._journal(J_FETCHED, job_id)
            self._gc_jobs()              # delivery can trigger eviction NOW:
        return out                       # retain_done=0 must not wait for a
        #                                  step that may never come

    def mark_fetched(self, job_id: str):
        """Record that a DONE result was delivered out-of-band (a wire
        front-end confirming its reply went out): snapshots stop carrying
        x, the journal remembers across kills, and the retention GC may
        evict the record immediately."""
        rec = self.jobs.get(job_id)
        if rec is not None and rec.status == DONE and not rec.fetched:
            rec.fetched = True
            self._mark_fetch_time(rec)
            self._journal(J_FETCHED, job_id)
            self._gc_jobs()

    def _mark_fetch_time(self, rec: JobState):
        if rec.t_fetch is None:
            rec.t_fetch = time.time()
            if rec.t_done is not None:
                self._h_fetch.observe(rec.t_fetch - rec.t_done)

    def cancel(self, job_id: str) -> bool:
        rec = self.jobs[job_id]
        if rec.status == QUEUED:
            rec.status = CANCELLED
            rec.done_seq = self._next_done_seq()
            self._c_cancelled.inc()
            try:                         # purge now, not at the next refill:
                self.queue.remove(job_id)   # stale ids would otherwise show
            except ValueError:              # up as phantom queued work in
                pass                        # stats until a refill drains them
            self._journal(J_CANCEL, job_id)
            self._gc_jobs()              # retention may evict it right away
            return True
        if rec.status == RUNNING:
            pool, slot = self._locate(job_id)
            if pool is not None:
                self._release_lane(pool, slot)
                pool.shrink_to_fit()
            rec.status = CANCELLED       # stale device state is benign: the
            rec.done_seq = self._next_done_seq()   # slot leaves every plan
            self._c_cancelled.inc()
            self._journal(J_CANCEL, job_id)
            self._gc_jobs()
            return True
        return False                     # already DONE/CANCELLED

    # --------------------------------------------------------------- stepping
    @property
    def active_lanes(self) -> int:
        return sum(p.active for p in self.pools.values())

    def pending(self) -> bool:
        return self.active_lanes > 0 or any(
            j in self.jobs and self.jobs[j].status == QUEUED
            for j in self.queue)

    def step(self) -> int:
        """Refill idle lanes, advance every active pool by one fused chunk
        of passes, harvest finished lanes. Returns the number of jobs
        completed.

        Per active pool the chunk is ``r = min`` remaining passes over its
        lanes — a full generation when lanes are phase-aligned (the steady
        state after a pool refill), one pass when a fresh job rides
        alongside nearly-finished ones. Either way no lane overshoots its
        job's pass budget, so per-job math is untouched. The whole fused
        chunk — every width band of the sweep plan plus the end-of-pass
        lane sync, times r passes — is ONE async dispatch of the plan
        signature's fused-step executable.

        In sanitize mode the whole step runs under
        ``repro.analysis.sanitize.sync_guard``: any implicit
        device->host sync outside the declared harvest/snapshot points
        raises ``HostSyncError``, and each fused dispatch asserts its
        donated pool buffers actually died.
        """
        if self.sanitize:
            with _sanitize.sync_guard():
                return self._step_impl()
        return self._step_impl()

    def _allowed(self, reason: str):
        """Context manager marking a designed sync point (no-op unless
        sanitize mode is on)."""
        return _sanitize.allowed_sync(reason) if self.sanitize else _NULL

    def _step_impl(self) -> int:
        tr = self.tracer
        with tr.span("step", step=self.step_count) as step_sp:
            with tr.span("refill") as sp:
                placed = self._refill()
                if tr.enabled:
                    sp.set(jobs=_job_ids(placed))
            finished = 0
            for pool in self.pools.values():
                if pool.active == 0:
                    # idle families still release capacity: a pool that
                    # drained while OTHER families had queued work skipped
                    # the harvest-time shrink and would otherwise pin its
                    # peak footprint forever (cheap no-op once shrunk)
                    with tr.span("resize", family=pool.key[0]) as sp:
                        resized = pool.shrink_to_fit()
                        sp.set(resized=resized)
                    if resized:
                        self._c_resizes.inc()
                    continue
                ops = batched.get_pool_ops(pool.obj, pool.key, pool.slots,
                                           pool.capacity, pool.mesh)
                cfg = batched.key_config(pool.key)
                remaining = [cfg.n_passes - self.jobs[j].passes_done
                             for j in pool.job_ids if j is not None]
                r = max(min(remaining), 1)
                if self.max_fuse is not None:
                    r = min(r, self.max_fuse)
                if pool.plan is None:
                    with tr.span("plan_build", family=pool.key[0],
                                 active=pool.active):
                        pool.plan = pool.build_plan()
                    self._c_plan_builds.inc()
                plan = pool.plan
                # failpoint: a fault armed here raises/kills BEFORE the
                # dispatch, so pool state is never half-stepped
                self.faults.trip("fused_step")
                # plan.args and the r constant are device-resident and
                # cached: steady-state stepping is one async dispatch
                # re-sending the same buffers — no per-step host wrap,
                # transfer, or sync (the fused_sweep span measures
                # dispatch, not device completion, for the same reason)
                with tr.span("fused_sweep", family=pool.key[0], passes=r,
                             swept_rows=plan.swept_slots,
                             est_bytes=r * plan.pass_bytes,
                             agg_bytes=pool.state.aggs.dtype.itemsize):
                    prev = pool.state if self.sanitize else None
                    pool.state = ops.fused_step(*plan.signature())(
                        pool.state, self._r_const(r), *plan.args)
                    if self.sanitize:
                        # donation is decided at (async) dispatch time:
                        # a live buffer here means XLA silently copied
                        # the pool instead of updating it in place
                        _sanitize.assert_donated(
                            jax.tree_util.tree_leaves(prev),
                            f"fused_step state ({pool.key[0]})")
                self.swept_slots += r * plan.swept_slots
                self.swept_slots_live += r * plan.live_slots
                self._c_passes.inc(r)
                self._c_est_bytes.inc(r * plan.pass_bytes)
                for job_id in pool.job_ids:
                    if job_id is not None:
                        self.jobs[job_id].passes_done += r
                fins = [(slot, self.jobs[jid])
                        for slot, jid in enumerate(pool.job_ids)
                        if jid is not None
                        and self.jobs[jid].passes_done >= cfg.n_passes]
                ids = {"jobs": _job_ids(fins)} if tr.enabled else {}
                with tr.span("harvest", family=pool.key[0], **ids) as sp:
                    got = self._harvest(pool, ops, fins)
                    sp.set(finished=got)
                finished += got
            self.step_count += 1
            self._c_steps.inc()
            self._gc_jobs()
            if self.ckpt is not None:
                if self.journal_every is not None:
                    # journal mode: whole-state snapshots become rare
                    # BASES; the journal already holds every client input
                    # since the last one, so a kill between bases
                    # re-derives everything (at the cost of re-running
                    # post-base passes)
                    if self.step_count % self.journal_every == 0:
                        with tr.span("snapshot", step=self.step_count):
                            self._snapshot()
                elif self.step_count % self.ckpt_every == 0:
                    with tr.span("snapshot", step=self.step_count):
                        self._snapshot()
            step_sp.set(finished=finished)
        return finished

    def run(self, max_steps: int | None = None, stop=None) -> int:
        """Drain the queue. Returns total jobs completed (DONE + FAILED
        finishers). ``stop`` is an optional zero-arg callable polled
        between steps — a signal handler sets it truthy and the drain
        returns at the next step boundary (state consistent, snapshot
        safe)."""
        done = 0
        while self.pending():
            if stop is not None and stop():
                break
            done += self.step()
            if max_steps is not None and self.step_count >= max_steps:
                break
        return done

    def submit_many(self, specs: Iterable[JobSpec]) -> list[str]:
        return [self.submit(s) for s in specs]

    # -------------------------------------------------------------- internals
    def _r_const(self, r: int) -> jnp.ndarray:
        arr = self._r_cache.get(r)
        if arr is None:
            arr = jnp.asarray(r, jnp.int32)
            if self.mesh is not None:
                arr = jax.device_put(
                    arr, NamedSharding(self.mesh, PartitionSpec()))
            self._r_cache[r] = arr
        return arr

    def _locate(self, job_id: str) -> tuple[LanePool | None, int]:
        for pool in self.pools.values():
            if job_id in pool.job_ids:
                return pool, pool.job_ids.index(job_id)
        return None, -1

    def _release_lane(self, pool: LanePool, slot: int):
        pool.job_ids[slot] = None
        if pool.page_table[slot]:
            self._c_pages_freed.inc(len(pool.page_table[slot]))
            dev = pool.lane_dev[slot]
            if isinstance(dev, list):    # striped: per-device returns
                for d in range(pool.n_dev):
                    pgs = [p for p, pd in zip(pool.page_table[slot], dev)
                           if pd == d]
                    if pgs:
                        pool.release_pages(pgs, d)
            else:
                pool.release_pages(pool.page_table[slot], dev or 0)
        pool.page_table[slot] = None
        pool.lane_dev[slot] = None
        pool.plan = None

    def _next_done_seq(self) -> int:
        seq = self._done_seq
        self._done_seq += 1
        return seq

    def _refill(self) -> list[tuple[int, JobState]]:
        # Stage lane bindings + page allocations first (growing each pool's
        # capacity plan at most once), then write every pool's new lanes in
        # batched place dispatches — refilling 8 lanes costs the same host
        # overhead as refilling one. Returns the (slot, job) pairs placed.
        staged: dict[tuple, list[tuple[int, JobState]]] = {}
        while self.queue and self.active_lanes < self.lanes:
            job_id = self.queue.popleft()
            rec = self.jobs.get(job_id)
            if rec is None or rec.status != QUEUED:  # cancelled / GC'd
                continue
            if rec.spec.ttl_s is not None and rec.t_submit is not None \
                    and time.time() - rec.t_submit > rec.spec.ttl_s:
                self._expire(rec)        # deadline passed while queued
                continue
            spec = rec.spec
            key = batched.family_key(spec.objective, spec.n, spec.config,
                                     self.dtype)
            pool = self.pools.get(key)
            if pool is None:
                pool = LanePool(key=key, obj=self.objectives[spec.objective],
                                lanes=self.lanes,
                                high_water=self.pool_high_water,
                                mesh=self.mesh, n_dev=self.n_dev)
                self.pools[key] = pool
                self.family_keys_seen.add(key)
            slot = pool.take_slot()      # slot plan sized to demand; a
            #                              whole-burst refill grows it in
            #                              one hop (device resize is staged)
            cfg = batched.key_config(key)
            n_pages = batched.pages_for(spec.n, cfg.block_size)
            pool.job_ids[slot] = rec.job_id
            if self._stripes(pool, cfg, spec):
                # spanning lane: fixed contiguous shards round-robin
                # across the mesh; lane_dev becomes the per-page device
                # map (page tables stay LOCAL ids, in global page order)
                pt, devs = pool.alloc_span_pages(
                    n_pages, cfg.span_coords // cfg.block_size)
                pool.page_table[slot] = pt
                pool.lane_dev[slot] = devs
            else:
                dev = pool.pick_device()     # whole lane on one device
                pool.lane_dev[slot] = dev
                pool.page_table[slot] = pool.alloc_pages(n_pages, dev)
            self._c_pages_alloc.inc(len(pool.page_table[slot]))
            pool.plan = None
            rec.passes_done = 0
            rec.status = RUNNING
            rec.t_place = time.time()
            if rec.t_submit is not None:
                self._h_queued.observe(rec.t_place - rec.t_submit)
            staged.setdefault(key, []).append((slot, rec))
        for key, placed in staged.items():
            pool = self.pools[key]
            # failpoint: fires before materialize so a kill here leaves
            # the pool un-grown — exactly a crash inside a resize window
            self.faults.trip("pool_resize")
            with self.tracer.span("resize", family=key[0]) as sp:
                resized = pool.materialize()
                sp.set(resized=resized)
            if resized:
                self._c_resizes.inc()
            ops = batched.get_pool_ops(pool.obj, key, pool.slots,
                                       pool.capacity, pool.mesh)
            self._place(pool, ops, placed)
            if self.faults:
                # objective_eval poison: decided per JOB (hashed/stepped
                # off the job id, not a process-local hit counter) so a
                # kill/resume replays to the identical FAILED set
                poisoned = []
                for slot, rec in placed:
                    f = self.faults.check("objective_eval", key=rec.job_id)
                    if f is not None:
                        f.execute(rec.job_id)   # returns for kind=poison
                        poisoned.append((slot, rec))
                if poisoned:
                    self._poison(pool, ops, poisoned)
        return [sr for placed in staged.values() for sr in placed]

    @staticmethod
    def _stripes(pool: LanePool, cfg: ABOConfig, spec: JobSpec) -> bool:
        """Whether this lane stripes across the mesh: spanning math
        (span_coords) is config semantics and applies on any topology,
        but STRIPING the pages additionally needs a mesh, shards that
        keep every REDUCE_TILE whole (span_coords % tile == 0 — the
        block multiple is already enforced by ABOConfig), and a
        non-explicit start (x0 rows are host data placed whole)."""
        return (pool.mesh is not None
                and cfg.span_coords is not None
                and cfg.span_coords % pool.obj.REDUCE_TILE == 0
                and spec.x0 is None)

    def _expire(self, rec: JobState):
        """TTL expiry: terminal FAILED. Wall-clock decided, so the
        verdict is journaled (J_EXPIRE) — replay re-applies it instead
        of re-reading a clock that has moved."""
        rec.status = FAILED
        rec.error = f"ttl expired: queued longer than {rec.spec.ttl_s}s"
        rec.done_seq = self._next_done_seq()
        rec.t_done = time.time()
        self._c_failed.inc()
        self._journal(J_EXPIRE, rec.job_id, error=rec.error)

    def _poison(self, pool: LanePool, ops: batched.PoolOps,
                poisoned: list[tuple[int, JobState]]):
        """Overwrite each chosen lane's fresh iterate with NaN through
        the same place_x executable explicit-x0 placement uses — the
        injected fault is indistinguishable from a user objective going
        non-finite on its first evaluation, and no new executable family
        or plan signature is introduced."""
        bsz = batched.key_config(pool.key).block_size
        for slot, rec in poisoned:
            if isinstance(pool.lane_dev[slot], list):
                # striped lane: re-place through place_span with the
                # poison flag (NaNs global coordinate 0 on its owning
                # device; the init-aggregate psum propagates it)
                self._place_span_one(pool, ops, slot, rec, poison=True)
                continue
            pages = pool.page_table[slot]
            g = batched.pad_ladder(len(pages), 1)
            n = rec.spec.n
            if pool.mesh is None:
                pages_np = np.full((g,), batched.SCRATCH_PAGE, np.int32)
                pages_np[: len(pages)] = pages
                # NaN only the lane's TRUE coordinates: columns past n
                # stay zero, exactly like the x0 path, so ladder-padding
                # writes keep the shared scratch page exactly zero —
                # sibling bit-identity depends on it
                xrow = np.zeros((g * bsz,), jnp.dtype(self.dtype).name)
                xrow[:n] = np.nan
                pool.state = ops.place_x(g)(
                    pool.state, jnp.asarray(slot, jnp.int32),
                    jnp.asarray(pages_np), jnp.asarray(xrow),
                    jnp.asarray(n, jnp.int32))
            else:
                D, dev = pool.n_dev, pool.lane_dev[slot]
                lane_np = np.full((D,), pool.slots, np.int32)
                pages_np = np.full((D, g), batched.SCRATCH_PAGE, np.int32)
                xrow = np.zeros((D, g * bsz), jnp.dtype(self.dtype).name)
                nv_np = np.zeros((D,), np.int32)
                lane_np[dev] = slot
                pages_np[dev, : len(pages)] = pages
                xrow[dev, :n] = np.nan
                nv_np[dev] = n
                owner_np = np.zeros((pool.slots + 1,), np.int32)
                owner_np[slot] = dev
                pool.state = ops.place_x(g)(
                    pool.state, jnp.asarray(owner_np),
                    jnp.asarray(lane_np), jnp.asarray(pages_np),
                    jnp.asarray(xrow), jnp.asarray(nv_np))

    # repro: allow[RPR001] placement planning over host page tables /
    # device maps (the device write is the single place_x dispatch)
    def _place_span_one(self, pool: LanePool, ops: batched.PoolOps,
                        slot: int, rec: JobState, poison: bool = False):
        """One striped spanning lane's placement dispatch: per-device
        page-write tables (each device writes only its resident pages,
        seeded starts via the per-coordinate counter draw) plus the
        owned-tile gather tables feeding the init-aggregate psum — the
        same fixed-origin tiling the per-pass span re-sync uses, so the
        initial aggregates are bit-identical to ``obj.aggregates`` over
        the dense start vector."""
        cfg = batched.key_config(pool.key)
        bsz = cfg.block_size
        tile = pool.obj.REDUCE_TILE
        D = pool.n_dev
        pt = np.asarray(pool.page_table[slot], np.int32)
        devs = np.asarray(pool.lane_dev[slot], np.int32)
        n_pages = len(pt)
        counts = np.bincount(devs, minlength=D)
        gl = batched.pad_ladder(int(counts.max()), 1)
        pg_tbl = np.full((D, gl), batched.SCRATCH_PAGE, np.int32)
        gpage_tbl = np.full((D, gl), -1, np.int32)
        gpages = np.arange(n_pages, dtype=np.int32)
        for d in range(D):
            m = devs == d
            k = int(m.sum())
            if k:
                pg_tbl[d, :k] = pt[m]
                gpage_tbl[d, :k] = gpages[m]
        n_tiles = (n_pages * bsz + tile - 1) // tile
        t_pad = batched.pad_ladder(n_tiles, 1)
        ppt = (tile + bsz - 1) // bsz + 1
        tt = np.arange(n_tiles, dtype=np.int64)
        tdev = ((tt * tile) // cfg.span_coords) % D
        p0 = (tt * tile) // bsz
        off = (tt * tile - p0 * bsz).astype(np.int32)
        ts = batched.pad_ladder(
            int(np.bincount(tdev, minlength=D).max()), 1)
        tile_idx = np.full((D, ts), t_pad, np.int32)
        tile_pages = np.zeros((D, ts, ppt), np.int32)
        tile_off = np.zeros((D, ts), np.int32)
        for d in range(D):
            m = tdev == d
            k = int(m.sum())
            if not k:
                continue
            tile_idx[d, :k] = tt[m]
            tile_off[d, :k] = off[m]
            for q in range(ppt):
                pg = p0[m] + q
                ok = (pg < n_pages) & (pg * bsz < (tt[m] + 1) * tile)
                tile_pages[d, :k, q] = np.where(
                    ok, pt[np.minimum(pg, n_pages - 1)],
                    batched.SCRATCH_PAGE)
        x64 = bool(jax.config.jax_enable_x64)
        seed_dt = np.uint64 if x64 else np.uint32
        seed_mask = 0xFFFFFFFFFFFFFFFF if x64 else 0xFFFFFFFF
        pool.state = ops.place_span(gl, ts, ppt, t_pad)(
            pool.state,
            jnp.asarray(np.full((1,), slot, np.int32)),
            jnp.asarray(np.full((1,), rec.spec.n, np.int32)),
            jnp.asarray(np.full(
                (1,), seed_dt((rec.spec.seed or 0) & seed_mask))),
            jnp.asarray(np.full((1,), rec.spec.seed is not None, bool)),
            jnp.asarray(np.full((1,), poison, bool)),
            jnp.asarray(np.full((1,), n_tiles, np.int32)),
            jnp.asarray(pg_tbl), jnp.asarray(gpage_tbl),
            jnp.asarray(tile_idx), jnp.asarray(tile_pages),
            jnp.asarray(tile_off))

    def _place(self, pool: LanePool, ops: batched.PoolOps,
               placed: list[tuple[int, JobState]]):
        cfg = batched.key_config(pool.key)
        bsz = cfg.block_size
        striped = [(s, r) for s, r in placed
                   if isinstance(pool.lane_dev[s], list)]
        placed = [(s, r) for s, r in placed
                  if not isinstance(pool.lane_dev[s], list)]
        for slot, rec in striped:        # rare: one dispatch per striped
            self._place_span_one(pool, ops, slot, rec)
        # PRNGKey folds a Python int to the widest uint the precision mode
        # traces: 32 bits by default, 64 under jax_enable_x64. Mirror that
        # exactly so engine starts stay bit-identical to abo_minimize's for
        # every accepted seed (negative and >= 2**32 included).
        x64 = bool(jax.config.jax_enable_x64)
        seed_dt = np.uint64 if x64 else np.uint32
        seed_mask = 0xFFFFFFFFFFFFFFFF if x64 else 0xFFFFFFFF
        members: list[tuple[int, JobState]] = []
        x0_jobs: list[tuple[int, JobState]] = []
        for slot, rec in placed:
            (x0_jobs if rec.spec.x0 is not None else members).append(
                (slot, rec))
        if members and pool.mesh is None:
            # one dispatch for the whole refill batch, gathered at the
            # deepest placed lane's page-count rung (short lanes' extra
            # columns are zeroed and land on the scratch page)
            g, v, lanes_np, pages_np = _gather_tables(
                [(s, pool.page_table[s]) for s, _ in members], pool.slots)
            seeded = np.zeros((v,), bool)
            seeds = np.zeros((v,), seed_dt)
            n_valid = np.zeros((v,), np.int32)
            for i, (_, rec) in enumerate(members):
                n_valid[i] = rec.spec.n
                if rec.spec.seed is not None:
                    seeded[i] = True
                    seeds[i] = seed_dt(rec.spec.seed & seed_mask)
            pool.state = ops.place(g, v)(
                pool.state, jnp.asarray(lanes_np), jnp.asarray(pages_np),
                jnp.asarray(seeded), jnp.asarray(seeds),
                jnp.asarray(n_valid))
        elif members:
            # sharded: still ONE dispatch for the whole refill batch —
            # per-device tables at rungs unified across devices, each
            # device writing its own lanes' pages and the owner psum
            # re-replicating the slot scalars
            D = pool.n_dev
            by_dev: list[list[tuple[int, JobState]]] = \
                [[] for _ in range(D)]
            for slot, rec in members:
                by_dev[pool.lane_dev[slot]].append((slot, rec))
            g = max(batched.pad_ladder(len(pool.page_table[s]), 1)
                    for s, _ in members)
            v = max(batched.pad_ladder(max(len(m), 1), 1) for m in by_dev)
            lanes_np = np.full((D, v), pool.slots, np.int32)
            pages_np = np.full((D, v, g), batched.SCRATCH_PAGE, np.int32)
            seeded = np.zeros((D, v), bool)
            seeds = np.zeros((D, v), seed_dt)
            n_valid = np.zeros((D, v), np.int32)
            owner_np = np.zeros((pool.slots + 1,), np.int32)
            for d, mem in enumerate(by_dev):
                for i, (slot, rec) in enumerate(mem):
                    lanes_np[d, i] = slot
                    pt = pool.page_table[slot]
                    pages_np[d, i, : len(pt)] = pt
                    n_valid[d, i] = rec.spec.n
                    owner_np[slot] = d
                    if rec.spec.seed is not None:
                        seeded[d, i] = True
                        seeds[d, i] = seed_dt(rec.spec.seed & seed_mask)
            pool.state = ops.place(g, v)(
                pool.state, jnp.asarray(owner_np), jnp.asarray(lanes_np),
                jnp.asarray(pages_np), jnp.asarray(seeded),
                jnp.asarray(seeds), jnp.asarray(n_valid))
        for slot, rec in x0_jobs:        # explicit-x0 jobs: rare, per-lane
            spec = rec.spec
            pages = pool.page_table[slot]
            g = batched.pad_ladder(len(pages), 1)
            if pool.mesh is None:
                pages_np = np.full((g,), batched.SCRATCH_PAGE, np.int32)
                pages_np[: len(pages)] = pages
                xrow = np.zeros((g * bsz,), jnp.dtype(self.dtype).name)
                # repro: allow[RPR001] spec.x0 is client host data, not a
                # device buffer; normalising dtype before device_put
                xrow[: spec.n] = np.asarray(spec.x0, xrow.dtype)
                pool.state = ops.place_x(g)(
                    pool.state, jnp.asarray(slot, jnp.int32),
                    jnp.asarray(pages_np), jnp.asarray(xrow),
                    jnp.asarray(spec.n, jnp.int32))
            else:
                D, dev = pool.n_dev, pool.lane_dev[slot]
                lane_np = np.full((D,), pool.slots, np.int32)
                pages_np = np.full((D, g), batched.SCRATCH_PAGE, np.int32)
                xrow = np.zeros((D, g * bsz), jnp.dtype(self.dtype).name)
                nv_np = np.zeros((D,), np.int32)
                lane_np[dev] = slot
                pages_np[dev, : len(pages)] = pages
                # repro: allow[RPR001] spec.x0 is client host data (sharded
                # placement path), same as above
                xrow[dev, : spec.n] = np.asarray(spec.x0, xrow.dtype)
                nv_np[dev] = spec.n
                owner_np = np.zeros((pool.slots + 1,), np.int32)
                owner_np[slot] = dev
                pool.state = ops.place_x(g)(
                    pool.state, jnp.asarray(owner_np),
                    jnp.asarray(lane_np), jnp.asarray(pages_np),
                    jnp.asarray(xrow), jnp.asarray(nv_np))

    # repro: allow[RPR001] harvest is THE designed sync point: finished
    # lanes' fun/x/history are read back exactly once, off the hot loop
    def _harvest(self, pool: LanePool, ops: batched.PoolOps,
                 fins: list[tuple[int, JobState]]) -> int:
        """Finish the lanes in ``fins`` (slot, job): one finalize
        dispatch per gather kind, the wait for the device, the read-back,
        then the host bookkeeping (each a span; see DESIGN.md
        "Observability"). No finisher, no sync."""
        if not fins:
            return 0
        span_fins = [(s, r) for s, r in fins
                     if isinstance(pool.lane_dev[s], list)]
        whole_fins = [(s, r) for s, r in fins
                      if not isinstance(pool.lane_dev[s], list)]
        # (slot, rec, fun array row, x row, hist row) for the completion
        # loop below — whole and striped finishers come from separate
        # gathers but finish identically
        outs: list[tuple] = []
        # compact gather: ONE dispatch + one device sync for the FINISHING
        # lanes only — running and idle lanes aren't touched, so turnover
        # costs the finishers' pages instead of O(K * n_pad)
        if whole_fins and pool.mesh is None:
            g, v, lanes_np, pages_np = _gather_tables(
                [(s, pool.page_table[s]) for s, _ in whole_fins],
                pool.slots)
            with self.tracer.span("finalize"):
                dev_outs = ops.finalize(g, v)(
                    pool.state, jnp.asarray(lanes_np), jnp.asarray(pages_np))
            f_np, x_np, h_np = self._read_back(dev_outs)
            outs += [(s, r, f_np[i], x_np[i], h_np[i])
                     for i, (s, r) in enumerate(whole_fins)]
        elif whole_fins:
            # sharded: finisher i's output row is computed by its resident
            # device (row_dev) and replicated by the owner psum
            D = pool.n_dev
            g = batched.pad_ladder(
                max(len(pool.page_table[s]) for s, _ in whole_fins), 1)
            v = batched.pad_ladder(len(whole_fins), 1)
            row_dev = np.zeros((v,), np.int32)
            lanes_np = np.full((D, v), pool.slots, np.int32)
            pages_np = np.full((D, v, g), batched.SCRATCH_PAGE, np.int32)
            for i, (slot, _) in enumerate(whole_fins):
                d = pool.lane_dev[slot]
                row_dev[i] = d
                lanes_np[d, i] = slot
                pt = pool.page_table[slot]
                pages_np[d, i, : len(pt)] = pt
            with self.tracer.span("finalize"):
                dev_outs = ops.finalize(g, v)(
                    pool.state, jnp.asarray(row_dev), jnp.asarray(lanes_np),
                    jnp.asarray(pages_np))
            f_np, x_np, h_np = self._read_back(dev_outs)
            outs += [(s, r, f_np[i], x_np[i], h_np[i])
                     for i, (s, r) in enumerate(whole_fins)]
        if span_fins:
            # striped finishers: no device holds a whole row, so the
            # gather is stitched per-PAGE by finalize_span's
            # owner_select over the (v, g) page→device map; f comes from
            # the lane's span-synced aggregates (exact by construction)
            D = pool.n_dev
            g = batched.pad_ladder(
                max(len(pool.page_table[s]) for s, _ in span_fins), 1)
            v = batched.pad_ladder(len(span_fins), 1)
            page_dev = np.zeros((v, g), np.int32)
            lanes_np = np.full((v,), pool.slots, np.int32)
            pages_np = np.full((D, v, g), batched.SCRATCH_PAGE, np.int32)
            for i, (slot, _) in enumerate(span_fins):
                lanes_np[i] = slot
                for p, (loc, d) in enumerate(zip(pool.page_table[slot],
                                                 pool.lane_dev[slot])):
                    page_dev[i, p] = d
                    pages_np[d, i, p] = loc
            with self.tracer.span("finalize"):
                dev_outs = ops.finalize_span(g, v)(
                    pool.state, jnp.asarray(page_dev), jnp.asarray(lanes_np),
                    jnp.asarray(pages_np))
            f_np, x_np, h_np = self._read_back(dev_outs)
            outs += [(s, r, f_np[i], x_np[i], h_np[i])
                     for i, (s, r) in enumerate(span_fins)]
        now = time.time()
        n_done = 0
        for slot, rec, f_row, x_row, h_row in outs:
            fun = float(f_row)
            x = x_row[: rec.spec.n]
            # quarantine: a non-finite fun/x is terminal FAILED, decided
            # on the buffers the harvest already read back — no extra
            # host sync. The lane is evicted and its pages recycled like
            # any finisher; sibling lanes never see the poison (their
            # pages, plans, and executables are untouched)
            if not (np.isfinite(fun) and np.isfinite(x).all()):
                rec.status = FAILED
                rec.error = ("non-finite result quarantined at harvest "
                             f"(fun={fun!r})")
                rec.fun = None
                rec.x = None
                rec.history = []
                self._c_failed.inc()
            else:
                rec.fun = fun
                rec.x = x.copy()
                rec.history = [float(vv) for vv in h_row]
                rec.status = DONE
                n_done += 1
            rec.done_seq = self._next_done_seq()
            rec.t_done = now
            if rec.t_place is not None:
                self._h_run.observe(now - rec.t_place)
            if rec.t_submit is not None:
                self._h_total.observe(now - rec.t_submit)
            self._release_lane(pool, slot)       # refilled next step
        self._c_done.inc(n_done)
        if not self.queue:               # a true drain, not inter-generation
            if pool.shrink_to_fit():     # turnover mid-burst (phase-aligned
                self._c_resizes.inc()    # lanes all finish together; the
        return len(fins)                 # next refill would regrow at once)

    # repro: allow[RPR001] the harvest's designed sync: the wait for the
    # device, then the read-back of the finishers' outputs
    def _read_back(self, outs: tuple) -> tuple:
        """``outs`` (device arrays) on the host. ``device_wait`` ends
        when the device has finished every dispatched step and the
        finalize; ``readback`` is the transfer alone."""
        tr = self.tracer
        with self._allowed("harvest read-back"):
            with tr.span("device_wait"):
                jax.block_until_ready(outs)
            with tr.span("readback",
                         bytes=sum(a.nbytes for a in outs)):
                return tuple(np.asarray(a) for a in outs)

    def _gc_jobs(self):
        """Whole-record job-table GC: keep only the ``retain_done`` most
        recently finished records among those the client is done with
        (fetched DONE results, cancellations, failures). Live work —
        queued, running, and undelivered DONE jobs — is never evicted,
        so results can't be lost; evicted ids simply answer "unknown
        job"."""
        if self.retain_done is None:
            return
        evictable = [rec for rec in self.jobs.values()
                     if rec.status in (CANCELLED, FAILED)
                     or (rec.status == DONE and rec.fetched)]
        excess = len(evictable) - self.retain_done
        if excess <= 0:
            return
        # records missing done_seq (pre-done_seq snapshots) count as oldest:
        # their true finish order is unknowable, and a (None, None) sort key
        # would TypeError the comparison
        evictable.sort(key=lambda r: (r.done_seq is not None,
                                      r.done_seq if r.done_seq is not None
                                      else 0))
        for rec in evictable[:excess]:
            del self.jobs[rec.job_id]

    def pad_stats(self) -> dict:
        """Packing economics of the paged layout.

        Coordinate-level (current active lanes): ``fill_ratio`` /
        ``pad_waste`` compare true n against occupied pages — the only
        coordinate padding left is the tail of each lane's last block,
        which the dense reference solver pays identically.

        Row-slot level (cumulative): ``swept_rows`` counts executed
        (lane, block-row) sweep slots including width-rung padding,
        ``swept_rows_live`` the slots that advanced real lanes;
        ``swept_waste`` is the padded-compute fraction — the number the
        old rung-padded layout pushed past 30% on mixed-n traffic and the
        ladder bounds at 1/3 worst-case, a few percent typical.
        """
        valid = paged = 0
        for pool in self.pools.values():
            bsz = batched.key_config(pool.key).block_size
            for jid, pt in zip(pool.job_ids, pool.page_table):
                if jid is not None:
                    valid += self.jobs[jid].spec.n
                    paged += len(pt) * bsz
        swept, live = self.swept_slots, self.swept_slots_live
        return {"active_valid_n": valid, "active_paged_n": paged,
                "fill_ratio": valid / paged if paged else None,
                "pad_waste": 1.0 - valid / paged if paged else None,
                "swept_rows": swept, "swept_rows_live": live,
                "swept_waste": 1.0 - live / swept if swept else None}

    def memory_stats(self) -> dict:
        """Elastic-pool footprint right now: materialized pages / lane
        slots across families and the device bytes they hold. With the
        default hysteresis these track live traffic — after a drain they
        fall back toward empty instead of pinning the historical peak.
        Sharded engines additionally break the footprint down per device
        (local pages, replicated slot rows, resident bytes).

        .. deprecated::
            These keys are kept as aliases for existing callers; the
            canonical snapshot is :meth:`stats` (the obs registry —
            ``engine_pool_pages`` / ``engine_pool_device_bytes`` /
            ``engine_device_bytes{device=...}`` carry the same census).
        """
        pages = slots = nbytes = 0
        per_dev = [{"pages": 0, "slots": 0, "bytes": 0}
                   for _ in range(self.n_dev)]
        for pool in self.pools.values():
            if pool.state is None:
                continue
            pages += pool.state.pool.shape[0]
            slots += pool.state.aggs.shape[0] - 1
            nbytes += pool.device_bytes()
            for d, st in enumerate(pool.per_device_stats()):
                for k in ("pages", "slots", "bytes"):
                    per_dev[d][k] += st[k]
        out = {"pool_pages": pages, "pool_slots": slots,
               "pool_device_bytes": nbytes,
               "pool_high_water": self.pool_high_water,
               "devices": self.n_dev}
        if self.n_dev > 1:
            out["per_device"] = per_dev
        return out

    # ------------------------------------------------------------- telemetry
    def trace(self, path: str | None = None):
        """Enable pass-level span tracing (``path`` becomes the default
        Chrome-trace export target for :meth:`trace_export`). Each span
        also opens a ``jax.profiler.TraceAnnotation`` named
        ``engine.<span>``, so under a running profiler the spans land on
        the device trace's clock. Until this is called every span is the
        shared null span — tracing costs one attribute check per
        phase."""
        self.tracer.enable(path, annotate=jax.profiler.TraceAnnotation)

    def trace_export(self, path: str | None = None) -> str:
        """Write recorded spans as Chrome trace-event JSON (loadable in
        chrome://tracing or Perfetto); returns the path written."""
        return self.tracer.export(path)

    def _refresh_gauges(self):
        """Sample device-derived and O(pools) gauges into the registry.

        Runs at stats/scrape boundaries ONLY — never on the step hot
        path: it walks pool shapes (host metadata, no device reads) and,
        in journal mode, stats the journal files."""
        g = self.metrics.gauge
        queued = sum(j in self.jobs and self.jobs[j].status == QUEUED
                     for j in self.queue)
        g("engine_active_lanes", "lanes bound to running jobs").set(
            self.active_lanes)
        g("engine_lane_budget", "engine-wide concurrent-lane cap").set(
            self.lanes)
        g("engine_queue_depth", "truly-QUEUED jobs awaiting a lane").set(
            queued)
        g("engine_families", "live lane pools").set(len(self.pools))
        g("engine_families_created",
          "distinct executable families ever opened").set(
            len(self.family_keys_seen))
        g("engine_executables", "compiled pool executables").set(
            batched.compiled_executable_count(self.family_keys_seen))
        ps = self.pad_stats()
        g("engine_fill_ratio", "true n / paged n over active lanes").set(
            ps["fill_ratio"] or 0.0)
        g("engine_swept_waste_ratio",
          "padded fraction of cumulative swept rows").set(
            ps["swept_waste"] or 0.0)
        ms = self.memory_stats()
        g("engine_pool_pages", "materialized pool pages").set(
            ms["pool_pages"])
        g("engine_pool_slots", "materialized lane slots").set(
            ms["pool_slots"])
        g("engine_pool_device_bytes",
          "device bytes held by pool arrays").set(ms["pool_device_bytes"])
        g("engine_span_lanes",
          "lanes striped across the device mesh").set(
            sum(isinstance(d, list) for pool in self.pools.values()
                for d in pool.lane_dev))
        per_dev = [{"pages": 0, "slots": 0, "bytes": 0}
                   for _ in range(self.n_dev)]
        for pool in self.pools.values():
            for d, st in enumerate(pool.per_device_stats()):
                for k in ("pages", "slots", "bytes"):
                    per_dev[d][k] += st[k]
        for d, st in enumerate(per_dev):
            g("engine_device_bytes", "resident pool bytes per device",
              device=d).set(st["bytes"])
            g("engine_device_pages", "local pool pages per device",
              device=d).set(st["pages"])
        if self.ckpt is not None and self.journal_every is not None:
            js = self.ckpt.journal_stats()
            g("ckpt_journal_segments", "live journal segment files").set(
                js["segments"])
            g("ckpt_journal_lag_records",
              "journal records not yet covered by a base snapshot").set(
                js["records"])
            g("ckpt_journal_bytes", "journal bytes on disk").set(
                js["bytes"])

    def stats(self) -> dict:
        """The canonical flat telemetry snapshot: every registry counter,
        gauge (freshly sampled), and histogram summary, keyed by metric
        name (labeled metrics render as ``name{k="v"}``). This is the one
        source of truth; ``memory_stats()`` / ``pad_stats()`` /
        ``SolveService.stats()`` keep their historical keys as aliases
        over the same census."""
        self._refresh_gauges()
        return self.metrics.snapshot()

    def render_prometheus(self) -> str:
        """Prometheus text exposition of the registry (gauges freshly
        sampled) — what ``solve_server``'s ``/metrics`` endpoint serves."""
        self._refresh_gauges()
        return self.metrics.render_prometheus()

    # ------------------------------------------------------------ checkpoint
    def snapshot(self):
        """Cut a checkpoint now (e.g. right after enqueueing a batch, so a
        kill before the first step's snapshot can't lose the queue)."""
        if self.ckpt is None:
            raise RuntimeError("engine has no checkpoint_dir")
        self._snapshot()

    def _snapshot(self):
        # the checkpoint writer reads every pool buffer back to the host:
        # with harvest, the only other designed sync point in a step
        with self._allowed("snapshot write-out"):
            return self._snapshot_impl()

    def _snapshot_impl(self):
        tree = {}
        pool_meta = []
        for i, pool in enumerate(self.pools.values()):
            pool.materialize()
            tree[f"p{i:03d}"] = pool.state
            pool_meta.append({
                "objective": pool.key[0],
                "config": dataclasses.asdict(pool.key[1]),
                "dtype": pool.key[2],
                "capacity": pool.capacity,
                "slots": pool.slots,
                "job_ids": pool.job_ids,
                # LOCAL page ids when sharded (n_dev > 1); lane_dev maps
                # each slot to its resident device — together the
                # lane→(device, page) table, round-tripped exactly
                "page_table": pool.page_table,
                "n_dev": pool.n_dev,
                # v3: an entry is an int (whole lane's device) OR a
                # per-page device list (striped spanning lane)
                "lane_dev": pool.lane_dev,
            })
        # journal records at or below this seq are reflected in this
        # snapshot's job table; resume replays only what came after
        journal_seq = (self.ckpt.journal_last_seq()
                       if self.journal_every is not None else None)
        aux = {
            # v3 = v2 + spanning: lane_dev entries may be per-page device
            # lists and span_pages records the engine budget (v2 readers
            # must not guess at striped page tables, so the version bumps)
            "version": 3,
            "lanes": self.lanes,
            "devices": self.n_dev,
            "max_fuse": self.max_fuse,
            "retain_done": self.retain_done,
            "pool_high_water": self.pool_high_water,
            "journal_every": self.journal_every,
            "max_queue": self.max_queue,
            "memory_budget_bytes": self.memory_budget_bytes,
            "span_pages": self.span_pages,
            "journal_seq": journal_seq,
            "dtype": jnp.dtype(self.dtype).name,
            "step_count": self.step_count,
            "swept_slots": self.swept_slots,
            "swept_slots_live": self.swept_slots_live,
            "next": self._next,
            "done_seq": self._done_seq,
            "queue": list(self.queue),
            "jobs": {jid: rec.to_dict() for jid, rec in self.jobs.items()},
            "pools": pool_meta,
            # pools can drain away before a snapshot; persist the full
            # compiled-family history so families_created survives resume
            "family_keys_seen": [
                {"objective": k[0], "config": dataclasses.asdict(k[1]),
                 "dtype": k[2]}
                for k in sorted(self.family_keys_seen,
                                key=lambda k: (k[0], k[2]))],
        }
        self.ckpt.save(self.step_count, tree, aux=aux)
        if journal_seq is not None:
            # this base covers everything up to journal_seq: compaction
            self.ckpt.journal_truncate(journal_seq)

    @classmethod
    def resume(cls, checkpoint_dir: str, *,
               objectives: dict[str, SeparableObjective] | None = None,
               keep: int = 3, ckpt_every: int = 1,
               devices: int | None = None,
               sanitize: bool = False,
               faults=None,
               **fresh_kw) -> "SolveEngine":
        """Rebuild an engine (jobs, queue, and mid-solve pools with their
        page tables) from the newest committed checkpoint in
        ``checkpoint_dir``, then replay any journal records newer than
        that base (journal mode): replayed submissions re-queue and
        re-run deterministically, so results match the uninterrupted run
        bit-for-bit. With no checkpoint present, returns a fresh engine
        built with ``fresh_kw`` (lanes, retain_done, journal_every, ...)
        — still replaying a journal if one exists (a kill can land before
        the first base). When a checkpoint IS found its recorded values
        win and ``fresh_kw`` is ignored — runtime knobs must round-trip
        the kill, or the resumed run would diverge from the uninterrupted
        one. ``devices`` is the exception: it is *topology*, not
        semantics — a snapshot cut on D devices resumes on D' by
        remapping every lane's pages onto the new shards host-side
        (reshard on load), and per-job results still match the
        uninterrupted run bit-for-bit, because per-lane math is placement-
        invariant. ``sanitize`` is likewise observation, not semantics,
        so it too may differ from the run that wrote the snapshot — and
        so is ``faults``: injection config is never persisted, a resumed
        life re-arms (or drops) its failpoints explicitly."""
        probe = CheckpointManager(checkpoint_dir, keep=keep)
        step = probe.latest_step()
        if step is None:
            fresh_kw.setdefault("sanitize", sanitize)
            fresh_kw.setdefault("faults", faults)
            eng = cls(checkpoint_dir=checkpoint_dir, keep=keep,
                      ckpt_every=ckpt_every, objectives=objectives,
                      devices=devices, **fresh_kw)
            # a kill can land before the first base snapshot: submissions
            # are journal-only at that point, so replay them into the
            # fresh engine instead of silently dropping the queue (only
            # in journal mode — a legacy resume must not replay stale
            # segments left behind by an earlier journaled life)
            if eng.journal_every is not None:
                eng._replay_journal(0)
            return eng
        aux = probe.aux(step)
        if aux is None:
            raise RuntimeError(
                f"checkpoint step {step} in {checkpoint_dir} has no engine "
                "aux metadata — not a SolveEngine checkpoint")
        if aux.get("version") not in (2, 3):
            raise RuntimeError(
                f"checkpoint step {step} in {checkpoint_dir} has engine aux "
                f"version {aux.get('version')}; this engine reads versions "
                "2-3 (the block-paged lane layout, v3 adding spanning "
                "lane_dev page maps) — re-run the jobs or resume with the "
                "engine version that wrote it")
        eng = cls(lanes=aux["lanes"], dtype=jnp.dtype(aux["dtype"]),
                  objectives=objectives, checkpoint_dir=checkpoint_dir,
                  ckpt_every=ckpt_every, keep=keep,
                  max_fuse=aux.get("max_fuse"),
                  retain_done=aux.get("retain_done"),
                  # pre-elastic v2 snapshots lack the key entirely (class
                  # default applies); null means shrinking was disabled
                  pool_high_water=aux.get("pool_high_water", 2.0),
                  journal_every=aux.get("journal_every"),
                  max_queue=aux.get("max_queue"),
                  memory_budget_bytes=aux.get("memory_budget_bytes"),
                  span_pages=aux.get("span_pages"),
                  devices=(devices if devices is not None
                           else aux.get("devices", 1)),
                  sanitize=sanitize, faults=faults)
        eng.step_count = aux["step_count"]
        eng.swept_slots = aux.get("swept_slots", 0)
        eng.swept_slots_live = aux.get("swept_slots_live", 0)
        eng._next = aux["next"]
        eng._done_seq = aux.get("done_seq", 0)
        eng.jobs = {jid: JobState.from_dict(d)
                    for jid, d in aux["jobs"].items()}
        eng.queue = deque(aux["queue"])
        like = {}
        metas = []
        for i, p in enumerate(aux["pools"]):
            obj = eng.objectives[p["objective"]]
            key = (p["objective"], ABOConfig(**p["config"]), p["dtype"])
            # pre-elastic v2 snapshots sized every pool to the engine budget
            slots = p.get("slots", aux["lanes"])
            like[f"p{i:03d}"] = jax.eval_shape(
                lambda o=obj, k=key, s=slots, c=p["capacity"]:
                batched.zeros_pool_state(o, k, s, c))
            metas.append((key, obj, p, slots))
        tree = probe.restore_host(step, like) if like else {}
        for i, (key, obj, p, slots) in enumerate(metas):
            eng._mount_pool(key, obj, p, slots, tree[f"p{i:03d}"])
        for d in aux.get("family_keys_seen", []):
            eng.family_keys_seen.add(
                (d["objective"], ABOConfig(**d["config"]), d["dtype"]))
        if eng.journal_every is not None:
            eng._replay_journal(aux.get("journal_seq") or 0)
        return eng

    # repro: allow[RPR001] checkpoint-restore cold path: operates on host
    # numpy state loaded from disk, never on live device buffers
    def _mount_pool(self, key, obj, p: dict, slots: int, host_state):
        """Attach one restored pool: remap its pages onto THIS engine's
        device count if the snapshot's differs (reshard on load), place
        the arrays (sharded when this engine has a mesh), and rebuild the
        per-device free lists from the page tables."""
        page_table = [list(pt) if pt is not None else None
                      for pt in p["page_table"]]
        # pre-sharded snapshots carry global==local ids and no lane_dev
        lane_dev = list(p.get("lane_dev") or
                        [0 if pt is not None else None
                         for pt in page_table])
        capacity = p["capacity"]
        n_dev_old = p.get("n_dev", 1)
        if n_dev_old != self.n_dev:
            # striped lanes re-derive their shard→device round-robin on
            # the new topology when the family config spans (and shards
            # keep reduction tiles whole); otherwise lanes land whole
            cfg = ABOConfig(**p["config"])
            span_pg = None
            if cfg.span_coords is not None \
                    and cfg.span_coords % obj.REDUCE_TILE == 0:
                span_pg = cfg.span_coords // cfg.block_size
            page_table, lane_dev, capacity, pool_np = self._reshard_pages(
                n_dev_old, capacity, page_table, lane_dev,
                np.asarray(host_state.pool), span_pg)
            host_state = dataclasses.replace(host_state, pool=pool_np)
        if self.mesh is not None:
            state = jax.device_put(host_state,
                                   batched.state_sharding(self.mesh))
        else:
            state = jax.tree_util.tree_map(jnp.asarray, host_state)
        cap_loc = capacity // self.n_dev
        used = [set() for _ in range(self.n_dev)]
        for pt, dev in zip(page_table, lane_dev):
            if pt:
                if isinstance(dev, list):
                    for pg, d in zip(pt, dev):
                        used[d].add(pg)
                else:
                    used[dev].update(pt)
        free = [sorted(set(range(1, cap_loc)) - used[d])
                for d in range(self.n_dev)]
        pool = LanePool(
            key=key, obj=obj, lanes=self.lanes, slots=slots,
            high_water=self.pool_high_water, state=state,
            capacity=capacity, mesh=self.mesh, n_dev=self.n_dev,
            job_ids=list(p["job_ids"]), page_table=page_table,
            lane_dev=lane_dev, free_pages=free)
        self.pools[key] = pool
        self.family_keys_seen.add(key)

    # repro: allow[RPR001] resume-time resharding cold path: pure host
    # numpy shuffle of the restored pool image
    def _reshard_pages(self, n_dev_old: int, capacity: int, page_table,
                       lane_dev, pool_np, span_pg=None):
        """Host-side page remap for a device-count change: every live
        lane lands whole on a new device (balanced by pages, slot order —
        deterministic), its rows copy to fresh local ids, and the new
        global pool array is rebuilt with one fancy-indexed row copy.
        Content is moved, never recomputed, so mid-flight lane state
        resumes bit-exactly on the new topology.

        ``span_pg`` (pages per span shard, when the family config spans
        with tile-whole shards) turns lanes longer than one shard back
        into striped placements: shard k of the lane re-derives its owner
        as ``k % n_dev`` — the same round-robin ``alloc_span_pages``
        uses — so a striped lane resharded D=2→4→1 visits the identical
        page content at every stop and collapses to a whole lane at D=1
        automatically (the striped branch requires ``n_dev > 1``)."""
        cap_loc_old = capacity // n_dev_old
        live = [0] * self.n_dev
        next_local = [1] * self.n_dev        # local 0 = per-device scratch
        new_pt = [None] * len(page_table)
        new_dev = [None] * len(page_table)
        src_idx, dst_rel = [], []            # dst_rel: (dev, local)
        for slot, (pt, dev) in enumerate(zip(page_table, lane_dev)):
            if pt is None:
                continue
            old_devs = dev if isinstance(dev, list) else [dev or 0] * len(pt)
            if span_pg is not None and self.n_dev > 1 and len(pt) > span_pg:
                locs, devs = [], []
                for pg_i, (pg, od) in enumerate(zip(pt, old_devs)):
                    d = (pg_i // span_pg) % self.n_dev
                    locs.append(next_local[d])
                    devs.append(d)
                    next_local[d] += 1
                    live[d] += 1
                    src_idx.append(od * cap_loc_old + pg)
                    dst_rel.append((d, locs[-1]))
                new_pt[slot] = locs
                new_dev[slot] = devs
                continue
            d = min(range(self.n_dev), key=lambda k: (live[k], k))
            live[d] += len(pt)
            start = next_local[d]
            next_local[d] += len(pt)
            new_pt[slot] = list(range(start, start + len(pt)))
            new_dev[slot] = d
            src_idx.extend(od * cap_loc_old + pg
                           for pg, od in zip(pt, old_devs))
            dst_rel.extend((d, loc) for loc in new_pt[slot])
        cap_loc_new = batched.pad_ladder(max(next_local), 1)
        new_pool = np.zeros((self.n_dev * cap_loc_new, pool_np.shape[1]),
                            pool_np.dtype)
        if src_idx:
            dst_idx = [d * cap_loc_new + loc for d, loc in dst_rel]
            new_pool[np.asarray(dst_idx)] = pool_np[np.asarray(src_idx)]
        return new_pt, new_dev, self.n_dev * cap_loc_new, new_pool

    def _replay_journal(self, after_seq: int):
        """Re-apply client inputs journaled after the restored base: new
        submissions re-queue (their post-base passes re-run
        deterministically, so fun/x match the uninterrupted run
        bit-for-bit), cancels cancel, delivery marks stick. Replay never
        re-journals — the records being replayed are already durable."""
        if self.ckpt is None:
            return                       # (no journal dir -> no entries;
        self._replaying = True           # legacy-mode resumes no-op here)
        try:
            for rec in self.ckpt.journal_entries(after_seq=after_seq):
                kind, jid = rec.get("t"), rec.get("job_id")
                if kind == J_SUBMIT:
                    if jid in self.jobs:
                        continue         # already in the base (idempotence)
                    self.jobs[jid] = JobState(
                        job_id=jid, spec=JobSpec.from_dict(rec["spec"]))
                    self.queue.append(jid)
                    self._next = max(self._next,
                                     int(jid.rsplit("-", 1)[1]) + 1)
                elif kind == J_CANCEL:
                    if jid in self.jobs and self.jobs[jid].status in (
                            QUEUED, RUNNING):
                        self.cancel(jid)
                elif kind == J_EXPIRE:
                    # the pre-kill life saw the deadline pass; re-apply
                    # the verdict rather than re-reading a moved clock
                    r = self.jobs.get(jid)
                    if r is not None and r.status == QUEUED:
                        r.status = FAILED
                        r.error = rec.get("error", "ttl expired")
                        r.done_seq = self._next_done_seq()
                        self._c_failed.inc()
                        try:
                            self.queue.remove(jid)
                        except ValueError:
                            pass
                elif kind == J_FETCHED:
                    r = self.jobs.get(jid)
                    if r is not None:
                        # the pre-kill life delivered this result; if the
                        # job must re-run first, the mark survives so the
                        # re-derived record is GC-evictable again
                        r.fetched = True
        finally:
            self._replaying = False
        self._gc_jobs()
