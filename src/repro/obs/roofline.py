"""Bytes-moved accounting for the engine: analytic model + HLO cross-check.

The paper's performance model (arXiv 1709.02500; SNIPPETS.md #1) is a
pure bandwidth roofline: a coordinate-sweep pass streams the working set
through memory, so ``throughput ≈ DRAM bandwidth / working-set bytes``.
This module turns a sweep plan into that working-set number.

Analytic model (primary). Per pass, one executed (lane, block-row) sweep
slot reads its coordinate block once and writes it back once; the
end-of-pass lane sync gathers every active lane's full row view once
more for the exact aggregate re-sync. Probe samples, pass schedules, and
per-slot scalars live in registers/cache against a 4 KiB+ block and are
not DRAM traffic. So::

    pass_bytes = 2 * swept_slots * block * itemsize      (sweep)
               + prod(sync_table_shape) * block * itemsize  (sync gather)

``swept_slots`` already includes width-rung padding (padded slots sweep
the scratch page — real traffic, wasted work; ``pad_stats`` reports the
fraction), and the sync term covers scratch reads past short lanes'
pages the same way. This is the number the engine accumulates into
``engine_est_bytes_moved_total`` at plan-dispatch time — zero device
syncs, pure host arithmetic on plan shapes.

HLO cross-check (secondary). ``hlo_bytes_accessed`` asks XLA's
``cost_analysis`` for the compiled fused step's "bytes accessed".
CAVEAT: XLA costs a while/scan BODY ONCE regardless of trip count (the
same limitation ``benchmarks/roofline.py`` documents), and the fused
step nests bands-in-pass-loop — so the HLO figure approximates ONE
pass's touched footprint, not r passes' traffic, and on top of that
counts cache-resident accesses. Use it as an order-of-magnitude sanity
bound on the analytic model, never as the roofline numerator.

``measured_peak_bandwidth`` calibrates the roof itself: best-of-N timing
of a donated jitted ``x + 1`` stream over an out-of-cache array — the
achievable (not datasheet) sequential read+write bandwidth of wherever
this process actually runs, which is what "achieved fraction" should be
measured against in a drifting container.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp


def plan_pass_bytes(plan, block_size: int, itemsize: int) -> int:
    """Estimated DRAM bytes one pass of this sweep plan moves.

    Works on unsharded and sharded plans alike: ``swept_slots`` counts
    executed slots across all devices and the sync table's shape carries
    the device axis when present, so both terms are global totals. Plans
    with spanning lanes add ``span_psum_bytes`` — the per-pass tile
    gather plus the bit-pattern psum of the partial-aggregate table
    (read + write per device), priced by the plan builder because only
    it knows the padded table rungs (engine/DESIGN.md § Spanning
    lanes).
    """
    if plan is None or plan.sync is None:
        return 0
    sweep = 2 * plan.swept_slots * block_size * itemsize
    sync_rows = 1
    for d in plan.sync.pages.shape:
        sync_rows *= int(d)
    return (sweep + sync_rows * block_size * itemsize
            + getattr(plan, "span_psum_bytes", 0))


def hlo_bytes_accessed(fn, *args) -> float | None:
    """XLA cost_analysis "bytes accessed" for ``fn(*args)`` — the
    ONE-ITERATION footprint (see module docstring), or None when the
    backend doesn't expose cost analysis. Lowering only traces; donated
    live buffers are safe to pass."""
    try:
        cost = fn.lower(*args).compile().cost_analysis()
        val = cost.get("bytes accessed")
        return float(val) if val is not None else None
    except Exception:                    # noqa: BLE001 — diagnostic only
        return None


def measured_peak_bandwidth(nbytes: int = 1 << 28,
                            repeats: int = 5) -> float:
    """Achievable sequential DRAM bandwidth (bytes/s) on this backend:
    best-of-``repeats`` donated ``x + 1`` stream over an ``nbytes``
    array (read + write = ``2 * nbytes`` per run). Best-of, not median:
    the roof is what the machine CAN do; container jitter only ever
    subtracts."""
    n = max(nbytes // 4, 1)
    step = jax.jit(lambda x: x + 1.0, donate_argnums=0)
    a = jnp.zeros((n,), jnp.float32)
    a = step(a)                          # warmup: compile outside timing
    a.block_until_ready()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        a = step(a)
        a.block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return 2 * n * 4 / best
