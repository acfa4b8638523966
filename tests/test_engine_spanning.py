"""Spanning lanes: one job's pages striped across the device mesh.

Coverage mirrors tests/test_engine_sharded.py's split (conftest keeps
this pytest process on ONE CPU device):

* subprocess tests force 2/4 host devices via XLA_FLAGS — the striped
  bit-identity, kill/resume reshard, and owner-select property suites
  run there in every tier-1 invocation;
* the span-coords math (Gauss-Seidel within a shard, Jacobi across) is
  a D=1 property, so the engine-vs-``abo_minimize`` agreement test runs
  in-process unconditionally;
* plan-builder scaling, the fixed-origin reduction fold, the
  ``use_kernel`` submit rejection, and fsck's device-map validation are
  host-side and run in-process too.
"""
import json
import os
import pathlib
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ABOConfig, abo_minimize
from repro.checkpoint.fsck import fsck
from repro.engine import batched
from repro.engine.jobs import JobSpec
from repro.engine.scheduler import LanePool, SolveEngine
from repro.objectives import OBJECTIVES

REPO = pathlib.Path(__file__).resolve().parent.parent

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >= 2 devices (CI matrix forces 2 via XLA_FLAGS)")


def _run(script: str, devices: int = 4, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ------------------------------------------------------- in-process (1 device)
def test_submit_rejects_use_kernel():
    eng = SolveEngine(lanes=2)
    cfg = ABOConfig(samples_per_pass=5, n_passes=2, use_kernel=True)
    with pytest.raises(ValueError, match="jnp fused-step path"):
        eng.submit(JobSpec("sphere", 64, cfg, seed=0))
    assert not eng.jobs and not eng.queue   # nothing half-admitted


def test_span_coords_math_matches_solo_d1():
    """span_coords is a *math* property (shard-boundary aggregate resets),
    independent of placement: the engine at D=1 with an explicit spanning
    config must reproduce ``abo_minimize`` of the same config bit-for-bit
    — this is the invariant that lets D>1 striping claim bit-identity by
    comparing against the single-device solver."""
    tile = OBJECTIVES["griewank"].REDUCE_TILE
    cfg = ABOConfig(samples_per_pass=5, n_passes=3, block_size=8,
                    span_coords=tile)
    n = 2 * tile + 640                       # 3 shards, ragged tail
    ref = abo_minimize(OBJECTIVES["griewank"], n, config=cfg, seed=3)
    eng = SolveEngine(lanes=2)
    jid = eng.submit(JobSpec("griewank", n, cfg, seed=3))
    eng.run()
    r = eng.result(jid)
    assert r.fun == ref.fun
    assert np.asarray(r.x).tobytes() == np.asarray(ref.x).tobytes()


def test_fold_tile_partials_bitwise_matches_aggregates():
    """The spanning resync's fixed-origin decomposition: per-tile
    partials folded in index order must equal the sequential streamed
    reduction bit-for-bit, including the masked ragged tail (this is
    what makes the cross-device tree sum safe to substitute for the
    whole-lane ``aggregates`` call)."""
    for name in ("griewank", "rastrigin"):
        obj = OBJECTIVES[name]
        tile = obj.REDUCE_TILE
        rng = np.random.default_rng(11)
        n_valid = 2 * tile + 777
        n_pad = 3 * tile                     # last tile: masked + zeros
        x = np.zeros((n_pad,), np.float32)
        x[:n_valid] = rng.uniform(-4, 4, n_valid).astype(np.float32)
        want = np.asarray(obj.aggregates(jnp.asarray(x), n_valid))
        parts = jnp.stack([
            obj.tile_partial(jnp.asarray(x[t * tile:(t + 1) * tile]),
                             jnp.asarray(t, jnp.int32), n_valid)
            for t in range(3)])
        got = np.asarray(obj.fold_tile_partials(parts, 3))
        assert got.tobytes() == want.tobytes(), name


def test_spanning_plan_builds_fast_for_1e9_coords():
    """Plan building is host-side metadata work: a single 1e9-coordinate
    spanning lane must plan in under a second, without materializing any
    pool state (the paper's headline n is a *plan-time* object long
    before it is a device-memory object)."""
    obj = OBJECTIVES["sphere"]
    block = 8192                             # keeps the page table small
    span = 1024 * block                      # lcm(block, REDUCE_TILE)-aligned
    n = 1_000_000_000
    cfg = batched.effective_config(
        ABOConfig(samples_per_pass=5, n_passes=1, block_size=block,
                  span_coords=span), n)
    pages = batched.pages_for(n, block)
    pool = LanePool(key=("sphere", cfg, "float32"), obj=obj, lanes=1,
                    slots=1, capacity=batched.pad_ladder(pages + 1, 1))
    pool.job_ids = ["J00000001"]
    pool.page_table = [list(range(1, pages + 1))]
    pool.lane_dev = [0]
    t0 = time.perf_counter()
    plan = pool.build_plan()
    dt = time.perf_counter() - t0
    assert plan.swept_slots >= pages
    assert plan.pass_bytes > n * 4           # sweeps touch every coordinate
    assert pool.state is None                # no device pool materialized
    assert dt < 1.0, f"plan build took {dt:.2f}s"


def _bad_map_ckpt(root: pathlib.Path, step: int, aux) -> pathlib.Path:
    d = root / f"step_{step:012d}"
    d.mkdir(parents=True)
    manifest = {"step": step, "treedef": "*", "n_leaves": 0, "shapes": [],
                "dtypes": [], "committed": True}
    if aux is not None:
        manifest["aux"] = aux
    (d / "manifest.json").write_text(json.dumps(manifest))
    return d


def test_fsck_flags_and_repairs_bad_device_maps(tmp_path):
    """aux v3 placement validation: orphaned claims (device/page out of
    range, device map not covering the lane) and duplicate (device, page)
    claims are reported as ``bad_device_map``; --repair removes the bad
    base, truncating the chain to the last consistent one."""
    def aux(pools):
        return {"version": 3, "pools": pools}

    good = aux([{"n_dev": 2, "capacity": 16,
                 "page_table": [[1, 2, 1, 2], [3, 4], None],
                 "lane_dev": [[0, 0, 1, 1], 1, None]}])
    _bad_map_ckpt(tmp_path, 1, good)
    assert fsck(tmp_path)["ok"]

    bad = [
        # duplicate: striped lane claims (1, 3) already owned by lane 1
        aux([{"n_dev": 2, "capacity": 16,
              "page_table": [[1, 2, 3, 2], [3, 4], None],
              "lane_dev": [[0, 0, 1, 1], 1, None]}]),
        # orphaned: device id out of the mesh
        aux([{"n_dev": 2, "capacity": 16, "page_table": [[1, 2]],
              "lane_dev": [[0, 5]]}]),
        # orphaned: page 0 is the per-device scratch, never claimable
        aux([{"n_dev": 2, "capacity": 16, "page_table": [[0, 1]],
              "lane_dev": [[0, 0]]}]),
        # striped device map shorter than the lane's page table
        aux([{"n_dev": 2, "capacity": 16, "page_table": [[1, 2, 3]],
              "lane_dev": [[0, 1]]}]),
        # capacity not divisible into per-device shards
        aux([{"n_dev": 3, "capacity": 16, "page_table": [[1]],
              "lane_dev": [[0]]}]),
    ]
    for i, a in enumerate(bad):
        d = _bad_map_ckpt(tmp_path, 10 + i, a)
        rep = fsck(tmp_path)
        kinds = {f["kind"] for f in rep["findings"]}
        assert kinds == {"bad_device_map"}, (i, rep["findings"])
        assert not rep["ok"]
        assert fsck(tmp_path, repair=True)["ok"], i
        assert not d.exists()                # chain truncated to step 1
    assert fsck(tmp_path)["ok"] and not fsck(tmp_path)["findings"]


# ---------------------------------------------------------- subprocess suite
def test_owner_select_properties_subprocess():
    """Property suite for the bit-pattern psum: payload bits (-0.0, NaN
    payloads, ±inf, denormals) survive owner replication untouched, every
    device agrees with a host-side gather of each row from its owner, a
    2-D (v, g) owner table broadcasts over trailing page axes (the
    spanning harvest shape), and int dtypes take the integer-psum path —
    at D in {1, 2, 4}."""
    out = _run("""
        import functools
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.core.sharded import axis_linear_index, owner_select

        D = len(jax.devices())
        mesh = Mesh(np.array(jax.devices()), ('pool',))
        rep = NamedSharding(mesh, P())

        def run(x, owner):
            def body(x, owner):
                my = axis_linear_index(('pool',))
                return owner_select(x, owner, my, 'pool')
            f = jax.shard_map(body, mesh=mesh, in_specs=(P(), P()),
                              out_specs=P(), check_vma=False)
            return np.asarray(jax.jit(f)(jax.device_put(x, rep),
                                         jax.device_put(owner, rep)))

        rng = np.random.default_rng(0)
        # payload bits that a float sum would NOT round-trip
        words = np.array([0x80000000,              # -0.0
                          0x7fc00abc, 0xffc00123,  # NaN payloads
                          0x7f800000, 0xff800000,  # +/-inf
                          0x00000001,              # denormal
                          0x3f800000, 0xc0490fdb], np.uint32)
        payload = words.view(np.float32)
        rows = rng.standard_normal((8, 3)).astype(np.float32)
        rows[:, 0] = payload
        for trial in range(3):
            owner = rng.integers(0, D, size=8).astype(np.int32)
            got = run(jnp.asarray(rows), jnp.asarray(owner))
            assert got.tobytes() == rows.tobytes(), (D, trial)

        # all rows owned by one device (the tie-break degenerate case)
        for d in range(D):
            owner = np.full((8,), d, np.int32)
            got = run(jnp.asarray(rows), jnp.asarray(owner))
            assert got.tobytes() == rows.tobytes(), (D, d)

        # 2-D (v, g) owner against a (v, g, block) page gather
        pages = rng.standard_normal((2, 4, 8)).astype(np.float32)
        pages[0, :, 0] = payload[:4]
        owner2 = rng.integers(0, D, size=(2, 4)).astype(np.int32)
        got = run(jnp.asarray(pages), jnp.asarray(owner2))
        assert got.tobytes() == pages.tobytes(), D

        # integer dtype rides the integer-psum branch
        iv = rng.integers(-2**31, 2**31 - 1, size=(8, 3),
                          dtype=np.int32)
        owner = rng.integers(0, D, size=8).astype(np.int32)
        got = run(jnp.asarray(iv), jnp.asarray(owner))
        assert got.tobytes() == iv.tobytes(), D
        print('OK', D)
    """, devices=4)
    assert "OK 4" in out
    for d in (1, 2):
        # same property at the other device counts the CI matrix uses
        assert "OK" in _run("""
            import jax, numpy as np
            print('OK', len(jax.devices()))
        """, devices=d)


def test_spanning_bit_identity_subprocess():
    """A lane too large for the per-device page budget stripes across
    D=4, coexists with whole small lanes, and still produces fun/x
    bit-identical to single-device ``abo_minimize`` with the derived
    spanning config."""
    out = _run("""
        import numpy as np
        from repro.core import ABOConfig, abo_minimize
        from repro.engine.jobs import JobSpec
        from repro.engine.scheduler import SolveEngine
        from repro.objectives import OBJECTIVES

        tile = OBJECTIVES['griewank'].REDUCE_TILE
        cfg = ABOConfig(samples_per_pass=5, n_passes=3, block_size=8)
        n_big = 3 * tile                      # 1536 pages > span budget
        span_pages = 512                      # derived span = 4096 coords
        small = [JobSpec('sphere', 40 + 9 * i, cfg, seed=i)
                 for i in range(3)]

        # max_fuse=1: keep the striped lane alive past the first step so
        # its placement is observable (unfused it finishes in one chunk)
        eng = SolveEngine(lanes=4, devices=4, span_pages=span_pages,
                          max_fuse=1)
        big_id = eng.submit(JobSpec('griewank', n_big, cfg, seed=7))
        ids = eng.submit_many(small)
        eng.step()
        pools = list(eng.pools.values())
        striped = [d for p in pools for d in p.lane_dev
                   if isinstance(d, list)]
        assert len(striped) == 1, striped
        assert sorted(set(striped[0])) == [0, 1, 2], striped[0][:8]
        eng.run()

        span_cfg = ABOConfig(samples_per_pass=5, n_passes=3, block_size=8,
                             span_coords=tile)
        ref = abo_minimize(OBJECTIVES['griewank'], n_big, config=span_cfg,
                           seed=7)
        r = eng.result(big_id)
        assert r.fun == ref.fun
        assert np.asarray(r.x).tobytes() == np.asarray(ref.x).tobytes()
        for s, jid in zip(small, ids):
            ref = abo_minimize(OBJECTIVES['sphere'], s.n, config=cfg,
                               seed=s.seed)
            r = eng.result(jid)
            assert r.fun == ref.fun
            assert np.asarray(r.x).tobytes() == np.asarray(ref.x).tobytes()
        print('OK')
    """)
    assert "OK" in out


def test_spanning_kill_resume_reshard_subprocess():
    """A journaled engine killed mid-run with a striped lane resumes at
    D=4 (stripe re-derived over more devices), then at D=1 (collapses to
    a whole lane), and the final bits still match the uninterrupted
    D=2 run — the aux v3 per-page device maps and the round-robin
    re-derivation rule together make resharding placement-only."""
    out = _run("""
        import shutil, tempfile
        import numpy as np
        from repro.core import ABOConfig
        from repro.engine.jobs import JobSpec
        from repro.engine.scheduler import SolveEngine
        from repro.objectives import OBJECTIVES

        tile = OBJECTIVES['griewank'].REDUCE_TILE
        cfg = ABOConfig(samples_per_pass=5, n_passes=4, block_size=8)
        n_big = 2 * tile + 1024
        def specs():
            return ([JobSpec('griewank', n_big, cfg, seed=7)]
                    + [JobSpec('sphere', 60 + 13 * i, cfg, seed=i)
                       for i in range(3)])

        solo = SolveEngine(lanes=4, devices=2, span_pages=512)
        ids0 = solo.submit_many(specs())
        solo.run()
        want = [(solo.result(j).fun, np.asarray(solo.jobs[j].x).tobytes())
                for j in ids0]

        ck = tempfile.mkdtemp(prefix='span_resume_')
        e1 = SolveEngine(lanes=4, devices=2, span_pages=512, max_fuse=1,
                         checkpoint_dir=ck, journal_every=1)
        ids = e1.submit_many(specs())
        e1.step()
        e1.snapshot()
        del e1                                # kill mid-flight

        e2 = SolveEngine.resume(ck, devices=4)
        p = [p for p in e2.pools.values()
             if any(isinstance(d, list) for d in p.lane_dev)]
        assert p, 'stripe lost on resume'
        stripe = next(d for d in p[0].lane_dev if isinstance(d, list))
        # 9216 coords / 4096-coord shards = 3 shards -> devices 0, 1, 2
        assert sorted(set(stripe)) == [0, 1, 2], stripe[:8]
        e2.step()
        e2.snapshot()
        del e2

        e3 = SolveEngine.resume(ck, devices=1)  # collapses to whole lane
        assert all(not isinstance(d, list)
                   for pl in e3.pools.values() for d in pl.lane_dev)
        e3.run()
        for (fun, xb), jid in zip(want, ids):
            r = e3.result(jid)
            assert r.fun == fun and np.asarray(r.x).tobytes() == xb, jid
        shutil.rmtree(ck, ignore_errors=True)
        print('OK')
    """)
    assert "OK" in out


def test_sanitized_step_after_snapshot_donates_subprocess():
    """Regression: the checkpoint writer's device->host read must not pin
    pool buffers. ``np.asarray`` on a fully-replicated multi-device array
    caches a zero-copy view on the array itself; the pinned buffer then
    silently turns every later donation into a copy — the sanitizer's
    DonationError on the first step after a snapshot. The save path now
    reads via a single shard's copy, so a journaled sanitized engine must
    step cleanly past its bases."""
    out = _run("""
        import shutil, tempfile
        from repro.core import ABOConfig
        from repro.engine.jobs import JobSpec
        from repro.engine.scheduler import SolveEngine

        cfg = ABOConfig(samples_per_pass=5, n_passes=6, block_size=8)
        ck = tempfile.mkdtemp(prefix='don_snap_')
        eng = SolveEngine(lanes=4, devices=2, max_fuse=1, sanitize=True,
                          checkpoint_dir=ck, journal_every=1)
        eng.submit_many([JobSpec('sphere', 100, cfg, seed=i)
                         for i in range(4)])
        for _ in range(3):
            eng.step()                        # snapshot after every step
        eng.snapshot()
        del eng
        e2 = SolveEngine.resume(ck, devices=2, sanitize=True)
        e2.run()
        shutil.rmtree(ck, ignore_errors=True)
        print('OK')
    """, devices=2)
    assert "OK" in out
