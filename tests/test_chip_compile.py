"""Compile-only checks against a described TPU v5e: the chip's compiler
accepts the main path's kernels and steps at the paper's widths.

Nothing here runs on a chip — ``get_topology_desc`` describes a v5e:2x2
that is not attached, and each test lowers and compiles a program for it
from shapes alone. What the TPU compiler refuses (tile shapes off the
(8, 128) grid, ops the kernel compiler lacks, programs that do not fit
the device) fails here at no chip cost. The topology is described inside
a fixture, never at import: only one process may hold the TPU library at
a time, and every test worker imports this file.
"""
import dataclasses
import json
import os
import pathlib
import re
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.core.abo import (ABOConfig, _abo_jit, _default_probe_tile,
                            effective_config)
from repro.engine import batched, scheduler
from repro.engine.scheduler import LanePool
from repro.kernels.coord_sweep.kernel import AGG_LANES, LANES
from repro.kernels.coord_sweep.ops import sweep_pass
from repro.kernels.griewank.kernel import griewank_aggregates_kernel
from repro.objectives import GRIEWANK

PAPER = ABOConfig()                  # m = 50 samples x 5 passes, block 4096
REPO = pathlib.Path(__file__).resolve().parent.parent
HBM_BYTES = 16 * 2 ** 30             # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # noqa: BLE001 — any failure means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(a, sharding):
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)


def _device_bytes(compiled) -> int:
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)


def test_coord_sweep_kernel_compiles_1e8(one_chip):
    n = 10 ** 8
    n_pad = -(-n // PAPER.block_size) * PAPER.block_size
    x = jax.ShapeDtypeStruct((n_pad // LANES, LANES), jnp.float32,
                             sharding=one_chip)
    aggs = jax.ShapeDtypeStruct((1, AGG_LANES), jnp.float32,
                                sharding=one_chip)
    for first in (True, False):
        compiled = sweep_pass.lower(
            x, aggs, block=PAPER.block_size, m=PAPER.samples_per_pass,
            n_valid=n, half_width=37.5, lam=0.25, is_first=first).compile()
        assert "tpu_custom_call" in compiled.as_text()


def test_griewank_kernel_compiles_1e8(one_chip):
    n = 10 ** 8
    n_pad = -(-n // 4096) * 4096
    x = jax.ShapeDtypeStruct((n_pad // LANES, LANES), jnp.float32,
                             sharding=one_chip)
    fn = jax.jit(lambda x: griewank_aggregates_kernel(x, chunk=4096,
                                                      n_valid=n))
    assert "tpu_custom_call" in fn.lower(x).compile().as_text()


def _one_lane_pool(n: int, cfg: ABOConfig, mesh=None, n_dev=1,
                   span_pages=None, lanes=1) -> LanePool:
    """A host-side LanePool holding ``lanes`` griewank lanes of ``n``
    coords — whole, or striped in shards of ``span_pages`` over the
    mesh."""
    key = batched.family_key("griewank", n, cfg)
    pool = LanePool(key=key, obj=GRIEWANK, lanes=8, mesh=mesh, n_dev=n_dev)
    pages = batched.pages_for(n, cfg.block_size)
    for j in range(lanes):
        slot = pool.take_slot()
        pool.job_ids[slot] = f"job{j}"
        if span_pages is None:
            pool.lane_dev[slot] = 0
            pool.page_table[slot] = pool.alloc_pages(pages, 0)
        else:
            pool.page_table[slot], pool.lane_dev[slot] = \
                pool.alloc_span_pages(pages, span_pages)
    return pool


def _compile_fused(pool: LanePool, state_sharding, arg_shape):
    key = pool.key
    plan = pool.build_plan()
    ops = batched.PoolOps(GRIEWANK, key, pool.slots, pool.capacity,
                          pool.mesh)
    state = jax.eval_shape(lambda: batched.zeros_pool_state(
        GRIEWANK, key, pool.slots, pool.capacity))
    state = jax.tree_util.tree_map(_shape, state, state_sharding)
    r = jax.ShapeDtypeStruct((), jnp.int32, sharding=state_sharding.n_valid)
    return ops.fused_step(*plan.signature()).lower(
        state, r, *[arg_shape(a) for a in plan.args]).compile()


def test_fused_step_1e9_fits_one_chip(one_chip):
    """The engine's unsharded fused step for one paper-scale lane: the
    whole 1e9-coordinate pool plus the step's scratch fits one chip."""
    pool = _one_lane_pool(10 ** 9, PAPER)
    sharding = batched.PoolState(*(one_chip,) * 5)
    compiled = _compile_fused(pool, sharding,
                              lambda a: _shape(a, one_chip))
    assert _device_bytes(compiled) < HBM_BYTES


def _float_sums(hlo: str) -> list[str]:
    """The reduce instructions of an optimized HLO text that add floats
    over a dimension longer than one. An argmin's (value, index) reduce
    has a tuple result and is not matched; the compiler's reduces over a
    size-1 dimension are layout changes that add nothing."""
    shapes = dict(re.findall(r"(%[\w.\-]+) = \w+\[([\d,]*)\]", hlo))
    found = []
    for m in re.finditer(r"(%[\w.\-]+) = f\d+\[[\d,]*\]\S* reduce\("
                         r"(%[\w.\-]+), [^)]*\), dimensions=\{([\d,]*)\}",
                         hlo):
        dims = [int(d) for d in shapes[m.group(2)].split(",") if d]
        if any(dims[int(a)] > 1 for a in m.group(3).split(",")):
            found.append(m.group(0))
    return found


def test_main_path_sums_in_fixed_order(one_chip):
    """The dense solver and the engine's fused step over three lanes sum
    floats only through explicit add trees. A native reduce leaves the
    association to the TPU compiler, which lays the engine's vmapped
    ``(v, 4096, 3)`` tile reduce out differently from the dense solver's
    ``(4096, 3)`` one, so engine and ``abo_minimize`` can round apart on
    the chip."""
    n = 10_000
    cfg = effective_config(ABOConfig(samples_per_pass=51), n)
    n_pad = -(-n // cfg.block_size) * cfg.block_size
    dense = _abo_jit.lower(
        jax.ShapeDtypeStruct((n_pad,), jnp.float32, sharding=one_chip),
        GRIEWANK, n, cfg, _default_probe_tile(GRIEWANK)).compile()
    pool = _one_lane_pool(n, cfg, lanes=3)
    fused = _compile_fused(pool, batched.PoolState(*(one_chip,) * 5),
                           lambda a: _shape(a, one_chip))
    assert _float_sums(dense.as_text()) == []
    assert _float_sums(fused.as_text()) == []


def _dims(shape: str) -> list[int]:
    return [int(d) for d in shape.split(",") if d]


def test_row_sweep_selects_without_copy_or_gather(one_chip):
    """The unsharded fused step at the benchmark's one-chip shape (n = 5e7,
    m = 51, block 4096, 8 lanes) picks each block's candidate and deltas
    inside the argmin's reduce. Gathered after it, the deltas made the
    compiler copy the (1, 4096, 51, 3) probe tile into a layout with the
    aggregate axis minor, padded from 3 to 128 lanes, on every block row;
    the only gather left is the sync's gather of the lane's page rows.
    The one lane's probe tile is tiled (8, 128), not (1, 128), which
    fills one sublane in eight."""
    m, n_aggs = 51, 3
    pool = _one_lane_pool(5 * 10 ** 7, ABOConfig(samples_per_pass=m))
    text = _compile_fused(pool, batched.PoolState(*(one_chip,) * 5),
                          lambda a: _shape(a, one_chip)).as_text()
    shapes = dict(re.findall(r"(%[\w.\-]+) = \w+\[([\d,]*)\]", text))
    copies = [_dims(s) for s in re.findall(
        r"= \w+\[([\d,]*)\]\S* copy\(", text)]
    assert copies and not [d for d in copies if {m, n_aggs} <= set(d)]
    gathers = re.findall(r"= (\w+\[[\d,]*\])\S* gather\((%[\w.\-]+),", text)
    assert [g for g, _ in gathers] == ["f32[12288,4096]"]
    assert not [a for _, a in gathers if m in _dims(shapes[a])]
    sparse = re.findall(r"f32\[([\d,]*)\]\{[\d,]*:T\(1,128\)", text)
    assert not [d for d in sparse if m in _dims(d)]


def test_row_sweep_f64_aggregates_selects_without_copy_or_gather(tmp_path):
    """The fused step in the float64-aggregate mode at the one-chip
    float64 cell's shape (``bench/configs/abo_griewank_paper_f64.json``:
    m = 51, block 4096, 8 lanes): x stays float32, the aggregates, probe
    values and deltas are float64, which the v5e has no unit for and
    XLA:TPU emulates as pairs of float32. The row loop still copies no
    probe tile with the m axis, keeps no one-sublane tile with it, and
    its only gather is the sync's. x64 is process-wide, so the compile
    runs in a child process, which writes the compiled text."""
    cfg_path = REPO / "bench" / "configs" / "abo_griewank_paper_f64.json"
    n = json.loads(cfg_path.read_text())["job"]["n"]
    hlo = tmp_path / "fused_step.txt"
    env = {**os.environ, "JAX_ENABLE_X64": "1", "JAX_PLATFORMS": "cpu",
           "TPU_LOG_DIR": "disabled", "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
           "PYTHONPATH": str(REPO / "src")}
    script = textwrap.dedent(f"""
        import pathlib, sys
        sys.path.insert(0, {str(REPO / "tests")!r})
        import jax
        from jax.sharding import SingleDeviceSharding
        from jax.experimental import topologies
        import test_chip_compile as t
        from repro.core.abo import ABOConfig
        from repro.engine import batched
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # noqa: BLE001 — any failure means skip
            print("skip:", e)
            raise SystemExit(0)
        one = SingleDeviceSharding(topo.devices[0])
        pool = t._one_lane_pool({n}, ABOConfig(samples_per_pass=51))
        state = jax.eval_shape(lambda: batched.zeros_pool_state(
            t.GRIEWANK, pool.key, pool.slots, pool.capacity))
        assert state.aggs.dtype == "float64", state.aggs.dtype
        pathlib.Path({str(hlo)!r}).write_text(t._compile_fused(
            pool, batched.PoolState(*(one,) * 5),
            lambda a: t._shape(a, one)).as_text())
    """)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    if proc.stdout.startswith("skip:"):
        pytest.skip(f"no v5e:2x2 topology can be described here: "
                    f"{proc.stdout}")
    text = hlo.read_text()
    m, n_aggs = 51, 3
    rows = batched.pad_ladder(batched.pages_for(n, 4096), 1)
    assert "f64[" in text
    shapes = dict(re.findall(r"(%[\w.\-]+) = \w+\[([\d,]*)\]", text))
    copies = [_dims(s) for s in re.findall(
        r"= \w+\[([\d,]*)\]\S* copy\(", text)]
    assert copies and not [d for d in copies if {m, n_aggs} <= set(d)]
    gathers = re.findall(r"= (\w+\[[\d,]*\])\S* gather\((%[\w.\-]+),", text)
    assert [g for g, _ in gathers] == [f"f32[{rows},4096]"]
    assert not [a for _, a in gathers if m in _dims(shapes[a])]
    sparse = re.findall(r"f32\[([\d,]*)\]\{[\d,]*:T\(1,128\)", text)
    assert not [d for d in sparse if m in _dims(d)]


def test_placement_writes_pages_without_scatter(one_chip):
    """Placing one n = 1e6 lane writes its 256 rung-padded page rows (245
    real, 11 on the scratch page) one dynamic_update_slice at a time. As
    one scatter with those duplicate indices, the v5e wrote the scratch
    rows' zeros over real pages 224-245."""
    key = batched.family_key("griewank", 10 ** 6, PAPER)
    ops = batched.PoolOps(GRIEWANK, key, 8, 256, None)
    state = jax.eval_shape(lambda: batched.zeros_pool_state(
        GRIEWANK, key, 8, 256))
    state = jax.tree_util.tree_map(lambda a: _shape(a, one_chip), state)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = ops.place(256, 1).lower(
        state, arg((1,), jnp.int32), arg((1, 256), jnp.int32),
        arg((1,), jnp.bool_), arg((1,), jnp.uint32),
        arg((1,), jnp.int32)).compile().as_text()
    assert not re.search(r"= f32\[256,4096\]\S* scatter\(", text)


def test_spanning_fused_step_d4_compiles(topo, monkeypatch):
    """The D = 4 spanning fused step: one lane striped over a v5e:2x2 mesh,
    with the per-pass cross-chip all-reduce in the compiled program."""
    mesh = Mesh(np.array(topo.devices), ("pool",))
    cfg = dataclasses.replace(PAPER, span_coords=6104 * PAPER.block_size)
    # the planner device_puts its tables; a described device holds no
    # array, so hand the compiler their shapes and shardings instead
    monkeypatch.setattr(scheduler.jax, "device_put",
                        lambda a, s: _shape(a, s))
    pool = _one_lane_pool(10 ** 8, cfg, mesh=mesh, n_dev=4, span_pages=6104)
    compiled = _compile_fused(pool, batched.state_sharding(mesh),
                              lambda a: a)
    text = compiled.as_text()
    assert "all-reduce" in text
    assert _float_sums(text) == []
    assert _device_bytes(compiled) < HBM_BYTES
