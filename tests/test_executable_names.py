"""The engine's executables are named for what they do.

Every ``jax.jit`` in ``repro.engine.batched`` lowers to an HLO module
``jit_<name>``, and the profiler names its device events after that
module: a trace reduction finds the fused step's time by the name
``fused_step`` (``bench/enginetrace.py``). Each case drives a small
engine workload in a child process (its own device count, a cleared
executable cache) with ``jax.jit`` wrapped so that every engine
executable is lowered once, before its first call, and its module name
recorded.
"""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

SCRIPT = """
import json, re, sys
import jax
import numpy as np
from repro.core import ABOConfig
from repro.engine import batched
from repro.engine.jobs import JobSpec
from repro.engine.scheduler import SolveEngine
from repro.objectives import OBJECTIVES

devices = int(sys.argv[1])
modules = set()
real_jit = jax.jit


def recording_jit(fun, **kw):
    jitted = real_jit(fun, **kw)
    if getattr(fun, "__module__", None) != batched.__name__:
        return jitted
    seen = []

    def call(*args):
        if not seen:
            text = jitted.lower(*args).as_text()
            modules.add(re.search(r"module @(\\S+)", text).group(1))
            seen.append(True)
        return jitted(*args)
    return call


jax.jit = recording_jit
cfg = ABOConfig(samples_per_pass=5, n_passes=2, block_size=8)
kw = {}
if devices > 1:
    tile = OBJECTIVES["griewank"].REDUCE_TILE
    kw = dict(devices=devices, span_pages=512)
eng = SolveEngine(lanes=4, max_fuse=1, **kw)
ids = [eng.submit(JobSpec("sphere", 40, cfg, seed=0)),
       eng.submit(JobSpec("sphere", 40, cfg, x0=np.linspace(-1, 1, 40)))]
if devices > 1:
    ids.append(eng.submit(JobSpec("griewank", 3 * tile, cfg, seed=7)))
eng.run()
assert all(eng.result(j).fun is not None for j in ids)
print(json.dumps(sorted(modules)))
"""

NAMES = {
    1: ["jit_finalize", "jit_fused_step", "jit_host_resize", "jit_place",
        "jit_place_x"],
    4: ["jit_finalize_sharded", "jit_finalize_span",
        "jit_fused_step_sharded", "jit_place_sharded", "jit_place_span",
        "jit_place_x_sharded", "jit_resize_sharded"],
}


@pytest.mark.parametrize("devices", sorted(NAMES))
def test_pool_ops_lower_to_named_modules(devices):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(SCRIPT), str(devices)],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    modules = json.loads(out.stdout.strip().splitlines()[-1])
    assert modules == NAMES[devices]
    assert not any(m.startswith("jit_run") for m in modules)


def test_resync_ops_carry_their_name_scope():
    """Inside the fused step, the pass-end re-sync compiles under the name
    scope ``batched.RESYNC_SCOPE``: its ops, the tile loop among them,
    carry it in their metadata's op name, which the device trace keeps
    as each op's ``tf_op``. The row sweep's selection does not."""
    import re

    import jax
    import jax.numpy as jnp

    from repro.core import ABOConfig
    from repro.engine import batched
    from repro.engine.scheduler import LanePool
    from repro.objectives import GRIEWANK

    n, cfg = 9_000, ABOConfig(samples_per_pass=5, n_passes=2,
                              block_size=1024)
    key = batched.family_key("griewank", n, cfg)
    pool = LanePool(key=key, obj=GRIEWANK, lanes=2)
    slot = pool.take_slot()
    pool.job_ids[slot] = "job0"
    pool.lane_dev[slot] = 0
    pool.page_table[slot] = pool.alloc_pages(
        batched.pages_for(n, cfg.block_size), 0)
    plan = pool.build_plan()
    ops = batched.PoolOps(GRIEWANK, key, pool.slots, pool.capacity)
    state = jax.eval_shape(lambda: batched.zeros_pool_state(
        GRIEWANK, key, pool.slots, pool.capacity))
    text = ops.fused_step(*plan.signature()).lower(
        state, jax.ShapeDtypeStruct((), jnp.int32),
        *plan.args).compile().as_text()
    names = re.findall(r" (\w[\w-]*)\(.*op_name=\"([^\"]+)\"", text)
    scoped = {op for op, name in names if batched.RESYNC_SCOPE in name}
    assert batched.RESYNC_SCOPE == "engine.resync"
    assert "while" in scoped
    # the selection's variadic reduce is the row sweep's, outside it
    selection = [name for _, name in names if name.endswith("/reduce")]
    assert selection
    assert not [n for n in selection if batched.RESYNC_SCOPE in n]
