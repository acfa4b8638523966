"""The engine's float64-aggregate mode (``jax_enable_x64``) on the CPU.

x64 is process-global and the test workers share a process per file, so
every check that needs it runs in a child process started with
``JAX_ENABLE_X64=1``. In that mode the solution rows stay float32 and
the aggregates, probe values and deltas are float64: the engine must
place, sweep, re-sync and finalize in it, and agree with ``abo_minimize``
bit for bit, as it does in float32.
"""
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


def _run_x64(script: str, devices: int = 1, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["JAX_ENABLE_X64"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# one lane alone, or three lanes of different n sharing block rows
LAYOUTS = {"one_lane": [9_000], "multi_lane": [5_000, 9_000, 13_000]}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("objective",
                         ["griewank", "shifted_sphere", "rastrigin"])
def test_engine_matches_abo_minimize_x64(objective, layout):
    out = _run_x64(f"""
        import jax
        import numpy as np
        from repro.core import ABOConfig, abo_minimize
        from repro.engine import JobSpec, SolveEngine
        from repro.objectives import OBJECTIVES

        assert jax.config.jax_enable_x64
        cfg = ABOConfig(samples_per_pass=51, n_passes=4, block_size=1024)
        sizes = {LAYOUTS[layout]!r}
        eng = SolveEngine(lanes=4)
        eng.trace()
        ids = [eng.submit(JobSpec({objective!r}, n, cfg, seed=11 + i))
               for i, n in enumerate(sizes)]
        eng.run()
        aggs = {{str(p.state.aggs.dtype) for p in eng.pools.values()}}
        assert aggs == {{'float64'}}, aggs
        sweeps = [e['args'] for e in eng.tracer.events
                  if e['name'] == 'fused_sweep']
        assert sweeps and all(a['agg_bytes'] == 8 for a in sweeps)
        for i, (n, jid) in enumerate(zip(sizes, ids)):
            got = eng.result(jid)
            ref = abo_minimize(OBJECTIVES[{objective!r}], n, config=cfg,
                               seed=11 + i)
            assert np.asarray(ref.history).dtype == np.float64
            assert got.fun == ref.fun, (n, got.fun, ref.fun)
            assert (np.asarray(got.history).tobytes()
                    == np.asarray(ref.history).tobytes())
            assert np.asarray(got.x).dtype == np.float32
            assert np.asarray(got.x).tobytes() == np.asarray(ref.x).tobytes()
        print('OK', eng.pools and len(eng.pools))
    """)
    assert "OK" in out


def test_spanning_lane_matches_abo_minimize_x64_d2():
    """A lane striped over two forced host devices, beside whole lanes:
    the span re-sync's bit-pattern psum moves float64 partials as
    uint64, and every answer still matches ``abo_minimize``."""
    out = _run_x64("""
        import numpy as np
        from repro.core import ABOConfig, abo_minimize
        from repro.engine import JobSpec, SolveEngine
        from repro.objectives import OBJECTIVES

        tile = OBJECTIVES['griewank'].REDUCE_TILE
        cfg = ABOConfig(samples_per_pass=7, n_passes=3, block_size=8)
        n_big = 3 * tile                      # 1536 pages > span budget
        eng = SolveEngine(lanes=4, devices=2, span_pages=512, max_fuse=1)
        big = eng.submit(JobSpec('griewank', n_big, cfg, seed=5))
        small = [(n, eng.submit(JobSpec('rastrigin', n, cfg, seed=n)))
                 for n in (300, 700)]
        eng.step()
        striped = [d for p in eng.pools.values() for d in p.lane_dev
                   if isinstance(d, list)]
        assert len(striped) == 1 and sorted(set(striped[0])) == [0, 1]
        eng.run()
        span_cfg = ABOConfig(samples_per_pass=7, n_passes=3, block_size=8,
                             span_coords=tile)
        ref = abo_minimize(OBJECTIVES['griewank'], n_big, config=span_cfg,
                           seed=5)
        got = eng.result(big)
        assert got.fun == ref.fun
        assert np.asarray(got.x).tobytes() == np.asarray(ref.x).tobytes()
        for n, jid in small:
            ref = abo_minimize(OBJECTIVES['rastrigin'], n, config=cfg,
                               seed=n)
            got = eng.result(jid)
            assert got.fun == ref.fun
            assert np.asarray(got.x).tobytes() == np.asarray(ref.x).tobytes()
        print('OK')
    """, devices=2)
    assert "OK" in out


@pytest.mark.parametrize("page_dtype", ["int32", "int64"])
def test_write_pages_x64(page_dtype):
    """Under x64 a page id of either width writes its row, the rows in
    order (a repeated scratch page takes the last of its rows)."""
    out = _run_x64(f"""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro.engine.batched import write_pages

        pool = jnp.zeros((6, 4), jnp.float32)
        pages = np.array([[3, 0], [5, 0]], np.{page_dtype})
        rows = np.arange(16, dtype=np.float64).reshape(2, 8)
        got = jax.jit(write_pages)(pool, jnp.asarray(pages),
                                   jnp.asarray(rows))
        want = np.zeros((6, 4), np.float32)
        for p, r in zip(pages.reshape(-1), rows.reshape(4, 4)):
            want[p] = r
        assert jnp.asarray(pages).dtype == np.{page_dtype}
        assert got.dtype == np.float32
        np.testing.assert_array_equal(np.asarray(got), want)
        print('OK')
    """)
    assert "OK" in out


_SELECT_CASE = """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.abo import _select_first_min

    def case(val_dt, cand_dt):
        rng = np.random.default_rng(3)
        b, m, a = 6, 9, 3
        f = rng.integers(0, 4, (b, m)).astype(val_dt)   # ties throughout
        f[1, 4] = np.nan                 # a NaN wins its row
        f[2] = np.inf                    # a row of +inf picks column 0
        cands = rng.uniform(-5, 5, (b, m)).astype(cand_dt)
        cands[0, 0] = -0.0               # the sign of a zero survives
        delta = rng.normal(size=(b, m, a)).astype(val_dt)
        x_sel, d_sel = jax.jit(_select_first_min)(
            jnp.asarray(f), jnp.asarray(cands), jnp.asarray(delta))
        pick = jnp.argmin(jnp.asarray(f), axis=1)
        want_x = jnp.take_along_axis(jnp.asarray(cands), pick[:, None], 1)
        want_d = jnp.take_along_axis(jnp.asarray(delta),
                                     pick[:, None, None], 1)
        assert x_sel.dtype == cands.dtype and d_sel.dtype == delta.dtype
        assert (np.asarray(x_sel).tobytes()
                == np.asarray(want_x[:, 0]).tobytes())
        assert (np.asarray(d_sel).tobytes()
                == np.asarray(want_d[:, 0]).tobytes())
"""


def test_select_first_min_narrow_candidate_x64():
    """A float32 candidate beside float64 values and deltas (the engine's
    float64-aggregate mode) rides the reduce as its bit pattern: equal to
    argmin plus ``take_along_axis``, ties, NaN and +inf rows included."""
    out = _run_x64(_SELECT_CASE + """
    case(np.float64, np.float32)
    case(np.float64, np.float64)
    print('OK')
    """)
    assert "OK" in out


@pytest.mark.parametrize("val_dt,cand_dt", [
    (jnp.float32, jnp.float32), (jnp.float32, jnp.bfloat16)])
def test_select_first_min_matches_argmin(val_dt, cand_dt):
    """The same identity without x64: a float32 candidate as the values'
    own dtype, and a bfloat16 one that rides as 16-bit integers."""
    assert not jax.config.jax_enable_x64
    ns = {}
    exec(textwrap.dedent(_SELECT_CASE), ns)
    ns["case"](val_dt, cand_dt)
