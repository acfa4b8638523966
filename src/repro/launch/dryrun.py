import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this proves, without hardware:
  * the sharding config is coherent (no mismatched collectives),
  * per-device memory fits (memory_analysis),
  * and it emits the roofline terms (cost_analysis + collective bytes parsed
    from the compiled HLO) consumed by benchmarks/roofline.py.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch mistral-nemo-12b \
      --shape train_4k --mesh single            # one cell
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out results/dryrun                      # the full 40-cell matrix
  PYTHONPATH=src python -m repro.launch.dryrun --arch griewank_1b ...   # paper core

The two lines above this docstring MUST stay the first statements in the
file: jax locks the device count on first init.
"""
import argparse
import json
import pathlib
import re
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES, input_specs, supported_shapes
from repro.launch.mesh import make_production_mesh
from repro.models.model import Model
from repro.train import steps as steps_mod
from repro.train import abo_zo as abo_zo_mod


# ---------------------------------------------------------------------------
# collective-byte accounting (cost_analysis has no collective term)
# ---------------------------------------------------------------------------
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"(bf16|f16|f32|f64|s32|u32|s8|u8|pred|s64|u64)"
                       r"\[([0-9,]*)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "s32": 4, "u32": 4,
          "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8}


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _BYTES[dt]
    return total


_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def collective_bytes(hlo_text: str) -> dict:
    """Per-device collective traffic from the post-SPMD HLO.

    HLO lines look like
      %all-gather.43 = f32[2,4,64,16]{...} all-gather(...), replica_groups=[G,S]<=[N], ...
    Bytes are converted to per-device *link traffic* with the standard ring
    model over the group size S:
      all-gather        out·(S-1)/S          (receives everyone else's shard)
      all-reduce        2·out·(S-1)/S        (reduce-scatter + all-gather)
      reduce-scatter    out·(S-1)            (out is the scattered piece)
      all-to-all        out·(S-1)/S
      collective-permute out                 (one hop)
    """
    out = {k: 0.0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        for c in _COLLECTIVES:
            tok = f" {c}("
            # exclude -start/-done duplicates by only counting the op itself
            idx = s.find(tok)
            if idx < 0 or " = " not in s[:idx]:
                continue
            lhs = s[:idx]
            nbytes = _shape_bytes(lhs.split(" = ", 1)[1])
            gm = _GROUPS_RE.search(s)
            gsize = int(gm.group(2)) if gm else 2
            if gsize <= 1:
                factor = 0.0
            elif c == "all-gather":
                factor = (gsize - 1) / gsize
            elif c == "all-reduce":
                factor = 2 * (gsize - 1) / gsize
            elif c == "reduce-scatter":
                factor = gsize - 1
            elif c == "all-to-all":
                factor = (gsize - 1) / gsize
            else:
                factor = 1.0
            out[c] += nbytes * factor
            counts[c] += 1
            break
    return {"bytes": out, "counts": counts,
            "total_bytes": sum(out.values())}


# ---------------------------------------------------------------------------
# cell builders
# ---------------------------------------------------------------------------
def build_cell(arch: str, shape: str, mesh, optimizer: str = "adamw",
               microbatches: int = 8, remat=True, moe_chunk=None):
    """Returns (jitted_fn, kwargs-of-ShapeDtypeStructs) for lower()."""
    import dataclasses as _dc
    cfg = ARCHS[arch]
    if moe_chunk is not None and cfg.n_experts:
        cfg = _dc.replace(cfg, moe_dispatch_chunk=moe_chunk or None)
    model = Model(cfg)
    cell = SHAPES[shape]
    specs = input_specs(cfg, shape)
    aparams = steps_mod.abstract_params(model)

    if cell.kind == "train":
        # per-device microbatch = global/(dp·microbatches); 8 keeps ~2 seqs
        # of activations live on v5e (16 GB HBM) — see §Perf iteration log
        dp = mesh.devices.size // mesh.shape["model"]
        mb = min(microbatches, max(1, cell.global_batch // dp))
        step, sh = steps_mod.make_train_step(
            model, mesh, optimizer=optimizer, remat=remat,
            grad_compression="bf16", microbatches=mb)
        ap = _with_sh(aparams, sh["params"])
        if optimizer == "abo_zo":
            astate = jax.eval_shape(
                lambda: abo_zo_mod.init_state(abo_zo_mod.ABOZOConfig()))
            astate = _with_sh(astate, sh["opt_state"])
            args = (ap, astate, _with_sh(specs, sh["batch"]),
                    jax.ShapeDtypeStruct((2,), jnp.uint32))
        else:
            from repro.optim import adamw as adamw_mod
            astate = jax.eval_shape(adamw_mod.init_state, aparams)
            astate = _with_sh(astate, sh["opt_state"])
            args = (ap, astate, _with_sh(specs, sh["batch"]))
        return step, args

    if cell.kind == "prefill":
        step, sh = steps_mod.make_prefill_step(model, mesh)
        return step, (_with_sh(aparams, sh["params"]),
                      _with_sh(specs, sh["batch"]))

    # decode
    step, sh = steps_mod.make_decode_step(
        model, mesh, batch=cell.global_batch, max_len=cell.seq_len)
    acache = jax.eval_shape(
        lambda: model.init_cache(cell.global_batch, cell.seq_len,
                                 dtype=cfg.param_dtype))
    return step, (_with_sh(aparams, sh["params"]),
                  _with_sh({"tokens": specs["tokens"]},
                           {"tokens": sh["tokens"]})["tokens"],
                  _with_sh(acache, sh["cache"]),
                  jax.ShapeDtypeStruct((), jnp.int32))


def _with_sh(avals, shardings):
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        avals, shardings)


def build_griewank_cell(mesh, n: int = 1_000_000_000):
    """The paper's own workload on the production mesh (one ABO pass)."""
    from repro.core.sharded import make_sharded_abo, input_specs as gspecs
    from repro.objectives import GRIEWANK
    step, x_sh, a_sh, n_pad = make_sharded_abo(GRIEWANK, n, mesh)
    sp = gspecs(GRIEWANK, n, mesh)
    args = (jax.ShapeDtypeStruct(sp["x"].shape, sp["x"].dtype, sharding=x_sh),
            jax.ShapeDtypeStruct(sp["aggs"].shape, sp["aggs"].dtype,
                                 sharding=a_sh),
            jax.ShapeDtypeStruct((), jnp.int32))
    return step, args


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------
def run_cell(arch: str, shape: str, *, multi_pod: bool, optimizer="adamw",
             out_dir: pathlib.Path | None = None, verbose=True,
             microbatches: int = 8, remat=True, moe_chunk=None, tag=""):
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    if arch == "griewank_1b":
        fn, args = build_griewank_cell(mesh)
    else:
        fn, args = build_cell(arch, shape, mesh, optimizer,
                              microbatches=microbatches, remat=remat,
                              moe_chunk=moe_chunk)
    with mesh:
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        coll = collective_bytes(compiled.as_text())

    n_dev = mesh.devices.size
    rec = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev,
        "optimizer": optimizer,
        "flops": cost.get("flops", 0.0),
        "bytes_accessed": cost.get("bytes accessed", 0.0),
        "collectives": coll,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0)
                           + getattr(mem, "output_size_in_bytes", 0)
                           + getattr(mem, "temp_size_in_bytes", 0)),
        },
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
    }
    if verbose:
        print(f"[dryrun] {arch} × {shape} × {rec['mesh']} "
              f"({optimizer}): OK "
              f"flops={rec['flops']:.3e} "
              f"coll={coll['total_bytes']:.3e}B "
              f"temp={rec['memory']['temp_bytes']/2**30:.2f}GiB "
              f"(lower {t_lower:.1f}s compile {t_compile:.1f}s)",
              flush=True)
        print("  memory_analysis:", rec["memory"], flush=True)
    if out_dir is not None:
        out_dir.mkdir(parents=True, exist_ok=True)
        fname = f"{arch}__{shape}__{rec['mesh']}__{optimizer}{tag}"
        (out_dir / f"{fname}.json").write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "abo_zo"])
    ap.add_argument("--all", action="store_true",
                    help="run the full arch × shape matrix")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()
    out_dir = pathlib.Path(args.out)

    meshes = {"single": [False], "multi": [True], "both": [False, True]}
    cells = []
    if args.all:
        for arch, cfg in ARCHS.items():
            for shape in supported_shapes(cfg):
                cells.append((arch, shape))
        cells.append(("griewank_1b", "abo_pass"))
    else:
        assert args.arch, "--arch required without --all"
        shapes = [args.shape] if args.shape else (
            supported_shapes(ARCHS[args.arch])
            if args.arch in ARCHS else ["abo_pass"])
        cells = [(args.arch, s) for s in shapes]

    failures = []
    for arch, shape in cells:
        for mp in meshes[args.mesh]:
            try:
                run_cell(arch, shape, multi_pod=mp,
                         optimizer=args.optimizer, out_dir=out_dir)
            except Exception as e:  # noqa: BLE001 — report, keep going
                failures.append((arch, shape, mp, repr(e)[:300]))
                print(f"[dryrun] FAIL {arch} × {shape} multi_pod={mp}: "
                      f"{e!r}"[:400], flush=True)
    if failures:
        print(f"\n{len(failures)} FAILURES:", flush=True)
        for f in failures:
            print("  ", f, flush=True)
        sys.exit(1)
    print("\nALL CELLS PASSED", flush=True)


if __name__ == "__main__":
    main()
