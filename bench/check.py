"""The comparison that decides ``correct``.

Each sampled answer (a job's ``fun``, per-pass ``history`` and ``x``, as
the timed path delivered them) is set beside the plain reference's solve
of the same job (``reference.solve``), and four gaps are read, each the
worst over the sample:

- ``fun_gap``: |fun - fun_ref| / max(|fun_ref|, 1);
- ``hist_gap``: the same, worst over the passes of the history;
- ``x_gap``: the share of coordinates whose value differs from the
  reference's;
- ``self_gap``: |fun - f(x)| / max(|f(x)|, 1), where f(x) is the
  objective of the delivered x summed in float64 on the host: does the
  reported objective belong to the reported solution?

A configuration's ``limits`` name the gaps it holds and the limit of
each; ``correct`` needs every held gap within its limit, every job due
in the window delivered, and no answer refused. ``PERF.md`` gives the
readings each limit was set from.
"""
from __future__ import annotations

import numpy as np

import reference

GAPS = ("fun_gap", "hist_gap", "x_gap", "self_gap")


def gaps(objective: str, got: dict, ref: dict) -> dict:
    """The four gaps of one answer against its reference solve."""
    x = np.asarray(got["x"], np.float32)
    rx = np.asarray(ref["x"], np.float32)
    if x.shape != rx.shape:
        return {k: float("inf") for k in GAPS}
    hist = np.asarray(got["history"], np.float64)
    rhist = np.asarray(ref["history"], np.float64)
    f64 = reference.value64(objective, x)
    out = {
        "fun_gap": abs(got["fun"] - ref["fun"]) / max(abs(ref["fun"]), 1.0),
        "hist_gap": float(np.max(np.abs(hist - rhist)
                                 / np.maximum(np.abs(rhist), 1.0)))
        if hist.shape == rhist.shape else float("inf"),
        "x_gap": float(np.count_nonzero(x != rx)) / max(x.size, 1),
        "self_gap": abs(got["fun"] - f64) / max(abs(f64), 1.0),
    }
    # a NaN compares false with everything: read it as the worst gap
    return {k: v if np.isfinite(v) else float("inf") for k, v in out.items()}


def reference_for(job: dict, config: dict, pad_to: int | None = None,
                  lower: bool = False) -> dict:
    """The reference solve of one job at the configuration's precision,
    or, with ``lower``, at the control's (``config["control"]``)."""
    import jax.numpy as jnp
    prec = config["control" if lower else "precision"]
    return reference.solve(
        job["objective"], int(job["n"]), m=int(job["samples_per_pass"]),
        n_passes=int(job["n_passes"]), block=int(job["block_size"]),
        seed=int(job["seed"]), x_dtype=jnp.dtype(prec["x"]),
        agg_dtype=jnp.dtype(prec["aggregates"]), pad_to=pad_to)


def worst(readings: list[dict]) -> dict:
    """Each gap's worst reading over the sample."""
    return {k: max((r[k] for r in readings), default=0.0) for k in GAPS}


def verdict(worst_gaps: dict, limits: dict, failed: int) -> tuple[bool, dict]:
    """``correct`` and the held numbers, each beside its limit."""
    held = {k: {"value": worst_gaps[k], "limit": limits[k]} for k in limits}
    held["failed_jobs"] = {"value": failed, "limit": 0}
    ok = all(v["value"] <= v["limit"] for v in held.values())
    return ok, held
