"""The one traffic generator: a mix's parameters and a seed -> the jobs.

A traffic mix is a JSON file of parameters, ``bench/traffic/<name>.json``:

- ``loop``: ``"closed"``, ``clients`` callers, each sending its next job
  when the last one's reply is in (the only loop a cell runs yet);
- ``mix``: job templates, each with a ``share`` and the job fields it
  fixes (``objective``, ``samples_per_pass``, ...); fields it leaves out
  come from the configuration's ``job`` block;
- ``n``: the job sizes, either ``{"values": [...]}`` or
  ``{"log_points": [lo, hi, k]}`` (k log-spaced sizes, rounded); left
  out, the configuration's own ``n``;
- ``warmup``: the ``seed`` and number of ``jobs`` set-up runs before the
  window;
- ``trace``: the number of the window's first ``jobs`` a traced run
  traces;
- ``check``: the check's sample, ``jobs`` stream indices drawn from the
  run's seed before the window (see :func:`checked`).

Every seed gets the same work in another order: templates and sizes
come in decks that hold each by its share, and the seed only shuffles
each deck. Job seeds, which pick each job's start, are drawn from the
run's seed.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
JOB_SEED_MAX = 2 ** 31 - 1       # job seeds fit 31 bits: one PRNG key
_CHECK_STREAM = 2 ** 32 - 1      # the sample's draw, apart from the decks'


def load(name: str) -> dict:
    with open(HERE / "traffic" / f"{name}.json") as fh:
        return json.load(fh)


def sizes(spec: dict | None, job_defaults: dict) -> list[int]:
    """The distinct job sizes a mix draws from (the configuration's own
    ``n`` where the mix names none)."""
    if spec is None:
        return [int(job_defaults["n"])]
    if "values" in spec:
        return [int(v) for v in spec["values"]]
    lo, hi, k = spec["log_points"]
    return sorted({int(round(v)) for v in np.geomspace(lo, hi, int(k))})


def _spread(count: int, weights: list[float]) -> np.ndarray:
    """Indices 0..len(weights)-1, each ``weight`` share of ``count``
    (largest remainders round up), unshuffled."""
    w = np.asarray(weights, np.float64)
    exact = count * w / w.sum()
    base = np.floor(exact).astype(int)
    rest = count - base.sum()
    base[np.argsort(base - exact, kind="stable")[:rest]] += 1
    return np.repeat(np.arange(len(w)), base)


def job_fields(traffic: dict, job_defaults: dict) -> list[dict]:
    """Each template of the mix completed from the configuration."""
    return [{**job_defaults, **{k: v for k, v in t.items() if k != "share"}}
            for t in traffic["mix"]]


class ClosedStream:
    """The closed loop's jobs, in the order the client takes them.
    Templates, sizes and job seeds come in seed-shuffled decks, so any
    run of whole decks holds each template and size exactly by its
    share."""

    def __init__(self, traffic: dict, job_defaults: dict, seed: int):
        self.templates = job_fields(traffic, job_defaults)
        self.n_all = sizes(traffic.get("n"), job_defaults)
        self.shares = [m["share"] for m in traffic["mix"]]
        self.seed = seed
        self.deck_len = 64 * len(self.n_all) * len(self.templates)
        self._next = 0
        self._deck = (-1, None)

    def job(self, k: int) -> dict:
        """Job ``k`` of the stream (the same for the same seed)."""
        d, i = divmod(k, self.deck_len)
        if self._deck[0] != d:
            rng = np.random.default_rng([self.seed, d])
            self._deck = (d, (
                rng.permutation(_spread(self.deck_len, self.shares)),
                rng.permutation(_spread(self.deck_len,
                                        [1.0] * len(self.n_all))),
                rng.integers(0, JOB_SEED_MAX, self.deck_len)))
        t_pick, n_pick, job_seeds = self._deck[1]
        return {**self.templates[t_pick[i]], "n": self.n_all[n_pick[i]],
                "seed": int(job_seeds[i]), "index": k}

    def next(self) -> dict:
        """The next job of the stream."""
        self._next += 1
        return self.job(self._next - 1)


def checked(traffic: dict, seed: int) -> list[int]:
    """The stream indices a run with this seed checks, drawn from the
    seed before the window opens: ``jobs`` of the first ``among``, which
    a run of the cell's full length completes. A shorter run checks
    those of them it completed, or else its first job."""
    want = traffic["check"]
    rng = np.random.default_rng([seed, _CHECK_STREAM])
    return sorted(int(k) for k in rng.choice(want["among"], want["jobs"],
                                             replace=False))
