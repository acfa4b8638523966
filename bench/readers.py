"""Arithmetic the metric readers share (``bench/metrics/<name>.py``).

Each reader takes the run's record (see ``run.py``) and returns a number,
or None where the record holds nothing for it to read."""
from __future__ import annotations

BYTES_PER_COORD_PASS = 8      # one f32 read and one f32 write of x


def delivered(record: dict) -> list[dict]:
    return [j for j in record["jobs"] if j["delivered"]]


def idle_pct(record: dict):
    """Share of the traced window in which the device ran no op."""
    dt = record["device_trace"]
    if not dt or dt["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - dt["busy_s"] / dt["window_s"])


def sweep_bytes(record: dict) -> int:
    """The bytes the traced jobs' sweeps need, whatever sweeps them: each
    pass reads and writes every coordinate once."""
    return sum(BYTES_PER_COORD_PASS * int(j["n"]) * int(j["n_passes"])
               for j in record["jobs"] if j.get("traced"))
