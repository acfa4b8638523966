"""The chips' published peaks, from ``peaks.json``, keyed by the
``device_kind`` JAX reports. A kind missing from the table is an error:
a roofline share against a guessed peak would be a guess."""
from __future__ import annotations

import json
import pathlib

TABLE = pathlib.Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(LookupError):
    """The device kind has no row in the peaks table."""


def peaks(device_kind: str, table: pathlib.Path = TABLE) -> dict:
    with open(table) as fh:
        rows = json.load(fh)
    if device_kind not in rows:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {table.name} "
            f"(known: {sorted(rows)})")
    return rows[device_kind]
