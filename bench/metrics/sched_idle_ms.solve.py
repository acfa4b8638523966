"""Milliseconds of the traced window in which the device ran no op while
the engine's scheduler worked on the host: idle time whose innermost
engine span is neither ``readback`` nor ``device_wait``
(``enginetrace.py``)."""
from enginetrace import read as _read


def read(record):
    return _read(record, "sched_idle_ms")
