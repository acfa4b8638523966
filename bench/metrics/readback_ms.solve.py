"""Milliseconds the engine spent copying finished jobs' outputs to the
host in the traced window: the summed ``engine.readback`` spans, which
open once the device has finished (``engine.device_wait``)
(``enginetrace.py``)."""
from enginetrace import read as _read


def read(record):
    return _read(record, "readback_ms")
