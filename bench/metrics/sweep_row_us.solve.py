"""Device time of the fused step per block row swept, in microseconds:
the ``fused_step`` modules' time on the device over the traced window,
over the sum of ``passes`` x ``swept_rows`` of the engine's
``fused_sweep`` spans there (``enginetrace.py``)."""
from enginetrace import read as _read


def read(record):
    return _read(record, "sweep_row_us")
