"""The fused sweep's share of the HBM roofline: the bytes the traced
solves' sweeps need (8 per coordinate per pass, counted by
``readers.sweep_bytes`` independently of any layout) over the chip's
peak bandwidth times the device's busy time in the traced window. The
trace names every engine executable alike (``run``), so the busy time
stands for the fused step's; placement and finalize run once per
5-pass solve and are inside it."""
from readers import sweep_bytes


def read(record):
    dt, pk = record["device_trace"], record["peaks"]
    work = sweep_bytes(record)
    if not dt or not pk or not work or dt["busy_s"] <= 0:
        return None
    return 100.0 * work / (pk["hbm_bytes_per_s"] * dt["busy_s"])
