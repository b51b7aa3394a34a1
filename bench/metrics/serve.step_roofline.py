"""Bandwidth share of the decode program: the bytes the window's steps
must move (the configuration's ``step_bytes`` of each step's per-slot
contexts: the weights once, the K/V of each occupied slot's valid
positions) over the chip's busy time, as a share of its HBM bandwidth."""
from bench import trace


def read(r):
    if r.trace is None or not r.trace.devices:
        return None
    busy = trace.busy_s(r.trace)
    need = r.window.counts.get("step_bytes")
    if not need or busy <= 0:
        return None
    return need / busy / r.peaks["hbm_bytes_per_s"] * 100
