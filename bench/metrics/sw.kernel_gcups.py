"""Real cells (query length times real subject residues, no padding) of
every pair scored while the profiler ran, over the summed device time of
the Smith-Waterman kernel's ops: the Pallas custom call, which the
compiler names after ``sw_pallas``, the jitted function around it (the
call carries no name of its own)."""
from bench import trace

KERNEL = r"%sw_pallas[.\d]* = "


def read(r):
    if r.trace is None:
        return None
    sec = trace.op_seconds(r.trace, KERNEL)
    if not sec or sum(sec) <= 0:
        return None
    return r.window.counts["scored_cells"] / sum(sec) / 1e9
