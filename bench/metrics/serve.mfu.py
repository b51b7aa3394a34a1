"""Model FLOP/s utilization of the whole step: the model FLOPs of the
window's decode steps (the configuration's ``step_flops`` of each step's
per-slot contexts) over the window's length on the host clock, as a share
of the chip's bf16 peak.  Read only where the chip was traced."""


def read(r):
    if r.trace is None or not r.trace.devices:
        return None
    flops = r.window.counts.get("model_flops")
    seconds = r.window.t1 - r.window.t0
    if not flops or seconds <= 0:
        return None
    return flops / seconds / r.peaks["bf16_flops"] * 100
