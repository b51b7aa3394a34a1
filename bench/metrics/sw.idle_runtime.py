"""Share of the traced window in which the chip is idle and no farm
worker is inside its ``svc`` span (every worker is in ring or farm code,
or the stream has not reached it): the idle time the host runtime, not
the kernel's callers, leaves.  The workers are the graph vertices whose
lanes hold ``sw.dispatch`` regions inside the window.  Same window and
idle time as ``device_idle.sw``."""
from bench import spans


def read(r):
    off = spans.offset_ns(r)
    if off is None:
        return None
    lo, hi = r.trace.window
    workers = {id(lane): lane for lane, *_ in spans.named(
        "sw.dispatch", (lo - off) / 1e9, (hi - off) / 1e9)}
    if not workers:
        return None
    svc = spans.on_trace(r, spans.named("svc", among=workers.values()))
    return spans.idle_share_while(r, spans.outside(svc, lo, hi))
