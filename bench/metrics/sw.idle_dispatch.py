"""Share of the traced window in which the chip is idle while at least
one farm worker is inside ``sw.dispatch`` (pad, argument transfers, jit
dispatch): the idle time that one call per database block would take
away.  Same window and idle time as ``device_idle.sw``."""
from bench import spans


def read(r):
    if spans.offset_ns(r) is None:
        return None
    got = spans.named("sw.dispatch")
    return spans.idle_share_while(r, spans.on_trace(r, got)) if got else None
