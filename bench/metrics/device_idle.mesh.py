"""Share of the traced window in which no op ran on a chip, mean over the four."""
from bench import trace


def read(r):
    idle = trace.idle_share(r.trace) if r.trace is not None else None
    return None if idle is None else idle * 100
