"""Share of the traced window in which no op ran on the chip, while the
engine serves its backlog: the time the decode loop's host work and its
per-token sync leave the device waiting."""
from bench import trace


def read(r):
    idle = trace.idle_share(r.trace) if r.trace is not None else None
    return None if idle is None else idle * 100
