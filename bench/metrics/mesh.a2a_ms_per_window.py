"""Device time of the collectives (all-to-all, psum) per window of bids,
mean over the chips."""
from bench import trace


def read(r):
    calls = r.window.counts.get("calls")
    if not calls or r.trace is None:
        return None
    sec = trace.op_seconds(r.trace, trace.COLLECTIVE.pattern)
    if not sec or sum(sec) <= 0:
        return None
    return sum(sec) / len(sec) / calls * 1e3
