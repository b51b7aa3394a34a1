"""Host time per window of bids: the harness's span around each program
call, less the device's busy time (mean over the chips), per call."""
from bench import trace


def read(r):
    calls = r.window.spans.get("mesh.call")
    if not calls or r.trace is None:
        return None
    return (sum(calls) - trace.busy_s(r.trace)) / len(calls) * 1e3
