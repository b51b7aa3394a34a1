"""Host time of one engine step outside the wait for its tokens: the
program's ``serve.step`` region (admission, building the batch, the jit
dispatch, the collector) less the ``serve.sync`` region inside it, mean
over the steps in the window."""
from bench import spans


def read(r):
    steps = spans.in_window(r, "serve.step")
    if not steps:
        return None
    synced = spans.in_window(r, "serve.sync")
    busy = sum(t1 - t0 for _, t0, t1, _ in steps) - \
        sum(t1 - t0 for _, t0, t1, _ in synced)
    return busy / len(steps) * 1e3
