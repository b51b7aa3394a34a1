"""Median host time of one kernel dispatch in the search: the program's
``sw.dispatch`` region in ``ops.smith_waterman`` (pad the subject, move
the arguments to the device, dispatch the jitted kernel; the wait for the
score is not in it), over the pairs dispatched inside the window."""
import statistics

from bench import spans


def read(r):
    got = spans.in_window(r, "sw.dispatch")
    return statistics.median(t1 - t0 for _, t0, t1, _ in got) * 1e6 \
        if got else None
