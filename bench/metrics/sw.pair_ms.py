"""Median host time of one (query, subject) pair: the harness's span
around each ``align`` call of the farm, kernel dispatch and host sync
included."""
import statistics


def read(r):
    spans = r.window.spans.get("sw.pair")
    return statistics.median(spans) * 1e3 if spans else None
