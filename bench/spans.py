"""The program's own spans, for the per-layer readers that split host time.

Under JAX's profiler the program records ``repro.core.obs`` regions (the
search's ``sw.dispatch``, the shuffle's ``mesh.*``) and its graph
vertices' ``svc`` spans into ``obs``'s process-wide record, stamped on
``time.monotonic()``: on Linux the same CLOCK_MONOTONIC as the harness's
``perf_counter``, so a window's ``t0``/``t1`` bound them directly.  The
device trace runs on the profiler's clock; :func:`offset_ns` is the shift
between the two, read from the window itself: its ``Window.t0`` and the
``bench.window`` host span opened right after it.

A program without that record (one that predates it) has no spans here:
every function returns an empty list or None, and the readers report
nothing.
"""
from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence, Tuple

from bench import trace

Span = Tuple[object, float, float, dict]      # (lane, t0_s, t1_s, args)
Interval = Tuple[float, float]                # ns on the profiler's clock


def lanes() -> list:
    """The lanes of the program's process-wide record, or [] without one."""
    from repro.core import obs

    record = getattr(obs, "process_tracer", None)
    return record().trace().lanes if record is not None else []


def named(name: str, lo: float = -math.inf, hi: float = math.inf,
          among: Optional[Iterable] = None) -> List[Span]:
    """Every span called ``name`` that lies inside ``[lo, hi]`` (host
    seconds; default: all), from ``among`` lanes (default: all of them)."""
    out = []
    for lane in lanes() if among is None else among:
        for e in list(lane.events):
            if e[0] == name and e[2] is not None and lo <= e[1] and \
                    e[2] <= hi:
                out.append((lane, e[1], e[2], e[3] if len(e) > 3 else {}))
    return out


def in_window(r, name: str) -> List[Span]:
    """The spans called ``name`` inside the measured window, or [] where
    the run was not traced (regions record only under the profiler)."""
    if r.trace is None:
        return []
    return named(name, r.window.t0, r.window.t1)


def offset_ns(r) -> Optional[float]:
    """Profiler-clock ns minus host-clock ns, from the ``bench.window``
    span; None without a trace or without that span."""
    if r.trace is None:
        return None
    starts = [s for n, s, _ in r.trace.host if n == trace.WINDOW_SPAN]
    return starts[0] - r.window.t0 * 1e9 if starts else None


def on_trace(r, spans: Sequence[Span]) -> List[Interval]:
    """``spans`` moved onto the profiler's clock, merged and clipped to
    the trace's window (so spans of an earlier window fall away)."""
    off = offset_ns(r)
    lo, hi = r.trace.window
    return [(float(a), float(b)) for a, b in trace.union(
        [("", t0 * 1e9 + off, (t1 - t0) * 1e9) for _, t0, t1, _ in spans],
        lo, hi)]


def outside(intervals: Sequence[Interval], lo: float,
            hi: float) -> List[Interval]:
    """The complement of merged, sorted ``intervals`` in ``[lo, hi]``."""
    out, edge = [], lo
    for a, b in intervals:
        if a > edge:
            out.append((edge, a))
        edge = max(edge, b)
    if edge < hi:
        out.append((edge, hi))
    return out


def overlap(xs: Sequence[Interval], ys: Sequence[Interval]) -> float:
    """Length of the intersection of two merged, sorted interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_share_while(r, host: Sequence[Interval]) -> Optional[float]:
    """Percent of the trace's window in which a device is idle and the
    host is inside ``host`` (profiler-clock intervals), mean over the
    devices: the same window and idle time as ``device_idle.*``."""
    lo, hi = r.trace.window
    if not r.trace.devices or hi <= lo:
        return None
    per = [overlap(outside(trace.union(ops, lo, hi), lo, hi), host)
           for ops in r.trace.devices.values()]
    return sum(per) / len(per) / (hi - lo) * 100
