"""From a profiler trace to the numbers the per-layer metrics read.

A run with ``--trace 1`` records its measured window with JAX's profiler
(Python tracer off).  :func:`load_dir` reduces the ``.xplane.pb`` to a
:class:`Trace`: for every TPU the events of its ``XLA Ops`` line, and the
harness's own host spans (``bench.*`` annotations), among them
``bench.window`` around the measured window.  The reductions below work
on that form only, so the tests check them on a small trace recorded on
the chip and kept as JSON.

- busy time: the union of a device's op intervals inside the window;
- self time of an op: its duration less that of the ops nested in it;
- kernel or collective time: the summed durations of the ops whose name
  matches, per device;
- idle gaps: the holes in the union, each put down to the innermost
  harness span that was open at its middle.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]          # (name, start_ns, duration_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(r"all-to-all|all-reduce|collective-permute|"
                        r"all-gather|reduce-scatter")
NO_SPAN = "(outside bench spans)"


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Event]]
    host: List[Event]

    @property
    def window(self) -> Tuple[int, int]:
        spans = [(s, s + d) for n, s, d in self.host if n == WINDOW_SPAN]
        if spans:
            return spans[0]
        evs = [e for ops in self.devices.values() for e in ops]
        return (min(s for _, s, _ in evs), max(s + d for _, s, d in evs))


@contextlib.contextmanager
def profiled(logdir: Optional[str]) -> Iterator[None]:
    """Profile the block into ``logdir``; a no-op for ``None``."""
    if logdir is None:
        yield
        return
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1            # the harness's own spans only
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def span(name: str):
    """A harness span in the profiler's trace (cheap when it is off)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def load_dir(logdir: str) -> Trace:
    files = sorted(Path(logdir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return from_xplane(files[-1])


def op_name(hlo: str) -> str:
    """An op's event name is its HLO text: keep the instruction and its
    operand shapes, drop layouts and attributes."""
    short = re.sub(r"\{[^{}]*\}", "", hlo)
    return short.split("), ")[0] + (")" if "), " in short else "")


def from_xplane(path: Path) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (op_name(e.name), int(e.start_ns), int(e.duration_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return Trace(devices, sorted(host, key=lambda e: e[1]))


def describe_dir(logdir: str, first: int = 8) -> list:
    """Every plane and line of the trace, with its event count and first
    event names: what a reader of a new kind of trace looks at first."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(sorted(Path(logdir).rglob(
        "*.xplane.pb"))[-1]))
    out = []
    for plane in data.planes:
        for line in plane.lines:
            evs = list(line.events)
            out.append([plane.name, line.name, len(evs),
                        [[e.name, int(e.start_ns), int(e.duration_ns)]
                         for e in evs[:first]]])
    return out


def to_json(t: Trace) -> dict:
    return {"devices": {k: [list(e) for e in v] for k, v in t.devices.items()},
            "host": [list(e) for e in t.host]}


def from_json(d: dict) -> Trace:
    return Trace({k: [tuple(e) for e in v] for k, v in d["devices"].items()},
                 [tuple(e) for e in d["host"]])


# -- reductions ----------------------------------------------------------------
def union(events: Sequence[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Merged busy intervals of ``events`` clipped to ``[lo, hi]``."""
    out: List[Tuple[int, int]] = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        a, b = max(s, lo), min(s + d, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def window_s(t: Trace) -> float:
    lo, hi = t.window
    return (hi - lo) / 1e9


def busy_s(t: Trace) -> float:
    """Seconds in which an op ran, mean over the devices traced."""
    lo, hi = t.window
    per = [sum(b - a for a, b in union(ops, lo, hi))
           for ops in t.devices.values()]
    return sum(per) / len(per) / 1e9 if per else 0.0


def idle_share(t: Trace) -> Optional[float]:
    w = window_s(t)
    if not t.devices or w <= 0:
        return None
    return 1.0 - busy_s(t) / w


def op_seconds(t: Trace, pattern: str) -> List[float]:
    """Summed duration of the ops whose name matches, per device."""
    rx = re.compile(pattern)
    lo, hi = t.window
    return [sum(min(s + d, hi) - max(s, lo) for n, s, d in ops
                if rx.search(n) and s + d > lo and s < hi) / 1e9
            for ops in t.devices.values()]


def self_seconds(ops: Sequence[Event]) -> Dict[str, float]:
    """Per op name, the time not covered by ops nested inside it."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[List] = []          # [name, end_ns, child_ns, dur_ns]
    for name, s, d in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            n, _, child, dur = stack.pop()
            out[n] += (dur - child) / 1e9
        if stack:
            stack[-1][2] += min(d, stack[-1][1] - s)
        stack.append([name, s + d, 0, d])
    for n, _, child, dur in stack:
        out[n] += (dur - child) / 1e9
    return out


def idle_gaps(t: Trace) -> Dict[str, float]:
    """Idle device seconds (mean over devices) by the harness span open at
    the middle of each gap."""
    lo, hi = t.window
    spans = [e for e in t.host if e[0] != WINDOW_SPAN]     # sorted by start
    starts = [e[1] for e in spans]
    out: Dict[str, float] = defaultdict(float)
    n = max(len(t.devices), 1)
    for ops in t.devices.values():
        edge = lo
        for a, b in union(ops, lo, hi) + [(hi, hi)]:
            if a > edge:
                out[_open_span(spans, starts, (edge + a) // 2)] += \
                    (a - edge) / 1e9 / n
            edge = max(edge, b)
    return dict(out)


def _open_span(spans: Sequence[Event], starts: Sequence[int], at: int,
               look_back: int = 64) -> str:
    """The shortest of the spans open at ``at``, among the ``look_back``
    that opened last before it."""
    i = bisect.bisect_right(starts, at)
    inner = [e for e in spans[max(0, i - look_back):i] if at < e[1] + e[2]]
    return min(inner, key=lambda e: e[2])[0] if inner else NO_SPAN


def breakdown(t: Trace, top: int = 10) -> dict:
    """The device ops that took most time, and idle time by host span."""
    ops: Dict[str, float] = defaultdict(float)
    lo, hi = t.window
    n = max(len(t.devices), 1)
    for evs in t.devices.values():
        inside = [e for e in evs if e[1] >= lo and e[1] + e[2] <= hi]
        for name, sec in self_seconds(inside).items():
            ops[name] += sec / n

    def ranked(d: Dict[str, float]) -> list:
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]

    return {"device_ops": ranked(ops), "idle_gaps": ranked(idle_gaps(t))}
