"""The readers of the program's own spans (``bench/spans.py`` and the
metrics that use it): on spans laid by hand over a trace recorded on the
chip, each gives the answer the layout fixes; a second traced window in
one process reads only its own spans; and a traced shuffle on four
virtual CPU devices reports its pack and unpack time."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src")) if p not in sys.path]

from bench import harness, spans, trace as tr  # noqa: E402
from repro.core import Tracer, obs  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
NEW = ["sw.dispatch_us", "sw.idle_dispatch", "sw.idle_runtime",
       "mesh.pack_ms_per_window", "mesh.unpack_ms_per_window"]
T0 = 100.0                          # host seconds at the window's start


def reader(name):
    return harness.load_module(ROOT / "bench" / "metrics" / f"{name}.py").read


@pytest.fixture
def recorded():
    """The recorded search trace, its window opening at host time T0."""
    t = tr.from_json(json.loads((DATA / "sw-q1000.json").read_text()))
    lo, hi = t.window
    window = harness.Window(T0, T0 + (hi - lo) / 1e9, {}, attempted=0)
    return harness.Reading(harness.load_cell("sw-q1000"), window, t, {})


@pytest.fixture
def record(monkeypatch):
    """A process-wide record of hand-laid lanes, in place of the program's."""
    rec = Tracer(sample=1, capacity=1 << 10)
    monkeypatch.setattr(obs, "process_tracer", lambda: rec)
    return rec


def host(r, ns):
    """Host seconds of profiler-clock ``ns`` in reading ``r``."""
    return T0 + (ns - r.trace.window[0]) / 1e9


def widest_gap(r):
    """The longest idle stretch of the recorded chip, in profiler ns."""
    lo, hi = r.trace.window
    (ops,) = r.trace.devices.values()
    gaps = spans.outside(tr.union(ops, lo, hi), lo, hi)
    return max(gaps, key=lambda g: g[1] - g[0])


def test_offset_puts_the_window_on_the_profilers_clock(recorded):
    assert spans.offset_ns(recorded) == recorded.trace.window[0] - T0 * 1e9
    recorded.trace = None
    assert spans.offset_ns(recorded) is None


def test_idle_while_dispatching_is_the_gap_a_dispatch_covers(recorded, record):
    a, b = widest_gap(recorded)
    lo, hi = recorded.trace.window
    worker = record.vertex("ff-worker-0")
    worker.span("sw.dispatch", host(recorded, a), host(recorded, b))
    # a dispatch while the chip computes adds nothing, nor one before the
    # window (a first window's, read again in a second)
    busy = tr.union(next(iter(recorded.trace.devices.values())), lo, hi)[0]
    worker.span("sw.dispatch", host(recorded, busy[0]),
                host(recorded, busy[1]))
    worker.span("sw.dispatch", T0 - 2.0, T0 - 1.0)
    want = (b - a) / (hi - lo) * 100
    assert reader("sw.idle_dispatch")(recorded) == pytest.approx(want)
    assert want > 0


def test_idle_in_the_runtime_is_the_gap_no_worker_serves(recorded, record):
    a, b = widest_gap(recorded)
    lo, hi = recorded.trace.window
    for i in range(2):
        worker = record.vertex(f"ff-worker-{i}")
        worker.span("sw.dispatch", host(recorded, lo) + 1e-6,
                    host(recorded, lo) + 2e-6)
        # before the gap on one worker, after it on the other: between,
        # no worker is in its svc
        worker.span("svc", *((host(recorded, lo) - 1.0, host(recorded, a))
                             if i == 0 else
                             (host(recorded, b), host(recorded, hi) + 1.0)))
    # a lane that never dispatches (the collector) is no worker
    record.vertex("ff-collector").span("svc", host(recorded, a),
                                       host(recorded, b))
    want = (b - a) / (hi - lo) * 100
    assert reader("sw.idle_runtime")(recorded) == pytest.approx(want)
    assert reader("sw.idle_runtime")(recorded) <= \
        reader("device_idle.sw")(recorded)


def test_dispatch_time_is_the_median_inside_the_window(recorded, record):
    lane = record.vertex("ff-worker-0")
    for start, us in ((0.1, 100), (0.2, 400), (0.3, 200)):
        lane.span("sw.dispatch", T0 + start * 1e-3, T0 + start * 1e-3 + us
                  * 1e-6)
    lane.span("sw.dispatch", T0 - 1.0, T0 - 0.9)             # before
    assert reader("sw.dispatch_us")(recorded) == pytest.approx(200)


@pytest.mark.parametrize("name", ["mesh.pack", "mesh.unpack"])
def test_mesh_phase_is_the_mean_a_call(recorded, record, name):
    lane = record.vertex("MainThread")
    for i, ms in enumerate((12.0, 14.0, 16.0)):
        lane.span(name, T0 + i * 0.02, T0 + i * 0.02 + ms * 1e-3,
                  {"parent": None, "seq": i})
    lane.span(name, T0 - 1.0, T0 - 0.5)                      # before
    lane.span("mesh.fetch", T0, T0 + 0.01)                    # another phase
    got = reader(f"{name}_ms_per_window")(recorded)
    assert got == pytest.approx(14.0)


@pytest.mark.parametrize("name", NEW)
def test_no_trace_or_no_record_reads_nothing(recorded, record, name,
                                             monkeypatch):
    lane = record.vertex("ff-worker-0")
    for phase in ("sw.dispatch", "svc", "mesh.pack", "mesh.unpack"):
        lane.span(phase, T0 + 0.001, T0 + 0.002)
    assert reader(name)(recorded) is not None
    untraced = harness.Reading(recorded.cell, recorded.window, None, {})
    assert reader(name)(untraced) is None
    # a program that predates the record (the parent of this reader)
    monkeypatch.delattr(obs, "process_tracer")
    assert reader(name)(recorded) is None


def test_a_second_window_reads_only_its_own_spans(tmp_path):
    jax = pytest.importorskip("jax")

    def window(i, calls):
        jax.profiler.start_trace(str(tmp_path / str(i)))
        t0 = time.perf_counter()
        try:
            for _ in range(calls):
                with obs.region("mesh.pack"):
                    time.sleep(0.001)
        finally:
            t1 = time.perf_counter()
            jax.profiler.stop_trace()
        return harness.Reading(None, harness.Window(t0, t1, {}, 0),
                               tr.Trace({}, []), {})

    first, second = window(0, 3), window(1, 5)
    assert len(spans.in_window(first, "mesh.pack")) == 3
    assert len(spans.in_window(second, "mesh.pack")) == 5


SHUFFLE = r"""
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax
from bench import harness, spans
cell = harness.load_cell("nexmark-q5count-4chip")
cell.config["generator"]["first_event_rate"] = 500      # 4600-bid windows
cell.config["query"]["nkeys"] = 512
r = harness.run_cell(cell, seed=2**31 + 9, seconds=0.5, trace=True,
                     t_start=time.perf_counter(), devices=jax.devices()[:4],
                     peaks={}, log=lambda m: None)
lanes = spans.lanes()
total = {}
for name in ("mesh.call", "mesh.pack", "mesh.dispatch", "mesh.fetch",
             "mesh.unpack"):
    total[name] = sum(t1 - t0 for _, t0, t1, _ in spans.named(name))
print(json.dumps({"correct": r["correct"], "metrics": r["metrics"],
                  "total": total,
                  "dropped": sum(vt.dropped for vt in lanes)}))
"""


def test_traced_shuffle_reports_pack_and_unpack():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", SHUFFLE, str(ROOT)],
                         capture_output=True, text=True, timeout=600,
                         env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["correct"] and got["dropped"] == 0
    m = {k: v["value"] for k, v in got["metrics"].items()}
    assert m["mesh.pack_ms_per_window"] > 0
    assert m["mesh.unpack_ms_per_window"] > 0
    assert m["mesh.pack_ms_per_window"] + m["mesh.unpack_ms_per_window"] \
        <= m["mesh.host_ms_per_window"]
    t = got["total"]
    phases = t["mesh.pack"] + t["mesh.dispatch"] + t["mesh.fetch"] + \
        t["mesh.unpack"]
    assert 0.9 * t["mesh.call"] <= phases <= t["mesh.call"]
