"""The trace reductions, on hand-made events and on small traces recorded
on the chip (``bench/tests/data``)."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src")) if p not in sys.path]

from bench import harness, trace as tr  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def _trace(devices, host=()):
    return tr.Trace({f"/device:TPU:{i}": list(ops)
                     for i, ops in enumerate(devices)}, list(host))


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    t = _trace([[("a", 0, 10), ("b", 5, 10), ("c", 30, 10), ("d", 95, 20)]],
               [("bench.window", 0, 100)])
    # [0, 15) and [30, 40) and [95, 100) -> 30 ns of 100
    assert tr.busy_s(t) == pytest.approx(30e-9)
    assert tr.window_s(t) == pytest.approx(100e-9)
    assert tr.idle_share(t) == pytest.approx(0.7)


def test_busy_is_the_mean_over_devices():
    t = _trace([[("a", 0, 50)], [("a", 0, 10)]], [("bench.window", 0, 100)])
    assert tr.busy_s(t) == pytest.approx(30e-9)
    assert tr.op_seconds(t, "a") == pytest.approx([50e-9, 10e-9])


def test_self_time_leaves_out_nested_ops():
    out = tr.self_seconds([("while", 0, 100), ("dot", 10, 30),
                           ("add", 50, 20), ("copy", 200, 5)])
    assert out == pytest.approx({"while": 50e-9, "dot": 30e-9,
                                 "add": 20e-9, "copy": 5e-9})


def test_idle_gaps_go_to_the_innermost_open_span():
    t = _trace([[("k", 0, 10), ("k", 50, 10)]],
               [("bench.window", 0, 100), ("bench.call", 0, 100),
                ("bench.sync", 20, 20)])
    gaps = tr.idle_gaps(t)
    # [10, 50): middle 30 in bench.sync; [60, 100): middle 80 in bench.call
    assert gaps == pytest.approx({"bench.sync": 40e-9, "bench.call": 40e-9})
    assert tr.breakdown(t)["idle_gaps"][0][1] == pytest.approx(40e-9)


def test_collectives_are_found_by_name():
    names = ["all-to-all.3", "all-reduce.1", "all-reduce-start.2",
             "collective-permute-done", "fusion.4", "custom-call.7"]
    assert [bool(tr.COLLECTIVE.search(n)) for n in names] == \
        [True, True, True, True, False, False]


@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob("*.json")))
def test_recorded_trace_reduces(name):
    t = tr.from_json(json.loads((DATA / name).read_text()))
    assert t.devices, "a recorded trace has device planes"
    lo, hi = t.window
    assert hi > lo
    busy = tr.busy_s(t)
    assert 0 < busy <= tr.window_s(t)
    b = tr.breakdown(t)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    idle = sum(v for _, v in tr.idle_gaps(t).items())
    assert idle == pytest.approx(tr.window_s(t) - busy, rel=1e-6)


def _recorded(name):
    path = DATA / name
    if not path.exists():
        pytest.skip(f"no recorded trace {name}")
    return tr.from_json(json.loads(path.read_text()))


def test_recorded_search_trace_finds_the_kernel():
    t = _recorded("sw-q1000.json")
    kernel = harness.load_module(
        ROOT / "bench/metrics/sw.kernel_gcups.py").KERNEL
    (sec,) = tr.op_seconds(t, kernel)
    assert 0 < sec <= tr.busy_s(t)
    assert not any(tr.COLLECTIVE.search(n) for n, _, _ in
                   next(iter(t.devices.values())))


def test_recorded_shuffle_trace_finds_the_collectives():
    t = _recorded("nexmark-q5count-4chip.json")
    assert len(t.devices) == 4
    per_chip = tr.op_seconds(t, tr.COLLECTIVE.pattern)
    assert len(per_chip) == 4 and all(s > 0 for s in per_chip)
    assert all(s <= tr.window_s(t) for s in per_chip)
