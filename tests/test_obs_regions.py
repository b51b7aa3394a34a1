"""Program regions (``obs.region``): spans that record only while JAX's
profiler traces, into the calling thread's lane of the process-wide
record, with their parent and sequence id, and with a ``TraceAnnotation``
twin of the same name in the profiler's own trace.

The pins:

* off, a region records nothing and allocates nothing in obs.py, and a
  threads run binds no lanes;
* on, regions nest under the span open on their lane (a farm worker's
  ``svc``, an outer region), every vertex records unsampled, and each
  region's twin lands in the ``.xplane.pb`` under the same name;
* the keyed shuffle's mesh program times its whole round trip: its
  ``call`` span holds the fetch and the unpack, its ``compile`` span the
  program's first run, and ``last_trace`` is read when asked for.
"""
import tracemalloc
from pathlib import Path

import pytest

from repro.core import Farm, Pipeline, Stage, Tracer, lower, reduce_by_key
from repro.core import obs

jax = pytest.importorskip("jax")


def _inner(x):
    with obs.region("test.inner"):
        return x + 1


def _key(x):
    return x


@pytest.fixture
def profiler(tmp_path):
    """JAX's profiler on for the test's body (CPU host tracer)."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        yield tmp_path
    finally:
        jax.profiler.stop_trace()


def _events(name, lanes=None):
    lanes = obs.process_tracer().trace().lanes if lanes is None else lanes
    return [(vt, e) for vt in lanes for e in vt.events if e[0] == name]


def _twins(logdir):
    from jax.profiler import ProfileData

    pb = sorted(Path(logdir).rglob("*.xplane.pb"))[-1]
    names = {}
    for plane in ProfileData.from_file(str(pb)).planes:
        for line in plane.lines:
            for e in line.events:
                names[e.name] = names.get(e.name, 0) + 1
    return names


def test_regions_off_record_nothing_and_allocate_nothing():
    assert not obs.profiling()
    prog = lower(Pipeline(Farm(_inner, 2), Stage(_inner)), "threads")
    prog(range(40))                   # warm the lowering
    before = sum(len(vt.events) for vt in obs.process_tracer().trace().lanes)
    lanes = len(obs.process_tracer().trace().lanes)
    tracemalloc.start()
    try:
        for i in range(1000):
            with obs.region("test.off", seq=i):
                pass
        assert sorted(prog(range(40))) == [x + 2 for x in range(40)]
        # what a region hands out is alive inside its body: snapshot there
        with obs.region("test.outer"):
            with obs.region("test.inner", seq=1):
                snaps = [tracemalloc.take_snapshot()]
        snaps.append(tracemalloc.take_snapshot())
    finally:
        tracemalloc.stop()
    for snap in snaps:
        total = sum(s.size for s in snap.filter_traces(
            [tracemalloc.Filter(True, obs.__file__)]).statistics("filename"))
        assert total == 0, f"regions off allocated {total}B in obs.py"
    after = obs.process_tracer().trace().lanes
    assert len(after) == lanes
    assert sum(len(vt.events) for vt in after) == before


def test_regions_nest_under_the_open_span_and_reach_the_profiler(tmp_path):
    prog = lower(Pipeline(Farm(_inner, 2), Stage(_key)), "threads")
    n_lanes = len(obs.process_tracer().trace().lanes)
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert sorted(prog(range(30))) == list(range(1, 31))
        with obs.region("test.outer", seq=7) as outer:
            with obs.region("test.middle"):
                pass
    finally:
        jax.profiler.stop_trace()
    lanes = obs.process_tracer().trace().lanes[n_lanes:]
    # Graph.run bound every vertex to the record, unsampled: one svc span
    # an item on the workers, and each region inside its worker's svc,
    # carrying that item's farm tag
    inner = _events("test.inner", lanes)
    assert len(inner) == 30
    for vt, (_, t0, t1, args) in inner:
        assert vt.name.startswith("ff-worker")
        parents = [e for e in vt.events if e[0] == "svc" and e[1] ==
                   args["parent"]]
        assert len(parents) == 1 and parents[0][1] <= t0 < t1 <= parents[0][2]
    assert sorted(args["seq"] for _, (*_, args) in inner) == list(range(30))
    assert len(_events("svc", [vt for vt in lanes
                               if vt.name.startswith("ff-worker")])) == 30
    # a thread that is no vertex gets a lane of its own; nesting there too
    (vt, middle), = _events("test.middle")
    (vt2, outer_e), = _events("test.outer")
    assert vt is vt2 and vt.name == "MainThread"
    assert middle[3] == {"parent": outer.t0, "seq": None}
    assert outer_e[3] == {"parent": None, "seq": 7}
    assert not vt.opened
    twins = _twins(tmp_path)
    assert twins.get("test.inner") == 30
    assert twins.get("test.outer") == 1 and twins.get("test.middle") == 1


def test_region_names_leave_bench_to_the_harness(profiler):
    with pytest.raises(ValueError, match="bench"):
        obs.region("bench.sw.pair")


def test_lanes_keep_their_newest_events():
    vt = Tracer(sample=1, capacity=8).vertex("v")
    for i in range(20):
        vt.span("s", float(i + 1), float(i + 2))
    assert len(vt.events) <= 8 and vt.dropped == 20 - len(vt.events)
    assert vt.events[-1][1] == 20.0
    assert [e[1] for e in vt.events] == sorted(e[1] for e in vt.events)


def test_mesh_call_span_times_the_round_trip(profiler):
    prog = lower(reduce_by_key(_key, "count", nkeys=16), "mesh",
                 trace=True, metrics=True)
    for _ in range(3):
        assert dict(prog(list(range(16)) * 2)) == {k: 2 for k in range(16)}
    lane = prog.last_trace.lane("mesh-program")
    calls = [e for e in lane.events if e[0] == "call"]
    (compile_,) = [e for e in lane.events if e[0] == "compile"]
    assert len(calls) == 3
    assert prog.metrics.counter("mesh.compiles").value == 1
    assert prog.last_report.hists["mesh.call_us"]["count"] == 3
    phases = {name: [e for _, e in _events(name) if e[1] >= calls[0][1]]
              for name in ("mesh.call", "mesh.pack", "mesh.dispatch",
                           "mesh.compile", "mesh.fetch", "mesh.unpack")}
    assert [len(v) for v in phases.values()] == [3, 3, 3, 1, 3, 3]
    (first,) = phases["mesh.compile"]
    assert compile_[1] <= first[1] and first[2] <= compile_[2]
    for seq, call in enumerate(calls):
        region = phases["mesh.call"][seq]
        assert call[1] <= region[1] and region[2] <= call[2]
        for name in ("mesh.pack", "mesh.dispatch", "mesh.fetch",
                     "mesh.unpack"):
            e = phases[name][seq]
            assert e[3]["parent"] == region[1]
            assert e[3]["seq"] == region[3]["seq"]
            assert call[1] <= e[1] <= e[2] <= call[2]
    # last_trace is the lane as it stands, read when asked for
    prog(list(range(16)))
    assert len([e for e in prog.last_trace.events() if e[0] == "call"]) == 4
