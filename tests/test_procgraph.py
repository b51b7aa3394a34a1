"""Procs backend: the process-graph runtime over shared-memory rings.

Correctness of the ``lower(skel, "procs")`` contract (ordered output,
GO_ON filtering, emitter/collector nodes, feedback loops, all scheduling
policies), the FarmStats surface, failure semantics (a raising worker
fails the run instead of wedging it; a hung child hits the run timeout),
and hygiene (no leaked /dev/shm segments).  All nodes live in
``tests/_procs_nodes.py`` — spawned children re-import the defining
module, which must stay free of test-only deps.
"""
import os
from multiprocessing import shared_memory

import pytest

import _procs_nodes as N
from repro.core import (EOS, Farm, Feedback, LoweringError, Pipeline,
                        ProcAccelerator, ProcProgram, Source, Stage, lower)


@pytest.fixture(autouse=True)
def no_leaked_segments(monkeypatch):
    """Every test must unlink every segment it creates.  The check follows
    the segments this process creates (every ring, board and flag is made
    caller-side), not the host's whole /dev/shm, which other test
    processes share."""
    made = []
    real_init = shared_memory.SharedMemory.__init__

    def recording_init(self, name=None, create=False, size=0, **kw):
        real_init(self, name=name, create=create, size=size, **kw)
        if create:
            made.append(self.name)

    monkeypatch.setattr(shared_memory.SharedMemory, "__init__",
                        recording_init)
    yield
    leaked = [n for n in made
              if os.path.exists(os.path.join("/dev/shm", n.lstrip("/")))]
    assert not leaked, f"leaked SharedMemory segments: {leaked}"


def test_lower_returns_proc_program():
    prog = lower(Farm(N.f, 2), "procs")
    assert isinstance(prog, ProcProgram) and prog.backend == "procs"


def test_ordered_farm_matches_threads():
    xs = list(range(60))
    skel = Farm(N.f, 2, ordered=True)
    assert lower(skel, "procs")(xs) == lower(Farm(N.f, 2, ordered=True),
                                             "threads")(xs) \
        == [N.f(x) for x in xs]


def test_unordered_farm_is_a_permutation():
    xs = list(range(40))
    out = lower(Farm(N.f, 2), "procs")(xs)
    assert sorted(out) == sorted(N.f(x) for x in xs)


def test_pipeline_of_farm_and_stage():
    xs = list(range(30))
    skel = Pipeline(Farm(N.f, 2, ordered=True), Stage(N.g))
    assert lower(skel, "procs")(xs) == [N.g(N.f(x)) for x in xs]


def test_go_on_filters_and_terminates():
    out = lower(Farm(N.drop_odd, 2, ordered=True), "procs")(range(20))
    assert out == [x for x in range(20) if x % 2 == 0]


def test_emitter_and_collector_nodes_run_in_arbiters():
    skel = Farm(N.f, 2, ordered=True, emitter=N.AddTagEmitter(),
                collector=N.NegateCollector())
    assert lower(skel, "procs")(range(10)) \
        == [-N.f(x + 100) for x in range(10)]


def test_empty_stream():
    assert lower(Farm(N.f, 2, ordered=True), "procs")([]) == []


@pytest.mark.parametrize("policy", ["rr", "ondemand", "worksteal",
                                    "costmodel"])
def test_scheduling_policies_preserve_ordered_output(policy):
    xs = list(range(48))
    skel = Farm(N.f, 3, ordered=True, scheduling=policy)
    assert lower(skel, "procs")(xs) == [N.f(x) for x in xs]
    st = skel.stats
    assert st.tasks_emitted == st.tasks_collected == len(xs)
    assert sum(st.per_worker.values()) == len(xs)
    if policy == "costmodel":
        # the worker-side service EWMA crossed back over SPSC rings
        assert st.service_ewma


def test_feedback_loop_and_max_trips():
    xs = list(range(0, 30, 3))
    fb = Feedback(N.fb_step, N.fb_pred, nworkers=2)
    assert lower(fb, "procs")(xs) == [N.fb_ref(x) for x in xs]
    capped = Feedback(N.fb_step, N.fb_pred, nworkers=2, max_trips=1)
    assert lower(capped, "procs")(xs) == [N.fb_step(x) for x in xs]


def test_oversized_payloads_stream_through_the_farm():
    xs = list(range(12))
    out = lower(Farm(N.big_payload, 2, ordered=True),
                "procs", slot_size=64)(xs)
    assert out == [N.big_payload(x) for x in xs]


# -- failure semantics --------------------------------------------------------
def test_worker_exception_propagates_and_cleans_up():
    with pytest.raises(ValueError, match="boom at 7"):
        lower(Farm(N.boom_on_seven, 2, ordered=True), "procs")(range(20))


def test_hung_child_hits_the_run_timeout():
    with pytest.raises(TimeoutError, match="procs graph"):
        lower(Farm(N.sleepy, 2), "procs", timeout=3.0)(range(4))


def test_unpicklable_node_is_a_lowering_error():
    with pytest.raises(LoweringError, match="picklable"):
        lower(Farm(lambda x: x, 2), "procs")([1, 2, 3])


def test_speculative_is_threads_only():
    with pytest.raises(LoweringError, match="threads-only"):
        lower(Farm(N.f, 2, speculative=True), "procs")([1])


def test_accelerator_dead_worker_full_ring_fails_fast():
    """A worker that dies with its input ring full must surface its error
    through offload/eos/wait, never wedge the caller (the caller is the
    dispatch arbiter: nobody else can notice for it)."""
    acc = ProcAccelerator(Farm(N.boom_on_seven, 1, ordered=True),
                          capacity=16)
    with pytest.raises((ValueError, RuntimeError)):
        for x in range(500):
            acc.offload(x)
        acc.wait(30)


# -- the self-offloading accelerator ------------------------------------------
def test_accelerator_caller_side_farm():
    skel = Farm(N.sq, 2, ordered=True)
    acc = ProcAccelerator(skel)
    assert acc._farm is not None  # caller-side arbitration engaged
    for x in range(40):
        acc.offload(x)
    assert acc.wait(60) == [N.sq(x) for x in range(40)]
    st = skel.stats
    assert st.tasks_emitted == st.tasks_collected == 40
    assert sum(st.per_worker.values()) == 40


def test_accelerator_falls_back_to_graph_for_worksteal():
    acc = ProcAccelerator(Farm(N.sq, 2, ordered=True,
                               scheduling="worksteal"))
    assert acc._farm is None  # token-holding policy needs the arbiter
    for x in range(30):
        acc.offload(x)
    assert acc.wait(60) == [N.sq(x) for x in range(30)]


def test_accelerator_composition_uses_graph_path():
    acc = ProcAccelerator(Farm(N.f, 2, ordered=True) >> Stage(N.g))
    for x in range(20):
        acc.offload(x)
    assert acc.wait(60) == [N.g(N.f(x)) for x in range(20)]


def test_program_source_wrapping_matches_explicit_source():
    xs = list(range(15))
    prog = lower(Pipeline(Source(xs), Farm(N.f, 2, ordered=True)), "procs")
    g = prog.to_graph()
    assert g.run_and_wait(60) == [N.f(x) for x in xs]


# -- spawn-pool reuse + the new lowering options -----------------------------
def test_spawn_pool_reuses_processes():
    from repro.core import pool_stats
    want = [N.f(x) for x in range(30)]
    assert lower(Farm(N.f, 2, ordered=True), "procs")(list(range(30))) == want
    before = pool_stats()
    assert lower(Farm(N.f, 2, ordered=True), "procs")(list(range(30))) == want
    after = pool_stats()

    def total(stats, key):
        return sum(v[key] for v in stats.values())

    # the second run's 4 vertices (disp + merge + 2 workers) all came from
    # the pool: zero fresh spawns, at least 4 reuses
    assert total(after, "spawned") == total(before, "spawned")
    assert total(after, "reused") >= total(before, "reused") + 4


def test_pool_opt_out_direct_spawn_still_works():
    xs = list(range(20))
    prog = lower(Farm(N.f, 2, ordered=True), "procs", pool=False)
    assert prog(xs) == [N.f(x) for x in xs]


def test_batched_emit_matches_unbatched():
    xs = list(range(80))
    want = [N.g(N.f(x)) for x in xs]
    skel = Pipeline(Stage(N.f), Stage(N.g))
    assert lower(skel, "procs", batch=16)(xs) == want
    assert lower(Pipeline(Stage(N.f), Stage(N.g)), "procs", batch=1)(xs) == want


def test_batch_grain_reads_stage_grain():
    xs = list(range(60))
    skel = Pipeline(Source(xs), Stage(N.f, grain=8), Stage(N.g, grain=8))
    # fuse=False: grain must feed the emit-batch size here, not the fusion
    # pass (which reads it as µs of work)
    prog = lower(skel, "procs", batch="grain", fuse=False)
    assert prog.to_graph().run_and_wait(60) == [N.g(N.f(x)) for x in xs]


def test_numpy_payloads_through_zero_copy_farm():
    np = pytest.importorskip("numpy")
    xs = [np.full((32,), float(i), dtype=np.float32) for i in range(24)]
    skel = Farm(N.np_double, 2, ordered=True)
    out = lower(skel, "procs", batch=4, zero_copy=True)(xs)
    assert len(out) == len(xs)
    for got, x in zip(out, xs):
        assert got.dtype == np.float32 and got.shape == (32,)
        assert np.array_equal(got, x * 2.0)
