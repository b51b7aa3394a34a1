"""All-to-all building block + stream_ops semantics: three-backend parity
of the SAME ``reduce_by_key`` skeleton (threads / procs / mesh, unordered
compare), EOS fan-in termination on an nleft≠nright matrix, key-affinity
routing (every key owned by exactly one right vertex — across processes,
where builtin ``hash`` salting would split it), ordered a2a via the
tagged-token machinery, the fuse-never-crosses-AllToAll guarantee, the
``KeyAffinity`` scheduling policy, and the lowering error contracts."""
import os

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

_REPO = os.path.join(os.path.dirname(__file__), "..")
_ENV = {k: v for k, v in os.environ.items() if k != "PYTHONHASHSEED"}

import _procs_nodes as N
from repro.core import (AllToAll, Farm, FnNode, KeyAffinity, LoweringError,
                        Pipeline, Stage, fuse, lower, partition_by,
                        reduce_by_key, stable_hash, window)
from repro.core.skeleton import FusedNode


def ref_rbk(xs, by, fold, seed=None):
    d = {}
    for x in xs:
        k = by(x)
        d[k] = fold(d[k], x) if k in d else (x if seed is None else fold(seed, x))
    return d


# Programs are built once at module scope: the mesh keyed shuffle caches
# its compiled shard_map per (rows, dtype) bucket, and every hypothesis
# example reuses one compile.  All THREE backends lower the same IR node.
RBK = reduce_by_key(N.mod5, "sum", nleft=2, nright=3, nkeys=5)
RBK_T = lower(RBK, "threads")
RBK_M = lower(RBK, "mesh")
RBK_P = lower(RBK, "procs")


# -- acceptance: three-backend parity on the same keyed reduction ------------
@given(st.lists(st.integers(0, 1000), max_size=40))
@settings(max_examples=8, deadline=None)
def test_reduce_by_key_parity_threads_mesh(xs):
    """The same reduce_by_key IR: host N×M shuffle + per-key fold vs the
    one-shard_map segment exchange — identical key→fold maps."""
    want = ref_rbk(xs, N.mod5, lambda a, b: a + b)
    assert dict(RBK_T(xs)) == want
    assert dict(RBK_M(xs)) == want


# Procs parity draws fewer examples: every example spawns a full process
# network (2 left + 3 right + scatter), which costs seconds.
@given(st.lists(st.integers(0, 1000), max_size=16))
@settings(max_examples=3, deadline=None)
def test_reduce_by_key_parity_procs(xs):
    assert dict(RBK_P(xs)) == ref_rbk(xs, N.mod5, lambda a, b: a + b)


def test_parity_empty_stream():
    assert RBK_T([]) == RBK_M([]) == RBK_P([]) == []


@pytest.mark.parametrize("fold,ref", [("min", min), ("max", max)])
def test_named_folds_threads_vs_mesh(fold, ref):
    xs = list(range(7, 43))
    skel = reduce_by_key(N.mod5, fold, nkeys=5)
    want = ref_rbk(xs, N.mod5, ref)
    assert dict(lower(skel, "threads")(xs)) == want
    assert dict(lower(skel, "mesh")(xs)) == want


def test_count_fold_threads_vs_mesh():
    xs = list(range(23))
    skel = reduce_by_key(N.mod5, "count", nkeys=5)
    want = {k: sum(1 for x in xs if x % 5 == k) for k in range(5)}
    assert dict(lower(skel, "threads")(xs)) == want
    assert dict(lower(skel, "mesh")(xs)) == want


def test_mesh_float_fold_tolerance():
    xs = [0.25 * i for i in range(40)]
    skel = reduce_by_key(N.mod2int, "sum", nkeys=2)
    t = dict(lower(skel, "threads")(xs))
    m = dict(lower(skel, "mesh")(xs))
    assert set(t) == set(m)
    for k in t:
        np.testing.assert_allclose(t[k], m[k], rtol=1e-5)


# -- EOS fan-in termination + key-partition integrity (nleft != nright) ------
def test_eos_fanin_nleft_ne_nright_threads():
    """A 3×2 matrix terminates by per-edge EOS counting: each right vertex
    waits for all 3 left EOSes, and no item is lost or duplicated."""
    skel = AllToAll(N.double, [N.TagPartition(0), N.TagPartition(1)],
                    by=N.mod3, nleft=3, nright=2)
    out = lower(skel, "threads")(range(200))
    assert sorted(v for _, v in out) == sorted(x * 2 for x in range(200))
    owners = {}
    for j, v in out:
        owners.setdefault(N.mod3(v), set()).add(j)
    # key-affinity: every key serviced by exactly one right vertex
    assert all(len(s) == 1 for s in owners.values()), owners


def test_eos_fanin_nleft_ne_nright_procs():
    """Same matrix across processes: stable_hash keeps all left vertices
    (separate interpreters, separate hash salts) agreeing on key owners."""
    skel = AllToAll(N.double, [N.TagPartition(0), N.TagPartition(1)],
                    by=N.mod3, nleft=3, nright=2)
    out = lower(skel, "procs")(range(60))
    assert sorted(v for _, v in out) == sorted(x * 2 for x in range(60))
    owners = {}
    for j, v in out:
        owners.setdefault(N.mod3(v), set()).add(j)
    assert all(len(s) == 1 for s in owners.values()), owners


def test_matrix_topology_is_nxm():
    """The threads lowering wires exactly N×M edges between the rows, one
    private ring per (left, right) pair — no arbiter between the layers."""
    skel = AllToAll(N.double, N.double, by=N.mod3, nleft=3, nright=4)
    g = lower(skel, "threads").to_graph(list(range(8)))
    lefts = [v for v in g.vertices if "-L" in v.name]
    rights = [v for v in g.vertices if "-R" in v.name]
    assert len(lefts) == 3 and len(rights) == 4
    assert all(len(lv.outs) == 4 for lv in lefts)
    assert all(len(rv.ins) == 3 for rv in rights)


# -- ordered= via the tagged-token machinery ---------------------------------
def test_ordered_a2a_preserves_stream_order():
    skel = AllToAll(N.double, N.double, by=N.mod3, nleft=2, nright=3,
                    ordered=True)
    xs = list(range(80))
    assert lower(skel, "threads")(xs) == [x * 4 for x in xs]


def test_ordered_a2a_procs():
    skel = AllToAll(N.double, N.double, by=N.mod3, nleft=2, nright=3,
                    ordered=True)
    xs = list(range(24))
    assert lower(skel, "procs")(xs) == [x * 4 for x in xs]


# -- composability inside Pipeline -------------------------------------------
def test_a2a_composes_in_pipeline_threads_and_procs():
    """Stage → shuffle → Stage: the downstream stage fan-in-merges the
    right row's rings (EOS counted per edge) on both host backends."""
    skel = Pipeline(Stage(N.double), reduce_by_key(N.mod3, "sum", nright=2),
                    Stage(N.second))
    want = ref_rbk([x * 2 for x in range(30)], N.mod3, lambda a, b: a + b)
    assert sorted(lower(skel, "threads")(range(30))) == sorted(want.values())
    assert sorted(lower(skel, "procs")(range(30))) == sorted(want.values())


def test_a2a_into_farm():
    """A Farm after an AllToAll: the dispatch arbiter merges the matrix's
    output rings like any other fan-in."""
    skel = Pipeline(AllToAll(N.double, N.double, by=N.mod3, nleft=2, nright=2),
                    Farm(N.f, 3))
    out = lower(skel, "threads")(range(40))
    assert sorted(out) == sorted(N.f(x * 4) for x in range(40))


# -- fuse must not cross an AllToAll boundary --------------------------------
def test_fuse_does_not_cross_a2a():
    a2a = reduce_by_key(N.mod3, "sum", nright=2)
    skel = Pipeline(Stage(N.f, grain=1), Stage(N.g, grain=1), a2a,
                    Stage(N.second, grain=1), Stage(N.double, grain=1))
    fused = fuse(skel, force=True)
    assert isinstance(fused, Pipeline)
    kinds = [type(s) for s in fused.stages]
    assert kinds == [Stage, AllToAll, Stage]
    assert fused.stages[1] is a2a  # the shuffle is untouched, not rebuilt
    assert isinstance(fused.stages[0].node, FusedNode)
    assert isinstance(fused.stages[2].node, FusedNode)
    # and the fused pipeline still computes the same reduction
    want = ref_rbk([N.g(N.f(x)) for x in range(20)], N.mod3,
                   lambda a, b: a + b)
    want = sorted(v * 2 for v in want.values())
    assert sorted(lower(fused, "threads", fuse=False)(range(20))) == want


def test_fused_stage_flushes_svc_eos():
    """Fusing a window stage with a neighbour must not lose the EOS flush:
    FusedNode chains each constituent's svc_eos through the rest."""
    skel = Pipeline(window(4, "sum"), Stage(N.double, grain=1))
    fused = fuse(skel, force=True)
    assert not isinstance(fused, Pipeline)  # collapsed into one stage
    assert lower(fused, "threads")(range(10)) == [12, 44, 34]
    assert lower(skel, "threads", fuse=False)(range(10)) == [12, 44, 34]


# -- stream_ops --------------------------------------------------------------
def test_window_tumbling_and_eos_flush():
    w = window(4, "sum")
    assert lower(w, "threads")(range(10)) == [6, 22, 17]
    assert lower(w, "procs")(range(10)) == [6, 22, 17]
    assert lower(window(3, "max"), "threads")([5, 1, 9, 2, 8]) == [9, 8]
    assert lower(window(5, "sum"), "threads")([]) == []


def test_partition_by_pure_shuffle():
    out = lower(partition_by(N.mod3, 3), "threads")(range(50))
    assert sorted(out) == list(range(50))


def test_partition_by_class_instantiates_per_partition():
    skel = partition_by(N.mod3, 2, worker=N.Dedup)
    out = lower(skel, "threads")([1, 2, 1, 3, 2, 4, 1])
    assert sorted(out) == [1, 2, 3, 4]
    assert len({id(n) for n in skel.right_nodes}) == 2  # fresh per partition


def test_custom_callable_fold_host_backends():
    skel = reduce_by_key(N.mod3, N.keep_larger)
    xs = [3, 10, 5, 9, 14, 2]
    want = ref_rbk(xs, N.mod3, N.keep_larger)
    assert dict(lower(skel, "threads")(xs)) == want


# -- KeyAffinity scheduling policy -------------------------------------------
def test_keyaffinity_farm_threads_and_procs():
    farm = Farm([N.TagPartition(0), N.TagPartition(1), N.TagPartition(2)],
                scheduling=KeyAffinity(N.mod3))
    for backend, n in (("threads", 60), ("procs", 18)):
        out = lower(farm, backend)(range(n))
        owners = {}
        for j, x in out:
            owners.setdefault(N.mod3(x), set()).add(j)
        assert all(len(s) == 1 for s in owners.values()), (backend, owners)


def test_keyaffinity_stage_route():
    """route()-based policies are legal for Stage fan-out (unlike
    token-holding place() policies such as worksteal)."""
    from repro.core.graph import StageVertex
    v = StageVertex(FnNode(N.double), route=KeyAffinity(N.mod3))
    assert v._sched is not None
    with pytest.raises(ValueError, match="token-holding"):
        StageVertex(FnNode(N.double), route="worksteal")


def test_stable_hash_is_deterministic_and_typed():
    assert stable_hash(7) == 7 and stable_hash(-3) == -3
    assert stable_hash(True) == 1
    assert stable_hash("tenant-a") == stable_hash("tenant-a")
    assert stable_hash(b"k") == stable_hash(b"k")
    assert stable_hash(("a", 1)) == stable_hash(("a", 1))
    assert stable_hash(("a", 1)) != stable_hash(("a", 2))
    assert stable_hash((2 ** 80, "x")) == stable_hash((2 ** 80, "x"))
    assert stable_hash(None) == 0 and stable_hash(2.5) == stable_hash(2.5)
    # frozensets combine order-independently (their iteration order is
    # interpreter-salted — the exact trap stable_hash exists to avoid)
    assert stable_hash(frozenset({"a", "b", "c"})) == \
        stable_hash(frozenset({"c", "a", "b"}))


def test_stable_hash_is_stable_across_interpreters():
    """The whole point: a spawned vertex with a different hash salt must
    compute identical routes (builtin hash('x') would differ)."""
    import subprocess
    import sys

    code = ("import sys; sys.path.insert(0, 'src')\n"
            "from repro.core import stable_hash\n"
            "print(stable_hash('tenant-a'), stable_hash(('a', frozenset("
            "{'x', 'y'}))))")
    outs = {subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=_REPO, env={**_ENV, "PYTHONHASHSEED": str(seed)},
    ).stdout for seed in (1, 2, 3)}
    assert len(outs) == 1 and outs != {""}, outs


def test_stable_hash_agrees_with_dict_equality_for_numbers():
    """dict-equal keys (3 == 3.0 == True==1, -0.0 == 0.0) fold together at
    the right vertex, so they must route together too — a type-sensitive
    hash would split one logical key across partitions."""
    assert stable_hash(3.0) == stable_hash(3)
    assert stable_hash(-0.0) == stable_hash(0.0) == stable_hash(0)
    assert stable_hash(True) == stable_hash(1)
    assert stable_hash(2.5) == stable_hash(2.5)  # non-integral still works
    # end to end: a mixed int/float stream folds each logical key once
    skel = reduce_by_key(N.mod3, "sum", nright=3)
    out = dict(lower(skel, "threads")([3, 3.0, 4, 4.0]))
    assert out == {0: 6.0, 1: 8.0}, out


def test_mesh_rejects_out_of_range_keys():
    """Keys outside [0, nkeys) must raise, not silently clip into the
    boundary segment (the host backends would fold them correctly, so
    clipping is a silent three-backend divergence)."""
    skel = reduce_by_key(N.mod7, "sum", nkeys=5)
    with pytest.raises(LoweringError, match="nkeys"):
        lower(skel, "mesh")(range(35))
    # in-range keys on the same program shape still work
    ok = reduce_by_key(N.mod5, "sum", nkeys=5)
    assert dict(lower(ok, "mesh")(range(35))) == \
        ref_rbk(range(35), N.mod5, lambda a, b: a + b)


def test_stable_hash_rejects_unstable_key_types():
    class Opaque:
        pass

    with pytest.raises(TypeError, match="process-stable"):
        stable_hash(Opaque())
    with pytest.raises(TypeError, match="process-stable"):
        stable_hash({"a": 1})  # dicts: use sorted tuples instead


def test_ordered_a2a_rejects_multi_emit():
    """Tags are 1:1: a left node multi-emitting under ordered= must fail
    loudly instead of routing the EmitMany container as one payload."""
    skel = AllToAll(N.emit_twice, N.double, by=N.mod3, nleft=2, nright=2,
                    ordered=True)
    with pytest.raises(RuntimeError, match="EmitMany"):
        lower(skel, "threads")(range(8))
    # unordered multi-emit routes per element, as StageVertex would
    out = lower(AllToAll(N.emit_twice, N.double, by=N.mod3, nright=2),
                "threads")(range(8))
    assert sorted(out) == sorted([x * 2 for x in range(8)] * 2)


# -- error contracts ---------------------------------------------------------
def test_mesh_rejects_generic_a2a():
    with pytest.raises(LoweringError, match="keyed"):
        lower(AllToAll(N.double, N.double, by=N.mod3, nright=2), "mesh")


def test_mesh_rejects_custom_fold():
    with pytest.raises(LoweringError, match="keyed"):
        lower(reduce_by_key(N.mod3, N.keep_larger, nkeys=3), "mesh")


def test_mesh_rejects_missing_nkeys():
    with pytest.raises(LoweringError, match="nkeys"):
        lower(reduce_by_key(N.mod3, "sum"), "mesh")


def test_mesh_rejects_stage_after_shuffle():
    with pytest.raises(LoweringError, match="ONE AllToAll"):
        lower(Pipeline(reduce_by_key(N.mod3, "sum", nkeys=3),
                       Stage(N.second)), "mesh")


def test_a2a_rejects_token_holding_scatter_policy():
    with pytest.raises(ValueError, match="token-holding"):
        AllToAll(N.double, N.double, nleft=2, nright=2,
                 scheduling="worksteal")


def test_ordered_a2a_requires_upstream():
    skel = AllToAll(N.double, N.double, by=N.mod3, ordered=True)
    with pytest.raises(LoweringError, match="upstream"):
        lower(skel, "threads").to_graph(None)


def test_ordered_reduce_is_rejected_at_ir():
    with pytest.raises(AssertionError, match="unordered|undefined"):
        AllToAll(N.double, N.double, by=N.mod3, ordered=True,
                 reduce=object())


def test_unknown_fold_name():
    with pytest.raises(ValueError, match="unknown fold"):
        reduce_by_key(N.mod3, "median")


# -- init= conflicts with a self-seeding fold spec (regression) --------------
def test_init_conflicts_with_named_fold():
    with pytest.raises(ValueError, match="conflicts with the named fold"):
        reduce_by_key(N.mod3, "sum", init=5)
    with pytest.raises(ValueError, match="conflicts with the named fold"):
        window(3, "count", init=2)
    with pytest.raises(ValueError, match="conflicts with the named fold"):
        reduce_by_key(N.mod3, "count", init=0)  # 0 is a conflict, not falsy


def test_init_conflicts_with_fold_spec():
    from repro.core import FOLDS
    with pytest.raises(ValueError, match="conflicts with the Fold spec"):
        reduce_by_key(N.mod3, FOLDS["max"], init=0)
    with pytest.raises(ValueError, match="conflicts with the Fold spec"):
        window(2, FOLDS["min"], init=1)


def test_init_with_bare_callable_seeds_the_accumulator():
    # the documented escape hatch: a bare callable takes a custom seed
    out = lower(window(2, N.keep_larger, init=100), "threads")([3, 7, 50, 9])
    assert out == [100, 100]  # every window folds from the 100 seed
    skel = reduce_by_key(N.mod3, N.keep_larger, init=1000)
    assert dict(lower(skel, "threads")([5, 9, 14])) == {0: 1000, 2: 1000}


# -- three backends, same skeleton objects, new lowering options -------------
def test_three_backend_parity_with_batched_zero_copy_procs():
    xs = list(range(64))
    want = ref_rbk(xs, N.mod5, lambda a, b: a + b)
    assert dict(lower(RBK, "threads")(xs)) == want
    assert dict(lower(RBK, "procs", batch=8, zero_copy=True)(xs)) == want
    assert dict(lower(RBK, "mesh")(xs)) == want


# -- mesh pack: an array packs whole, with the list path's checks and output --
def double_in_place(x):
    # rebinds a traced JAX array, writes a numpy column in place: the
    # pack must hand the pre-maps its own copy, never the caller's array
    x *= 2
    return x


def _typed(out):
    return [(type(k), type(v)) for k, v in out]


@pytest.mark.parametrize("premap", [False, True], ids=["by", "premap"])
@pytest.mark.parametrize("dtype", [np.int32, np.float32])
@pytest.mark.parametrize("fold", ["count", "sum", "min", "max"])
def test_mesh_array_path_matches_list_path(fold, dtype, premap):
    """A numpy array and its list form give the identical pairs: same keys
    in the same ascending order, same values, same Python types; key 7
    never occurs and is left out."""
    rbk = reduce_by_key(N.mod7, fold, nkeys=8)
    prog = lower(Pipeline(Stage(N.double), rbk) if premap else rbk, "mesh")
    xs = (np.random.default_rng(7).integers(-40, 900, 301) *
          (0.25 if dtype is np.float32 else 1)).astype(dtype)
    got, want = prog(xs), prog(xs.tolist())
    assert got == want and _typed(got) == _typed(want)
    vtype = int if fold == "count" or dtype is np.int32 else float
    assert set(_typed(got)) == {(int, vtype)}
    assert [k for k, _ in got] == sorted(k for k, _ in got)
    assert set(dict(got)) == {int(N.mod7(2 * x if premap else x))
                              for x in xs.tolist()}


@pytest.mark.parametrize("case", ["2d", "int64_range", "key_range"])
def test_mesh_array_path_raises_as_list_path(case):
    """Each refusal reads the same for an array and for its list form."""
    skel = reduce_by_key(N.mod5, "sum", nkeys=5)
    if case == "2d":
        xs = np.arange(12, dtype=np.int32).reshape(6, 2)
        lst = xs.tolist()
    elif case == "int64_range":
        xs = np.array([1, 2, 1 << 40], np.int64)
        lst = xs.tolist()
    else:
        skel = reduce_by_key(N.mod7, "sum", nkeys=5)
        xs = np.arange(35, dtype=np.int32)
        lst = range(35)
    prog = lower(skel, "mesh")
    with pytest.raises(LoweringError) as from_array:
        prog(xs)
    with pytest.raises(LoweringError) as from_list:
        prog(lst)
    assert str(from_array.value) == str(from_list.value)


@pytest.mark.parametrize("xs", [np.zeros(0, np.int32), np.zeros(0, np.float32),
                                np.zeros((0, 1), np.int32)],
                         ids=["int32", "float32", "2d"])
def test_mesh_empty_array_returns_empty(xs):
    assert RBK_M(xs) == RBK_M(list(xs)) == []


@pytest.mark.parametrize("dtype", [np.int32, np.float32, np.int64])
def test_mesh_array_path_leaves_callers_array(dtype):
    prog = lower(Pipeline(Stage(double_in_place),
                          reduce_by_key(N.mod5, "sum", nkeys=5)), "mesh")
    xs = np.arange(40).astype(dtype)
    before = xs.copy()
    out = prog(xs)
    np.testing.assert_array_equal(xs, before)
    assert xs.dtype == before.dtype
    assert out == prog(before.tolist())


def test_mesh_pack_array_counts_array_calls_only():
    """``mesh.pack_array`` counts the calls that packed an array (numpy or
    JAX), not those fed a list, a range or a generator."""
    import jax.numpy as jnp

    prog = lower(reduce_by_key(N.mod5, "count", nkeys=5), "mesh",
                 metrics=True)
    xs = np.arange(23, dtype=np.int32)
    want = prog(list(xs))
    assert prog(range(23)) == prog(x for x in range(23)) == want
    assert prog.metrics.counter("mesh.pack_array").value == 0
    assert prog(xs) == prog(jnp.asarray(xs)) == want
    assert prog.metrics.counter("mesh.pack_array").value == 2
    assert prog.metrics.counter("mesh.calls").value == 5
