"""All-to-all subsystem — the keyed-shuffle lowerings of :class:`AllToAll`.

FastFlow's tutorial (TR-12-04) makes **all-to-all** the third core
building block next to pipeline and farm: N left workers, each able to
route every emission to any of M right workers.  It is the shape that
keyed shuffles, partitioned reduction and data-parallel aggregation (the
parquet-aggregator workload) are made of, and the configuration where the
paper's per-hand-off overhead argument bites hardest — a single streamed
item crosses ``O(1)`` edges, but the *network* holds ``N×M`` of them.

Two lowerings of the same IR node:

**threads / procs** (``repro.core.vertex``, the host runners' shared walker)
    An N×M matrix of SPSC rings.  Each left vertex owns one private ring
    per right vertex, so the single-writer discipline of the whole runtime
    survives with *no arbiter between the layers*: routing is a pure
    function of the emission (``stable_hash(by(x)) % nright``) computed in
    the producing vertex, and termination is per-edge EOS fan-in counting
    at each right vertex (a right vertex EOSes only after all N of its
    inbound edges have).  ``ordered=`` composes with the existing
    tagged-token machinery: a tagger at the scatter, tags riding the
    matrix untouched, a reorder stage downstream.

**mesh** (:class:`A2AMeshProgram`)
    A keyed shuffle as ONE ``shard_map`` program, for skeletons carrying a
    static keyed-reduction spec (:class:`repro.core.stream_ops.
    KeyedReduce`): map stages apply elementwise, keys pick a destination
    worker (``key % axis_size``), :func:`repro.core.dfarm.dispatch` moves
    every row to its key's owner (``all_to_all`` or the collective-permute
    ring schedule), and a segment reduction + one tiny per-key collective
    folds each partition — the device-side image of "all rows of a key
    meet at one worker".

Routing determinism matters more here than anywhere else in the runtime:
two left vertices in *different processes* must agree where key ``"a"``
lives, so the route hashes with :func:`stable_hash`, never the
interpreter-salted builtin ``hash``.
"""
from __future__ import annotations

import math
import time
import zlib
from typing import Any, Callable, Dict, List, Optional, Tuple

from . import obs as _obs
from .skeleton import (BACKENDS, AllToAll, KeyBatch, LoweringError,
                       MeshProgram, Pipeline, Skeleton, Stage, WORKER_AXIS,
                       _coerce_metrics, _coerce_tracer, _jax_callable)

__all__ = ["stable_hash", "KeyRouter", "A2AMeshProgram"]


def stable_hash(key: Any) -> int:
    """Deterministic, process-independent hash for shuffle routing.

    Python's builtin ``hash`` is salted per interpreter (PYTHONHASHSEED),
    so two left vertices running as *processes* (the procs backend) would
    route the same string key to different right vertices — silently
    splitting every key's partition across workers.  Route on a stable
    digest instead: ints map to themselves (so mod-partitioning stays the
    obvious one, and the host route agrees with the mesh's ``key % W`` for
    integer keys); str/bytes/float via crc32 of a canonical encoding;
    tuples recursively; frozensets order-independently (their iteration
    order is itself hash-salted).  Any other type raises — a default
    ``repr`` embeds the object's address, which would differ per process
    (and per object) and silently split partitions; route on a canonical
    key (int / str / tuple of those) instead.
    """
    if isinstance(key, bool):
        return int(key)
    if isinstance(key, int):
        return key
    if isinstance(key, bytes):
        return zlib.crc32(key)
    if isinstance(key, str):
        return zlib.crc32(key.encode("utf-8"))
    if key is None:
        return 0
    if isinstance(key, float):
        # hash-consistency with dict equality: 3.0 == 3 and -0.0 == 0.0,
        # and the fold dict at the right vertex merges them — so they must
        # route identically too, or one logical key splits across workers
        if math.isfinite(key) and key == int(key):
            return int(key)
        return zlib.crc32(repr(key).encode("utf-8"))  # repr is canonical
    if isinstance(key, tuple):
        acc = 1
        for k in key:
            # decimal repr of the element hash: canonical and unbounded
            # (int keys hash to themselves, at any magnitude)
            acc = zlib.crc32(b"%d," % stable_hash(k), acc)
        return acc
    if isinstance(key, frozenset):
        return sum(stable_hash(k) for k in key) & 0xFFFFFFFF
    raise TypeError(
        f"no process-stable hash for key type {type(key).__name__!r} "
        f"(its repr/hash varies per interpreter or per object, which "
        f"would split the key's partition across workers) — route on a "
        f"canonical key: int, str, bytes, float, None, or tuples/"
        f"frozensets of those")


def _ident(x: Any) -> Any:
    return x


class KeyRouter:
    """Per-left-vertex routing rule: which of the M private rings an
    emission takes.  ``by=None`` degrades to per-vertex round-robin (a
    plain repartition); otherwise ``stable_hash(by(x)) % nright``, so all
    left vertices agree on every key's owner with zero coordination.
    Plain picklable state — the procs backend ships one per left-vertex
    process, and the counter/keys are private to that process."""

    def __init__(self, by: Optional[Callable[[Any], Any]], nright: int,
                 tagged: bool = False):
        self.by = by
        self.nright = nright
        self.tagged = tagged
        self._rr = 0

    def __call__(self, out: Any) -> int:
        x = out[1] if self.tagged else out
        if self.by is None:
            w = self._rr
            self._rr = (self._rr + 1) % self.nright
            return w
        return stable_hash(self.by(x)) % self.nright

    def split(self, batch: KeyBatch) -> List[Tuple[int, KeyBatch]]:
        """Partition a :class:`~repro.core.skeleton.KeyBatch` by
        destination: one sub-batch per right vertex that owns any of its
        keys — the whole batch then costs one ring message per *destination*
        instead of one per item."""
        if self.nright == 1:
            return [(0, batch)] if batch else []
        buckets: List[Optional[KeyBatch]] = [None] * self.nright
        for x in batch:
            w = self(x)
            b = buckets[w]
            if b is None:
                buckets[w] = b = KeyBatch()
            b.append(x)
        return [(w, b) for w, b in enumerate(buckets) if b]


# ---------------------------------------------------------------------------
# mesh lowering: the keyed shuffle as ONE shard_map program
# ---------------------------------------------------------------------------
def _plan_mesh_a2a(skel: Skeleton) -> Tuple[List[Callable], AllToAll]:
    """Flatten a skeleton into (elementwise pre-maps, the one AllToAll).
    The shuffle must be the last stage: whatever follows it would consume
    ``(key, fold)`` pairs, which have no array form on the mesh."""
    stages = skel.stages if isinstance(skel, Pipeline) else [skel]
    pre: List[Callable] = []
    a2a: Optional[AllToAll] = None
    for s in stages:
        if isinstance(s, AllToAll):
            if a2a is not None:
                raise LoweringError(
                    "the mesh keyed-shuffle program lowers exactly one "
                    "AllToAll; chain reductions on the host backends")
            a2a = s
        elif a2a is None and isinstance(s, Stage):
            pre.append(_jax_callable(s.node))
        else:
            raise LoweringError(
                f"the mesh keyed-shuffle program is Stage maps followed by "
                f"ONE AllToAll; cannot place {type(s).__name__} "
                f"{'after the shuffle' if a2a is not None else 'here'}")
    assert a2a is not None
    if len({id(n) for n in a2a.left_nodes}) != 1:
        raise LoweringError(
            "the mesh all-to-all is SPMD: all left workers must share one "
            "jax-traceable function")
    pre.append(_jax_callable(a2a.left_nodes[0]))
    if a2a.reduce is None:
        raise LoweringError(
            "the mesh backend lowers AllToAll only as a static keyed "
            "reduction (stream_ops.reduce_by_key with a named fold and "
            "nkeys=): generic host-side right nodes cannot be traced — "
            "use the threads or procs backend for them")
    return pre, a2a


# mesh-side segment/collective implementation of each named fold kind
_SEG_KINDS = ("sum", "min", "max", "count")


class A2AMeshProgram:
    """The keyed shuffle compiled whole: ONE ``shard_map`` over a 1-D
    ``(skel_worker,)`` mesh.

    Per call: items pack into a padded ``(rows, payload+flag)`` array per
    worker (same bucketing discipline as :class:`~repro.core.skeleton.
    MeshProgram`, so nearby sizes reuse the compile); inside the program
    each row computes its key (``reduce.by``, applied to the whole column
    — it must be array-polymorphic, which for arithmetic like ``x % k``
    is the scalar form verbatim), every row travels to the worker that
    owns its key (``key % axis_size`` — the same mod-partitioning the host
    route's :func:`stable_hash` gives integer keys) via
    :func:`repro.core.dfarm.dispatch`, and a segment reduction folds each
    key's partition locally; one per-key collective (psum/pmin/pmax)
    assembles the replicated result.  Returns ``[(key, fold), ...]`` for
    the keys that actually occurred — the same unordered contract as the
    host backends' EOS flush.

    Static key space required: ``reduce.nkeys`` bounds the segment arrays,
    and ``by`` must yield integer keys in ``[0, nkeys)``.

    Input: an object whose type offers ``__array__`` (a numpy array, a JAX
    array) packs as one array, never item by item, and is not written to;
    it counts in ``mesh.pack_array``.  Any other iterable (a list,
    ``range``, a generator) is gathered into a list first.  Both paths run
    the same checks and give the same result.
    """

    backend = "mesh"

    def __init__(self, skeleton: Skeleton, *, devices: Optional[int] = None,
                 block: int = 64, check_vma: Optional[bool] = None,
                 capacity: Optional[int] = None, grain: Optional[int] = None,
                 trace: Any = False, metrics: Any = False):
        import jax

        self.skeleton = skeleton
        self.pre, self.a2a = _plan_mesh_a2a(skeleton)
        red = self.a2a.reduce
        kind = getattr(red.fold, "kind", None)
        if kind not in _SEG_KINDS:
            raise LoweringError(
                f"mesh keyed reduction needs a named fold with a segment "
                f"implementation (have {_SEG_KINDS}), got {kind!r}")
        if red.nkeys is None:
            raise LoweringError(
                "mesh keyed reduction needs a static key space: pass "
                "nkeys= to reduce_by_key (keys must lie in [0, nkeys))")
        self.by = red.by
        self.kind = kind
        self.nkeys = int(red.nkeys)
        self.block = block
        self.check_vma = check_vma
        ndev = len(jax.devices()) if devices is None else devices
        self.n_worker = max(1, ndev)
        from .. import compat
        self.mesh = compat.make_mesh((self.n_worker,), (WORKER_AXIS,))
        self._programs: Dict[Tuple[int, str], Callable] = {}
        self.tracer = _coerce_tracer(trace)
        self.metrics = _coerce_metrics(metrics)
        self.last_report = None
        self._calls = 0
        self._lane = None
        if self.tracer is not None:
            self._lane = self.tracer.vertex("mesh-program")
            self._lane.instant("devices", {
                "devices": self.n_worker, "n_stage": 1,
                "n_worker": self.n_worker})

    @property
    def last_trace(self):
        """The program's lane as it stands (``trace=`` on), else None."""
        return self.tracer.trace() if self.tracer is not None else None

    def _bucket_rows(self, n: int) -> int:
        rows = max(-(-n // self.n_worker), 1, self.block)
        return 1 << (rows - 1).bit_length()

    def __call__(self, items: Any) -> List[Tuple[int, Any]]:
        """One round trip: pack on the host, run, wait for the result and
        unpack it.  Under JAX's profiler each phase is an ``obs`` region
        (``mesh.pack``, ``mesh.dispatch`` with ``mesh.compile`` inside on
        a program's first run, ``mesh.fetch``, ``mesh.unpack``) inside
        ``mesh.call``, all carrying the call's sequence id."""
        import numpy as np

        seq = self._calls
        self._calls += 1
        t0 = time.monotonic()
        with _obs.region("mesh.call", seq):
            with _obs.region("mesh.pack", seq):
                padded, n, rows, as_array = self._pack(items)
            if padded is None:
                return []
            with _obs.region("mesh.dispatch", seq):
                acc, cnt = self._run(padded, rows, seq)
            with _obs.region("mesh.fetch", seq):
                cnt = np.asarray(cnt)[0]      # waits for the device
                # a count's fold is its count: one fetch, not two
                acc = cnt if self.kind == "count" else np.asarray(acc)[0]
            with _obs.region("mesh.unpack", seq):
                live = np.flatnonzero(cnt > 0)
                out = list(zip(live.tolist(), acc[live].tolist()))
        t1 = time.monotonic()
        if self._lane is not None:
            self._lane.span("call", t0, t1, {"items": n, "rows": rows})
        if self.metrics is not None:
            reg = self.metrics
            reg.counter("mesh.calls").inc()
            if as_array:
                reg.counter("mesh.pack_array").inc()
            reg.counter("mesh.items").inc(n)
            reg.gauge("mesh.devices").set(self.n_worker)
            reg.histogram("mesh.call_us").observe((t1 - t0) * 1e6)
            self.last_report = reg.finalize(reg.report(meta={
                "backend": "mesh", "items_in": n, "rows": rows,
                "wall_s": t1 - t0}))
        return out

    def _pack(self, items: Any):
        """Items to the padded ``(workers * rows, 2)`` payload-and-flag
        array, after the dtype and key-range checks, with its item count,
        rows a worker and whether ``items`` packed as one array;
        ``(None, 0, 0, ...)`` for an empty stream."""
        import numpy as np

        as_array = hasattr(type(items), "__array__")
        arr = np.asarray(items if as_array else list(items))
        if arr.ndim and not arr.shape[0]:
            return None, 0, 0, as_array
        # astype copies, so the checks and pre-maps below never touch the
        # caller's array
        if arr.dtype.kind == "f":
            arr = arr.astype(np.float32)
        elif arr.dtype.kind in "iub":
            cast = arr.astype(np.int32)
            if arr.dtype != np.int32 and not np.array_equal(cast, arr):
                raise LoweringError(
                    "integer payloads exceed int32 (the mesh compute "
                    "dtype); the host backends fold exact Python ints — "
                    "refusing to silently diverge")
            arr = cast
        else:
            raise LoweringError(
                f"mesh payloads must be numeric, got dtype {arr.dtype}")
        if arr.ndim != 1:
            raise LoweringError(
                "the mesh keyed shuffle streams scalar items (fold values "
                "are per-key scalars)")
        n = arr.shape[0]
        # key-range precondition, checked host-side with the same pre-map
        # and key fns (array-polymorphic, so eager semantics match the
        # traced program): an out-of-range key would otherwise clip into
        # the boundary segment and silently diverge from the threads/procs
        # fold
        col = arr[:, None]
        for f in self.pre:
            col = np.asarray(f(col))
        keys = np.asarray(self.by(col[:, 0])).astype(np.int64)
        if keys.size and (keys.min() < 0 or keys.max() >= self.nkeys):
            raise LoweringError(
                f"mesh keyed reduction saw keys in "
                f"[{keys.min()}, {keys.max()}] but nkeys={self.nkeys}: "
                f"keys must lie in [0, nkeys) — refusing to silently "
                f"merge out-of-range keys into the boundary segment")
        rows = self._bucket_rows(n)
        padded = np.zeros((self.n_worker * rows, 2), arr.dtype)
        padded[:n, 0] = arr
        padded[:n, 1] = 1  # validity flag: padding rows never reduce
        return padded, n, rows, as_array

    def _run(self, padded, rows: int, seq: int):
        """Dispatch ``padded`` to its bucket's program.  A program's first
        run traces and compiles it: that run is the ``compile`` span and
        the ``mesh.compile`` region, and counts in ``mesh.compiles``."""
        key = (rows, str(padded.dtype))
        prog = self._programs.get(key)
        if prog is not None:
            return prog(padded)
        prog = self._programs[key] = self._program(rows)
        t0 = time.monotonic()
        with _obs.region("mesh.compile", seq):
            out = prog(padded)
        if self._lane is not None:
            self._lane.span("compile", t0, time.monotonic(),
                            {"rows": rows, "dtype": key[1]})
        if self.metrics is not None:
            self.metrics.counter("mesh.compiles").inc()
        return out

    def _program(self, rows: int) -> Callable:
        """The ``shard_map`` program for ``rows`` rows a worker (lazy:
        ``jax.jit`` compiles it on its first run)."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from .. import compat
        from . import dfarm

        W, nkeys, kind = self.n_worker, self.nkeys, self.kind
        pre, by = self.pre, self.by

        def body(xf):                       # (rows, 2) per worker column
            x, flag = xf[:, :1], xf[:, 1]
            for f in pre:
                x = f(x)                    # elementwise maps, (rows, 1)
            aug = jnp.concatenate([x, flag[:, None].astype(x.dtype)], axis=1)
            keys = jnp.asarray(by(x[:, 0])).astype(jnp.int32)
            # every row travels to its key's owner; padding rows carry an
            # arbitrary (valid) destination, their flag keeps them inert
            dest = jnp.clip(keys, 0, nkeys - 1) % W
            # capacity = rows: even "every local row to one worker" fits,
            # so the exchange can never drop (unlike capacity-factor MoE)
            recv, _ = dfarm.dispatch(aug, dest, WORKER_AXIS, rows)
            flat = recv.reshape(-1, 2)      # (W*rows, payload+flag)
            vals = flat[:, 0]
            valid = flat[:, 1] != 0
            k2 = jnp.asarray(by(vals)).astype(jnp.int32)
            # invalid rows (padding, unfilled capacity slots) reduce into
            # segment nkeys, which is sliced away
            k2 = jnp.where(valid, jnp.clip(k2, 0, nkeys - 1), nkeys)
            ones = jnp.where(valid, 1, 0).astype(jnp.int32)
            cnt = jax.ops.segment_sum(ones, k2, nkeys + 1)[:nkeys]
            cnt = lax.psum(cnt, WORKER_AXIS)
            if kind == "count":
                acc = cnt.astype(jnp.int32)
            elif kind == "sum":
                seg = jax.ops.segment_sum(vals, k2, nkeys + 1)[:nkeys]
                acc = lax.psum(seg, WORKER_AXIS)
            elif kind == "min":
                seg = jax.ops.segment_min(vals, k2, nkeys + 1)[:nkeys]
                acc = lax.pmin(seg, WORKER_AXIS)
            else:                           # "max"
                seg = jax.ops.segment_max(vals, k2, nkeys + 1)[:nkeys]
                acc = lax.pmax(seg, WORKER_AXIS)
            # each worker returns its (replicated) copy as one row; vma
            # typing on newer JAX wants an explicit worker-varying cast
            acc = compat.vma_align(acc[None, :], (WORKER_AXIS,))
            cnt = compat.vma_align(cnt[None, :], (WORKER_AXIS,))
            return acc, cnt

        return jax.jit(compat.shard_map(
            body, mesh=self.mesh, in_specs=(P(WORKER_AXIS),),
            out_specs=(P(WORKER_AXIS), P(WORKER_AXIS)),
            check_vma=self.check_vma))


def _contains_a2a(skel: Skeleton) -> bool:
    if isinstance(skel, Pipeline):
        return any(_contains_a2a(s) for s in skel.stages)
    return isinstance(skel, AllToAll)


def _mesh_backend(skeleton: Skeleton, **opts: Any):
    """Mesh-backend factory: skeletons containing an :class:`AllToAll`
    compile to the keyed-shuffle program (:class:`A2AMeshProgram` —
    dispatch-by-key exchange + segment reduction in one ``shard_map``);
    everything else to :class:`~repro.core.skeleton.MeshProgram`."""
    if _contains_a2a(skeleton):
        return A2AMeshProgram(skeleton, **opts)
    return MeshProgram(skeleton, **opts)


BACKENDS["mesh"] = _mesh_backend
