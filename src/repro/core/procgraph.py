"""The procs runner — the skeleton IR's process backend.

The threads runner (``graph.py``) keeps the runtime cheap but leaves
pure-Python stages serialised behind the GIL: the FastFlow speedup story
(paper Sec. 6) only materialises there for GIL-releasing kernels.  This
runner executes the same vertex protocol (:mod:`repro.core.vertex`) with
each vertex a **spawned process** and every edge a
:class:`~repro.core.shm.ShmRing` (the paper's SPSC ring over shared memory,
cache-line-separated head/tail and all), so a farm of pure-Python ``svc``
functions scales with cores.

What this runner decides (vs the threads runner)
------------------------------------------------
=============================  =============================================
threads (``graph.py``)         procs (this module)
=============================  =============================================
``threading.Thread`` vertex    ``spawn``-ed, pooled ``multiprocessing``
                               process
``SPSCQueue`` edge             ``ShmRing`` edge (pickled = attach by name)
``Graph.results`` list         a results ring per sink, drained by the caller
``Graph.failed`` list          a shared failure flag (:class:`ShmFlag`) + a
                               per-vertex control ring carrying ready/error
                               messages back to the caller
``TagSpace.retired``           a one-slot ``ShmCounters`` board (the merge
                               arbiter writes it, the dispatch arbiter reads)
``FarmStats`` (shared object)  one per vertex, shipped over its control ring
                               at EOS and folded into ``Farm.stats``
50 µs idle sleep, one item     AIMD ``_Backoff``, up to ``_BATCH`` items per
per ring per pass              ring per wake
=============================  =============================================

Single-writer discipline holds end to end: every ring has one producer and
one consumer process; the scheduling policy lives entirely inside the
dispatch arbiter's process (worker side-channels — worksteal idle rings,
costmodel service-EWMA rings — are ShmRings).  Even the *control plane* is
shared-memory SPSC, and every control primitive pickles as a plain segment
attach, which is what lets vertices ride through a queue to **pooled**
worker processes.

Spawn-pool reuse: starting a spawned interpreter costs ~0.1s (import of
``repro.core`` dominates); a program that lowers the same skeleton
repeatedly would pay it per run, per vertex.  ``run()`` therefore leases
processes from a module-level pool (one per start method): each pooled
worker loops ``job = jobq.get(); vertex._run()``, re-arming between
graphs, so only the first run pays the spawn.  Workers whose graph
failed or timed out are terminated and replaced; clean workers return to
the pool.  Opt out per program (``lower(skel, "procs", pool=False)``)
or globally (``REPRO_PROCS_POOL=0``).

Constraints of the process world (all spawn-start-method induced):

* nodes, payloads and scheduling policies must be **picklable** —
  module-level functions, ``functools.partial``, or ``ff_node``
  subclasses; lambdas and closures are rejected at ``run()`` with a
  :class:`~repro.core.skeleton.LoweringError`;
* ``speculative=`` straggler re-issue is threads-only (its tag bookkeeping
  is cross-arbiter shared state), rejected at lowering;
* ``Farm.stats`` is updated *after* the run (merged snapshot), not live.

The start method defaults to ``spawn`` (fork would duplicate JAX/XLA
runtime threads); override with ``REPRO_PROCS_START`` if you must.
"""
from __future__ import annotations

import atexit
import os
import pickle
import time
import multiprocessing as mp
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from .obs import VertexTracer, farm_stats_snapshot, qualname as _qualname
from .sched import Scheduler, make_scheduler
from .shm import ShmCounters, ShmFlag, ShmRing
from .skeleton import (BACKENDS, Farm, FarmStats, KeyBatch, LoweringError,
                       Skeleton, Source, _coerce_metrics, _coerce_monitor,
                       _coerce_tracer, _fuse_for_lowering, as_skeleton,
                       walk_stats)
from .spsc import EOS, SPSCQueue
from .vertex import (_POLL, MergeVertex, Runner, TagSpace, Vertex,
                     WorkerVertex, build)

__all__ = [
    "ProcGraph", "build", "ProcProgram", "ProcAccelerator", "pool_stats",
    "pool_shutdown",
]

_EMPTY = SPSCQueue._EMPTY
_BATCH = 256              # max items drained per ring per arbiter wake-up
# a token's ``issued`` stamp is taken on 1 tag in 16 (tag & _LAT_SAMPLE ==
# 0): clock reads are syscalls, expensive under sandboxed kernels, and the
# latency reservoir only needs a sample
_LAT_SAMPLE = 15


def _start_ctx():
    return mp.get_context(os.environ.get("REPRO_PROCS_START", "spawn"))


class _Backoff:
    """Adaptive idle backoff: 50µs doubling to 5ms while nothing moves.

    The thread runner can poll at a fixed 50µs because a sleeping thread
    is nearly free; here every vertex is a *process* competing for the
    same cores as the workers, and on a small machine every arbiter
    wake-up is a context switch that preempts a worker mid-task (markedly
    expensive under sandboxed kernels, where ``sleep(50µs)`` rounds up to
    ~1ms anyway).  Doubling the sleep caps the idle wake rate at ~200/s
    per vertex while bounding added latency at 5ms — noise against any
    grain worth sending to a process farm, and the arbiters batch-drain
    their rings per wake (``_BATCH``) so throughput never rides on the
    wake rate.

    AIMD, not reset-to-floor: progress *halves* the delay, idleness
    doubles it.  A full reset on every popped token would pin a collector
    at the maximum wake rate whenever results trickle in one at a time —
    exactly the steady state of a coarse-grain farm — while halving
    converges the wake rate to ~2× the arrival rate and lets the batch
    drain do the rest."""

    __slots__ = ("delay",)
    _CAP = 0.005

    def __init__(self):
        self.delay = _POLL

    def reset(self) -> None:
        self.delay = max(self.delay / 2, _POLL)

    def idle(self) -> None:
        time.sleep(self.delay)
        self.delay = min(self.delay * 2, self._CAP)


def _vertex_main(vertex: Vertex) -> None:
    """Child-process entry point (module-level: spawn pickles by name)."""
    vertex._run()


# ---------------------------------------------------------------------------
# the spawn pool: reusable vertex-host processes, one pool per start method
# ---------------------------------------------------------------------------
def _pool_main(jobq, doneq) -> None:
    """Pooled vertex-host: run one vertex per job, then re-arm.  The spawn
    and import cost is paid once per *process*, not once per run."""
    base_cpus = None
    if hasattr(os, "sched_getaffinity"):
        try:
            base_cpus = os.sched_getaffinity(0)
        except OSError:  # pragma: no cover - exotic kernels
            pass
    while True:
        vertex = jobq.get()
        if vertex is None:
            return
        try:
            _vertex_main(vertex)
        finally:
            if base_cpus is not None and vertex.cpus:
                try:  # undo the vertex's pin: the next job chooses its own
                    os.sched_setaffinity(0, base_cpus)
                except OSError:  # pragma: no cover
                    pass
            vertex = None  # drop ring attachments before signalling done
            doneq.put(True)


class _PoolWorker:
    """One leased process: a job queue in, a done-token queue out."""

    __slots__ = ("jobq", "doneq", "proc", "busy")

    def submit(self, vertex: Vertex) -> None:
        # SimpleQueue.put pickles synchronously in THIS thread — an
        # unpicklable vertex raises here, before any bytes hit the pipe,
        # so the worker stays clean and reusable
        self.jobq.put(vertex)
        self.busy = True

    def poll_done(self) -> bool:
        if self.busy:
            while not self.doneq.empty():
                self.doneq.get()
                self.busy = False
        return not self.busy


class _ProcPool:
    """Reusable spawned processes for one start method.

    ``acquire`` hands out an idle worker (or spawns one), ``release``
    parks it for the next graph.  Workers are generic vertex hosts — a
    process that ran a farm worker last graph may run a merge arbiter in
    the next — so the pool needs no shape bookkeeping, only liveness."""

    MAX_IDLE = 12  # parked interpreters cost memory; beyond this, retire

    def __init__(self, ctx):
        self._ctx = ctx
        self._idle: List[_PoolWorker] = []
        self.spawned = 0  # telemetry: processes ever started
        self.reused = 0   # telemetry: acquisitions that skipped a spawn

    def acquire(self) -> _PoolWorker:
        while self._idle:
            w = self._idle.pop()
            if w.proc.is_alive():
                self.reused += 1
                return w
            self.discard(w)
        w = _PoolWorker()
        w.jobq = self._ctx.SimpleQueue()
        w.doneq = self._ctx.SimpleQueue()
        w.busy = False
        self.spawned += 1
        w.proc = self._ctx.Process(target=_pool_main,
                                   args=(w.jobq, w.doneq),
                                   name=f"ff-pool-{self.spawned}",
                                   daemon=True)
        w.proc.start()
        return w

    def release(self, w: _PoolWorker) -> None:
        if w.proc.is_alive() and not w.busy \
                and len(self._idle) < self.MAX_IDLE:
            self._idle.append(w)
        else:
            self.discard(w)

    def discard(self, w: _PoolWorker) -> None:
        try:
            if w.proc.is_alive() and not w.busy:
                w.jobq.put(None)  # polite: let the loop return
                w.proc.join(0.5)
        except Exception:  # pragma: no cover - pipes may already be gone
            pass
        if w.proc.is_alive():
            w.proc.terminate()
            w.proc.join(5.0)
        for q in (w.jobq, w.doneq):
            try:
                q.close()
            except Exception:  # pragma: no cover
                pass

    def shutdown(self) -> None:
        while self._idle:
            self.discard(self._idle.pop())


_POOLS: Dict[str, _ProcPool] = {}


def _get_pool(ctx) -> _ProcPool:
    key = ctx.get_start_method()
    pool = _POOLS.get(key)
    if pool is None:
        pool = _POOLS[key] = _ProcPool(ctx)
    return pool


def _pool_enabled(pool: Optional[bool]) -> bool:
    if pool is not None:
        return pool
    return os.environ.get("REPRO_PROCS_POOL", "1") != "0"


def pool_stats() -> Dict[str, Dict[str, int]]:
    """Spawn-pool telemetry per start method (spawned/reused/idle)."""
    return {k: {"spawned": p.spawned, "reused": p.reused,
                "idle": len(p._idle)}
            for k, p in _POOLS.items()}


def pool_shutdown() -> None:
    """Retire every idle pooled worker (tests and interpreter exit)."""
    for pool in _POOLS.values():
        pool.shutdown()


atexit.register(pool_shutdown)


# ---------------------------------------------------------------------------
# the vertex side: what each vertex process carries of its runner
# ---------------------------------------------------------------------------
class _ProcPort:
    """A vertex's ``rt`` on procs (see :class:`~repro.core.vertex.Runner`),
    pickled into every vertex process with it: the graph's failure flag
    and its trace settings, as plain picklable state.  Each vertex also
    carries ``ctl``, its own control ring (vertex → caller): ready, an
    optional error, and at EOS its trace lane and its ``FarmStats``."""

    drain = _BATCH
    lat_mask = _LAT_SAMPLE
    blocking_pop = False  # a blocked pop would never see the failure flag

    def __init__(self, flag: ShmFlag):
        self.flag = flag
        self.trace_sample = 0     # 0 = tracing off
        self.trace_capacity = 0

    @property
    def failed(self) -> bool:
        return self.flag.is_set()

    def waiter(self) -> _Backoff:
        return _Backoff()

    def open(self, v: Vertex) -> Optional[VertexTracer]:
        # the lane is built child-side and shipped back at EOS (close)
        if self.trace_sample:
            v.tracer = VertexTracer(v.name, v.path, sample=self.trace_sample,
                                    capacity=self.trace_capacity)
        if v.cpus:
            try:
                os.sched_setaffinity(0, v.cpus)
            except (AttributeError, OSError):  # hint only: never fatal
                pass
        return v.tracer

    @staticmethod
    def _put(v: Vertex, msg: Tuple) -> None:
        # the ring never legitimately fills (<= 4 messages against 8
        # slots): a timeout means the caller is gone, and the message is
        # dropped rather than wedging teardown
        v.ctl.push_wait(msg, timeout=10.0)

    def ready(self, v: Vertex) -> None:
        self._put(v, ("ready",))

    def fail(self, v: Vertex, e: BaseException) -> None:
        self.flag.set()
        # push_wait pickles synchronously, so an unpicklable exception
        # would raise mid-report and LOSE the message: probe first and
        # degrade to the repr
        try:
            pickle.dumps(e)
        except Exception:
            e_ship = None
        else:
            e_ship = e
        self._put(v, ("error", v.name, repr(e), e_ship))

    def close(self, v: Vertex) -> None:
        tr = v.tracer
        try:  # best-effort at teardown: the caller may be gone
            if tr is not None:
                self._put(v, ("trace", v.name, v.path, os.getpid(),
                              tr.events, tr.dropped))
            if v.stats is not None:
                self._put(v, ("stats", v.stats))
        except Exception:  # pragma: no cover
            pass
        for q in v.ins + v.outs:
            q.close()


class _BoardTags:
    """A wrap-around farm's :class:`~repro.core.vertex.TagSpace` on procs:
    ``retired`` lives in a one-slot shared board (the merge arbiter is its
    one writer, the dispatch arbiter reads it for quiescence — stores are
    ordered after the looped-back pushes, on x86-TSO store order)."""

    inflight = done = None  # no speculation on procs

    def __init__(self, board: ShmCounters):
        self._board = board

    @property
    def retired(self) -> int:
        return self._board.get(0)

    @retired.setter
    def retired(self, n: int) -> None:
        self._board.set(0, n)


def _fold_stats(dst: FarmStats, src: FarmStats) -> None:
    """Merge one FarmStats snapshot into another (disjoint writers: each
    counter was filled by exactly one vertex, so += is exact)."""
    dst.tasks_emitted += src.tasks_emitted
    dst.tasks_collected += src.tasks_collected
    dst.duplicates_issued += src.duplicates_issued
    dst.duplicates_dropped += src.duplicates_dropped
    dst.steals += src.steals
    dst.spills += src.spills
    dst.spill_bytes += src.spill_bytes
    dst.backpressure_stalls += src.backpressure_stalls
    for k, v in src.per_worker.items():
        dst.per_worker[k] = dst.per_worker.get(k, 0) + v
    dst.service_ewma.update(src.service_ewma)
    for x in src.latencies:
        dst.latencies.append(x)
    dst.worker_failures.extend(src.worker_failures)


# ---------------------------------------------------------------------------
# the runner: spawned vertices + shared-memory edges, driven by the caller
# ---------------------------------------------------------------------------
class ProcGraph(Runner):
    """A streaming network of processes over shared-memory SPSC rings.

    Mirrors :class:`graph.Graph`'s API (``add``/``connect``/``run``/
    ``wait``) with process semantics: the caller is the single consumer of
    the results rings, errors arrive over per-vertex control rings, and
    ``wait`` tears everything down — returns pooled workers (or joins /
    terminates direct-spawned ones) and unlinks every shared-memory
    segment, so no run leaks processes or ``/dev/shm`` entries.

    ``zero_copy`` flows to every edge ring (typed buffer-protocol slots);
    ``batch`` turns on batched emit for stage vertices — ``None`` off,
    an int for a global batch size, or ``"grain"`` to read each stage's
    declared ``grain=`` as its batch size; ``pool`` selects spawn-pool
    reuse (default: on unless ``REPRO_PROCS_POOL=0``)."""

    speculation = False
    drain = _BATCH  # a caller-side merge arbiter drains like a vertex

    def __init__(self, *, capacity: int = 512, slot_size: int = 248,
                 zero_copy: bool = True, batch: Any = None,
                 pool: Optional[bool] = None):
        self.capacity = capacity
        self.slot_size = slot_size
        self.zero_copy = zero_copy
        self.batch = batch
        self._ctx = _start_ctx()
        self._pool = _get_pool(self._ctx) if _pool_enabled(pool) else None
        self.vertices: List[Vertex] = []
        self.results: List[Any] = []
        self.failed: List[BaseException] = []
        self._rings: List[Any] = []          # every segment, for unlink
        self.failed_event = ShmFlag()
        self._rings.append(self.failed_event)
        self._port = _ProcPort(self.failed_event)
        self._ctl_rings: List[ShmRing] = []  # one per vertex, vertex->caller
        # the Farm whose stats each farm vertex's shipped snapshot joins
        self._homes: Dict[Vertex, Farm] = {}
        self._procs: List[Any] = []
        self._pool_workers: List[_PoolWorker] = []
        # post-run hooks (builders register them): read telemetry boards
        # back into the IR node's stats BEFORE shared memory is unlinked
        self.finalizers: List[Callable[[], None]] = []
        self._results_rings: List[ShmRing] = []
        self._eos_rings: set = set()
        self._eos_seen = False
        self._ready = 0
        self._cleaned = False
        # observability: when set (obs.Tracer), run() hands each vertex
        # its sampling config; lanes come home over the control rings at
        # EOS and are absorbed here (caller side) by _on_ctl
        self.tracer = None
        # live monitoring: with live_telemetry set before build(), each
        # farm gets a 2-slot single-writer board (slot 0 = emitted by the
        # dispatch arbiter, slot 1 = collected by the merge arbiter)
        # registered here by farm qualname — the Monitor reads them
        # caller-side with peek(), no ring traffic
        self.live_boards: Dict[str, ShmCounters] = {}

    # -- construction -------------------------------------------------------
    def channel(self, capacity: Optional[int] = None) -> ShmRing:
        ring = ShmRing(capacity or self.capacity, self.slot_size,
                       zero_copy=self.zero_copy)
        self._rings.append(ring)
        return ring

    def counters(self, n: int = 2) -> ShmCounters:
        board = ShmCounters(n)
        self._rings.append(board)
        return board

    def loop_channel(self, capacity: int) -> ShmRing:
        return self.channel(min(capacity, 4096))  # one segment per ring

    def tags(self, farm: Farm) -> Any:
        return _BoardTags(self.counters(1)) if farm.feedback is not None \
            else TagSpace()

    def farm_stats(self, farm: Farm, v: Vertex) -> FarmStats:
        self._homes[v] = farm
        return FarmStats()

    def live_board(self, path: str) -> Optional[ShmCounters]:
        if not self.live_telemetry:
            return None  # a monitorless lowering allocates nothing extra
        board = self.live_boards[_qualname("ff-farm", path)] = self.counters(2)
        return board

    def service_ring(self) -> ShmRing:
        return self.channel(64)

    def terminal(self, v: Vertex) -> None:
        v.outs.append(self.results_ring())

    def batch_for(self, grain: Optional[int]) -> int:
        """The emit-batch size for a stage declaring ``grain`` (1 =
        unbatched; see the class docstring)."""
        if self.batch is None:
            return 1
        if self.batch == "grain":
            return int(grain) if grain else 1
        return max(1, int(self.batch))

    def share_budget(self, budget: Any, stats: FarmStats) -> None:
        if not (hasattr(budget, "share") and hasattr(budget, "n_slots")):
            return
        # swap in a shared counter board NOW, before run() pickles the
        # vertices: every partition process attaches the same segment
        # (ShmCounters travels by name) and writes only its own slots
        budget.share(self.counters(budget.n_slots))

        def collect() -> None:
            budget.collect()  # copy the board out before it is unlinked
            budget.fold_into(stats)
        self.finalizers.append(collect)

    def add(self, v: Vertex) -> Vertex:
        v.rt = self._port
        # control edge: SPSC (this vertex produces, the caller consumes);
        # plain pickle — identity and fidelity over speed off the data path
        ring = ShmRing(8, 512, zero_copy=False)
        self._rings.append(ring)
        self._ctl_rings.append(ring)
        v.ctl = ring
        self.vertices.append(v)
        return v

    def results_ring(self) -> ShmRing:
        """A terminal edge: produced by ONE sink vertex, consumed by the
        calling process (SPSC discipline includes the caller).  Every call
        creates a fresh ring — a network with several sinks (the right row
        of a terminal all-to-all) gets one ring per sink, each
        single-producer, and the caller drains them all; the stream is
        complete when every ring has delivered EOS."""
        ring = self.channel(max(self.capacity, 1024))
        self._results_rings.append(ring)
        return ring

    # -- execution ----------------------------------------------------------
    def run(self) -> "ProcGraph":
        assert not self._procs, "graph already running"
        tr = self.tracer
        if tr is not None:  # before the vertices (and the port) pickle
            self._port.trace_sample = tr.sample
            self._port.trace_capacity = tr.capacity
        pickling_errors = (pickle.PicklingError, AttributeError, TypeError)
        if self._pool is not None:
            for v in self.vertices:
                w = self._pool.acquire()
                try:
                    w.submit(v)
                except pickling_errors as e:
                    self._pool.release(w)  # put failed pre-pipe: still clean
                    self.shutdown()
                    raise self._lowering_error(e) from e
                self._pool_workers.append(w)
                self._procs.append(w.proc)
            return self
        try:
            for v in self.vertices:
                p = self._ctx.Process(target=_vertex_main, args=(v,),
                                      name=v.name, daemon=True)
                p.start()
                self._procs.append(p)
        except pickling_errors as e:
            self.shutdown()
            raise self._lowering_error(e) from e
        return self

    @staticmethod
    def _lowering_error(e: BaseException) -> LoweringError:
        return LoweringError(
            f"the procs backend spawns vertices, so nodes/payloads/"
            f"policies must be picklable (module-level functions, "
            f"functools.partial, or ff_node subclasses — not lambdas "
            f"or closures): {e!r}")

    def wait_ready(self, timeout: float = 60.0) -> None:
        """Block until every vertex has finished ``svc_init`` (used to
        exclude spawn/import cost from steady-state measurements)."""
        deadline = time.monotonic() + timeout
        while self._ready < len(self.vertices):
            if time.monotonic() > deadline:
                self.shutdown()
                raise TimeoutError(
                    f"procs graph: {self._ready}/{len(self.vertices)} "
                    f"vertices ready after {timeout}s")
            self._drain_ctl()
            if not self.failed:
                self._check_liveness()
            if self.failed:
                self.shutdown()
                raise self.failed[0]
            if self._ready < len(self.vertices):
                time.sleep(0.002)

    def poll_results(self) -> bool:
        """Drain whatever the results rings hold right now (non-blocking).
        Returns True once EVERY results ring has delivered EOS."""
        if self._eos_seen or not self._results_rings:
            return self._eos_seen
        for i, ring in enumerate(self._results_rings):
            if i in self._eos_rings:
                continue
            while True:
                item = ring.pop()
                if item is _EMPTY:
                    break
                if item is EOS:
                    self._eos_rings.add(i)
                    break
                if type(item) is KeyBatch:  # batched wire: caller sees items
                    self.results.extend(item)
                else:
                    self.results.append(item)
        self._eos_seen = len(self._eos_rings) == len(self._results_rings)
        return self._eos_seen

    def _on_ctl(self, v: Vertex, msg: Tuple) -> None:
        if msg[0] == "ready":
            self._ready += 1
        elif msg[0] == "error":
            _, name, rep, exc = msg
            self.failed.append(
                exc if exc is not None else RuntimeError(f"{name}: {rep}"))
        elif msg[0] == "trace":
            _, name, path, pid, events, dropped = msg
            if self.tracer is not None:
                self.tracer.absorb(name, path, pid, events, dropped)
        elif msg[0] == "stats":
            _fold_stats(self._homes[v].stats, msg[1])

    def _drain_ctl(self) -> None:
        for v, ring in zip(self.vertices, self._ctl_rings):
            while True:
                msg = ring.pop()
                if msg is _EMPTY:
                    break
                self._on_ctl(v, msg)

    def _all_vertices_exited(self) -> bool:
        if self._pool is not None:
            return bool(self._pool_workers) and all(
                w.poll_done() or not w.proc.is_alive()
                for w in self._pool_workers)
        return bool(self._procs) and all(not p.is_alive()
                                         for p in self._procs)

    def _check_liveness(self) -> None:
        for p in self._procs:
            if not p.is_alive() and p.exitcode not in (0, None):
                self._drain_ctl()
                if not self.failed:
                    self.failed.append(RuntimeError(
                        f"vertex process {p.name!r} died with exit code "
                        f"{p.exitcode} (killed?)"))
                return
        if self._results_rings and self._all_vertices_exited() \
                and not self.poll_results():
            self._drain_ctl()
            if not self.failed:  # pragma: no cover - defensive
                self.failed.append(RuntimeError(
                    "every vertex exited but EOS never reached the "
                    "results ring"))

    def wait(self, timeout: Optional[float] = None) -> List[Any]:
        """Drain results to EOS, join every vertex, surface FarmStats,
        unlink all shared memory; raise the first vertex error (or
        TimeoutError after terminating a wedged network)."""
        return self._wait_until(self.poll_results, timeout)

    def _wait_until(self, done_fn: Callable[[], bool],
                    timeout: Optional[float]) -> List[Any]:
        """Shared teardown: poll ``done_fn`` (which drains whatever rings
        the caller consumes and returns True once the stream has fully
        arrived), then join/terminate and unlink everything."""
        deadline = None if timeout is None else time.monotonic() + timeout
        timed_out = False
        try:
            backoff = _Backoff()
            last_ctl_check = 0.0
            while not done_fn():
                now = time.monotonic()
                if deadline is not None and now > deadline:
                    timed_out = True
                    break
                if now - last_ctl_check > 0.05:
                    # error/liveness checks off the hot path: the caller
                    # is a polling process too, and must not tax the cores
                    # the workers are using
                    last_ctl_check = now
                    self._drain_ctl()
                    if not self.failed:
                        self._check_liveness()
                    if self.failed:
                        break
                backoff.idle()
            if timed_out or self.failed:
                self.failed_event.set()  # unblock every vertex
            self._join_vertices(deadline,
                                aborting=timed_out or bool(self.failed))
            self._drain_ctl()
            if self.failed_event.is_set() and not self.failed \
                    and not timed_out:  # timeout sets the flag itself
                # belt over _report_error: a set flag with no message must
                # never let a truncated stream pass as success
                self.failed.append(RuntimeError(
                    "a vertex signalled failure but its error report was "
                    "lost"))
            self._collect_stats()
        finally:
            self._cleanup()
        if self.failed:
            raise self.failed[0]
        if timed_out:
            raise TimeoutError(
                f"procs graph did not reach EOS within {timeout}s "
                f"(vertices terminated, shared memory unlinked)")
        return self.results

    def run_and_wait(self, timeout: Optional[float] = None) -> List[Any]:
        return self.run().wait(timeout)

    def _collect_stats(self) -> None:
        while self.finalizers:
            self.finalizers.pop()()  # runs before _cleanup unlinks boards

    def _join_vertices(self, deadline: Optional[float],
                       aborting: bool) -> None:
        """Wait for every vertex to finish, then hand processes back.

        Pool mode: poll each worker's done token; clean live workers
        return to the pool, wedged or dead ones are terminated and
        retired (a failed graph must never donate a poisoned process).
        Direct-spawn mode: join, then terminate stragglers — as before.
        """
        if self._pool is not None:
            grace = 2.0 if aborting else (
                10.0 if deadline is None
                else max(0.1, deadline - time.monotonic()))
            end = time.monotonic() + grace
            while time.monotonic() < end:
                if all(w.poll_done() or not w.proc.is_alive()
                       for w in self._pool_workers):
                    break
                time.sleep(0.001)
            for w in self._pool_workers:
                if w.poll_done() and w.proc.is_alive():
                    self._pool.release(w)
                else:
                    w.proc.terminate()
                    w.proc.join(5.0)
                    self._pool.discard(w)
            self._pool_workers = []
            self._procs = []
            return
        for p in self._procs:
            grace = 10.0 if deadline is None \
                else max(0.1, deadline - time.monotonic())
            p.join(grace if not aborting else 2.0)
        for p in self._procs:
            if p.is_alive():
                p.terminate()
                p.join(5.0)

    def shutdown(self) -> None:
        """Hard stop: abort live vertices, unlink all shared memory.

        Pooled workers get a short grace to notice the failure flag and
        finish their job cleanly (so the pool keeps them); anything still
        busy after that is terminated and retired."""
        self.failed_event.set()
        if self._pool is not None:
            self._join_vertices(time.monotonic() + 1.0, aborting=True)
        else:
            for p in self._procs:
                if p.is_alive():
                    p.terminate()
            for p in self._procs:
                p.join(5.0)
        self._cleanup()

    def _cleanup(self) -> None:
        if self._cleaned:
            return
        self._cleaned = True
        for ring in self._rings:
            ring.unlink()


class ProcProgram:
    """Procs lowering: the skeleton wired onto spawned processes over
    shared-memory SPSC rings — ``lower(skel, "procs")``.

    Same ordered-output contract as the other two backends; the win is
    that pure-Python (GIL-holding) ``svc`` functions actually run in
    parallel.  ``timeout`` bounds the whole run: a hung child process is
    terminated (and all shared memory unlinked) instead of wedging the
    caller.  ``fuse`` is the same grain-aware pass as the threads backend
    — with processes costing more per vertex than threads, collapsing
    sub-threshold hand-offs pays off even sooner.

    Data-plane options (see :class:`ProcGraph`): ``zero_copy`` (typed
    buffer-protocol slots, default on), ``batch`` (batched emit: ``None``
    off / int / ``"grain"``), ``pool`` (spawn-pool reuse; ``None`` =
    honour ``REPRO_PROCS_POOL``, default on)."""

    backend = "procs"

    def __init__(self, skeleton: Skeleton, *, capacity: int = 512,
                 slot_size: int = 248, timeout: Optional[float] = 120.0,
                 fuse: Any = "auto", fuse_threshold_us: Optional[float] = None,
                 zero_copy: bool = True, batch: Any = None,
                 pool: Optional[bool] = None,
                 trace: Any = False, metrics: Any = False,
                 monitor: Any = None):
        self.skeleton = _fuse_for_lowering(skeleton, fuse, fuse_threshold_us)
        self.capacity = capacity
        self.slot_size = slot_size
        self.timeout = timeout
        self.zero_copy = zero_copy
        self.batch = batch
        self.pool = pool
        self.tracer = _coerce_tracer(trace)
        self.metrics = _coerce_metrics(metrics)
        self.monitor = _coerce_monitor(monitor)
        self.last_trace = None
        self.last_report = None

    def to_graph(self, stream: Optional[Iterable[Any]] = None) -> ProcGraph:
        g = ProcGraph(capacity=self.capacity, slot_size=self.slot_size,
                      zero_copy=self.zero_copy, batch=self.batch,
                      pool=self.pool)
        # per-farm live counter boards exist only when a monitor will read
        # them
        g.live_telemetry = self.monitor is not None
        try:
            # Build the driving Source separately (at path "in") so the
            # user skeleton keeps its root IR paths — telemetry keys
            # vertices by path, and wrapping in a fresh Pipeline would
            # shift every top-level index by one.
            in_ring = None
            if stream is not None:
                in_ring = build(Source(stream), g, None, False, "in")
            build(self.skeleton, g, in_ring, True)
        except BaseException:
            g.shutdown()  # unlink whatever the partial build created
            raise
        if self.tracer is not None:
            g.tracer = self.tracer
        return g

    def __call__(self, items: Iterable[Any]) -> List[Any]:
        xs = list(items)
        if not xs:
            return []  # nothing to stream; skip the spawn entirely
        g = self.to_graph(xs)
        reg = self.metrics
        mon = self.monitor
        if mon is not None:
            mon.attach(g, skeleton=self.skeleton, backend="procs")
        try:
            if reg is None:
                out = g.run_and_wait(self.timeout)
            else:
                hw: Dict[str, int] = {}
                t0 = time.monotonic()
                g.run()

                def drain() -> bool:  # the wait loop doubles as the hw tap
                    g.sample_high_water(hw)
                    return g.poll_results()

                out = g._wait_until(drain, self.timeout)
                farms = {q: farm_stats_snapshot(st)
                         for q, st in walk_stats(self.skeleton)}
                self.last_report = reg.finalize(reg.report(
                    farms=farms, queues=hw, pool=pool_stats(),
                    meta={"backend": "procs", "vertices": len(g.vertices),
                          "items_in": len(xs), "items_out": len(out),
                          "wall_s": time.monotonic() - t0}))
        finally:
            if mon is not None:
                mon.detach()
        if self.tracer is not None:
            self.last_trace = self.tracer.trace()
        return out


BACKENDS["procs"] = ProcProgram


class ProcAccelerator:
    """Self-offloading accelerator over processes (TR-10-03, procs twin of
    :class:`graph.Accelerator`): the *caller* is the single producer of
    the inbound ring(s) and the single consumer of the results, so a
    Python main thread can offload pure-Python kernels to a process farm
    and keep computing.

        acc = ProcAccelerator(Farm(f, 4))   # f must be picklable
        for x in tasks: acc.offload(x)
        results = acc.wait()

    For a plain farm — no emitter/collector node, no feedback edge, a
    ``pick()``-based scheduling policy (rr / ondemand / costmodel) — the
    accelerator runs **caller-side arbitration**: the calling thread IS
    the dispatch and merge arbiter (tagging and placement here, collection
    and reorder-by-tag through a :class:`~repro.core.vertex.MergeVertex`
    it drives itself), so the network is exactly ``nworkers`` processes
    and zero polling arbiters.  That is the paper's self-offloading design
    taken literally, and on a small machine it matters: every extra
    polling process is a core-thief.  Skeletons that need an arbiter
    process (compositions, feedback loops, worksteal's pump) fall back to
    the full process graph transparently.

    ``offload`` opportunistically drains results while the target ring is
    full — the caller is part of the network, so it must not create a
    blocking cycle through itself."""

    def __init__(self, net: Any, *, capacity: int = 512,
                 slot_size: int = 248, ready_timeout: float = 60.0,
                 zero_copy: bool = True, pool: Optional[bool] = None):
        skel = as_skeleton(net)
        self._g = ProcGraph(capacity=capacity, slot_size=slot_size,
                            zero_copy=zero_copy, pool=pool)
        self._farm: Optional[Farm] = None
        try:
            if self._caller_side_ok(skel):
                self._build_caller_farm(skel)
            else:
                self._in = self._g.channel()
                build(skel, self._g, self._in, True)
        except BaseException:
            self._g.shutdown()  # unlink whatever the partial build created
            raise
        self._g.run()
        self._g.wait_ready(ready_timeout)
        self._closed = False

    @staticmethod
    def _caller_side_ok(skel: Skeleton) -> bool:
        if not isinstance(skel, Farm):
            return False
        if skel.emitter is not None or skel.collector is not None \
                or skel.feedback is not None or skel.speculative:
            return False
        sched = make_scheduler(skel.scheduling)
        # token-holding policies (custom place/pump, e.g. worksteal) need
        # the dispatch arbiter's pump loop — same test StageVertex uses
        return type(sched).place is Scheduler.place

    def _build_caller_farm(self, skel: Farm) -> None:
        g = self._g
        self._farm = skel
        self._sched = make_scheduler(skel.scheduling)
        self._stats = FarmStats()
        self._in_rings: List[ShmRing] = []
        self._service_rings: List[ShmRing] = []
        self._merge = merge = MergeVertex(TagSpace(), ordered=skel.ordered)
        merge.rt, merge.stats = g, self._stats
        cap = skel.capacity or g.capacity
        needs_service = self._sched.needs_service_stats
        for i, node in enumerate(skel.worker_nodes):
            w = g.add(WorkerVertex(node, i, record_service=needs_service,
                                   name=f"ff-worker-{i}"))
            w.stats = g.farm_stats(skel, w)
            if needs_service:
                w.service_ring = g.service_ring()
                self._service_rings.append(w.service_ring)
            w.cpus = self._sched.worker_cpus(i, len(skel.worker_nodes))
            q_in, q_out = g.channel(cap), g.channel(cap)
            w.ins.append(q_in)
            w.outs.append(q_out)
            self._in_rings.append(q_in)
            merge.ins.append(q_out)
        self._sched.bind(self._in_rings, self._stats)
        self._next_tag = 0
        self._drain_backoff = _Backoff()

    # -- caller-side merge ---------------------------------------------------
    def _drain(self) -> bool:
        """One pass over the worker service and output rings; True if
        anything moved."""
        for ring in self._service_rings:
            while True:
                upd = ring.pop()
                if upd is _EMPTY:
                    break
                self._sched.observe_service(upd[0], upd[1])
        return self._merge._collect()

    def _caller_done(self) -> bool:
        self._drain()
        return len(self._merge._eos) >= len(self._merge.ins)

    def _network_dead(self) -> bool:
        """A vertex raised (failure flag) or silently died (liveness
        probe): the caller's push loops must stop blocking on rings no
        process will ever drain."""
        if self._g.failed_event.is_set():
            return True
        self._g._check_liveness()
        return bool(self._g.failed)

    # -- public surface ------------------------------------------------------
    @property
    def results(self) -> List[Any]:
        return self._g.results

    def offload(self, task: Any) -> None:
        assert not self._closed, "accelerator already EOS'd"
        if self._farm is None:
            spins = 0
            while not self._in.push(task):
                self._g.poll_results()
                if self._network_dead():
                    self._g.wait(timeout=5.0)  # raises the vertex error
                    raise RuntimeError("accelerator network failed")
                spins += 1
                if spins > 64:
                    time.sleep(_POLL)
            return
        tag = self._next_tag
        issued = time.monotonic() if tag & _LAT_SAMPLE == 0 else 0.0
        tok = (tag, issued, task)
        self._next_tag += 1
        ring = self._in_rings[self._sched.pick()]
        while not ring.push(tok):
            if self._drain():
                self._drain_backoff.reset()
                continue
            if self._network_dead():
                self._g._wait_until(self._caller_done, 5.0)  # raises
                raise RuntimeError("accelerator network failed")
            self._drain_backoff.idle()
        self._stats.tasks_emitted += 1

    def eos(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self._farm is None:
            spins = 0
            while not self._in.push(EOS):
                self._g.poll_results()
                if self._network_dead():
                    return  # wait() will surface the vertex error
                spins += 1
                if spins > 64:
                    time.sleep(_POLL)
            return
        for q in self._in_rings:
            # keep draining while pushing: a full out-ring must not wedge
            # the caller against a full in-ring (the caller is both
            # arbiters — it cannot block on itself).  A dead vertex never
            # drains its ring: bail and let wait() raise its error.
            while not q.push(EOS):
                if self._drain():
                    self._drain_backoff.reset()
                    continue
                if self._network_dead():
                    return
                self._drain_backoff.idle()

    def wait(self, timeout: Optional[float] = None) -> List[Any]:
        self.eos()
        if self._farm is None:
            return self._g.wait(timeout)
        try:
            return self._g._wait_until(self._caller_done, timeout)
        finally:
            # flush reorder residue + surface the caller's FarmStats onto
            # the IR node (the workers' arrive over their control rings)
            self._merge._flush()
            _fold_stats(self._farm.stats, self._stats)
