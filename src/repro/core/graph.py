"""The threads runner — the skeleton IR's in-process host backend.

Every vertex of the protocol in :mod:`repro.core.vertex` runs as a thread
and every edge is a lock-free :class:`~repro.core.spsc.SPSCQueue` (paper
Sec. 3.1).  :class:`Graph` is the runner: it supplies the decisions that
differ from the procs runner — a failure is an entry in ``Graph.failed``,
a farm's ``FarmStats`` is one object its vertices share, an idle arbiter
sleeps 50 µs and takes one item per ring per pass, an idle worker blocks
in ``pop_wait`` — and starts and joins the threads.  ``lower(skel,
"threads")`` builds a :class:`ThreadProgram`; :class:`Accelerator` is
TR-10-03's self-offloading (the caller thread is the source, ``offload()``
a push onto the accelerator's inbound ring).

The old names (``Net``, ``Pipeline``, ``Farm``, ``compose``, ``ff_node``,
the vertex classes, ``build``) remain importable from here.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Type

from . import obs as _obs
from .obs import farm_stats_snapshot
from .skeleton import (BACKENDS, GO_ON, AllToAll, Farm, FarmStats, Feedback,
                       FnNode, Pipeline, Skeleton, Source, Stage,
                       _coerce_metrics, _coerce_monitor, _coerce_tracer,
                       _fuse_for_lowering, as_skeleton, compose, ff_node,
                       walk_stats)
from .spsc import EOS, SPSCQueue
from .vertex import (_POLL, DispatchVertex, MergeVertex, Runner, StageVertex,
                     TagSpace, Vertex, WorkerVertex, build, ring_list)

__all__ = [
    "GO_ON", "FarmStats", "TagSpace", "ff_node", "FnNode",
    "Graph", "Vertex", "StageVertex", "DispatchVertex", "WorkerVertex",
    "MergeVertex", "build", "ring_list", "ThreadProgram",
    "Net", "Stage", "Source", "Pipeline", "Farm", "Feedback", "AllToAll",
    "compose", "Accelerator",
]

# PR 1's Net API: the declarative layer lives in skeleton.py now
Net = Skeleton


class _Poll:
    """The threads idle policy: a fixed 50 µs sleep (a sleeping thread is
    nearly free, so there is nothing to adapt)."""

    __slots__ = ()

    def reset(self) -> None:
        pass

    def idle(self) -> None:
        time.sleep(_POLL)


_WAIT = _Poll()


class Graph(Runner):
    """A streaming network: vertices (one thread each) + SPSC edges.

    The low-level API (``add`` / ``connect``) supports arbitrary
    topologies; :func:`~repro.core.vertex.build` wires skeletons.
    ``results`` collects whatever reaches a vertex with no outbound edge.
    The graph is also every vertex's ``rt`` (see
    :class:`~repro.core.vertex.Runner`)."""

    drain = 1           # items per inbound ring per pass
    lat_mask = 0        # stamp every token: the clock is a cheap vDSO read
    blocking_pop = True

    def __init__(self, *, queue_class: Type = SPSCQueue, capacity: int = 512):
        self.queue_class = queue_class
        self.capacity = capacity
        self.vertices: List[Vertex] = []
        self.results: List[Any] = []
        self.failed: List[BaseException] = []
        self._threads: List[threading.Thread] = []
        # post-run hooks (builders register them): fold telemetry boards
        # back into the IR node's stats once the vertices have joined
        self.finalizers: List[Callable[[], None]] = []
        # observability: when set (obs.Tracer), run() hands each vertex its
        # own single-writer lane before the threads start
        self.tracer = None
        # drain-time taps: callables run exactly once inside wait(), after
        # the vertex threads have joined but BEFORE the finalizers tear any
        # telemetry down — the only point where "the stream is complete"
        # and "the rings still exist" are both true, so a short run that
        # finished before the first caller-side poll still lands exactly
        # one sample per edge (and never races the caller's results drain)
        self.drain_samplers: List[Callable[[], None]] = []

    # -- the vertex side ----------------------------------------------------
    def waiter(self) -> _Poll:
        return _WAIT

    def open(self, v: Vertex) -> Any:
        return v.tracer  # bound by run()

    def ready(self, v: Vertex) -> None:
        pass

    def fail(self, v: Vertex, e: BaseException) -> None:
        self.failed.append(e)

    def close(self, v: Vertex) -> None:
        pass

    # -- construction -------------------------------------------------------
    def channel(self, capacity: Optional[int] = None,
                queue_class: Optional[Type] = None) -> Any:
        qc = queue_class or self.queue_class
        return qc(capacity or self.capacity)

    ring = channel  # a skeleton's queue_class is honoured here

    def add(self, v: Vertex) -> Vertex:
        v.rt = self
        self.vertices.append(v)
        return v

    # -- execution ----------------------------------------------------------
    def run(self) -> "Graph":
        assert not self._threads, "graph already running"
        tr = self.tracer
        if tr is None and _obs.profiling():
            # under JAX's profiler every vertex records, unsampled, into
            # the process-wide record: checked once a run, never per item
            tr = _obs.process_tracer()
        if tr is not None:
            for v in self.vertices:
                if v.tracer is None:
                    v.tracer = tr.vertex(v.name, v.path)
        self._threads = [
            threading.Thread(target=v._run, name=v.name, daemon=True)
            for v in self.vertices
        ]
        for t in self._threads:
            t.start()
        return self

    def wait(self, timeout: Optional[float] = None) -> List[Any]:
        for t in self._threads:
            t.join(timeout)
        while self.drain_samplers:
            self.drain_samplers.pop()()  # run once, even if wait() re-enters
        while self.finalizers:
            self.finalizers.pop()()  # run once, even if wait() is re-entered
        if self.failed:
            raise self.failed[0]
        return self.results

    def run_and_wait(self) -> List[Any]:
        return self.run().wait()


class ThreadProgram:
    """Threads lowering: the skeleton wired onto a :class:`Graph` (one
    thread per vertex, lock-free SPSC rings for every edge).

    ``fuse`` is the grain-aware fusion pass (``"auto"``, ``True`` or
    ``False``; see :func:`repro.core.skeleton.fuse`), with
    ``fuse_threshold_us`` its threshold (None = the measured hand-off
    cost).

    ``trace=True`` (or a :class:`~repro.core.obs.Tracer`) gives every
    vertex a sampled event lane; the merged
    :class:`~repro.core.obs.Trace` lands on ``last_trace`` after each
    call.  ``metrics=True`` (or a
    :class:`~repro.core.obs.MetricsRegistry`) samples queue depths while
    the run drains and absorbs the skeleton's ``FarmStats`` into a
    :class:`~repro.core.obs.RunReport` on ``last_report``.

    ``monitor=True`` (or a :class:`~repro.core.monitor.Monitor`) attaches
    the continuous live sampler for the duration of each call: queue
    depths, farm EWMAs and counters land in ``monitor.timeline`` while
    the stream runs — see :mod:`repro.core.monitor`."""

    backend = "threads"

    def __init__(self, skeleton: Skeleton, *,
                 queue_class: Optional[Type] = None, capacity: int = 512,
                 fuse: Any = "auto", fuse_threshold_us: Optional[float] = None,
                 trace: Any = False, metrics: Any = False,
                 monitor: Any = None):
        self.skeleton = _fuse_for_lowering(skeleton, fuse, fuse_threshold_us)
        self.queue_class = queue_class
        self.capacity = capacity
        self.tracer = _coerce_tracer(trace)
        self.metrics = _coerce_metrics(metrics)
        self.monitor = _coerce_monitor(monitor)
        self.last_trace = None
        self.last_report = None

    def to_graph(self, stream: Optional[Iterable[Any]] = None) -> Graph:
        g = Graph(queue_class=self.queue_class or SPSCQueue,
                  capacity=self.capacity)
        # a live monitor wants per-worker service EWMAs: opt the farm
        # workers into the timing they otherwise skip
        g.live_telemetry = self.monitor is not None
        # Build the driving Source separately (at path "in") so the user
        # skeleton keeps its root IR paths — telemetry keys vertices by
        # path, and wrapping in a fresh Pipeline would shift every
        # top-level index by one.
        in_ring = None
        if stream is not None:
            in_ring = build(Source(stream), g, None, False, "in")
        build(self.skeleton, g, in_ring, True)
        if self.tracer is not None:
            g.tracer = self.tracer
        return g

    def __call__(self, items: Iterable[Any]) -> List[Any]:
        xs = list(items)
        g = self.to_graph(xs)
        reg = self.metrics
        mon = self.monitor
        if mon is not None:
            mon.attach(g, skeleton=self.skeleton, backend="threads")
        try:
            if reg is None:
                out = g.run_and_wait()
            else:
                hw: Dict[str, int] = {}
                t0 = time.monotonic()
                # a short run can finish before the first poll below: the
                # drain sampler runs inside wait() after the vertex threads
                # join but before teardown, so every edge key still lands
                # exactly once — and never races the caller's results drain
                g.drain_samplers.append(lambda: g.sample_high_water(hw))
                g.run()
                while any(t.is_alive() for t in g._threads):
                    g.sample_high_water(hw)
                    time.sleep(0.0005)
                out = g.wait()
                farms = {q: farm_stats_snapshot(st)
                         for q, st in walk_stats(self.skeleton)}
                self.last_report = reg.finalize(reg.report(
                    farms=farms, queues=hw,
                    meta={"backend": "threads", "vertices": len(g.vertices),
                          "items_in": len(xs), "items_out": len(out),
                          "wall_s": time.monotonic() - t0}))
        finally:
            if mon is not None:
                mon.detach()
        if self.tracer is not None:
            self.last_trace = self.tracer.trace()
        return out


BACKENDS["threads"] = ThreadProgram


class Accelerator:
    """Self-offloading accelerator (TR-10-03): run a network alongside the
    caller, who streams tasks into it and harvests results later.

    The caller thread is the single producer of the inbound ring (SPSC
    discipline holds: ``offload`` must be called from one thread), so the
    main thread of an application can offload kernels to a farm and keep
    computing — the paper's "accelerator" usage of FastFlow.

        acc = Accelerator(Farm(FnNode(f), 4))
        for x in tasks: acc.offload(x)
        results = acc.wait()
    """

    def __init__(self, net: Any, *, queue_class: Type = SPSCQueue,
                 capacity: int = 512):
        self._g = Graph(queue_class=queue_class, capacity=capacity)
        self._in = self._g.channel()
        build(as_skeleton(net), self._g, self._in, True)
        self._g.run()
        self._closed = False

    @property
    def results(self) -> List[Any]:
        return self._g.results

    def offload(self, task: Any) -> None:
        assert not self._closed, "accelerator already EOS'd"
        self._in.push_wait(task)

    def eos(self) -> None:
        if not self._closed:
            self._closed = True
            self._in.push_wait(EOS)

    def wait(self, timeout: Optional[float] = None) -> List[Any]:
        self.eos()
        return self._g.wait(timeout)
