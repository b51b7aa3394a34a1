"""The vertex protocol of the host runtime, written once for both runners.

FastFlow (paper Sec. 2-3) is a layered design: between the lock-free SPSC
ring (``spsc.py``, Sec. 3.1) and the declarative skeletons
(``skeleton.py``) sits a runtime for arbitrary streaming networks in which
any ``ff_node`` is a vertex, every edge is an SPSC ring, and all
multi-party coordination is done by *active arbiters* walking their
private ring endpoints — never a lock or an atomic read-modify-write on
the data path.

This module is that runtime: the vertex lifecycle, the stage vertex, the
farm's dispatch arbiter, worker and merge arbiter, the all-to-all's host
vertices, and :func:`build`, the walker that wires a skeleton IR tree into
vertices and rings.  Two runners execute it and supply only what differs
by transport (see :class:`Runner`):

=======================  =========================  ==========================
decision                 threads (``graph.Graph``)  procs (``procgraph``)
=======================  =========================  ==========================
a vertex is              a thread                   a spawned, pooled process
an edge is               ``SPSCQueue``              ``ShmRing``
loop ``retired`` count   a plain int                a ``ShmCounters`` slot
failure                  the ``Graph.failed`` list  a ``ShmFlag``
an idle loop             ``pop_wait``, or a 50 µs   an AIMD ``_Backoff``; up
                         sleep; one item per ring   to 256 items per ring per
                         per pass                   wake
``FarmStats``            one object, shared         one per vertex, shipped
                                                    home at EOS and folded
results                  ``Graph.results``          one results ring per sink
=======================  =========================  ==========================

Construct-to-paper map
----------------------
=============================  ================================================
``SPSCQueue``/``ShmRing`` edge Sec. 3.1 "Fast SPSC queues" (Lamport ring)
``Vertex`` + a runner          Sec. 2, Fig. 1: networks of concurrent entities
                               over SPSC channels
``ff_node`` svc/svc_init/_end  Fig. 2: the node API
``DispatchVertex``             Figs. 1-2 "Emitter": fans one stream out over
                               private rings through a ``sched.Scheduler``
``MergeVertex``                Figs. 1-2 "Collector": fans the rings back in
``Farm(ordered=True)``         Fig. 1 (right): tagged tokens reordered at the
                               collector
``Farm(feedback=...)``         Sec. 5 wrap-around edge; termination by loop
                               quiescence
=============================  ================================================

A farm token is the tuple ``(tag, issued, payload)`` on both runners.
``issued`` is a ``time.monotonic()`` stamp on the tags the runner samples
(``lat_mask``: every tag on threads, 1 in 16 on procs, where a clock read
costs a syscall) and ``0.0`` elsewhere.

Single-writer discipline (what makes this lock-free): every ring has one
producer and one consumer; the dispatch arbiter counts the tokens it sends
into a loop and the merge arbiter counts those that leave it
(``TagSpace.retired``), and each reads the other's count benignly stale.

Straggler re-issue (``Farm(speculative=True)``) is threads-only: the
dispatch arbiter re-sends tokens older than ``straggler_factor × p95`` and
the merge arbiter drops duplicates by tag, through ``TagSpace.inflight`` and
``TagSpace.done``, which both arbiters share in one address space.  A
worker that dies in a speculative farm is survivable: its tags age out and
go to live workers.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from . import obs as _obs
from .a2a import KeyRouter, _ident
from .obs import qualname as _qualname
from .sched import Scheduler, make_scheduler
from .skeleton import (GO_ON, AllToAll, EmitMany, Farm, FarmStats, Feedback,
                       FnNode, KeyBatch, LoweringError, Pipeline, Skeleton,
                       Source, Stage, _FarmEmitMany, _ReorderNode, ff_node)
from .spsc import EOS, SPSCQueue

__all__ = [
    "TagSpace", "Runner", "Vertex", "StageVertex", "DispatchVertex",
    "WorkerVertex", "MergeVertex", "A2ALeftVertex", "build", "ring_list",
]

_EMPTY = SPSCQueue._EMPTY
_POLL = 0.000_05  # a blocked push's sleep after 64 spins (and threads' idle)


class _Aborted(Exception):
    """Internal: this vertex gave up because another vertex already failed
    (its peer may be dead and its ring full — blocking would hang)."""


class TagSpace:
    """A farm's tag state shared by its two arbiters, one writer per field.

    The merge arbiter writes ``retired`` (tokens that left a wrap-around
    loop; the dispatch arbiter counts those it sent in) and, under
    speculation, ``done``; the dispatch arbiter writes ``inflight``, the
    tokens a straggler sweep may re-issue.  ``inflight``/``done`` exist
    only for a speculative farm: nothing else needs a per-tag record."""

    __slots__ = ("inflight", "done", "retired")

    def __init__(self, speculative: bool = False):
        self.inflight: Optional[Dict[int, tuple]] = {} if speculative else None
        self.done: Optional[Dict[int, bool]] = {} if speculative else None
        self.retired = 0


# ---------------------------------------------------------------------------
# what a backend supplies
# ---------------------------------------------------------------------------
class Runner:
    """The transport decisions a backend makes for the protocol.

    **Build side** (called by :func:`build` in the caller's process):
    ``add(v)``, ``channel(capacity)``, ``connect(src, dst, ...)`` and the
    hooks below, whose defaults are those of a runner whose vertices share
    one address space.

    **Vertex side** (the object a vertex holds as ``v.rt``, set by
    ``add``; on procs it is pickled into the vertex's process):

    * ``failed`` — truthy once any vertex of the graph has failed;
    * ``results`` — the list a vertex with no outbound edge appends to;
    * ``drain`` — items a loop takes from one inbound ring per pass;
    * ``lat_mask`` — a token is stamped when ``tag & lat_mask == 0``;
    * ``blocking_pop`` — a worker with no steal ring may block in
      ``pop_wait`` (it then never sees the failure signal);
    * ``waiter()`` — an idle policy: ``reset()`` after a pass that moved
      something, ``idle()`` after one that did not;
    * ``open(v)`` → the vertex's trace lane or None, ``ready(v)``,
      ``fail(v, exc)`` and ``close(v)`` — the lifecycle's hand-offs.
    """

    capacity = 512
    # set by a monitored lowering: farm workers time their service so the
    # live sampler has EWMAs to read (see monitor.py)
    live_telemetry = False
    # straggler re-issue shares TagSpace.inflight/done between the arbiters
    speculation = True

    def connect(self, src: "Vertex", dst: "Vertex", *,
                capacity: Optional[int] = None) -> Any:
        return _link(src, dst, self.channel(capacity))

    def ring(self, capacity: Optional[int], queue_class: Optional[type]) -> Any:
        """A farm's or all-to-all's edge: ``queue_class`` (the skeleton's
        ring choice) is a threads option, which other runners ignore."""
        return self.channel(capacity)

    def loop_channel(self, capacity: int) -> Any:
        """A farm's wrap-around ring (merge → dispatch)."""
        return self.channel(capacity)

    def tags(self, farm: Farm) -> Any:
        return TagSpace(farm.speculative)

    def farm_stats(self, farm: Farm, v: "Vertex") -> FarmStats:
        """The ``FarmStats`` vertex ``v`` of ``farm`` writes into."""
        return farm.stats

    def live_board(self, path: str) -> Any:
        """A farm's live emitted/collected counters, where the monitor
        cannot read its ``FarmStats`` directly."""
        return None

    def service_ring(self) -> Any:
        """A worker → dispatch ring of service-time EWMAs, where the
        policy cannot read the workers' ``FarmStats`` directly."""
        return None

    def terminal(self, v: "Vertex") -> None:
        """Give a sink vertex its way out (here: ``results``)."""

    def batch_for(self, grain: Optional[int]) -> int:
        """Items a stage vertex gathers per outbound push."""
        return 1

    def share_budget(self, budget: Any, stats: FarmStats) -> None:
        """Surface a reduction's memory budget into ``stats`` after a run."""
        self.finalizers.append(lambda: budget.fold_into(stats))

    # -- telemetry taps (caller side, racy-but-benign ring reads) ----------
    def _depths(self) -> Iterable[Tuple[str, int]]:
        for v in self.vertices:
            depth = 0
            for ring in v.outs:
                try:
                    depth = max(depth, len(ring))
                except (TypeError, OSError, ValueError):
                    pass  # ValueError: a procs segment released mid-teardown
            yield _qualname(v.name, v.path), depth

    def sample_high_water(self, into: Dict[str, int]) -> Dict[str, int]:
        """Profile tap: each vertex's outbound queue depth, keeping the
        per-key maximum across calls.  Keys are ``name@path``, so two
        same-named vertices in different IR positions never collide."""
        for key, depth in self._depths():
            if depth > into.get(key, -1):
                into[key] = depth
        return into

    def sample_depths(self, into: Dict[str, int]) -> Dict[str, int]:
        """Live-monitor tap: the *instantaneous* outbound depth per vertex
        (overwrite semantics: one call is one timeline frame)."""
        into.update(self._depths())
        return into


# ---------------------------------------------------------------------------
# the vertices
# ---------------------------------------------------------------------------
class Vertex:
    """A network vertex: private ring endpoints, run by its runner.

    ``tracer`` (an obs lane) and ``path`` (the IR position) default at
    class level, so an untraced vertex pays one attribute read that
    resolves against the class dict — no per-vertex storage, no
    allocation.  ``cpus`` is a placement hint only the procs runner
    applies; ``stats`` is set for farm vertices by the walker."""

    tracer = None
    path = ""
    cpus: Optional[Tuple[int, ...]] = None
    stats: Optional[FarmStats] = None
    rt: Any = None

    def __init__(self, node: Optional[ff_node] = None, *,
                 name: str = "ff-vertex"):
        self.node = node
        self.name = name
        # batch-aware nodes (SpillFold) take a whole KeyBatch in one svc
        # call; everyone else gets it unpacked by the vertex loop
        self._takes_batches = bool(getattr(node, "accepts_batches", False))
        self.ins: List[Any] = []
        self.outs: List[Any] = []

    def _run(self) -> None:
        rt = self.rt
        tr = rt.open(self)
        t_birth = time.monotonic() if tr is not None else 0.0
        if tr is not None:
            _obs.bind_thread(tr)  # regions in svc record into this lane
        try:
            if self.node is not None:
                if tr is not None and getattr(self.node, "wants_tracer",
                                              False):
                    # opt-in node-level events (SpillFold's spill instants)
                    # go into THIS vertex's lane: one writer per buffer
                    self.node.tracer = tr
                self.node.svc_init()
            rt.ready(self)
            self._loop()
        except _Aborted:
            pass  # secondary shutdown: the original error is already out
        except BaseException as e:
            self._on_error(e)
        finally:
            for q in self.outs:
                self._push_abortable(q, EOS)
            if self.node is not None:
                try:
                    self.node.svc_end()
                except BaseException as e:  # pragma: no cover - defensive
                    rt.fail(self, e)
            if tr is not None:
                tr.instant("eos")
                tr.span("life", t_birth, time.monotonic())
            rt.close(self)

    def _on_error(self, e: BaseException) -> None:
        self.rt.fail(self, e)

    def _loop(self) -> None:
        raise NotImplementedError

    def _push_abortable(self, q: Any, item: Any) -> bool:
        """Blocking push that gives up (returns False) once the graph has
        failed — the ring's consumer may be dead, and blocking on a full
        ring would hang the whole network's teardown."""
        spins = 0
        while not q.push(item):
            spins += 1
            if spins > 64:
                if self.rt.failed:
                    return False
                time.sleep(_POLL)
        return True

    def _deliver(self, payload: Any) -> None:
        """Emit one payload downstream, or into the runner's results when
        this vertex has no outbound edge."""
        if self.outs:
            if not self._push_abortable(self.outs[0], payload):
                raise _Aborted()
        else:
            self.rt.results.append(payload)


class StageVertex(Vertex):
    """Generic vertex: any fan-in (nondeterministic merge of untagged
    payloads), any fan-out (``"bcast"``, or any scheduling policy — name or
    :class:`~repro.core.sched.Scheduler` — for single-consumer routes).
    With no inbound edge it is a *source*: ``svc(None)`` until it returns
    ``None`` (EOS) — paper Fig. 2's emitter protocol.

    ``batch > 1`` (procs, ``ProcGraph(batch=...)``) gathers single-route
    output locally and ships it ``batch`` items at a time through
    ``push_many``: one slot header and one tail store per run of items.
    The buffer is shipped when the inbound rings run dry and after the
    node's EOS flush, before this vertex's own EOS, so the stream is the
    same as unbatched."""

    def __init__(self, node: ff_node, *, route: Any = "rr", batch: int = 1,
                 name: str = "ff-stage"):
        super().__init__(node, name=name)
        if route == "bcast":
            self._sched: Optional[Scheduler] = None
            self._route: Optional[Callable] = None
        else:
            try:
                self._sched = make_scheduler(route)
            except ValueError:
                raise ValueError(
                    f"unknown Stage route {route!r}: expected 'bcast', a "
                    f"scheduling policy name, or a Scheduler") from None
            # resolve the payload-dependent hook ONCE: the per-emission
            # path must not pay a route() call for the policies that never
            # override it
            self._route = (self._sched.route
                           if type(self._sched).route is not Scheduler.route
                           else None)
            if type(self._sched).place is not Scheduler.place \
                    and self._route is None:
                # stage fan-out is routed per emission; a policy that
                # holds tokens in the arbiter (custom place/pump, e.g.
                # worksteal) needs the farm dispatch arbiter
                raise ValueError(
                    f"Stage route {route!r} is a token-holding policy "
                    f"(custom place()); stage fan-out supports only "
                    f"pick()/route()-based policies — use a Farm for it")
        self.route = route
        self.batch = max(1, int(batch))
        self._obuf: Optional[List[Any]] = [] if self.batch > 1 else None

    def _loop(self) -> None:
        if self._sched is not None:
            self._sched.bind(self.outs, None)
        node, tr = self.node, self.tracer
        if not self.ins:  # source
            while True:
                if tr is not None:
                    t0 = tr.begin()
                    out = node.svc(None)
                    tr.end(t0, "svc")
                else:
                    out = node.svc(None)
                if out is None or out is EOS:
                    break
                if out is GO_ON:
                    continue
                self._emit(out)
            self._flush_eos()
            return
        rt, ins = self.rt, self.ins
        drain, wait = rt.drain, rt.waiter()
        eos: set = set()
        while len(eos) < len(ins):
            progress = False
            for i, q in enumerate(ins):
                if i in eos:
                    continue
                n = drain
                while n:
                    item = q.pop()
                    if item is _EMPTY:
                        break
                    n -= 1
                    progress = True
                    if item is EOS:
                        eos.add(i)
                        break
                    if type(item) is KeyBatch and not self._takes_batches:
                        # batched wire format: unpack here so the node
                        # still sees items (batching is transport only)
                        for x in item:
                            if tr is not None:
                                t0 = tr.begin()
                                out = node.svc(x)
                                tr.end(t0, "svc")
                            else:
                                out = node.svc(x)
                            if out is None or out is GO_ON:
                                continue
                            self._emit(out)
                        continue
                    if tr is not None:
                        t0 = tr.begin()
                        out = node.svc(item)
                        tr.end(t0, "svc")
                    else:
                        out = node.svc(item)
                    if out is None or out is GO_ON:
                        continue  # filtered
                    self._emit(out)
            if progress:
                wait.reset()
            else:
                if rt.failed:
                    raise _Aborted()
                if self._obuf:
                    # nothing inbound: ship the partial batch rather than
                    # hold the stream's tail hostage to the batch size
                    self._flush_batch()
                wait.idle()
        self._flush_eos()

    def _flush_eos(self) -> None:
        """EOS flush (FastFlow's eosnotify): the node may emit buffered
        state (``svc_eos``) before this vertex's own EOS propagates — how
        the keyed folds and window operators release their accumulators."""
        out = self.node.svc_eos()
        if out is not None and out is not GO_ON:
            self._emit(out)
        if self._obuf:
            self._flush_batch()

    def _emit(self, out: Any) -> None:
        if type(out) is KeyBatch:  # one wire message; consumers unpack
            if not out:
                return
            if not self.outs:
                self.rt.results.extend(out)  # the caller sees items
                return
        elif isinstance(out, EmitMany):  # multi-emit (e.g. a reorder flush)
            for o in out:
                self._emit(o)
            return
        if not self.outs:
            self.rt.results.append(out)
        elif self._obuf is not None:
            self._obuf.append(out)
            if len(self._obuf) >= self.batch:
                self._flush_batch()
        elif self.route == "bcast":
            for q in self.outs:
                if not self._push_abortable(q, out):
                    raise _Aborted()
        else:
            w = self._sched.pick() if self._route is None else self._route(out)
            if not self._push_abortable(self.outs[w], out):
                raise _Aborted()

    def _flush_batch(self) -> None:
        buf, out = self._obuf, self.outs[0]
        wait = self.rt.waiter()
        i = 0
        while i < len(buf):
            n = out.push_many(buf[i:] if i else buf)
            if n:
                i += n
                continue
            if self.rt.failed:
                buf.clear()
                raise _Aborted()
            wait.idle()
        buf.clear()


class DispatchVertex(Vertex):
    """The farm's Emitter arbiter (paper Figs. 1-2).

    One logical input — a source ``ff_node``, upstream rings, or the
    wrap-around ring — fanned out over private rings to the workers.  It
    owns tag assignment and straggler re-issue; *placement* is delegated
    to a :class:`~repro.core.sched.Scheduler` (rr / ondemand / worksteal /
    costmodel / user-supplied) driven entirely from this arbiter, so the
    single-writer discipline is untouched.  With a ``loop_ring`` this
    vertex is the loop master: it ends only when every upstream edge has
    delivered EOS *and* the loop is quiescent (as many tokens retired as
    it sent in, the wrap-around ring drained, none left in the policy).

    ``live`` (a counter board, slot 0 = emitted) and ``service_rings``
    (workers' service-time EWMAs) are set by runners whose monitor and
    policy cannot read ``FarmStats`` directly."""

    def __init__(
        self,
        tags: TagSpace,
        node: Optional[ff_node] = None,
        *,
        scheduling: Any = "rr",
        speculative: bool = False,
        straggler_factor: float = 4.0,
        min_straggler_age: float = 0.05,
        loop_ring: Optional[Any] = None,
        name: str = "ff-emitter",
    ):
        super().__init__(node, name=name)
        self.sched = make_scheduler(scheduling)
        self.tags = tags
        self.speculative = speculative
        self.straggler_factor = straggler_factor
        self.min_straggler_age = min_straggler_age
        self.loop_ring = loop_ring
        self.live: Any = None
        self.service_rings: List[Any] = []
        # wrap-around tokens stashed while a worker ring is full (see
        # _push_with_loop_drain: this is what breaks cyclic backpressure)
        self._stash: List[Any] = []
        self._next_tag = 0
        self._entered = 0
        self._lat_mask = 0

    def _push_with_loop_drain(self, q: Any, tok: tuple) -> None:
        """Blocking push that keeps draining the wrap-around ring while the
        target worker ring is full.  Without this a full worker ring can
        deadlock the cycle: workers blocked on the merge arbiter, the merge
        arbiter blocked on the wrap-around ring, and this arbiter blocked
        here — draining into the local stash breaks the wait cycle.  Gives
        up once the graph has failed (the ring's worker may be dead)."""
        if q.push(tok):
            return  # fast path: no stall, no clock read
        tr = self.tracer
        t0 = time.monotonic() if tr is not None else 0.0
        spins = 0
        while not q.push(tok):
            if self.loop_ring is not None:
                item = self.loop_ring.pop()
                if item is not _EMPTY:
                    self._stash.append(item)
                    continue
            spins += 1
            if spins > 64:
                if self.rt.failed:
                    raise _Aborted()
                time.sleep(_POLL)
        if tr is not None:
            tr.span("stall", t0, time.monotonic())

    def _dispatch(self, task: Any) -> None:
        tag = self._next_tag
        self._next_tag = tag + 1
        tok = (tag, 0.0 if tag & self._lat_mask else time.monotonic(), task)
        if self.speculative:
            self.tags.inflight[tag] = tok
        if self.loop_ring is not None:
            self._entered += 1
        self.sched.place(tok, self._emit_to)
        self.stats.tasks_emitted += 1
        if self.live is not None:
            self.live.add(0, 1)
        # backpressure for token-holding policies (worksteal): stop taking
        # input while the policy backlog is over its high-water mark
        hw = self.sched.high_water
        if hw is not None and self.sched.pending() > hw:
            self._throttle(hw)

    def _throttle(self, hw: int) -> None:
        """Pump the policy below ``hw``, draining the wrap-around ring
        meanwhile (the same deadlock-avoidance as _push_with_loop_drain)."""
        sched, tr = self.sched, self.tracer
        t0 = time.monotonic() if tr is not None else 0.0
        spins = 0
        while sched.pending() > hw:
            if sched.pump():
                continue
            if self.rt.failed:
                raise _Aborted()
            if self.loop_ring is not None:
                item = self.loop_ring.pop()
                if item is not _EMPTY:
                    self._stash.append(item)
                    continue
            spins += 1
            if spins > 64:
                time.sleep(_POLL)
        if tr is not None:
            tr.span("stall", t0, time.monotonic())

    def _emit_to(self, widx: int, tok: tuple) -> None:
        """Blocking-push callback handed to ``Scheduler.place`` (policies
        that hold tokens, like worksteal, push from ``pump`` instead)."""
        self._push_with_loop_drain(self.outs[widx], tok)

    def _drain_service(self) -> None:
        for ring in self.service_rings:
            while True:
                upd = ring.pop()
                if upd is _EMPTY:
                    break
                self.sched.observe_service(upd[0], upd[1])

    def _respeculate(self) -> None:
        ts, st = self.tags, self.stats
        now = time.monotonic()
        threshold = self.straggler_factor * max(st.p95_latency(),
                                                self.min_straggler_age)
        for t, tok in list(ts.inflight.items()):
            if t in ts.done:
                continue
            if now - tok[1] > threshold:
                dup = (t, now, tok[2])
                if self.outs[self.sched.pick()].push(dup):
                    # re-arm the age clock; a still-stale tag (its copy
                    # landed on a dead worker) speculates again, to another
                    # worker — what makes the farm survive worker loss
                    ts.inflight[t] = dup
                    st.duplicates_issued += 1

    def _loop(self) -> None:
        rt, ts, st, sched = self.rt, self.tags, self.stats, self.sched
        sched.bind(self.outs, st)
        self._lat_mask = rt.lat_mask
        node, tr = self.node, self.tracer
        ins, drain = self.ins, rt.drain
        loop_ring, service = self.loop_ring, self.service_rings
        # source mode: with no inbound edge the emitter node generates the
        # stream, one svc(None) a pass, until it returns None
        source = node is not None and not ins
        steals0 = st.steals if tr is not None else 0
        wait = rt.waiter()
        eos: set = set()
        ndisp = spec_mark = 0  # dispatches, and at the last straggler sweep
        while True:
            progress = sched.pump()  # flush/steal for token-holding policies
            if service:
                self._drain_service()
            if tr is not None and st.steals != steals0:
                tr.instant("steal", {"count": st.steals - steals0})
                steals0 = st.steals
            # wrap-around tokens first: looped-back work is older
            while self._stash:
                self._dispatch(self._stash.pop(0))
                ndisp += 1
                progress = True
            if loop_ring is not None:
                while True:
                    item = loop_ring.pop()
                    if item is _EMPTY:
                        break
                    progress = True
                    self._dispatch(item)
                    ndisp += 1
                    if tr is not None:
                        tr.tick("loop")
            if source:
                progress = True
                if tr is not None:
                    t0 = tr.begin()
                    task = node.svc(None)
                    tr.end(t0, "svc")
                else:
                    task = node.svc(None)
                if task is None or task is EOS:
                    source = False
                elif task is not GO_ON:
                    self._dispatch(task)
                    ndisp += 1
            for i, q in enumerate(ins):
                if i in eos:
                    continue
                n = drain
                while n:
                    item = q.pop()
                    if item is _EMPTY:
                        break
                    n -= 1
                    progress = True
                    if item is EOS:
                        eos.add(i)
                        break
                    if node is not None:
                        # emitter node as per-item scheduler/filter
                        if tr is not None:
                            t0 = tr.begin()
                            item = node.svc(item)
                            tr.end(t0, "svc")
                        else:
                            item = node.svc(item)
                        if item is None or item is GO_ON:
                            continue
                    self._dispatch(item)
                    ndisp += 1
            if self.speculative and ndisp - spec_mark >= 32:
                # per 32 dispatches, not per pass: _respeculate sorts the
                # latency sample and must not run while idle
                spec_mark = ndisp
                self._respeculate()
            # the EOS goes out only behind every token the policy still
            # holds (e.g. worksteal backlogs)
            if not source and len(eos) == len(ins) and not self._stash \
                    and not sched.pending():
                # quiescence check — read order matters: ``retired``
                # first, then the ring.  The merge arbiter pushes looped-
                # back tasks *before* it counts the token retired, so if
                # the counts match here, every task looped back by a
                # retired token is already visible in the ring.
                if loop_ring is None or (self._entered == ts.retired
                                         and loop_ring.empty()):
                    break
            if rt.failed:
                raise _Aborted()  # a vertex died: no quiescence possible
            if progress:
                wait.reset()
            elif sched.pending():
                # yield (not sleep) while the policy still holds tokens: a
                # fine-grain worker drains its primed ring in far less
                # than a poll tick
                time.sleep(0)
            else:
                wait.idle()
        # straggler watchdog: keep re-issuing until everything is collected
        while self.speculative and any(t not in ts.done for t in ts.inflight):
            if rt.failed:
                break  # e.g. the collector died: tags can never complete
            self._respeculate()
            time.sleep(0.002)


class WorkerVertex(Vertex):
    """Farm worker: one inbound and one outbound ring; the tag rides
    through untouched (the node never sees it) and names the ``svc`` span
    (``seq``), so regions inside it carry the item's id.

    ``record_service`` (a policy that reads service times, or a live
    monitor) keeps this worker's service-time EWMA in
    ``stats.service_ewma[index]`` (single writer per key), and also
    pushes it on ``service_ring`` where the runner has one.  With an
    ``idle_ring`` (the worksteal policy's side-channel) the worker
    advertises itself whenever its inbound ring runs dry, which is what
    triggers a steal from the deepest peer backlog."""

    def __init__(self, node: ff_node, index: int, *,
                 survivable: bool = False, idle_ring: Optional[Any] = None,
                 record_service: bool = False, name: str = "ff-worker"):
        super().__init__(node, name=name)
        self.index = index
        self.survivable = survivable
        self.idle_ring = idle_ring
        self.record_service = record_service
        self.service_ring: Any = None

    def _loop(self) -> None:
        q_in, q_out = self.ins[0], self.outs[0]
        rt, node, tr, index = self.rt, self.node, self.tracer, self.index
        per_worker = self.stats.per_worker
        ewmas = self.stats.service_ewma
        record, service = self.record_service, self.service_ring
        idle_ring = self.idle_ring
        blocking = idle_ring is None and rt.blocking_pop
        wait = rt.waiter()
        signaled = False
        spins = 0
        while True:
            if blocking:
                tok = q_in.pop_wait()
            else:
                tok = q_in.pop()
                if tok is _EMPTY:
                    if idle_ring is not None and \
                            (not signaled or spins % 512 == 511):
                        # steal side-channel: advertise idleness, and again
                        # now and then — a signal consumed while the
                        # arbiter had nothing to give must not strand us
                        signaled = idle_ring.push(index) or signaled
                    spins += 1
                    if spins > 64:
                        if rt.failed:
                            raise _Aborted()
                        wait.idle()
                    continue
                signaled = False
                spins = 0
                wait.reset()
            if tok is EOS:
                return
            tag, issued, payload = tok
            if tr is not None:
                tr.seq = tag          # the item's id, for its regions
                tb = tr.begin()
            if record:
                t0 = time.monotonic()
                result = node.svc(payload)
                dt = time.monotonic() - t0
                prev = ewmas.get(index)
                ewma = ewmas[index] = dt if prev is None \
                    else 0.8 * prev + 0.2 * dt
                if service is not None:
                    service.push((index, ewma))  # drop-if-full is fine
            else:
                result = node.svc(payload)
            if tr is not None:
                tr.end(tb, "svc")
            if not self._push_abortable(q_out, (tag, issued, result)):
                raise _Aborted()
            per_worker[index] = per_worker.get(index, 0) + 1

    def _on_error(self, e: BaseException) -> None:
        if self.survivable:
            # a dying worker of a speculative farm is survivable: its
            # outstanding tags age out and re-speculate to live workers
            self.stats.worker_failures.append((self.index, repr(e)))
        else:
            super()._on_error(e)


class MergeVertex(Vertex):
    """The farm's Collector arbiter (paper Figs. 1-2).

    Merges the worker rings into one logical stream: optional
    reorder-by-tag (``ordered`` — the tagged-token collector of Fig. 1
    right), exactly-once by tag under speculation, an optional collector
    ``ff_node``, and an optional wrap-around route: ``feedback`` decides,
    per result, what leaves the loop and what goes back around.
    :meth:`_collect` is one pass over the worker rings, so a caller can
    arbitrate a farm itself (``ProcAccelerator``)."""

    def __init__(
        self,
        tags: TagSpace,
        node: Optional[ff_node] = None,
        *,
        ordered: bool = False,
        loop_ring: Optional[Any] = None,
        feedback: Optional[Callable[[Any], Tuple[Any, Iterable[Any]]]] = None,
        name: str = "ff-collector",
    ):
        super().__init__(node, name=name)
        self.tags = tags
        self.ordered = ordered
        self.loop_ring = loop_ring
        self.feedback = feedback
        self.live: Any = None  # counter board, slot 1 = collected
        self._eos: set = set()
        self._next = 0
        self._reorder: Dict[int, Any] = {}

    def _loop(self) -> None:
        rt, ins = self.rt, self.ins
        wait = rt.waiter()
        while len(self._eos) < len(ins):
            if self._collect():
                wait.reset()
            else:
                if rt.failed:
                    raise _Aborted()
                wait.idle()
        self._flush()

    def _collect(self) -> bool:
        """One pass over the worker rings; True if anything moved."""
        st, done, live, eos = self.stats, self.tags.done, self.live, self._eos
        reorder = self._reorder if self.ordered else None
        drain = self.rt.drain
        progress = False
        for i, q in enumerate(self.ins):
            if i in eos:
                continue
            n = drain
            while n:
                tok = q.pop()
                if tok is _EMPTY:
                    break
                n -= 1
                progress = True
                if tok is EOS:
                    eos.add(i)
                    break
                tag, issued, payload = tok
                if done is not None:  # speculation: exactly once by tag
                    if tag in done:
                        st.duplicates_dropped += 1
                        continue
                    done[tag] = True
                st.tasks_collected += 1
                if live is not None:
                    live.add(1, 1)
                if issued:
                    st.latencies.append(time.monotonic() - issued)
                if reorder is None:
                    self._complete(payload)
                else:
                    reorder[tag] = payload
                    nxt = self._next
                    while nxt in reorder:
                        self._complete(reorder.pop(nxt))
                        nxt += 1
                    self._next = nxt
        return progress

    def _flush(self) -> None:
        """Release any reorder residue (tags skipped upstream)."""
        reorder = self._reorder
        for t in sorted(reorder):
            self._complete(reorder.pop(t))

    def _complete(self, payload: Any) -> None:
        if payload is GO_ON:
            # a worker returning GO_ON emits nothing (ff_node contract);
            # the token just retires silently
            self._retire()
            return
        tr = self.tracer
        if self.node is not None:
            if tr is not None:
                t0 = tr.begin()
                payload = self.node.svc(payload)
                tr.end(t0, "svc")
            else:
                payload = self.node.svc(payload)
            if payload is None or payload is GO_ON:
                self._retire()
                return
        if self.feedback is not None:
            emit, new_tasks = self.feedback(payload)
            # push wrap-around tasks BEFORE retiring the token: the dispatch
            # arbiter's quiescence check relies on this ordering
            for t in new_tasks:
                if not self._push_abortable(self.loop_ring, t):
                    raise _Aborted()
                if tr is not None:
                    tr.tick("loop")
            self._retire()
            if emit is None:
                return
            payload = emit
        else:
            self._retire()
        if isinstance(payload, _FarmEmitMany):
            # a farm-absorbed tail multi-emitted: flatten downstream, as
            # the unfused trailing stage would have
            for p in payload:
                self._deliver(p)
            return
        self._deliver(payload)

    def _retire(self) -> None:
        if self.loop_ring is not None:
            self.tags.retired += 1


# ---------------------------------------------------------------------------
# the all-to-all's host vertices (TR-12-04): an N×M matrix of SPSC rings
# ---------------------------------------------------------------------------
class A2ALeftVertex(StageVertex):
    """Left vertex of the matrix: applies its node, then key-routes each
    emission onto its own private ring to the owning right vertex —
    single writer per edge, no arbiter between the layers."""

    def __init__(self, node: ff_node, router: KeyRouter, *,
                 name: str = "ff-a2a-left"):
        super().__init__(node, name=name)
        self.router = router

    def _emit(self, out: Any) -> None:
        if type(out) is KeyBatch:
            for w, sub in self.router.split(out):  # one message per dest
                if not self._push_abortable(self.outs[w], sub):
                    raise _Aborted()
            return
        if isinstance(out, EmitMany):
            for o in out:
                self._emit(o)
            return
        if not self._push_abortable(self.outs[self.router(out)], out):
            raise _Aborted()


class _A2ATagger(ff_node):
    """Attach the global stream index at the scatter of an ordered a2a."""

    def __init__(self):
        self._next = 0

    def svc(self, x):
        i = self._next
        self._next += 1
        return i, x


class _TagCarry(ff_node):
    """Run a node under the ``(index, payload)`` envelope; tags ride the
    matrix untouched.  ``GO_ON``/``None`` filters the item — the reorder
    stage's EOS residue flush releases everything past the gap."""

    def __init__(self, node: ff_node):
        self._node = node

    def svc_init(self) -> None:
        self._node.svc_init()

    def svc_end(self) -> None:
        self._node.svc_end()

    def svc(self, task):
        i, x = task
        r = self._node.svc(x)
        if r is None or r is GO_ON:
            return GO_ON
        if isinstance(r, EmitMany):
            raise RuntimeError(
                "multi-emit (EmitMany) under AllToAll(ordered=True) is "
                "unsupported: stream tags are 1:1, so several emissions "
                "cannot share one index — use ordered=False for 1:n nodes")
        return i, r

    def svc_eos(self):
        out = self._node.svc_eos()
        if out is not None and out is not GO_ON:
            raise RuntimeError(
                "an EOS-flushing node (svc_eos) cannot run under "
                "AllToAll(ordered=True): flush items carry no stream index "
                "— keyed reductions are unordered by construction")
        return None


def _a2a_budgets(skel: AllToAll) -> List[Any]:
    """The distinct memory-budget boards carried by the right row.

    Duck-typed (a budget exposes ``fold_into``, and ``share``/``collect``/
    ``n_slots`` for the procs board swap — :class:`repro.core.oocore.
    MemoryBudget` is the implementation), so the walker stays free of an
    oocore import; identity-deduped because one reduction's partitions
    share one budget."""
    out: List[Any] = []
    for n in skel.right_nodes:
        # a fused right row (autotune's a2a absorption) hides the budget
        # holder behind a FusedNode wrapper — look through its parts
        for p in getattr(n, "nodes", None) or [n]:
            b = getattr(p, "budget", None)
            if b is not None and hasattr(b, "fold_into") \
                    and not any(b is x for x in out):
                out.append(b)
    return out


# ---------------------------------------------------------------------------
# the skeleton walker: IR tree -> vertices + rings, on any runner
# ---------------------------------------------------------------------------
def ring_list(in_ring: Optional[Any]) -> List[Any]:
    """Normalise a build edge: ``None`` (no upstream), one ring, or a list
    of rings (an all-to-all's right row emits one ring per vertex — the
    downstream vertex fan-in-merges them all, EOS counted per edge)."""
    if in_ring is None:
        return []
    return list(in_ring) if isinstance(in_ring, (list, tuple)) else [in_ring]


def _link(src: Vertex, dst: Vertex, ring: Any) -> Any:
    src.outs.append(ring)
    dst.ins.append(ring)
    return ring


def _add(rt: Runner, v: Vertex, path: str,
         farm: Optional[Farm] = None) -> Vertex:
    rt.add(v)
    v.path = path
    if farm is not None:
        v.stats = rt.farm_stats(farm, v)
    return v


def _out(rt: Runner, v: Vertex, terminal: bool,
         capacity: Optional[int]) -> Optional[Any]:
    """Give ``v`` its outbound edge: the runner's results when terminal,
    else a fresh ring that the caller wires downstream."""
    if terminal:
        rt.terminal(v)
        return None
    ring = rt.channel(capacity)
    v.outs.append(ring)
    return ring


def build(skel: Skeleton, rt: Runner, in_ring: Optional[Any],
          terminal: bool, path: str = "") -> Optional[Any]:
    """Wire a skeleton IR node into runner ``rt`` between an optional
    inbound ring (or ring *list* — see :func:`ring_list`) and, unless
    ``terminal``, a fresh outbound ring, which it returns.

    ``path`` is the node's position in the IR tree (``"1"``, ``"1.2"`` …);
    vertices remember it so telemetry keys (``sample_high_water``, trace
    lanes) are namespaced per IR path and two same-named nodes never
    collide.  This is what makes skeletons close under composition: a
    ``Farm`` is a vertex of the enclosing ``Pipeline``, and vice versa."""
    if isinstance(skel, AllToAll):
        return _build_a2a(skel, rt, ring_list(in_ring), terminal, path)

    if isinstance(skel, Source):
        assert in_ring is None, "Source cannot have an upstream edge"
        return build(Stage(skel.node, name=skel.name, grain=skel.grain,
                           capacity=skel.capacity), rt, None, terminal, path)

    if isinstance(skel, Pipeline):
        ring = in_ring
        last = len(skel.stages) - 1
        for i, s in enumerate(skel.stages):
            p = f"{path}.{i}" if path else str(i)
            if i == last:
                return build(s, rt, ring, terminal, p)
            ring = build(s, rt, ring, False, p)

    if isinstance(skel, Feedback):
        # predicate loop -> tagger + wrap-around farm + reorder (Sec. 5)
        return build(skel.as_thread_net(), rt, in_ring, terminal, path)

    if isinstance(skel, Farm):
        return _build_farm(skel, rt, in_ring, terminal, path)

    if isinstance(skel, Stage):
        v = _add(rt, StageVertex(skel.node, name=skel.name,
                                 batch=rt.batch_for(skel.grain)), path)
        v.ins.extend(ring_list(in_ring))
        # per-edge capacity: a tuned Stage sizes its own outbound ring
        return _out(rt, v, terminal, skel.capacity)

    raise TypeError(f"cannot lower {skel!r} to a host graph")


def _build_farm(skel: Farm, rt: Runner, in_ring: Optional[Any],
                terminal: bool, path: str) -> Optional[Any]:
    if skel.speculative and not rt.speculation:
        raise LoweringError(
            "speculative straggler re-issue is threads-only (its tag "
            "bookkeeping is shared between the two arbiters); use "
            "lower(skel, 'threads') for it")
    qc = skel.queue_class
    cap = skel.capacity or rt.capacity
    tags = rt.tags(skel)
    loop_ring = (rt.loop_channel(skel.feedback_capacity)
                 if skel.feedback is not None else None)
    disp = _add(rt, DispatchVertex(
        tags, skel.emitter, scheduling=skel.scheduling,
        speculative=skel.speculative,
        straggler_factor=skel.straggler_factor,
        min_straggler_age=skel.min_straggler_age, loop_ring=loop_ring),
        path, skel)
    if in_ring is not None:
        disp.ins.extend(ring_list(in_ring))
    else:
        assert skel.emitter is not None, \
            "a standalone farm needs an emitter (or compose it after a Source)"
    merge = _add(rt, MergeVertex(
        tags, skel.collector, ordered=skel.ordered, loop_ring=loop_ring,
        feedback=skel.feedback), path, skel)
    disp.live = merge.live = rt.live_board(path)

    sched = disp.sched
    record = sched.needs_service_stats or rt.live_telemetry
    nworkers = len(skel.worker_nodes)
    for i, node in enumerate(skel.worker_nodes):
        # the policy may want a steal side-channel (worker -> arbiter)
        idle = sched.worker_channel(i, lambda c: rt.ring(c, qc))
        w = _add(rt, WorkerVertex(node, i, survivable=skel.speculative,
                                  idle_ring=idle, record_service=record,
                                  name=f"ff-worker-{i}"), path, skel)
        if sched.needs_service_stats:
            w.service_ring = rt.service_ring()
            if w.service_ring is not None:
                disp.service_rings.append(w.service_ring)
        w.cpus = sched.worker_cpus(i, nworkers)
        _link(disp, w, rt.ring(cap, qc))
        _link(w, merge, rt.ring(cap, qc))
    return _out(rt, merge, terminal, skel.capacity)


def _build_a2a(skel: AllToAll, rt: Runner, in_rings: List[Any],
               terminal: bool, path: str) -> Optional[Any]:
    """``[scatter] → N left → (N×M rings) → M right → [reorder]``.

    The scatter exists only with an upstream stream (without one the left
    nodes run as sources); the reorder stage only under ``ordered=``.
    Returns the outbound ring list — one ring per right vertex (the
    downstream vertex fan-in-merges them), or a single ring after a
    reorder stage.  A terminal all-to-all gives each sink its own way out
    (on procs, one results ring per sink, EOS counted per ring)."""
    qc = skel.queue_class
    cap = skel.capacity or rt.capacity
    if skel.ordered:
        lnodes = [_TagCarry(n) for n in skel.left_nodes]
        rnodes = [_TagCarry(n) for n in skel.right_nodes]
    else:
        lnodes, rnodes = list(skel.left_nodes), list(skel.right_nodes)
    for b in _a2a_budgets(skel):
        rt.share_budget(b, skel.stats)

    if in_rings:
        scatter = _add(rt, StageVertex(
            _A2ATagger() if skel.ordered else FnNode(_ident),
            route=skel.scheduling, name=f"{skel.name}-scatter"), path)
        scatter.ins.extend(in_rings)
    elif skel.ordered:
        raise LoweringError(
            "AllToAll(ordered=True) needs an upstream stream to assign "
            "stream indices; compose it after a Source")
    else:
        scatter = None  # left nodes are sources (svc(None) protocol)

    lefts = []
    for i, node in enumerate(lnodes):
        lv = _add(rt, A2ALeftVertex(
            node, KeyRouter(skel.by, skel.nright, tagged=skel.ordered),
            name=f"{skel.name}-L{i}"), path)
        if scatter is not None:
            _link(scatter, lv, rt.ring(cap, qc))
        lefts.append(lv)
    rights = [_add(rt, StageVertex(n, name=f"{skel.name}-R{j}"), path)
              for j, n in enumerate(rnodes)]
    for lv in lefts:           # the N×M edge matrix
        for rv in rights:
            _link(lv, rv, rt.ring(cap, qc))

    tails = rights
    if skel.ordered:
        tail = _add(rt, StageVertex(_ReorderNode(),
                                    name=f"{skel.name}-reorder"), path)
        for rv in rights:
            _link(rv, tail, rt.ring(cap, qc))
        tails = [tail]
    out_rings = [_out(rt, tv, terminal, cap) for tv in tails]
    if terminal:
        return None
    return out_rings[0] if len(out_rings) == 1 else out_rings
