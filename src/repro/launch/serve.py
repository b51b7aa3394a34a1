"""Serving driver — continuous batching as the order-preserving farm,
running ON the skeleton graph (``Source(requests) ∘ Farm(decode_step,
feedback=still_generating)``).

The mapping from paper Sec. 3.1 to an inference engine:

  Emitter   = the **admitter**: pulls requests off an SPSC ring, assigns a
              monotone tag, a decode-batch slot and KV pages from the SPMC
              ``PagePool`` (one allocating entity — the admitter; freers —
              the collector — return pages over SPSC free-rings);
  Workers   = the decode step itself: every mesh device advances its shard
              of the (continuously re-filled) batch each iteration;
  Collector = detokeniser: detects finished sequences, releases their pages,
              and emits results **in tag order** (the reorder buffer of the
              order-preserving farm).

Since the skeleton-IR redesign, ``run()`` no longer drives a hand-rolled
while loop: it lowers ``compose(Source(submitted_requests),
Farm(decode_step, feedback=still_generating))`` to the thread graph.
Requests stream through the farm's dispatch arbiter; each *decode tick*
token circulates the wrap-around (collector → emitter) SPSC ring while any
admitted sequence is still generating, and the loop-quiescence protocol —
upstream EOS ∧ all tokens retired ∧ wrap-around ring drained — is exactly
the engine's old termination condition, now provided by the runtime.  One
tick = one jitted decode step advancing the whole continuous batch, so the
batching behaviour (and ``steps_run`` accounting) is unchanged.

Requests are admitted into recycled slots mid-stream; per-slot ``start_pos``
masks each request's attention to its own KV span.  Prompt ingestion is
token-by-token (one decode step per prompt token), which keeps one jitted
step for everything; a batched prefill path is the obvious production
extension and exists as ``steps.make_prefill_step`` for the dry-run.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCHS
from ..core.allocator import PagePool
from ..core.obs import MetricsRegistry, Tracer
from ..core.sched import CostModel
from ..core.skeleton import Farm, Source, compose, lower
from ..core.spsc import SPSCQueue
from ..models import decode_step as model_decode, init_cache, init_params
from ..models.config import ModelConfig
from ..runtime.compile_cache import enable_compile_cache

__all__ = ["Request", "ServeEngine"]

_TICK = object()  # the decode-tick token circulating the wrap-around ring


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int = 16
    eos_id: Optional[int] = None
    # filled by the engine:
    submitted: float = 0.0  # monotonic submit() timestamp (latency origin)
    tag: int = -1
    slot: int = -1
    start: int = -1
    generated: List[int] = dataclasses.field(default_factory=list)
    fed: int = 0  # prompt tokens consumed


class ServeEngine:
    """``slo=`` takes a :class:`~repro.core.monitor.SLOMonitor` — after
    every ``run()`` its thresholds (p99 latency over the engine's
    ``serve.request_latency_us`` histogram, goodput in tokens/s) are
    checked; alerts land in ``slo.events``, in the registry's
    ``slo.alerts`` counter (and its ``watch()`` callbacks), and as
    ``alert`` instants on an ``slo-monitor`` trace lane
    (``engine.last_trace``), time-aligned with the run."""

    def __init__(self, cfg: ModelConfig, *, max_batch: int = 4,
                 max_len: int = 256, seed: int = 0, params=None,
                 slo=None):
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_len = max_len
        # one jitted program: eager init would hold each stacked leaf's
        # float32 draw on the device next to its cast copy
        self.params = params if params is not None else jax.jit(
            init_params, static_argnums=0)(cfg, jax.random.PRNGKey(seed))
        self.cache = init_cache(cfg, max_batch, max_len)
        # SPMC pool: slots are the pages (admitter allocs, collector frees)
        self.pool = PagePool(max_batch, nfreers=1)
        self.in_q = SPSCQueue(1024)
        self._pending: deque = deque()             # admitted-to-graph queue
        self.active: Dict[int, Request] = {}       # slot -> request
        self.done: Dict[int, Request] = {}         # tag -> finished request
        self.emit_next = 0
        self.results: List[Request] = []
        self.cache_len = 0
        self.tag_counter = 0
        self._step = jax.jit(
            lambda p, b, c, l: model_decode(p, b, c, l, cfg),
            donate_argnums=(2,))
        self.steps_run = 0
        self.metrics = MetricsRegistry()
        self._latency = self.metrics.histogram("serve.request_latency_us")
        self.last_report = None
        self.slo = slo
        self.tracer = None
        self.last_trace = None
        if slo is not None:
            if slo.registry is None:
                slo.registry = self.metrics
            self.tracer = Tracer()
            slo.bind(self.tracer)

    # -- emitter side --------------------------------------------------------
    def submit(self, req: Request) -> None:
        if req.submitted == 0.0:
            req.submitted = time.monotonic()
        self.in_q.push_wait(req)

    def _admit(self) -> None:
        while self.pool.available() or self.pool.drain():
            if self._pending:                      # streamed in via the graph
                nxt = self._pending.popleft()
            else:
                nxt = self.in_q.pop()
                if nxt is SPSCQueue._EMPTY:
                    return
            slot = self.pool.alloc()
            nxt.tag = self.tag_counter
            self.tag_counter += 1
            nxt.slot = slot
            nxt.start = self.cache_len
            self._reset_slot(slot)
            self.active[slot] = nxt

    def _reset_slot(self, slot: int) -> None:
        """Zero the recycled slot's cache state (SSM state must reset;
        attention K/V is masked by start_pos, zeroing is belt-and-braces)."""
        def z(leaf):
            if leaf.ndim >= 2 and leaf.shape[-4:-3] != ():  # kv caches (.., B, T, H, D)
                pass
            return leaf

        def zero_slot(leaf):
            # batch dim position differs per leaf family; all our cache
            # leaves carry batch at axis -4 (kv: L,B,T,H,D) or -3/-2 (ssm)
            for ax in range(leaf.ndim):
                if leaf.shape[ax] == self.max_batch:
                    idx = [slice(None)] * leaf.ndim
                    idx[ax] = slot
                    return leaf.at[tuple(idx)].set(0)
            return leaf

        self.cache = jax.tree.map(zero_slot, self.cache)

    # -- one farm iteration ----------------------------------------------------
    def step(self) -> None:
        self._admit()
        if not self.active:
            return
        tokens = np.zeros((self.max_batch, 1), np.int32)
        start = np.zeros((self.max_batch,), np.int32)
        for slot, req in self.active.items():
            if req.fed < len(req.prompt):
                tokens[slot, 0] = req.prompt[req.fed]
            else:
                tokens[slot, 0] = req.generated[-1] if req.generated else 0
            start[slot] = req.start
        batch = {"tokens": jnp.asarray(tokens), "start_pos": jnp.asarray(start)}
        if self.cfg.family == "audio":
            raise NotImplementedError("audio serving uses frame embeddings")
        logits, self.cache = self._step(self.params, batch, self.cache,
                                        jnp.int32(self.cache_len))
        self.cache_len += 1
        self.steps_run += 1
        nxt = np.asarray(jnp.argmax(logits, axis=-1))
        finished = []
        for slot, req in self.active.items():
            if req.fed < len(req.prompt):
                req.fed += 1          # still ingesting the prompt
                continue
            tok = int(nxt[slot])
            req.generated.append(tok)
            if (req.eos_id is not None and tok == req.eos_id) or \
               len(req.generated) >= req.max_new:
                finished.append(slot)
        # -- collector: free pages, emit in tag order -------------------------
        for slot in finished:
            req = self.active.pop(slot)
            self.pool.free(slot, 0)
            self.done[req.tag] = req
        while self.emit_next in self.done:
            req = self.done.pop(self.emit_next)
            if req.submitted:
                self._latency.observe(
                    (time.monotonic() - req.submitted) * 1e6)
            self.results.append(req)
            self.emit_next += 1

    def _drain_submitted(self) -> List[Request]:
        """Everything submitted so far, in submission order (the stream the
        serving graph's Source replays)."""
        reqs: List[Request] = []
        while True:
            r = self.in_q.pop()
            if r is SPSCQueue._EMPTY:
                return reqs
            reqs.append(r)

    def run(self, *, max_steps: int = 10_000) -> List[Request]:
        """Serve everything submitted so far, by running the serving graph

            Source(requests) ∘ Farm(decode_step, feedback=still_generating)

        to loop quiescence.  Request tasks flow from the Source through the
        farm's dispatch arbiter into the single decode worker (which owns
        params/cache — SPSC discipline makes the shared state race-free);
        the worker admits them next tick.  A ``_TICK`` token circulates the
        wrap-around ring while anything is still generating; each pass runs
        one jitted decode step over the whole continuous batch.  Results
        are emitted in tag order by the engine's reorder buffer, exactly as
        before — only the driver loop moved into the runtime.  Requests
        submitted concurrently while ticks are in flight are still served
        (``_admit`` and the ``more`` check fall through to ``in_q``); a
        run() entered with an empty queue returns immediately, as the old
        while-loop did."""
        budget = [max_steps]

        def decode_step(task):
            if task is not _TICK:
                self._pending.append(task)         # admitted on the next tick
                return ("enq",)
            self._admit()
            if self.active and self.cache_len < self.max_len and budget[0]:
                budget[0] -= 1
                self.step()
            more = bool(self.active or self._pending or len(self.in_q)) \
                and self.cache_len < self.max_len and budget[0] > 0
            return ("tick", more)

        tick_in_flight = [False]                   # touched only by the route

        def still_generating(result):
            if result[0] == "enq":
                if tick_in_flight[0]:
                    return None, []
                tick_in_flight[0] = True
                return None, [_TICK]
            _, more = result
            if more:
                tick_in_flight[0] = True   # seeded ticks arrive via Source
                return None, [_TICK]
            tick_in_flight[0] = False
            return None, []

        stream: List = self._drain_submitted()
        if self.active or self._pending:
            # a previous run() was truncated (budget / max_len): seed a
            # tick so the leftover batch resumes without new submissions
            stream.insert(0, _TICK)
        # CostModel placement: the decode worker's per-tick service time
        # feeds stats.service_ewma, so when the decode farm is widened to
        # several workers (data-parallel replicas), a replica pinned by a
        # slow sequence stops accumulating queue — requests no longer
        # serialize behind a round-robin slot.  With today's single shared
        # -cache worker it is placement-neutral, and the EWMA doubles as
        # live tick-latency telemetry.
        net = compose(Source(stream),
                      Farm(decode_step, feedback=still_generating,
                           scheduling=CostModel()))
        n_before = len(self.results)
        toks_before = sum(len(r.generated) for r in self.results)
        t0 = time.monotonic()
        prog = lower(net, "threads",
                     trace=self.tracer if self.tracer is not None else False)
        prog.to_graph().run_and_wait()
        wall = time.monotonic() - t0
        served = len(self.results) - n_before
        toks = sum(len(r.generated) for r in self.results) - toks_before
        reg = self.metrics
        reg.counter("serve.requests").inc(served)
        reg.counter("serve.tokens").inc(toks)
        reg.counter("serve.steps").inc(self.steps_run)
        if wall > 0:
            reg.gauge("serve.tokens_per_s").set(toks / wall)
        if self.slo is not None:
            # SLO pass before the final report, so last_report carries the
            # slo.alerts counter; each alert is an instant on the trace's
            # slo-monitor lane and a watch() firing of its own
            self.slo.check(self._latency,
                           goodput=(toks / wall) if wall > 0 else None)
        self.last_report = reg.finalize(reg.report(meta={
            "backend": "threads", "engine": "serve",
            "requests": served, "tokens": toks, "wall_s": wall}))
        if self.tracer is not None:
            self.last_trace = self.tracer.trace()
        return self.results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--smoke", action="store_true",
                    help="serve the reduced same-family config (CPU-sized) "
                         "instead of the published one")
    args = ap.parse_args()
    enable_compile_cache()
    cfg = ARCHS[args.arch]
    if args.smoke:
        cfg = cfg.smoke()
    eng = ServeEngine(cfg, max_batch=4, max_len=256)
    rng = np.random.default_rng(0)
    t0 = time.time()
    for i in range(args.requests):
        plen = int(rng.integers(3, 10))
        eng.submit(Request(rid=i, prompt=list(rng.integers(0, cfg.vocab_size, plen)),
                           max_new=args.max_new))
    results = eng.run()
    dt = time.time() - t0
    toks = sum(len(r.generated) for r in results)
    print(f"[serve] {len(results)} requests, {toks} tokens, "
          f"{eng.steps_run} engine steps, {toks/dt:.1f} tok/s")
    lat = eng._latency
    tok_s = eng.last_report.gauges.get("serve.tokens_per_s", 0.0) \
        if eng.last_report is not None else 0.0
    print(f"[serve] latency p50={lat.p50/1e3:.1f}ms "
          f"p95={lat.p95/1e3:.1f}ms p99={lat.p99/1e3:.1f}ms, "
          f"{tok_s:.1f} tok/s (engine wall)")
    for r in results[:4]:
        print(f"  tag={r.tag} rid={r.rid} out={r.generated[:8]}")
    assert [r.tag for r in results] == sorted(r.tag for r in results), \
        "collector must emit in tag order"


if __name__ == "__main__":
    main()
