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

Requests are admitted into recycled slots mid-stream.  Each slot has its
own cache position: 0 at admission, one more for every step the slot is
occupied, so a request's tokens sit at their own positions and its
attention reads only what it wrote.  Prompt ingestion is token-by-token
(one decode step per prompt token), which keeps one jitted step for
everything; the step that feeds a prompt's last token yields its first
output token, so a request with a prompt of P tokens and ``max_new`` N
occupies its slot for P + N - 1 steps.  Each step is dispatched before
the previous step's tokens are fetched (a generating slot feeds its last
token from the device), so the host's bookkeeping overlaps the chip's
step.  A batched prefill path is the obvious production extension and
exists as ``steps.make_prefill_step`` for the dry-run.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCHS
from ..core import obs
from ..core.allocator import PagePool
from ..core.obs import MetricsRegistry, Tracer
from ..core.sched import CostModel
from ..core.skeleton import Farm, Source, compose, lower
from ..core.spsc import SPSCQueue
from ..models import decode_step as model_decode, init_cache, init_params
from ..models.config import ModelConfig
from ..models.model import reset_cache_slot
from ..runtime.compile_cache import enable_compile_cache

__all__ = ["Request", "ServeEngine", "serve_step"]

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
    start: int = -1  # engine step that admitted it (fed its position 0)
    generated: List[int] = dataclasses.field(default_factory=list)
    fed: int = 0  # tokens fed so far: its slot's cache position
    asked: int = 0  # steps dispatched that yield one of its tokens
    # the float32 logits row behind each generated token, kept when the
    # engine's ``keep_logits`` holds ``rid``
    logits: List[np.ndarray] = dataclasses.field(default_factory=list)


def serve_step(params, batch, cache, positions, cfg: ModelConfig):
    """The engine's decode program: one token for every slot at its own
    position.  A row fed -1 feeds ``batch["last"]`` instead, the token
    the previous step chose for it, which stays on the device.  Returns
    (greedy next tokens (B,), logits, new cache)."""
    batch = dict(batch)
    tokens, last = batch["tokens"], batch.pop("last")
    batch["tokens"] = jnp.where(tokens < 0, last[:, None], tokens)
    logits, cache = model_decode(params, batch, cache, positions, cfg)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits, cache


class ServeEngine:
    """``slo=`` takes a :class:`~repro.core.monitor.SLOMonitor` — after
    every ``run()`` its thresholds (p99 latency over the engine's
    ``serve.request_latency_us`` histogram, goodput in tokens/s) are
    checked; alerts land in ``slo.events``, in the registry's
    ``slo.alerts`` counter (and its ``watch()`` callbacks), and as
    ``alert`` instants on an ``slo-monitor`` trace lane
    (``engine.last_trace``), time-aligned with the run.

    ``keep_logits`` is a set of request ids whose served logits rows the
    engine keeps (``Request.logits``), fetched in the same transfer as the
    step's tokens: the seam through which served logits are compared
    with a reference.

    Each step runs inside an ``obs.region("serve.step", seq=step)`` that
    holds ``serve.admit``, ``serve.dispatch``, ``serve.sync`` (the wait
    for the previous step's tokens) and ``serve.collect``; the registry
    counts the slot-steps that fed a prompt token and yielded nothing
    (``serve.prompt_slot_steps``) and that held no request
    (``serve.free_slot_steps``) as it dispatches a step, and the tokens
    it hands to requests (``serve.gen_slot_steps``) as it collects one;
    the row of a step dispatched after a request's end token is counted
    by none."""

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
        self.keep_logits: set = set()
        self.tag_counter = 0
        self._step = jax.jit(functools.partial(serve_step, cfg=cfg),
                             donate_argnums=(2,))
        # recurrent state must start from zero in a recycled slot; K/V need
        # no reset (a row reads only positions it has written)
        self._reset = None if set(self.cache) <= {"k", "v"} else jax.jit(
            lambda c, slot: reset_cache_slot(c, slot, cfg), donate_argnums=0)
        self.steps_run = 0
        # the last step's tokens, on the device: a generating request was
        # fed in that step, so its latest token is there
        self._last = jnp.zeros((max_batch,), jnp.int32)
        self._inflight = None      # (tokens, logits, fed) not yet collected
        self.metrics = MetricsRegistry()
        self._latency = self.metrics.histogram("serve.request_latency_us")
        self._prompt_steps = self.metrics.counter("serve.prompt_slot_steps")
        self._gen_steps = self.metrics.counter("serve.gen_slot_steps")
        self._free_steps = self.metrics.counter("serve.free_slot_steps")
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
        """Queue ``req``; refused (``ValueError``) where its prompt is empty,
        it asks for no token, or its P + ``max_new`` tokens do not fit a
        slot's ``max_len`` positions."""
        if not req.prompt or req.max_new < 1:
            raise ValueError(f"request {req.rid}: needs a prompt and "
                             f"max_new >= 1")
        if len(req.prompt) + req.max_new > self.max_len:
            raise ValueError(
                f"request {req.rid}: {len(req.prompt)} prompt + "
                f"{req.max_new} new tokens exceed max_len={self.max_len}")
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
            nxt.start = self.steps_run
            if self._reset is not None:
                self.cache = self._reset(self.cache, jnp.int32(slot))
            self.active[slot] = nxt

    @staticmethod
    def _feed(req: Request):
        """The token ``req`` feeds this step (``None``: its latest output
        token), and whether the step yields its next output token: it
        does from its last prompt token on."""
        p, n = req.fed, len(req.prompt)
        return (req.prompt[p] if p < n else None), p >= n - 1

    @staticmethod
    def _finished(req: Request) -> bool:
        g = req.generated
        return len(g) >= req.max_new or (
            req.eos_id is not None and bool(g) and g[-1] == req.eos_id)

    # -- one farm iteration ----------------------------------------------------
    def step(self) -> None:
        """Dispatch one decode step, then collect the step before it.

        The host never waits for a step before dispatching the next: a
        generating slot is fed -1, for the token the previous step chose
        for it, still on the device, so admission, the batch and the
        collector run while the chip computes.  A request leaves its
        slot when the step that yields its last token is dispatched; one
        that ends on ``eos_id`` is seen a step later, and the row of that
        extra step is dropped."""
        with obs.region("serve.step", seq=self.steps_run):
            with obs.region("serve.admit"):
                self._admit()
            if not self.active:
                self._drain()
                return
            if self.cfg.family == "audio":
                raise NotImplementedError("audio serving uses frame embeddings")
            with obs.region("serve.dispatch"):
                # free slots feed a pad at position 0, and their row is
                # ignored
                tokens = np.zeros((self.max_batch, 1), np.int32)
                pos = np.zeros((self.max_batch,), np.int32)
                fed = []
                for slot, req in list(self.active.items()):
                    tok, yields = self._feed(req)
                    tokens[slot, 0] = -1 if tok is None else tok
                    pos[slot] = req.fed
                    req.fed += 1
                    fed.append((slot, req, yields))
                    req.asked += yields
                    if req.asked >= req.max_new:
                        del self.active[slot]      # its last token is asked
                        self.pool.free(slot, 0)
                nxt, logits, self.cache = self._step(
                    self.params, {"tokens": tokens, "last": self._last},
                    self.cache, pos)
            self.steps_run += 1
            self._prompt_steps.inc(sum(not y for _, _, y in fed))
            self._free_steps.inc(self.max_batch - len(fed))
            prev, self._inflight = self._inflight, (nxt, logits, fed)
            self._last = nxt
            if prev is not None:
                self._collect(prev)

    def _drain(self) -> None:
        """Collect the step still in flight, if any."""
        if self._inflight is not None:
            prev, self._inflight = self._inflight, None
            self._collect(prev)

    def _collect(self, inflight) -> None:
        nxt, logits, fed = inflight
        with obs.region("serve.sync"):
            rows = None
            if any(y and req.rid in self.keep_logits for _, req, y in fed):
                nxt, rows = jax.device_get((nxt, logits))
            else:
                nxt = np.asarray(nxt)
        with obs.region("serve.collect"):
            for slot, req, yields in fed:
                if not yields or self._finished(req):
                    continue      # a prompt token, or a row past its end
                req.generated.append(int(nxt[slot]))
                self._gen_steps.inc()
                if rows is not None and req.rid in self.keep_logits:
                    req.logits.append(np.array(rows[slot], np.float32))
                if self._finished(req):
                    if self.active.get(slot) is req:     # ended on eos_id
                        del self.active[slot]
                        self.pool.free(slot, 0)
                    self.done[req.tag] = req
            # -- collector: emit in tag order ---------------------------------
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
            if self.active and budget[0]:
                budget[0] -= 1
                self.step()
            more = bool(self.active or self._pending or len(self.in_q)) \
                and budget[0] > 0
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
            # a previous run() was truncated by its budget: seed a
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
        self._drain()
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
