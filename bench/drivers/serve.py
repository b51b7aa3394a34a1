"""Model serving through the program's ``ServeEngine``: a continuous batch
of ``max_batch`` slots, each with its own cache position, advanced one
token a slot per jitted decode step, the loop itself lowered to the
threads backend as ``Source(requests) ∘ Farm(decode_step,
feedback=still_generating)``.

Set-up draws the weights here from the seed (``draw_weights``, not the
program's own initialiser), hands them to the engine, and warms the
decode step (and the slot reset, for a family with recurrent state) with
one request.  The window is a closed loop: before each chunk of
``chunk_steps`` engine steps (``ServeEngine.run(max_steps=...)``) the
backlog is topped up so that a request always waits for every slot that
can free inside the chunk.  ``events_per_s`` is the tokens generated
inside the window over its length.

Requests are the traffic's chat mix (``chat_backlog``): prompt and output
lengths log-uniform over their ranges, in one stratified order that is
the same for every seed, so that the sixteen or so requests a window
serves follow the mix; token ids uniform over the vocabulary, from the
seed.  Each runs to ``max_new`` (no end token).  So every seed does the
same work.

The engine keeps the served logits rows of a seeded share of the
requests (at least ``least`` of the first two batches' worth, which finish
early) and of every request sent in the window's last ``last_share``;
the check compares each row of the sampled requests finished in the
window, and of the last request finished in it, with the reference's full
forward pass over that request's own tokens.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import time
from typing import Dict, List, Tuple

import numpy as np

from bench import generate, harness
from bench.trace import span


def model_config(config: Dict):
    """The program's ``ModelConfig`` for the configuration file's widths."""
    from repro.models.config import ModelConfig

    d, h = config["hidden_size"], config["num_attention_heads"]
    return ModelConfig(
        name=config["name"], family="dense",
        n_layers=config["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        head_dim=d // h, rope_theta=config["rope_theta"],
        norm_eps=config["rms_norm_eps"], dtype=config["dtype"],
        pad_heads_to=0, pad_vocab_to=0)


def strata(n: int) -> np.ndarray:
    """``(n, 2)``: the stratum, of ``n``, of each of the two coordinates
    of the first ``n`` points of the two-dimensional Sobol sequence (van
    der Corput in base 2, and its partner by the Pascal matrix mod 2).
    Each column takes every stratum once, and the first ``2**j`` rows
    fall one in each of the ``2**j`` equal strata of either column (and
    of any grid of ``2**j`` equal cells of the square): every prefix is
    spread over both ranges as the whole is."""
    bits = n.bit_length() - 1
    if n != 1 << bits:
        raise ValueError(f"{n} requests: the stratified order needs a "
                         f"power of two")
    k = np.arange(n)
    out = np.zeros((n, 2), np.int64)
    v = 1 << bits >> 1
    for i in range(bits):
        on = (k >> i) & 1 == 1
        out[on, 0] ^= 1 << (bits - 1 - i)
        out[on, 1] ^= v
        v ^= v >> 1
    return out


def chat_backlog(traffic: Dict, vocab: int,
                 seed: int) -> List[Tuple[List[int], int]]:
    """``(prompt, max_new)`` of every request, in the order sent.  The
    lengths are the same for every seed: request k's prompt and output
    lengths are log-uniform over their ranges, each at a place drawn
    from ``length_seed`` inside the stratum ``strata`` gives it, so the
    lengths of any prefix follow the mix.  The seed draws the ids."""
    n = traffic["requests"]
    rng = generate.rng_for(traffic["length_seed"])

    def log_uniform(stratum, lo, hi):
        u = (stratum + rng.random(n)) / n
        return np.rint(np.exp(np.log(lo) + u * np.log(hi / lo))
                       ).astype(np.int64)

    cells = strata(n)
    lengths = np.stack([log_uniform(cells[:, 0], *traffic["prompt_tokens"]),
                        log_uniform(cells[:, 1], *traffic["output_tokens"])],
                       1)
    ids = generate.rng_for(seed, 2)
    return [(ids.integers(0, vocab, int(p)).tolist(), int(m))
            for p, m in lengths]


def draw_weights(model: Dict, seed: int):
    """A dense model's weights, drawn from ``seed`` under the leaf names
    of the program's ``init_params``: matrices normal over the square
    root of their fan-in, the embedding and the head normal times 0.02,
    in the configuration's dtype; RMSNorm scales 1 + 0.1 x normal, in
    float32, so that a scale left out or taken from another layer moves
    the logits."""
    import jax

    d, h = model["hidden_size"], model["num_attention_heads"]
    draw = _draw_program(d, h, model["num_key_value_heads"],
                         model["intermediate_size"],
                         model["num_hidden_layers"], model["vocab_size"],
                         model["dtype"])
    return draw(jax.random.PRNGKey(seed))


@functools.lru_cache(maxsize=None)
def _draw_program(d: int, h: int, kv: int, f: int, layers: int, vocab: int,
                  dtype: str):
    """The jitted draw, one program for every seed: the key is an
    argument, so the program is compiled (or read from the compile
    cache) once, and never folds a seed in as a constant."""
    import jax
    import jax.numpy as jnp

    hd, L, dtype = d // h, layers, jnp.dtype(dtype)
    # leaf: (shape, scale of a matrix; None for a norm scale)
    plan = {"embed": ((vocab, d), 0.02), "lm_head": ((vocab, d), 0.02),
            "final_norm": ((d,), None),
            "blocks": {"norm1": ((L, d), None), "norm2": ((L, d), None),
                       "wq": ((L, d, h, hd), d ** -0.5),
                       "wk": ((L, d, kv, hd), d ** -0.5),
                       "wv": ((L, d, kv, hd), d ** -0.5),
                       "wo": ((L, h, hd, d), (h * hd) ** -0.5),
                       "mlp": {"w_gate": ((L, d, f), d ** -0.5),
                               "w_up": ((L, d, f), d ** -0.5),
                               "w_down": ((L, f, d), f ** -0.5)}}}
    leaves, tree = jax.tree.flatten(
        plan, is_leaf=lambda x: isinstance(x, tuple))

    def draw(key):
        out = []
        for k, (shape, scale) in zip(jax.random.split(key, len(leaves)),
                                     leaves):
            z = jax.random.normal(k, shape, jnp.float32)
            out.append(1 + 0.1 * z if scale is None
                       else (z * scale).astype(dtype))
        return jax.tree.unflatten(tree, out)

    return jax.jit(draw)


def free(engine) -> None:
    """Free an engine's weights and cache on the device now, not when the
    serving graph's closures that hold the engine are next collected."""
    import jax

    for a in jax.tree.leaves((engine.params, engine.cache)):
        a.delete()


def round_to_fp8(params, dtype):
    """The weights of ``dtype`` rounded to float8_e4m3fn and back, leaf by
    leaf, each leaf freed once rounded: the control, one precision below
    the configuration's.  The norm scales, float32 in the program, are
    left as they are.  The two casts are two programs, since XLA may
    fold a pair of casts inside one program away (on the TPU it does,
    and the control then serves the very same rows)."""
    import jax
    import jax.numpy as jnp

    down = jax.jit(lambda a: a.astype(jnp.float8_e4m3fn))

    def round_trip(a):
        if a.dtype != dtype:
            return a
        small = down(a)
        a.delete()
        return small.astype(dtype)

    return jax.tree.map(round_trip, params)


class Driver:
    def __init__(self, config: Dict, traffic: Dict, *, seed: int, devices,
                 log, tracing: bool = False):
        from repro.launch.serve import Request, ServeEngine

        if "logits" not in {f.name for f in dataclasses.fields(Request)}:
            raise RuntimeError(
                "this ServeEngine keeps no served logits and no cache "
                "position per slot, which this cell needs")
        self.Request, self.ServeEngine = Request, ServeEngine
        self.config, self.traffic, self.seed, self.log = \
            config, traffic, seed, log
        self.cfg = model_config(config)
        self.ref = harness.reference(config)
        self.serving = config["serving"]
        self.chunk = traffic["chunk_steps"]
        self.backlog = chat_backlog(traffic, self.cfg.vocab_size, seed)
        # a slot frees at most once a shortest request inside a chunk
        shortest = min(len(p) + m - 1 for p, m in self.backlog)
        self.waiting = self.serving["max_batch"] * -(-self.chunk // shortest)
        self.weights_seed = int(generate.rng_for(seed, 3).integers(2 ** 31))
        check = traffic["check"]
        draw = generate.rng_for(seed, 4)
        early = 2 * self.serving["max_batch"]
        self.sample = {int(i) for i in np.flatnonzero(
            draw.random(len(self.backlog)) < check["share"])}
        self.sample |= {int(i) for i in draw.choice(early, check["least"],
                                                    replace=False)}
        self.sent: List = []
        self.admitted = 0                       # sent requests admitted
        self.engine = self._engine(params=self._weights())
        self.engine.keep_logits = set(self.sample)
        warm = Request(rid=-1, prompt=[0, 1], max_new=2)
        self.engine.keep_logits.add(warm.rid)
        self.engine.submit(warm)
        self.engine.run()
        self.engine.keep_logits.discard(warm.rid)
        # keep what set-up made (JAX, the programs, the backlog) out of
        # the collector's sweeps until release(), as a serving process
        # does once started: a full sweep of it stalls the host, and with
        # it the chip, in the middle of the window
        gc.collect()
        gc.freeze()
        self.finished: List = []
        p = [len(q) for q, _ in self.backlog]
        m = [n for _, n in self.backlog]
        log(f"[serve] {self.cfg.name}: {self.cfg.n_layers} layers, d_model "
            f"{self.cfg.d_model}, {self.cfg.dtype}; {self.serving['max_batch']}"
            f" slots x {self.serving['max_len']} positions; prompts "
            f"{min(p)}-{max(p)} (mean {np.mean(p):.1f}), outputs "
            f"{min(m)}-{max(m)} (mean {np.mean(m):.1f}); "
            f"{len(self.sample)} of {len(self.backlog)} sampled")

    def _engine(self, **kw):
        return self.ServeEngine(self.cfg, max_batch=self.serving["max_batch"],
                                max_len=self.serving["max_len"], **kw)

    def _counts(self) -> Dict[str, int]:
        return {k: self.engine.metrics.counter(f"serve.{k}_slot_steps").value
                for k in ("prompt", "gen", "free")}

    def _top_up(self, keep: bool) -> None:
        """Send requests until ``waiting`` of them wait for a slot; with
        ``keep``, the engine keeps their logits rows."""
        while self.admitted < len(self.sent) and \
                self.sent[self.admitted].tag >= 0:
            self.admitted += 1
        while len(self.sent) - self.admitted < self.waiting:
            rid = len(self.sent)
            prompt, max_new = self.backlog[rid % len(self.backlog)]
            req = self.Request(rid=rid, prompt=prompt, max_new=max_new)
            if keep:
                self.engine.keep_logits.add(rid)
            self.engine.submit(req)
            self.sent.append(req)

    def window(self, seconds: float) -> harness.Window:
        eng = self.engine
        c0, s0, n0 = self._counts(), eng.steps_run, len(eng.results)
        last_share = self.traffic["check"]["last_share"]
        t0 = time.perf_counter()
        deadline = t0 + seconds
        late = deadline - last_share * seconds
        with span("bench.window"):
            while True:
                self._top_up(keep=time.perf_counter() >= late)
                eng.run(max_steps=self.chunk)
                if time.perf_counter() >= deadline:
                    break
        t1 = time.perf_counter()
        c1, s1 = self._counts(), eng.steps_run
        self.finished = eng.results[n0:]
        counts = {f"{k}_slot_steps": c1[k] - c0[k] for k in c1}
        contexts = self._contexts(s0, s1)
        counts.update(steps=s1 - s0, requests=len(self.finished),
                      step_bytes=sum(self.ref.step_bytes(self.config, c)
                                     for c in contexts),
                      model_flops=sum(self.ref.step_flops(self.config, c)
                                      for c in contexts))
        tokens = counts["gen_slot_steps"]
        return harness.Window(
            t0, t1, {"events_per_s": tokens / (t1 - t0)},
            attempted=len(self.finished), counts=counts,
            notes=[f"[serve] {tokens} tokens, {len(self.finished)} requests "
                   f"finished, {s1 - s0} steps in {t1 - t0:.3f} s; "
                   f"slot-steps {counts['prompt_slot_steps']} prompt, "
                   f"{tokens} generating, {counts['free_slot_steps']} free"])

    def _contexts(self, s0: int, s1: int) -> List[List[int]]:
        """For each engine step in ``[s0, s1)``, the context length of
        every occupied slot: a request admitted at step ``start`` is at
        position ``s - start`` in step ``s``, and has held its slot for
        as many steps as it was fed.  Finished requests wait in ``done``
        until those before them in tag order are emitted."""
        out: List[List[int]] = [[] for _ in range(s1 - s0)]
        eng = self.engine
        held = [(r.start, r.start + r.fed) for r in
                eng.results + list(eng.done.values()) +
                list(eng.active.values())]
        for start, end in held:
            for s in range(max(start, s0), min(end, s1)):
                out[s - s0].append(s - start + 1)
        return out

    def release(self) -> None:
        free(self.engine)
        self.engine = None
        gc.unfreeze()

    # -- correctness -----------------------------------------------------------
    def compared(self) -> List:
        """The sampled requests finished in the window, and the last
        request finished in it whose rows were kept."""
        picks = [r for r in self.finished if r.rid in self.sample]
        kept = [r for r in self.finished if r.logits]
        if kept and kept[-1] not in picks:
            picks.append(kept[-1])
        return picks

    def _weights(self):
        """The weights of the window, drawn (again) from the seed."""
        return draw_weights(self.config, self.weights_seed)

    def _serve_again(self, picks) -> List[Tuple]:
        """The picked requests served afresh by an engine whose weights
        are rounded through float8."""
        eng = self._engine(params=round_to_fp8(
            self._weights(), self.cfg.param_dtype))
        eng.keep_logits = {r.rid for r in picks}
        for r in picks:
            eng.submit(self.Request(rid=r.rid, prompt=r.prompt,
                                    max_new=r.max_new))
        eng.run()
        free(eng)
        return [(r.prompt, r.generated, np.stack(r.logits))
                for r in eng.results]

    def check(self, control: bool = False) -> List[harness.Check]:
        """Every served logits row of each compared request against the
        reference's row for the same position of that request's own
        tokens.  With ``control``, the rows compared come from the same
        requests served again with the weights rounded through float8."""
        picks = self.compared()
        if control:
            served = self._serve_again(picks)
        else:
            served = [(r.prompt, r.generated, np.stack(r.logits))
                      for r in picks if r.logits]
        weights = self._weights()
        t = time.perf_counter()
        gap, rows = 0.0, 0
        for prompt, generated, got in served:
            want = self.ref.logits(weights, prompt + generated[:-1],
                                   self.config)[len(prompt) - 1:]
            if got.shape != want.shape:
                raise AssertionError(f"{got.shape[0]} served rows for "
                                     f"{want.shape[0]} generated tokens")
            gap = max(gap, float(np.abs(got - want).max()))
            rows += len(got)
        del weights
        self.log(f"[check] {len(served)} requests, {rows} rows against the "
                 f"reference in {time.perf_counter() - t:.3f} s")
        return [harness.Check("max_logit_gap", gap,
                              self.config["check"]["max_logit_gap"]),
                harness.Check("compared_rows", rows, 1, least=True)]
