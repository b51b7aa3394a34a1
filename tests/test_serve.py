"""``ServeEngine`` against the plain reference: every served logits row of a
request equals ``models/reference.py``'s full forward pass over that
request's own prompt and earlier tokens, however its admission was
staggered, its slot recycled or its positions wrapped in a rolling
window, and past ``max_len`` engine steps.  Families the reference does
not cover (SSM, hybrid) are held to the same request served alone."""
import jax
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.launch.serve import Request, ServeEngine
from repro.models import reference

# float32 on both sides: the engine and the reference differ only in the
# order of their sums (the reference's products at "highest" precision),
# about 1e-6 at these logits (|logit| < 1).  A token fed at a wrong
# position, or one stale cache entry attended, moves a row by 1e-3 or more.
TOL = 1e-4


def dense(**kw):
    return ARCHS["phi3-mini-3.8b"].smoke().replace(dtype="float32", **kw)


def serve(cfg, waves, *, max_batch, max_len, steps_between=5, keep=True):
    """Serve each wave of ``(prompt, max_new)`` after the previous one has
    run ``steps_between`` steps, then everything to the end."""
    eng = ServeEngine(cfg, max_batch=max_batch, max_len=max_len, seed=3)
    rid = 0
    for i, wave in enumerate(waves):
        for prompt, max_new in wave:
            if keep:
                eng.keep_logits.add(rid)
            eng.submit(Request(rid=rid, prompt=list(prompt), max_new=max_new))
            rid += 1
        eng.run(max_steps=steps_between if i < len(waves) - 1 else 10_000)
    assert len(eng.results) == rid
    return eng


def widest_gap(eng, eos=False):
    """The largest gap between a served logits row and the reference's,
    over every row of every request."""
    gap = 0.0
    for r in eng.results:
        want = reference.forward(eng.params, r.prompt + r.generated[:-1],
                                 eng.cfg)[len(r.prompt) - 1:]
        got = np.stack(r.logits)
        rows = len(r.generated) if eos else r.max_new
        assert got.shape == want.shape == (rows, eng.cfg.vocab_size)
        assert r.generated == [int(t) for t in got.argmax(-1)]
        gap = max(gap, float(np.abs(got - want).max()))
    return gap


def requests(seed, n, prompt, new, vocab=256):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, vocab, int(rng.integers(*prompt))).tolist(),
             int(rng.integers(*new))) for _ in range(n)]


CASES = {
    # three waves into three slots: admissions mid-stream, slots recycled
    "staggered": (dense(), dict(max_batch=3, max_len=64),
                  [requests(1, 4, (1, 12), (1, 10)),
                   requests(2, 3, (1, 12), (1, 10)),
                   requests(3, 4, (1, 12), (1, 10))]),
    # as many layers as slots: an admission must not touch a layer
    "layers_eq_batch": (dense(), dict(max_batch=2, max_len=64),
                        [requests(4, 3, (2, 9), (2, 8)),
                         requests(5, 3, (2, 9), (2, 8))]),
    # an 8-slot rolling cache: positions wrap, recycled slots hold the
    # previous request's entries
    "rolling_window": (dense(sliding_window=8), dict(max_batch=2, max_len=64),
                       [requests(6, 3, (4, 20), (4, 16)),
                        requests(7, 3, (4, 20), (4, 16))]),
    # grouped-query attention: two query heads a KV head, staggered and
    # recycled as in "staggered"
    "gqa": (dense(n_kv_heads=2), dict(max_batch=3, max_len=64),
            [requests(11, 4, (1, 12), (1, 10)),
             requests(12, 3, (1, 12), (1, 10))]),
    # eight requests of 19 positions through two slots: 76 engine steps,
    # three times a slot's 24 positions
    "past_max_len": (dense(), dict(max_batch=2, max_len=24),
                     [[(p[:12] + [1] * (12 - len(p[:12])), 8)
                       for p, _ in requests(8, 8, (12, 13), (1, 2))]]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_served_logits_match_the_reference(case):
    cfg, engine, waves = CASES[case]
    eng = serve(cfg, waves, **engine)
    assert widest_gap(eng) < TOL
    if case == "past_max_len":
        assert eng.steps_run > 3 * eng.max_len


def test_the_old_continuation_fails_the_comparison(monkeypatch):
    """Planted: the step that feeds a prompt's last token yields nothing
    and the next feeds 0, so every request continues ``prompt + [0]``."""
    def old_feed(req):
        p, n = req.fed, len(req.prompt)
        tok = req.prompt[p] if p < n else (0 if p == n else None)
        return tok, p >= n

    monkeypatch.setattr(ServeEngine, "_feed", staticmethod(old_feed))
    cfg, engine, waves = CASES["staggered"]
    assert widest_gap(serve(cfg, waves, **engine)) > 100 * TOL


@pytest.mark.parametrize("arch", ["mamba2-130m", "zamba2-2.7b"])
def test_state_families_serve_each_request_as_alone(arch):
    """Two slots, and as many layers (or layer groups) as slots: admitting
    a request zeroes its own slot's recurrent state, nothing else, so a
    request served among others gives the logits it gives alone."""
    cfg = ARCHS[arch].smoke().replace(dtype="float32")
    assert jax.tree.leaves(ServeEngine(cfg, max_batch=2).cache)[0].shape[0] == 2
    waves = [requests(9, 3, (2, 9), (2, 8)), requests(10, 2, (2, 9), (2, 8))]
    together = serve(cfg, waves, max_batch=2, max_len=32, steps_between=3)
    for r in together.results:
        alone = serve(cfg, [[(r.prompt, r.max_new)]], max_batch=2, max_len=32)
        np.testing.assert_allclose(np.stack(r.logits),
                                   np.stack(alone.results[0].logits),
                                   atol=TOL, rtol=0)


def test_a_request_ends_on_its_end_token():
    """A request whose end token comes up stops there, though the step
    after it was already dispatched; the other requests, and the one
    admitted into its slot, are served as without it.  The dropped row of
    that extra step is counted neither as a token nor as a prompt step,
    and the slot-steps each request held are the steps it was fed."""
    waves = [requests(12, 5, (2, 9), (6, 10))]
    plain = serve(dense(), waves, max_batch=2, max_len=32)
    first = plain.results[0]
    eos = first.generated[2]
    upto = first.generated.index(eos) + 1
    eng = ServeEngine(dense(), max_batch=2, max_len=32, seed=3)
    for i, (prompt, max_new) in enumerate(waves[0]):
        eng.keep_logits.add(i)
        eng.submit(Request(rid=i, prompt=list(prompt), max_new=max_new,
                           eos_id=eos if i == 0 else None))
    eng.run()
    got = {r.rid: r for r in eng.results}
    assert got[0].generated == first.generated[:upto]
    assert len(got[0].logits) == upto
    for r in plain.results[1:]:
        assert got[r.rid].generated == r.generated
    assert widest_gap(eng, eos=True) < TOL
    count = {k: eng.metrics.counter(f"serve.{k}_slot_steps").value
             for k in ("prompt", "gen", "free")}
    assert count["gen"] == sum(len(r.generated) for r in eng.results)
    assert count["prompt"] == sum(len(r.prompt) - 1 for r in eng.results)
    assert got[0].fed == len(got[0].prompt) + upto      # one step past its end
    assert all(r.fed == len(r.prompt) + len(r.generated) - 1
               for r in eng.results if r.rid)
    assert sum(count.values()) + 1 == 2 * eng.steps_run  # the dropped row


def test_a_step_is_dispatched_before_the_previous_is_collected(monkeypatch):
    """The host dispatches step k + 1 before it fetches step k's tokens,
    and a generating slot is fed -1, for its last token on the device."""
    events = []
    eng = ServeEngine(dense(), max_batch=2, max_len=32, seed=3)
    step, collect = eng._step, eng._collect

    def dispatch(params, batch, cache, pos):
        events.append(("dispatch", bool((batch["tokens"] < 0).any())))
        return step(params, batch, cache, pos)

    def fetch(inflight):
        events.append(("collect", None))
        return collect(inflight)

    monkeypatch.setattr(eng, "_step", dispatch)
    monkeypatch.setattr(eng, "_collect", fetch)
    eng.submit(Request(rid=0, prompt=[1, 2], max_new=4))
    eng.run()
    kinds = [k for k, _ in events]
    assert kinds == ["dispatch"] * 2 + ["collect", "dispatch"] * 3 + \
        ["collect"] * 2
    assert [u for k, u in events if k == "dispatch"] == \
        [False, False, True, True, True]
    assert len(eng.results[0].generated) == 4


def test_slot_steps_are_counted_by_kind():
    waves = [requests(11, 5, (1, 9), (1, 7))]
    eng = serve(dense(), waves, max_batch=3, max_len=32, keep=False)
    count = {k: eng.metrics.counter(f"serve.{k}_slot_steps").value
             for k in ("prompt", "gen", "free")}
    assert count["gen"] == sum(n for _, n in waves[0])
    assert count["prompt"] == sum(len(p) - 1 for p, _ in waves[0])
    assert sum(count.values()) == 3 * eng.steps_run
    assert all(not r.logits for r in eng.results)      # none asked for


def test_kv_blocks_read_counts_each_slots_blocks_up_to_its_position():
    """``serve.kv_blocks_read`` adds, each step, the blocks up to each
    occupied slot's position.  Eight float32 KV heads of 16 make kv·dh
    128; 384 positions take blocks of 128.  A request of P prompt tokens
    and N new ones holds its slot at positions 0 .. P + N - 2, however it
    is scheduled: 2 + 200 reads one block at 0..127 and two at 128..200
    (128 + 2 * 73), 5 + 20 one at each of 24 steps, 1 + 3 one at each of
    3: 301 in all."""
    cfg = dense(n_heads=8, n_kv_heads=8)
    assert cfg.n_kv_heads * cfg.hdim == 128
    waves = [[([1, 2], 200), ([3] * 5, 20), ([4], 3)]]
    eng = serve(cfg, waves, max_batch=2, max_len=384, keep=False)
    assert eng.metrics.counter("serve.kv_blocks_read").value == 301
    # a cache of kv·dh 64 is not tiled: the plain path, nothing counted
    eng = serve(dense(), waves, max_batch=2, max_len=384, keep=False)
    assert eng.metrics.counter("serve.kv_blocks_read").value == 0


@pytest.mark.parametrize("prompt,max_new", [([], 4), ([1, 2], 0),
                                            ([1] * 20, 13)])
def test_a_request_that_cannot_fit_a_slot_is_refused(prompt, max_new):
    eng = ServeEngine(dense(), max_batch=2, max_len=32)
    with pytest.raises(ValueError):
        eng.submit(Request(rid=0, prompt=prompt, max_new=max_new))
