"""The serving cell rehearsed on the CPU at a tiny size, through the
harness's whole run but the look for a chip: sound runs come out correct,
traced and untraced; the old ``prompt + [0]`` continuation, planted, and
the fp8 control come out not correct.  Beside them: the mix's lengths,
the work a step is counted to need, and the readers of the new metrics."""
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src")) if p not in sys.path]

from bench import harness, trace as tr  # noqa: E402
from bench.drivers import serve  # noqa: E402

CELL = "phi3-chat-backlog"
DATA = Path(__file__).resolve().parent / "data"
# At 64 wide the logits are about an eighth of the published model's
# (0.02 * sqrt(64) against 0.02 * sqrt(3072)), and so are the gaps: sound
# runs read about 0.01 on the CPU, the fp8 control 0.11-0.17.  The
# published config's limit is set from chip readings at its own width.
TINY_LIMIT = 0.05


def tiny():
    cell = harness.load_cell(CELL)
    cell.config.update(hidden_size=64, num_hidden_layers=2,
                       num_attention_heads=4, num_key_value_heads=4,
                       intermediate_size=128, vocab_size=256)
    cell.config["serving"]["max_len"] = 48
    cell.config["check"]["max_logit_gap"] = TINY_LIMIT
    cell.traffic.update(prompt_tokens=[2, 16], output_tokens=[2, 24],
                        requests=64, chunk_steps=4)
    cell.traffic["check"]["share"] = 0.3
    return cell


def run(cell, seed=2**31 + 5, seconds=0.5, trace=False):
    import jax

    return harness.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                            t_start=time.perf_counter(),
                            devices=jax.devices()[:1], peaks={},
                            log=lambda m: None)


@pytest.mark.parametrize("trace", [False, True])
def test_serving_runs_and_is_correct(trace):
    r = run(tiny(), trace=trace)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert r["checks"]["max_logit_gap"]["value"] < TINY_LIMIT / 3
    assert r["checks"]["compared_rows"]["value"] > 0
    # no TPU planes on the CPU: the device readers and mfu find nothing
    names = {"events_per_s", "setup_s"} if not trace else \
        {"serve.host_ms_per_step", "serve.prompt_slot_share"}
    assert set(r["metrics"]) == names
    assert all(m["value"] > 0 for m in r["metrics"].values())


def test_the_old_continuation_is_caught(monkeypatch):
    """Planted: the step that feeds a prompt's last token yields nothing
    and the next feeds 0, so every request continues ``prompt + [0]``."""
    from repro.launch.serve import ServeEngine

    def old_feed(req):
        p, n = req.fed, len(req.prompt)
        tok = req.prompt[p] if p < n else (0 if p == n else None)
        return tok, p >= n

    monkeypatch.setattr(ServeEngine, "_feed", staticmethod(old_feed))
    r = run(tiny())
    assert not r["correct"]
    assert r["checks"]["max_logit_gap"]["value"] > TINY_LIMIT


def test_serving_control_is_caught():
    import jax

    cell = tiny()
    drv = harness.driver(cell.config).Driver(
        cell.config, cell.traffic, seed=7, devices=jax.devices(),
        log=lambda m: None)
    drv.window(0.3)
    drv.release()
    assert all(c.ok for c in drv.check())
    bad = {c.name: c for c in drv.check(control=True)}
    assert not bad["max_logit_gap"].ok and bad["compared_rows"].ok


def test_window_counts_every_slot_step():
    """The window's counts: tokens are the generating slot-steps, every
    step's slots are prompt, generating or free, and the counted contexts
    are the occupied slot-steps at their positions."""
    import jax

    cell = tiny()
    drv = harness.driver(cell.config).Driver(
        cell.config, cell.traffic, seed=11, devices=jax.devices(),
        log=lambda m: None)
    w = drv.window(0.3)
    c = w.counts
    assert c["prompt_slot_steps"] + c["gen_slot_steps"] + \
        c["free_slot_steps"] == 4 * c["steps"]
    assert c["free_slot_steps"] == 0            # a request always waits
    assert w.end_to_end["events_per_s"] == pytest.approx(
        c["gen_slot_steps"] / (w.t1 - w.t0))
    s1 = drv.engine.steps_run
    contexts = drv._contexts(s1 - c["steps"], s1)
    assert sum(map(len, contexts)) == c["prompt_slot_steps"] + \
        c["gen_slot_steps"]
    assert max(max(x) for x in contexts) < cell.config["serving"]["max_len"]
    assert c["step_bytes"] == sum(drv.ref.step_bytes(cell.config, x)
                                  for x in contexts)
    # the sampled requests finished in the window are compared, and the
    # last finished whose rows were kept
    kept = [r for r in drv.finished if r.logits]
    assert kept[-1] in drv.compared()
    assert {r.rid for r in drv.finished} & drv.sample <= \
        {r.rid for r in drv.compared()}


def test_set_up_is_kept_out_of_the_window_s_collections():
    """What set-up made is frozen out of the collector's sweeps for the
    window (a full sweep of it stalled the chip's host mid-window), and
    handed back at release."""
    import gc

    import jax

    cell = tiny()
    drv = harness.driver(cell.config).Driver(
        cell.config, cell.traffic, seed=13, devices=jax.devices(),
        log=lambda m: None)
    assert gc.get_freeze_count() > 0
    drv.window(0.1)
    drv.release()
    assert gc.get_freeze_count() == 0


def test_the_mix_does_the_same_work_for_every_seed():
    """Every seed sends the same lengths in the same order, and every
    prefix of 16 or more requests (a window serves about that many)
    holds the mix's share of prompt slot-steps: the order is stratified,
    where the draw's own order gave its first 16 a share of 0.41."""
    cell = harness.load_cell(CELL)
    a = serve.chat_backlog(cell.traffic, 32064, 1)
    b = serve.chat_backlog(cell.traffic, 32064, 2**40 + 3)
    lens = [[(len(p), m) for p, m in x] for x in (a, b)]
    assert lens[0] == lens[1]
    prompts = np.array([p for p, _ in lens[0]])
    outputs = np.array([m for _, m in lens[0]])
    assert (prompts.min(), prompts.max()) == (8, 128)
    assert (outputs.min(), outputs.max()) == (16, 256)
    # log-uniform: means (hi - lo) / ln(hi / lo), 43.3 and 86.6
    assert abs(prompts.mean() - 43.3) < 0.5 and abs(outputs.mean() - 86.6) < 1
    assert abs(np.corrcoef(np.log(prompts), np.log(outputs))[0, 1]) < 0.05

    def share(k):
        return (prompts[:k] - 1).sum() / (prompts[:k] + outputs[:k] - 1).sum()

    for k in (16, 24, 32, 48, 64, 128, 1024):
        assert abs(share(k) - 0.328) < 0.02, k
    assert a[0][0] != b[0][0] and max(max(p) for p, _ in a) < 32064


@pytest.mark.parametrize("n", [16, 64, 1024])
def test_every_prefix_of_the_order_is_stratified(n):
    cells = serve.strata(n)
    for col in (0, 1):
        assert sorted(cells[:, col]) == list(range(n))
    for j in range(n.bit_length()):
        k = 1 << j
        for col in (0, 1):
            assert sorted(cells[:k, col] * k // n) == list(range(k))
        # one point in each cell of the k x 1 ... 1 x k grids of the square
        for a in range(j + 1):
            rows, cols = 1 << a, 1 << (j - a)
            at = cells[:k, 0] * rows // n * cols + cells[:k, 1] * cols // n
            assert sorted(at) == list(range(k))


def test_the_drawn_weights_have_the_programs_leaves():
    """The bench's own weights fit the engine's tree (the leaves, shapes
    and dtypes of ``init_params``), the same seed draws the same ones,
    and no norm scale is 1 throughout."""
    import jax

    from repro.models import init_params

    cell = tiny()
    got = serve.draw_weights(cell.config, 5)
    want = jax.eval_shape(lambda: init_params(
        serve.model_config(cell.config), jax.random.PRNGKey(0)))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(got)] == \
        [(a.shape, a.dtype) for a in jax.tree.leaves(want)]
    again = serve.draw_weights(cell.config, 5)
    assert all(np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(got), jax.tree.leaves(again)))
    norms = [got["final_norm"], got["blocks"]["norm1"],
             got["blocks"]["norm2"]]
    assert all(0.05 < float(np.std(x)) < 0.15 for x in norms)
    assert not np.array_equal(got["blocks"]["norm1"][0],
                              got["blocks"]["norm1"][1])


def test_a_step_is_counted_the_work_it_needs():
    """At the published widths: 7.44e9 bytes of weights a step (the
    embedding only as the rows fed), 2 * 3072 * 2 * 32 bytes of K/V a
    position, and two FLOPs a weight a slot."""
    cell = harness.load_cell(CELL)
    ref = harness.reference(cell.config)
    m = cell.config
    weights = ref.step_bytes(m, [])
    assert weights == pytest.approx(7.44e9, rel=0.01)
    per_pos = 32 * 2 * 3072 * 2
    assert ref.step_bytes(m, [10, 1]) - weights == 11 * per_pos + 2 * 3072 * 2
    assert ref.step_flops(m, []) == 0
    one = ref.step_flops(m, [1])
    assert one == pytest.approx(2 * 3.72e9, rel=0.01)   # all but the embedding
    assert ref.step_flops(m, [101]) - one == 100 * 32 * 4 * 3072


def test_the_device_readers_on_a_recorded_trace():
    """The readers on the chip's recorded search trace, given a window's
    counts: each is the quantity its docstring names."""
    t = tr.from_json(json.loads((DATA / "sw-q1000.json").read_text()))
    lo, hi = t.window
    window = harness.Window(5.0, 5.0 + (hi - lo) / 1e9, {}, attempted=1,
                            counts={"step_bytes": 4e9, "model_flops": 3e12,
                                    "prompt_slot_steps": 30,
                                    "gen_slot_steps": 70})
    peaks = harness.peaks_of("TPU v5 lite")
    r = harness.Reading(harness.load_cell(CELL), window, t, peaks)
    got = {m: harness.load_module(ROOT / "bench" / "metrics" / f"{m}.py").read(r)
           for m in ("device_idle.serve", "serve.step_roofline", "serve.mfu",
                     "serve.prompt_slot_share")}
    assert got["device_idle.serve"] == pytest.approx(tr.idle_share(t) * 100)
    assert got["serve.step_roofline"] == pytest.approx(
        4e9 / tr.busy_s(t) / 819e9 * 100)
    assert got["serve.mfu"] == pytest.approx(
        3e12 / ((hi - lo) / 1e9) / 197e12 * 100)
    assert got["serve.prompt_slot_share"] == pytest.approx(0.3)
