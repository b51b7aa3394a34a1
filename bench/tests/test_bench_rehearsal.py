"""Each driver rehearsed on the CPU at a tiny size, through the harness's
whole run but the look for a chip: sound runs come out correct, and each
fault a cell can have, and each cell's control, come out not correct.

The mesh cell runs in a child process with four virtual CPU devices,
since the device count is fixed when JAX starts."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src")) if p not in sys.path]

from bench import harness  # noqa: E402


def tiny_search(query_length=20):
    cell = harness.load_cell("sw-q144")
    cell.config["database"].update(sequences=40, mean_length=30,
                                   max_length=150)
    cell.config["search"].update(tile=64, ring_capacity=4)
    cell.traffic["query_length"] = query_length
    cell.traffic["check"].update(sample=12, per_bucket=3)
    return cell


def run(cell, seed=2**31 + 5, seconds=0.5, trace=False):
    import jax

    return harness.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                            t_start=time.perf_counter(),
                            devices=jax.devices()[:cell.chips], peaks={},
                            log=lambda m: None)


@pytest.mark.parametrize("trace", [False, True])
def test_search_runs_and_is_correct(trace):
    r = run(tiny_search(), trace=trace)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert r["checks"]["mismatched_scores"]["value"] == 0
    assert list(r["checks"])[-1] == "compared_scores" and \
        list(r)[-1] == "checks"
    names = {"gcups", "setup_s"} if not trace else {"sw.pair_ms"}
    assert set(r["metrics"]) == names       # no TPU planes on the CPU
    assert r["metrics"][sorted(names)[0]]["value"] > 0


def test_search_answer_altered_is_caught(monkeypatch):
    from bench import generate
    from repro.kernels import ops

    cell, seed = tiny_search(), 2**31 + 5
    longest = generate.protein_db(cell.config["database"], seed).lengths.max()
    exact = ops.smith_waterman

    def altered(query, subject, **kw):   # the longest subject's answer
        s = exact(query, subject, **kw)
        return s + 1 if len(subject) == longest else s

    monkeypatch.setattr(ops, "smith_waterman", altered)
    r = run(cell, seed=seed)
    assert not r["correct"]
    assert r["checks"]["mismatched_scores"]["value"] > 0


def test_search_control_is_caught():
    import jax

    cell = tiny_search()
    drv = harness.driver(cell.config).Driver(
        cell.config, cell.traffic, seed=7, devices=jax.devices(),
        log=lambda m: None)
    drv.window(0.3)
    drv.release()
    assert all(c.ok for c in drv.check())
    bad = {c.name: c for c in drv.check(control=True)}
    assert not bad["mismatched_scores"].ok


def test_gcups_counts_real_residues_not_padding():
    import jax

    cell = tiny_search()
    drv = harness.driver(cell.config).Driver(
        cell.config, cell.traffic, seed=11, devices=jax.devices(),
        log=lambda m: None)
    w = drv.window(0.2)
    n, tile, q = len(drv.db), drv.tile, len(drv.query)
    lens = [int(drv.db.lengths[i % n]) for i in range(w.counts["pairs"])]
    assert 0 < w.counts["pairs"] <= len(drv.scores)
    assert all(t <= w.t1 for t in drv.arrivals[:w.counts["pairs"]])
    assert w.counts["cells"] == q * sum(lens)
    assert w.counts["cells"] < q * sum(-(-ln // tile) * tile for ln in lens)
    assert w.end_to_end["gcups"] == pytest.approx(w.counts["cells"] / 0.2 / 1e9)


MESH = r"""
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax
from bench import harness
from bench.drivers import shuffle

def tiny():
    cell = harness.load_cell("nexmark-q5count-4chip")
    cell.config["generator"]["first_event_rate"] = 500   # 4600-bid windows
    cell.config["query"]["nkeys"] = 512
    cell.traffic["check"]["share"] = 0.3
    return cell

def run(trace=False):
    r = harness.run_cell(tiny(), seed=2**31 + 9, seconds=0.3, trace=trace,
                         t_start=time.perf_counter(),
                         devices=jax.devices()[:4], peaks={},
                         log=lambda m: None)
    return {"correct": r["correct"], "metrics": sorted(r["metrics"]),
            "checks": r["checks"]}

out = {"sound": run(), "traced": run(True)}

from repro.core import dfarm, a2a
from repro.core.a2a import A2AMeshProgram
exact_dispatch, exact_call = dfarm.dispatch, A2AMeshProgram.__call__

def local_only(items, dest, axis_name, capacity, **kw):
    # the exchange left out: every row stays on the chip it started on
    import jax.numpy as jnp
    n = jax.lax.axis_size(axis_name)
    send = jnp.zeros((n, capacity, items.shape[1]), items.dtype)
    return send.at[0, :items.shape[0]].set(items), None

import jax.lax as lax
exact_psum = lax.psum
dfarm.dispatch = local_only
lax.psum = lambda x, axis_name: x
out["no_exchange"] = run()
dfarm.dispatch, lax.psum = exact_dispatch, exact_psum

def altered(self, items):
    got = exact_call(self, items)
    return [(k, c + 1) if i == 0 else (k, c) for i, (k, c) in enumerate(got)]
A2AMeshProgram.__call__ = altered
out["count_altered"] = run()
A2AMeshProgram.__call__ = exact_call

cell = tiny()
d = shuffle.Driver(cell.config, cell.traffic, seed=3,
                   devices=jax.devices()[:4], log=lambda m: None)
d.window(0.2)
out["control"] = {"correct": all(c.ok for c in d.check(control=True))}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", MESH, str(ROOT)],
                         capture_output=True, text=True, timeout=600,
                         env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_mesh_runs_and_is_correct(mesh_runs):
    assert mesh_runs["sound"]["correct"]
    assert mesh_runs["sound"]["metrics"] == ["events_per_s", "setup_s"]
    assert mesh_runs["traced"]["correct"]
    assert mesh_runs["sound"]["checks"]["mismatched_counts"]["value"] == 0


@pytest.mark.parametrize("fault", ["no_exchange", "count_altered", "control"])
def test_mesh_fault_is_caught(mesh_runs, fault):
    assert mesh_runs[fault]["correct"] is False
