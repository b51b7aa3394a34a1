"""Keyed streaming aggregation on the mesh backend: each call of
``lower(reduce_by_key(by, "count", nkeys=K), "mesh")`` counts one sliding
window of bids per auction, as one ``shard_map`` program over every chip
(an all-to-all moves each bid to its key's owner, a psum assembles the
counts).  Windows overlap: each call covers the previous call's newer half
and one new period of bids, so every bid is counted twice, as Q5 counts it.

The window calls the program on one window of bids after another, from a
pool made from the seed in set-up, until ``seconds`` have passed.
``events_per_s`` is the new bids of every call (one period each) over the
time from the first call to the end of the last.  Outputs of a seeded sample of calls are kept and
compared, every key of each, with the reference once the window closes.
"""
from __future__ import annotations

import contextlib
import functools
import time
from typing import Dict, List

import numpy as np

from bench import generate, harness
from bench.trace import span


def auction_of(x):
    """The key of a bid: its window-relative auction id (array-polymorphic,
    as the mesh lowering needs)."""
    return x


@contextlib.contextmanager
def bf16_wire():
    """The program's own quantised-wire path (``dfarm.dispatch(wire_dtype=
    bfloat16)``) switched on for every shuffle lowered inside the block."""
    import jax.numpy as jnp

    from repro.core import dfarm

    exact = dfarm.dispatch
    dfarm.dispatch = functools.partial(exact, wire_dtype=jnp.bfloat16)
    try:
        yield
    finally:
        dfarm.dispatch = exact


class Driver:
    def __init__(self, config: Dict, traffic: Dict, *, seed: int, devices,
                 log, tracing: bool = False):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.tracing = tracing
        self.ref = harness.reference(config)
        q = config["query"]
        self.nkeys = q["nkeys"]
        self.bids, self.slide = generate.nexmark_sizes(config["generator"], q)
        self.pool = [generate.nexmark_window(config["generator"], w,
                                             self.bids, self.slide, seed)
                     for w in range(traffic["pool_windows"])]
        top = max(int(w.max()) for w in self.pool)
        if top >= self.nkeys:
            raise ValueError(f"auction id {top} outside nkeys={self.nkeys}")
        self.n_devices = len(devices)
        self.program = self._lower()
        ids = [int(d.id) for d in self.program.mesh.devices.flat]
        if len(set(ids)) != self.n_devices:
            raise RuntimeError(f"the shuffle spans device ids {ids}, not "
                               f"{self.n_devices} distinct chips")
        self.program(self.pool[0])               # compile this row bucket
        self.kept: Dict[int, tuple] = {}
        self.calls = 0
        log(f"[shuffle] {len(self.pool)} windows of {self.bids} bids "
            f"sliding by {self.slide}, nkeys {self.nkeys}, all-to-all over {self.program.n_worker} "
            f"workers on device ids {ids}")

    def _lower(self):
        from repro.core import lower, reduce_by_key

        return lower(reduce_by_key(auction_of, "count", nright=self.n_devices,
                                   nkeys=self.nkeys),
                     "mesh", devices=self.n_devices)

    def window(self, seconds: float) -> harness.Window:
        keep = generate.rng_for(self.seed, 8).random(1 << 20) < \
            self.traffic["check"]["share"]
        call_s: List[float] = []
        pool, n = self.pool, len(self.pool)
        i = 0
        t0 = time.perf_counter()
        deadline = t0 + seconds
        with span("bench.window"):
            while True:
                if self.tracing:
                    with span("bench.mesh.call"):
                        t = time.perf_counter()
                        out = self.program(pool[i % n])
                        call_s.append(time.perf_counter() - t)
                else:
                    out = self.program(pool[i % n])
                if keep[i]:
                    self.kept[i] = out
                i += 1
                if time.perf_counter() >= deadline:
                    break
        t1 = time.perf_counter()
        self.kept[i - 1] = out                    # and always the last call
        self.calls = i
        return harness.Window(
            t0, t1, {"events_per_s": i * self.slide / (t1 - t0)},
            attempted=i, counts={"calls": i, "bids": i * self.slide},
            spans={"mesh.call": call_s},
            notes=[f"[shuffle] {i} windows, {i * self.slide} new bids in "
                   f"{t1 - t0:.3f} s; {len(self.kept)} kept to compare"])

    def release(self) -> None:
        self.program = None

    def check(self, control: bool = False) -> List[harness.Check]:
        """Every key's count in each kept window against the reference.
        With ``control``, the answers compared come from the program with
        its wire quantised to bfloat16, on the same windows."""
        outs = self.kept
        if control:
            with bf16_wire():
                prog = self._lower()
                outs = {i: prog(self.pool[i % len(self.pool)])
                        for i in self.kept}
        bad = 0
        for i, out in outs.items():
            want = self.ref.counts(self.pool[i % len(self.pool)], self.nkeys)
            got = np.zeros(self.nkeys, np.int64)
            stray = 0
            for k, c in out:
                if 0 <= k < self.nkeys:
                    got[k] = c
                else:
                    stray += 1
            bad += int(np.sum(got != want)) + stray
        return [harness.Check("mismatched_counts", bad, 0),
                harness.Check("compared_windows", len(outs), 1, least=True)]
