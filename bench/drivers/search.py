"""Protein database search (paper Sec. 4.2) through the program's ordered
farm: ``lower(Pipeline(Farm(align, workers, ordered=True), collector),
"threads")`` with ``align`` one ``ops.smith_waterman`` call and its host
sync per (query, subject) pair.

The window streams the database from subject 0 (cycling if it ever runs
out) until ``seconds`` have passed, then lets the farm drain what its
rings hold.  ``gcups`` is the query length times the real residues of the
subjects whose scores came back inside the window, over the window.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from bench import generate, harness
from bench.trace import span


class Driver:
    def __init__(self, config: Dict, traffic: Dict, *, seed: int, devices,
                 log, tracing: bool = False):
        import jax

        from repro.core import Farm, Pipeline, Stage, lower
        from repro.kernels import ops

        self.config, self.traffic, self.seed = config, traffic, seed
        self.log = log
        self.ref = harness.reference(config)
        search = config["search"]
        self.tile = search["tile"]
        self.gap_open, self.gap_extend = traffic["gap_open"], traffic["gap_extend"]
        self.db = generate.protein_db(config["database"], seed)
        self.query = generate.protein_query(traffic["query_length"], seed)
        query = jax.device_put(self.query, devices[0])
        self.pair_s: List[float] = []
        self.scores: List[float] = []
        self.arrivals: List[float] = []
        self.sent = 0

        def align(subject):
            return float(ops.smith_waterman(
                query, subject, gap_open=self.gap_open,
                gap_extend=self.gap_extend, tile=self.tile))

        def traced_align(subject):
            with span("bench.sw.pair"):
                t = time.perf_counter()
                s = align(subject)
                self.pair_s.append(time.perf_counter() - t)
            return s

        def collect(score):
            self.scores.append(score)
            self.arrivals.append(time.perf_counter())
            return score

        self.align = traced_align if tracing else align
        self.program = lower(Pipeline(
            Farm(self.align, search["farm_workers"], ordered=True),
            Stage(collect)), "threads", capacity=search["ring_capacity"])
        # one compiled kernel per padding bucket: warm each that the
        # database holds
        buckets = -(-self.db.lengths // self.tile)
        for b in np.unique(buckets):
            align(self.db.subject(int(np.argmax(buckets == b))))
        log(f"[search] {len(self.db)} subjects, {self.db.lengths.sum()} "
            f"residues, query {len(self.query)}, gaps "
            f"{self.gap_open}-{self.gap_extend}, buckets "
            f"{np.unique(buckets).tolist()} x {self.tile}")

    def window(self, seconds: float) -> harness.Window:
        n = len(self.db)
        t0 = time.perf_counter()
        deadline = t0 + seconds

        def stream():
            i = 0
            while time.perf_counter() < deadline:
                yield self.db.subject(i % n)
                i += 1
                self.sent = i

        with span("bench.window"):
            self.program.to_graph(stream()).run_and_wait()
        done = int(np.searchsorted(self.arrivals, deadline, side="right"))
        lens = self.db.lengths[np.arange(len(self.scores)) % n]
        residues = int(lens[:done].sum())
        cells = len(self.query) * residues
        drained = self.arrivals[-1] - deadline
        return harness.Window(
            t0, deadline, {"gcups": cells / seconds / 1e9},
            attempted=self.sent, failed=self.sent - len(self.scores),
            counts={"cells": cells, "pairs": done,
                    # every scored pair's cells, the drain's included: what
                    # the kernel computed while the profiler ran
                    "scored_cells": len(self.query) * int(lens.sum())},
            spans={"sw.pair": self.pair_s},
            notes=[f"[search] {done} pairs, {residues} residues in the "
                   f"{seconds} s window; {len(self.scores) - done} more "
                   f"drained from the rings in {drained:.3f} s after it"])

    def release(self) -> None:
        self.program = None

    # -- correctness ---------------------------------------------------------
    def sample(self) -> np.ndarray:
        """Positions to compare, drawn from the seed: ``sample`` of all the
        scored positions, ``per_bucket`` more of every padding bucket, and
        the longest subject scored."""
        n, done = len(self.db), len(self.scores)
        pos = np.arange(done)
        lens = self.db.lengths[pos % n]
        buckets = -(-lens // self.tile)
        rng = generate.rng_for(self.seed, 7)
        check = self.traffic["check"]
        picks = [int(np.argmax(lens))]
        picks += rng.choice(pos, min(check["sample"], done),
                            replace=False).tolist()
        for b in np.unique(buckets):
            where = pos[buckets == b]
            picks += rng.choice(where, min(check["per_bucket"], len(where)),
                                replace=False).tolist()
        return np.unique(picks)

    def reference_scores(self, picks: np.ndarray, gap_open: int,
                         gap_extend: int, batch: int = 128) -> np.ndarray:
        """The reference's scores, in batches of subjects of like length."""
        n = len(self.db)
        subjects = [self.db.subject(int(p) % n) for p in picks]
        order = np.argsort([len(s) for s in subjects], kind="stable")
        out = np.zeros(len(picks), np.int64)
        for at in range(0, len(order), batch):
            idx = order[at:at + batch]
            out[idx] = self.ref.scores(self.query, [subjects[i] for i in idx],
                                       gap_open, gap_extend)
        return out

    def check(self, control: bool = False) -> List[harness.Check]:
        """Exact comparison of sampled scores with the reference.  With
        ``control``, the answers compared are the reference's own with a
        linear gap cost (every gap residue ``gap_extend``), which breaks the
        configuration's affine gaps."""
        picks = self.sample()
        t = time.perf_counter()
        want = self.reference_scores(picks, self.gap_open, self.gap_extend)
        self.log(f"[check] {len(picks)} scores from the reference in "
                 f"{time.perf_counter() - t:.3f} s")
        if control:
            got = self.reference_scores(picks, self.gap_extend, self.gap_extend)
        else:
            got = np.asarray([self.scores[p] for p in picks])
        return [harness.Check("mismatched_scores", int(np.sum(got != want)), 0),
                harness.Check("missing_scores", self.sent - len(self.scores), 0),
                harness.Check("compared_scores", len(picks), 1, least=True)]
