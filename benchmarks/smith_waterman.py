"""Paper Fig. 7 + Table 1 — Smith-Waterman database search, GCUPS.

UniProt is not available offline, so the reference database is synthesised
with the Swiss-Prot release 57.5 statistics the paper quotes (mean length
352, min 2, long tail) and queries mirror the paper's P02232/P10635/P27895
lengths (144 / 497 / 1000).  The pipeline is the paper's: a farm streams
⟨query, subject⟩ pairs through the vectorised SW kernel; the collector
gathers scores in order.  GCUPS = |Q|·|D| / (T·1e9).

Both of the paper's gap regimes (10-2k, 5-2k) are exercised; Table 1's
min/max/avg per-task service times are reported for each query length.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import FnNode, TaskFarm
from repro.kernels import ops

QUERY_LENS = [144, 497, 1000]          # P02232, P10635, P27895
DB_SIZE = 64                           # sequences (interpret-mode sized)
MEAN_LEN = 352


def gcups(qlen: int, db_cells: int, seconds: float) -> float:
    return qlen * db_cells / (seconds * 1e9)


def make_db(rng, n: int = DB_SIZE) -> list:
    """``n`` encoded subjects with Swiss-Prot 57.5's length statistics
    (gamma-distributed, mean 352, clipped to [2, 2000])."""
    lens = np.clip(rng.gamma(2.0, MEAN_LEN / 2.0, n).astype(int), 2, 2000)
    return [rng.integers(0, 20, int(l)).astype(np.int32) for l in lens]


def run(emit):
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    db = make_db(rng)
    db_res = int(sum(len(s) for s in db))
    for gap_open, tag in [(10.0, "10-2k"), (5.0, "5-2k")]:
        for qlen in QUERY_LENS:
            query = jnp.asarray(rng.integers(0, 20, qlen), jnp.int32)
            # warm the kernel cache (compile once per subject-pad bucket)
            _ = ops.smith_waterman(query, db[0], gap_open=gap_open,
                                   gap_extend=2.0)
            times = []

            def worker(subj):
                t0 = time.perf_counter()
                s = float(ops.smith_waterman(query, subj,
                                             gap_open=gap_open, gap_extend=2.0))
                times.append(time.perf_counter() - t0)
                return s

            farm = TaskFarm(2, preserve_order=True)
            farm.add_stream(db)
            farm.add_worker(FnNode(worker))
            t0 = time.perf_counter()
            scores = farm.run_and_wait()
            dt = time.perf_counter() - t0
            assert len(scores) == DB_SIZE and all(s >= 0 for s in scores)
            g = gcups(qlen, db_res, dt)
            emit(f"sw_{tag}_q{qlen}", dt / DB_SIZE * 1e6,
                 f"gcups={g:.6f},task_min_us={min(times)*1e6:.0f},"
                 f"task_max_us={max(times)*1e6:.0f},"
                 f"task_avg_us={np.mean(times)*1e6:.0f}")
