"""Plain reference for ``swissprot-57.5-search``: the best local alignment
score of a query against each subject, by the textbook recurrence with
affine gaps (Gotoh), in exact integers.  A gap of ``k`` residues costs
``gap_open + (k - 1) * gap_extend``.

    E[i,j] = max(H[i,j-1] - go, E[i,j-1] - ge)
    F[i,j] = max(H[i-1,j] - go, F[i-1,j] - ge)
    H[i,j] = max(0, H[i-1,j-1] + s(q_i, d_j), E[i,j], F[i,j])

Subject positions ``j`` are taken one at a time, every query position and
a batch of subjects at once.  Along the query, ``F`` is a running maximum:
``F[i,j] = max over k < i of H[k,j] - go - (i-1-k) * ge``, and ``H[k,j]``
may there be taken without its own ``F`` term, since a gap that reopens
right after a gap never beats extending it (``go >= ge``).  Subjects
shorter than the longest are padded at their end, where no cell can feed a
real one, and the best is taken over real cells only.  Nothing here comes
from the program: the BLOSUM50 table is this file's own copy.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

ALPHABET = "ARNDCQEGHILKMFPSTWYVBZX*"
# BLOSUM50, NCBI, rows and columns in ALPHABET order
_BLOSUM50 = """
 5 -2 -1 -2 -1 -1 -1  0 -2 -1 -2 -1 -1 -3 -1  1  0 -3 -2  0 -2 -1 -1 -5
-2  7 -1 -2 -4  1  0 -3  0 -4 -3  3 -2 -3 -3 -1 -1 -3 -1 -3 -1  0 -1 -5
-1 -1  7  2 -2  0  0  0  1 -3 -4  0 -2 -4 -2  1  0 -4 -2 -3  4  0 -1 -5
-2 -2  2  8 -4  0  2 -1 -1 -4 -4 -1 -4 -5 -1  0 -1 -5 -3 -4  5  1 -1 -5
-1 -4 -2 -4 13 -3 -3 -3 -3 -2 -2 -3 -2 -2 -4 -1 -1 -5 -3 -1 -3 -3 -2 -5
-1  1  0  0 -3  7  2 -2  1 -3 -2  2  0 -4 -1  0 -1 -1 -1 -3  0  4 -1 -5
-1  0  0  2 -3  2  6 -3  0 -4 -3  1 -2 -3 -1 -1 -1 -3 -2 -3  1  5 -1 -5
 0 -3  0 -1 -3 -2 -3  8 -2 -4 -4 -2 -3 -4 -2  0 -2 -3 -3 -4 -1 -2 -2 -5
-2  0  1 -1 -3  1  0 -2 10 -4 -3  0 -1 -1 -2 -1 -2 -3  2 -4  0  0 -1 -5
-1 -4 -3 -4 -2 -3 -4 -4 -4  5  2 -3  2  0 -3 -3 -1 -3 -1  4 -4 -3 -1 -5
-2 -3 -4 -4 -2 -2 -3 -4 -3  2  5 -3  3  1 -4 -3 -1 -2 -1  1 -4 -3 -1 -5
-1  3  0 -1 -3  2  1 -2  0 -3 -3  6 -2 -4 -1  0 -1 -3 -2 -3  0  1 -1 -5
-1 -2 -2 -4 -2  0 -2 -3 -1  2  3 -2  7  0 -3 -2 -1 -1  0  1 -3 -1 -1 -5
-3 -3 -4 -5 -2 -4 -3 -4 -1  0  1 -4  0  8 -4 -3 -2  1  4 -1 -4 -4 -2 -5
-1 -3 -2 -1 -4 -1 -1 -2 -2 -3 -4 -1 -3 -4 10 -1 -1 -4 -3 -3 -2 -1 -2 -5
 1 -1  1  0 -1  0 -1  0 -1 -3 -3  0 -2 -3 -1  5  2 -4 -2 -2  0  0 -1 -5
 0 -1  0 -1 -1 -1 -1 -2 -2 -1 -1 -1 -1 -2 -1  2  5 -3 -2  0  0 -1  0 -5
-3 -3 -4 -5 -5 -1 -3 -3 -3 -3 -2 -3 -1  1 -4 -4 -3 15  2 -3 -5 -2 -3 -5
-2 -1 -2 -3 -3 -1 -2 -3  2 -1 -1 -2  0  4 -3 -2 -2  2  8 -1 -3 -2 -1 -5
 0 -3 -3 -4 -1 -3 -3 -4 -4  4  1 -3  1 -1 -3 -2  0 -3 -1  5 -4 -3 -1 -5
-2 -1  4  5 -3  0  1 -1  0 -4 -4  0 -3 -4 -2  0  0 -5 -3 -4  5  2 -1 -5
-1  0  0  1 -3  4  5 -2  0 -3 -3  1 -1 -4 -1  0 -1 -2 -2 -3  2  5 -1 -5
-1 -1 -1 -1 -2 -1 -1 -2 -1 -1 -1 -1 -1 -2 -2 -1  0 -3 -1 -1 -1 -1 -1 -5
-5 -5 -5 -5 -5 -5 -5 -5 -5 -5 -5 -5 -5 -5 -5 -5 -5 -5 -5 -5 -5 -5 -5  1
"""
BLOSUM50 = np.asarray([[int(v) for v in row.split()]
                       for row in _BLOSUM50.strip().splitlines()], np.int32)
NEG = -(10 ** 9)


def scores(query: np.ndarray, subjects: Sequence[np.ndarray], gap_open: int,
           gap_extend: int) -> np.ndarray:
    """Best local alignment score of ``query`` against each subject."""
    if gap_open < gap_extend:
        raise ValueError("the running maximum for F needs gap_open >= "
                         "gap_extend")
    m, b = len(query), len(subjects)
    lens = np.asarray([len(s) for s in subjects], np.int64)
    subj = np.zeros((b, int(lens.max())), np.int64)
    for k, s in enumerate(subjects):
        subj[k, :lens[k]] = s
    prof = np.ascontiguousarray(BLOSUM50[np.asarray(query, np.int64)].T)
    ramp = np.arange(m + 1, dtype=np.int32) * gap_extend  # (i-1-k) ge terms
    h = np.zeros((b, m + 1), np.int32)               # column j-1, row 0 = 0
    e = np.full((b, m), NEG, np.int32)
    best = np.zeros(b, np.int32)
    for j in range(subj.shape[1]):
        e = np.maximum(h[:, 1:] - gap_open, e - gap_extend)
        hp = np.maximum(h[:, :-1] + prof[subj[:, j]], e)
        np.maximum(hp, 0, out=hp)                    # H without F, rows 1..m
        # F[i] = max_{k<i} H[k] + k ge, less go + (i-1) ge; row 0 is H = 0
        run = np.maximum.accumulate(hp[:, :-1] + ramp[1:m], axis=1)
        f = np.concatenate([np.zeros((b, 1), np.int32),
                            np.maximum(run, 0)], axis=1) - gap_open - ramp[:m]
        hj = np.maximum(hp, f)
        live = j < lens
        best = np.where(live, np.maximum(best, hj.max(axis=1)), best)
        h = np.concatenate([np.zeros((b, 1), np.int32), hj], axis=1)
    return best.astype(np.int64)
