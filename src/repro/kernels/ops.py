"""Jit'd public wrappers for the Pallas kernels.

``interpret=None`` (the default) resolves from the backend: the Pallas
interpreter on the CPU backend, the compiled Mosaic kernel everywhere else.
Module-level tables are numpy, so importing this module never touches a
device.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import obs
from .flash_attention import flash_attention as _flash
from .smith_waterman import sw_pallas as _sw
from .ssd_scan import ssd_scan as _ssd

__all__ = ["smith_waterman", "smith_waterman_padded", "pad_subject",
           "flash_attention_op", "ssd_scan_op", "build_profile", "BLOSUM50",
           "AA_ALPHABET", "encode_seq", "resolve_interpret"]


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Interpret only where Mosaic cannot run: the CPU backend."""
    return jax.default_backend() == "cpu" if interpret is None else interpret


# ---------------------------------------------------------------------------
# Smith-Waterman front-end: alphabet, BLOSUM50, profiles
# ---------------------------------------------------------------------------
AA_ALPHABET = "ARNDCQEGHILKMFPSTWYVBZX*"        # 24 codes, BLOSUM order

# BLOSUM50 (upper triangle source: NCBI), 24x24
_B50 = """
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
BLOSUM50 = np.asarray(
    [[int(v) for v in row.split()] for row in _B50.strip().splitlines()],
    np.float32)


def encode_seq(seq: str) -> np.ndarray:
    lut = {c: i for i, c in enumerate(AA_ALPHABET)}
    return np.asarray([lut.get(c, lut["X"]) for c in seq.upper()], np.int32)


def build_profile(query, matrix=BLOSUM50,
                  pad_to: int = 128) -> Tuple[jnp.ndarray, int]:
    """Farrar's query profile, TPU layout: (A, Qp) with Qp multiple of 128.
    Padded query positions score a large negative so they never align."""
    q_len = int(query.shape[0])
    qp = -(-q_len // pad_to) * pad_to
    prof = jnp.asarray(matrix)[:, jnp.asarray(query)]       # (A, Q)
    prof = jnp.pad(prof, ((0, 0), (0, qp - q_len)), constant_values=-1e4)
    return prof, q_len


@functools.partial(jax.jit, static_argnames=("gap_open", "gap_extend",
                                             "tile", "interpret"))
def smith_waterman_padded(query, subject, matrix, *, gap_open, gap_extend,
                          tile, interpret):
    """The whole device program of one alignment: profile build + kernel.
    ``subject`` must already be padded to a whole number of tiles
    (:func:`pad_subject`)."""
    prof, q_len = build_profile(query, matrix)
    return _sw(prof, subject, gap_open=gap_open, gap_extend=gap_extend,
               q_len=q_len, tile=tile, interpret=interpret)


def smith_waterman(query, subject, *, gap_open: float = 10.0,
                   gap_extend: float = 2.0, matrix=BLOSUM50, tile: int = 512,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """Best local alignment score of two encoded sequences (the paper's
    application, Sec. 4.2).  The subject is padded on the host to a whole
    number of tiles, so one compiled program serves every subject length
    in a tile bucket.  Returns as soon as the kernel is dispatched; the
    ``sw.dispatch`` region (under the profiler) times the pad, the
    argument transfers and the dispatch."""
    with obs.region("sw.dispatch"):
        subj = pad_subject(subject, tile, matrix.shape[0])
        return smith_waterman_padded(
            query, subj, matrix, gap_open=float(gap_open),
            gap_extend=float(gap_extend), tile=tile,
            interpret=resolve_interpret(interpret))


def pad_subject(subject, tile: int, fill: int) -> np.ndarray:
    """Host-side pad of an encoded subject to a multiple of ``tile`` with
    ``fill`` (a code >= the alphabet size, which the kernel skips)."""
    subject = np.asarray(subject, np.int32)
    dlen = subject.shape[0]
    return np.pad(subject, (0, -(-dlen // tile) * tile - dlen),
                  constant_values=fill)


def flash_attention_op(q, k, v, *, causal=True, window=None,
                       interpret: Optional[bool] = None, **kw):
    return _flash(q, k, v, causal=causal, window=window,
                  interpret=resolve_interpret(interpret), **kw)


def ssd_scan_op(x, dt, A, B, C, *, chunk=256, interpret: Optional[bool] = None):
    return _ssd(x, dt, A, B, C, chunk=chunk, interpret=resolve_interpret(interpret))
