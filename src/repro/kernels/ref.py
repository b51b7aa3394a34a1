"""Pure-jnp oracles for every Pallas kernel in this package.

Each reference is deliberately implemented with a *different* algorithmic
mechanism than its kernel (e.g. sequential F-scan vs closed-form prefix-max
in Smith-Waterman) so that agreement is meaningful evidence of correctness.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["sw_ref", "sw_numpy", "attention_ref", "ssd_ref"]

NEG = np.float32(-1e9)


# --------------------------------------------------------------------------
# Smith-Waterman, affine gaps (gap_open charged on the first gap residue)
# --------------------------------------------------------------------------
def sw_ref(profile: jnp.ndarray, subject: jnp.ndarray, gap_open: float,
           gap_extend: float, subject_len: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Oracle: outer scan over subject chars, INNER SEQUENTIAL scan over the
    query for F (the column-direction gap) — no prefix-max closed form.

    profile: (A, Q) f32 — profile[c, i] = score(query_i, char c)
    subject: (D,) int32 character codes; entries ≥ A (or beyond
    subject_len) are padding and are skipped.
    Returns the best local alignment score (scalar f32).
    """
    A, Q = profile.shape
    D = subject.shape[0]
    slen = jnp.int32(D) if subject_len is None else subject_len

    def per_char(carry, inp):
        h_prev, e_prev, best = carry
        j, c = inp
        prof = profile[jnp.clip(c, 0, A - 1)]                       # (Q,)
        e = jnp.maximum(h_prev - gap_open, e_prev - gap_extend)     # gap in col dir
        diag = jnp.concatenate([jnp.zeros((1,), jnp.float32), h_prev[:-1]]) + prof
        h_hat = jnp.maximum(jnp.maximum(diag, e), 0.0)

        def f_step(f_prev_and_h, i):
            f_prev, h_up = f_prev_and_h
            f_i = jnp.maximum(h_up - gap_open, f_prev - gap_extend)
            h_i = jnp.maximum(h_hat[i], f_i)
            return (f_i, h_i), h_i

        (_, _), h = lax.scan(f_step, (NEG, jnp.float32(0)), jnp.arange(Q))
        valid = (j < slen) & (c < A)
        h = jnp.where(valid, h, h_prev)
        e = jnp.where(valid, e, e_prev)
        best = jnp.where(valid, jnp.maximum(best, h.max()), best)
        return (h, e, best), None

    init = (jnp.zeros((Q,), jnp.float32), jnp.full((Q,), NEG), jnp.float32(0))
    (h, e, best), _ = lax.scan(per_char, init, (jnp.arange(D), subject))
    return best


def sw_numpy(query: str, subject: str, score_fn, gap_open: float, gap_extend: float) -> float:
    """Cell-by-cell numpy triple-check (tests and the chip smoke check)."""
    Q, D = len(query), len(subject)
    H = np.zeros((Q + 1, D + 1))
    E = np.full((Q + 1, D + 1), -1e9)
    F = np.full((Q + 1, D + 1), -1e9)
    best = 0.0
    for i in range(1, Q + 1):
        for j in range(1, D + 1):
            E[i, j] = max(H[i, j - 1] - gap_open, E[i, j - 1] - gap_extend)
            F[i, j] = max(H[i - 1, j] - gap_open, F[i - 1, j] - gap_extend)
            H[i, j] = max(0.0, H[i - 1, j - 1] + score_fn(query[i - 1], subject[j - 1]),
                          E[i, j], F[i, j])
            best = max(best, H[i, j])
    return best


# --------------------------------------------------------------------------
# Flash attention oracle (materialised, fp32)
# --------------------------------------------------------------------------
def attention_ref(q, k, v, *, causal: bool = True, window: Optional[int] = None):
    """q (B,H,S,D); k/v (B,Hkv,T,D). Returns (B,H,S,D)."""
    B, H, S, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    g = H // Hkv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhsd,bhtd->bhst", q, k).astype(jnp.float32) * (D ** -0.5)
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", p.astype(v.dtype), v)


# --------------------------------------------------------------------------
# SSD oracle: token-by-token recurrence (see also models/ssm.ssd_reference)
# --------------------------------------------------------------------------
def ssd_ref(x, dt, A, B, C, h0=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    from ..models.ssm import ssd_reference
    return ssd_reference(x, dt, A, B, C, h0=h0)
