"""Attention: GQA + RoPE + optional sliding window.

Two execution paths:
  * ``chunked``  — pure-jnp flash-style attention: double tiling over query
    and key/value chunks with an online-softmax carry inside ``lax.scan``.
    Never materialises the (S, S) score matrix, so 32k prefill fits.  This is
    what the dry-run lowers (it compiles for any XLA backend) and it is the
    numerical oracle for the Pallas kernel in ``repro/kernels``.
  * ``naive``    — materialised scores; used for tiny shapes and as the
    reference in tests.

With ``causal_skip=True`` the chunked path only visits the lower-triangular
(query-chunk, kv-chunk) pairs — S(S+ck)/2 instead of S² score FLOPs — by
enumerating the valid pairs statically (beyond-paper optimisation, §Perf).

Decode (single new token against a KV cache) is a separate, simpler path;
``cached_decode_attention`` reads the decode cache, whose K/V keep heads
and head dimension merged, (B, T, Hkv·Dh).
All other shapes: q (B, S, H, Dh); k/v (B, T, Hkv, Dh) with H % Hkv == 0.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .layers import scan_unroll

__all__ = ["attention", "decode_attention", "cached_decode_attention"]

_NEG = -1e30


def _repeat_kv(k: jnp.ndarray, groups: int) -> jnp.ndarray:
    if groups == 1:
        return k
    return jnp.repeat(k, groups, axis=2)


def naive_attention(q, k, v, *, causal: bool, window: Optional[int],
                    q_offset: int = 0) -> jnp.ndarray:
    """Materialised reference. q_offset: absolute position of q[0] vs k[0]."""
    B, S, H, Dh = q.shape
    T = k.shape[1]
    k = _repeat_kv(k, H // k.shape[2])
    v = _repeat_kv(v, H // v.shape[2])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores *= Dh ** -0.5
    qpos = jnp.arange(S)[:, None] + q_offset
    kpos = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, None], scores, _NEG)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def _chunk_body(q_blk, k_blk, v_blk, carry, qpos, kpos, kv_len, *, causal, window, scale):
    """One (q-chunk × kv-chunk) flash step. carry = (m, l, acc) in fp32."""
    m, l, acc = carry
    s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_blk).astype(jnp.float32) * scale
    mask = kpos[None, :] < kv_len  # mask kv padding
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    s = jnp.where(mask[None, None], s, _NEG)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + p.sum(axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p.astype(v_blk.dtype), v_blk).astype(jnp.float32)
    return m_new, l_new, acc_new


def chunked_attention(q, k, v, *, causal: bool, window: Optional[int],
                      q_chunk: int, kv_chunk: int, causal_skip: bool = False,
                      q_offset: int = 0) -> jnp.ndarray:
    B, S, H, Dh = q.shape
    T = k.shape[1]
    groups = H // k.shape[2]
    scale = Dh ** -0.5
    cq = min(q_chunk, S)
    ck = min(kv_chunk, T)
    nq, nk = -(-S // cq), -(-T // ck)
    # pad to multiples
    qp = jnp.pad(q, ((0, 0), (0, nq * cq - S), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, nk * ck - T), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, nk * ck - T), (0, 0), (0, 0)))
    kp = kp.reshape(B, nk, ck, *kp.shape[2:])
    vp = vp.reshape(B, nk, ck, *vp.shape[2:])
    kpos_all = jnp.arange(nk * ck)

    def q_block(qi, q_blk):
        qpos = qi * cq + jnp.arange(cq) + q_offset
        m0 = jnp.full((B, H, cq), _NEG, jnp.float32)
        l0 = jnp.zeros((B, H, cq), jnp.float32)
        a0 = jnp.zeros((B, H, cq, Dh), jnp.float32)

        if causal_skip and causal and q_offset == 0 and S == T:
            # triangular schedule: q-chunk qi only needs kv-chunks [0, qi·cq/ck]
            # (static upper bound via scan length == nk but sliced per row is
            # dynamic; instead enumerate with a fori over a *dynamic* count)
            n_valid = jnp.minimum(((qi + 1) * cq + ck - 1) // ck, nk)

            def body(ki, carry):
                k_blk = lax.dynamic_index_in_dim(kp, ki, 1, keepdims=False)
                v_blk = lax.dynamic_index_in_dim(vp, ki, 1, keepdims=False)
                kpos = lax.dynamic_slice_in_dim(kpos_all, ki * ck, ck)
                k_blk = _repeat_kv(k_blk, groups)
                v_blk = _repeat_kv(v_blk, groups)
                return _chunk_body(q_blk, k_blk, v_blk, carry, qpos, kpos, T,
                                   causal=causal, window=window, scale=scale)

            m, l, acc = lax.fori_loop(0, n_valid, body, (m0, l0, a0))
        else:
            def step(carry, inputs):
                k_blk, v_blk, kpos = inputs
                k_blk = _repeat_kv(k_blk, groups)
                v_blk = _repeat_kv(v_blk, groups)
                return _chunk_body(q_blk, k_blk, v_blk, carry, qpos, kpos, T,
                                   causal=causal, window=window, scale=scale), None

            (m, l, acc), _ = lax.scan(
                step, (m0, l0, a0),
                (kp.transpose(1, 0, 2, 3, 4), vp.transpose(1, 0, 2, 3, 4),
                 kpos_all.reshape(nk, ck)), unroll=scan_unroll())
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.astype(q.dtype)                       # (B, H, cq, Dh)

    qp = qp.reshape(B, nq, cq, H, Dh)
    _, outs = lax.scan(lambda c, args: (c, q_block(*args)), 0,
                       (jnp.arange(nq), qp.transpose(1, 0, 2, 3, 4)),
                       unroll=scan_unroll())
    out = outs.transpose(1, 0, 3, 2, 4).reshape(B, nq * cq, H, Dh)
    return out[:, :S]


def attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
              impl: str = "chunked", q_chunk: int = 1024, kv_chunk: int = 512,
              causal_skip: bool = False, q_offset: int = 0) -> jnp.ndarray:
    if impl == "naive" or q.shape[1] * k.shape[1] <= 256 * 256:
        return naive_attention(q, k, v, causal=causal, window=window, q_offset=q_offset)
    return chunked_attention(q, k, v, causal=causal, window=window,
                             q_chunk=q_chunk, kv_chunk=kv_chunk,
                             causal_skip=causal_skip, q_offset=q_offset)


def decode_attention(q, k_cache, v_cache, positions) -> jnp.ndarray:
    """Single-step attention against a cache, masked per row.

    q: (B, 1, H, Dh); caches: (B, T, Hkv, Dh); positions: (B,) — row b's
    new token sits at position ``positions[b]`` (its k/v already written)
    and attends to cache indices ``<= positions[b]``.  A sliding-window
    model keeps a rolling cache of T = window slots written at
    ``pos % T``: once a row has filled it every slot is valid and holds one
    of its last T positions, so the same mask is its window.  Indices
    above a row's position hold an earlier request's entries (a recycled
    slot) or nothing, and are never read.
    """
    B, _, H, Dh = q.shape
    T, Hkv = k_cache.shape[1], k_cache.shape[2]
    g = H // Hkv
    # grouped-GQA form: NEVER materialise repeated KV.  The repeat would
    # break the cache's sequence (T) sharding under GSPMD and trigger a
    # full cache all-gather per layer (§Perf H2 — found by the exact
    # accounting: 2.15 GB/layer/step of avoidable all-gather).
    qg = q.reshape(B, 1, Hkv, g, Dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_cache).astype(jnp.float32)
    s = s * (Dh ** -0.5)
    valid = jnp.arange(T)[None, :] <= positions[:, None]
    s = jnp.where(valid[:, None, None, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v_cache.dtype), v_cache)
    return out.reshape(B, 1, H, Dh)


def cached_decode_attention(q, k_cache, v_cache, positions) -> jnp.ndarray:
    """``decode_attention`` over a self-attention cache that keeps heads
    and head dimension merged in its minor axis: caches (B, T, Hkv·Dh).

    Splitting that axis back into (Hkv, Dh) changes the array's layout
    on a TPU, where a head dimension such as 96 does not fill a 128-lane
    tile: a copy of every layer a step.  So the heads stay in the minor
    axis.  The query is laid out block-diagonally, (B, Hkv·Dh, H): query
    head h·g + j holds its Dh values in the rows of KV head h and zeros
    elsewhere.  Scores are one contraction over the merged axis;
    probabilities times V give every (query head, KV head) block, of
    which the diagonal ones are kept.  The zero blocks cost Hkv times the
    MXU work of the attention products, which a step bound by HBM
    bandwidth hides.  The mask is ``decode_attention``'s."""
    B, _, H, Dh = q.shape
    T, C = k_cache.shape[1], k_cache.shape[2]
    Hkv = C // Dh
    g = H // Hkv
    eye = jnp.eye(Hkv, dtype=q.dtype)
    qt = q.reshape(B, Hkv, g, Dh).transpose(0, 3, 1, 2)          # (B,Dh,h,g)
    qbd = (qt[:, None] * eye[None, :, None, :, None]).reshape(B, C, H)
    s = jnp.einsum("btc,bch->bht", k_cache, qbd,
                   preferred_element_type=jnp.float32) * (Dh ** -0.5)
    valid = jnp.arange(T)[None, :] <= positions[:, None]
    s = jnp.where(valid[:, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bht,btc->bhc", p.astype(v_cache.dtype), v_cache)
    o = jnp.diagonal(o.reshape(B, Hkv, g, Hkv, Dh), axis1=1, axis2=3)
    return o.transpose(0, 3, 1, 2).reshape(B, 1, H, Dh)     # from (B,g,Dh,h)
