"""Flash attention (GQA / causal / sliding-window) — Pallas TPU kernel.

Tiling: grid (B, H, nq, nk) with the kv dimension minor-most (sequential);
online-softmax state (m, l, acc) lives in VMEM scratch and is reset at
kv-block 0.  GQA is zero-copy: the K/V BlockSpec index map sends query head
``h`` to kv head ``h // group`` — no repeated KV ever materialises in HBM.
Causal + sliding-window masking is block-level: fully-masked kv blocks skip
their matmuls entirely via ``pl.when`` (the triangular schedule).

Block sizes default to (128, 512) — q tile fills the 128-lane registers, kv
tile amortises HBM→VMEM latency; VMEM footprint per step ≈
bq·D + bk·D·2 + bq·bk scores ≈ 0.6 MB at D=128 — far under the ~16 MB VMEM
budget, leaving room for double buffering (the compiler's async copies are
the SPSC queue here).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

NEG = -1e9  # python float: keeps pallas kernels constant-free


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               scale: float, causal: bool, window, bq: int, bk: int,
               seq_q: int, seq_k: int):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq
    k_start = ki * bk
    # block-level schedule: skip blocks that are entirely masked out
    live = jnp.bool_(True)
    if causal:
        live &= k_start <= q_start + bq - 1
    if window is not None:
        live &= k_start + bk - 1 > q_start - window

    @pl.when(live)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)                   # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                   # (bk, D)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale  # (bq,bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < seq_k
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG)
        m_prev = m_scr[...][:, :1]                            # (bq,1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[...][:, :1] * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        acc_scr[...] = acc

    @pl.when(ki == pl.num_programs(3) - 1)
    def _emit():
        l = l_scr[...][:, :1]
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    bq: int = 128, bk: int = 512, interpret: bool = False):
    """q (B,H,S,D); k/v (B,Hkv,T,D), H % Hkv == 0. Returns (B,H,S,D)."""
    B, H, S, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    group = H // Hkv
    bq = min(bq, S)
    bk = min(bk, T)
    nq, nk = -(-S // bq), -(-T // bk)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, nq * bq - S), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, nk * bk - T), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, nk * bk - T), (0, 0)))
    kernel = functools.partial(
        _fa_kernel, scale=D ** -0.5, causal=causal, window=window,
        bq=bq, bk=bk, seq_q=S, seq_k=T)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, qi, ki: (b, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * bq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # m
            pltpu.VMEM((bq, 128), jnp.float32),   # l
            pltpu.VMEM((bq, D), jnp.float32),     # acc
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, :S]
