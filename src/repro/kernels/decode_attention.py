"""Decode attention over the serving cache — Pallas TPU kernel.

One new token a slot attends to the cache positions at or below the
slot's own position.  The cache is the decode step's K and V stacks as
they ride the layer scan, ``(layers, slots, T, kv·dh)``, heads and head
dimension merged in the minor axis; the layer index and the per-slot
positions arrive as scalar prefetch, so no layer is ever sliced out (a
slice handed to a custom call is materialised).

Grid (slot, K/V block) with the block axis minor-most (sequential).  The
K/V index map clamps the block index to the slot's last valid block,
``min(pos, T-1) // blk``: past it the index repeats, so Pallas issues no
DMA, and ``pl.when`` skips the body.  A slot reads only the blocks that
hold its positions; a rolling cache that is full (``pos >= T``) reads
them all, as its mask says.

Heads stay merged.  The query is laid out block-diagonally, ``(H,
kv·dh)``, row ``j·Hkv + h`` holding query head ``h·g + j`` in the
columns of KV head ``h``: scores are one ``(H, kv·dh) @ (blk, kv·dh)ᵀ``
product, the weighted sum one ``(H, blk) @ (blk, kv·dh)``, and the
output keeps each row's diagonal block, summed over the KV heads of each
``j``.  Online softmax in float32; K/V products in the cache's dtype,
accumulated in float32, as ``models.attention.cached_decode_attention``
(the plain reference) computes them.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attention", "kv_block", "kv_blocks_read"]

NEG = -1e30  # python float: keeps pallas kernels constant-free

# VMEM for the K and V blocks, each double-buffered
_KV_VMEM = 8 << 20


def kv_block(T: int, C: int, itemsize: int) -> Optional[int]:
    """Positions in one K/V block for a cache of ``T`` positions of ``C``
    = kv·dh values of ``itemsize`` bytes: the largest of 512, 256 and 128
    that divides ``T`` and whose K and V blocks, double-buffered, fit
    8 MiB of VMEM.  ``None`` where the kernel cannot tile the cache: ``C``
    not a multiple of the 128 lanes, or no such block."""
    if C % 128:
        return None
    for blk in (512, 256, 128):
        if T % blk == 0 and 4 * blk * C * itemsize <= _KV_VMEM:
            return blk
    return None


def kv_blocks_read(pos: int, T: int, blk: int) -> int:
    """K/V blocks the kernel reads, in each of K and V, for a slot at
    position ``pos`` of a cache of ``T`` positions in blocks of ``blk``."""
    return min(pos, T - 1) // blk + 1


def _kernel(at_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
            acc_scr, *, blk: int, T: int, dh: int, hkv: int, g: int,
            scale: float):
    b, j = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[b]
    last = jnp.minimum(pos, T - 1) // blk

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j <= last)
    def _step():
        k, v = k_ref[...], v_ref[...]                          # (blk, C)
        s = jax.lax.dot_general(
            q_ref[...].astype(k.dtype), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # (H, blk)
        t = j * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(t <= pos, s, NEG)
        m_prev = m_scr[...][:, :1]                             # (H, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[...][:, :1] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # (H, C)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == last)
    def _emit():
        o = acc_scr[...] / l_scr[...][:, :1]                   # (H, C)
        C = o.shape[1]
        lo = jax.lax.broadcasted_iota(jnp.int32, (hkv, C), 0) * dh
        c = jax.lax.broadcasted_iota(jnp.int32, (hkv, C), 1)
        diag = (c >= lo) & (c < lo + dh)
        for jj in range(g):
            rows = o[jj * hkv:(jj + 1) * hkv]
            o_ref[jj:jj + 1, :] = jnp.where(diag, rows, 0.0).sum(
                axis=0, keepdims=True).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention(q, k, v, at: Sequence, positions, *,
                     interpret: bool = False):
    """``cached_decode_attention(q, k[at], v[at], positions)`` without
    slicing the layer out, reading each slot's blocks up to its position.

    q: (B, 1, H, Dh); k, v: (*lead, B, T, C), C = Hkv·Dh, a cache that
    ``kv_block`` tiles (``ValueError`` otherwise); ``at``: one index per
    axis of ``lead`` (ints or int32 scalars); positions: (B,).  Returns
    (B, 1, H, Dh) in q's dtype."""
    B, _, H, dh = q.shape
    lead, (T, C) = k.shape[:-3], k.shape[-2:]
    blk = kv_block(T, C, k.dtype.itemsize)
    if blk is None:
        raise ValueError(f"no K/V block tiles a cache of {T} positions of "
                         f"{C} values")
    hkv = C // dh
    g = H // hkv
    layers = math.prod(lead)
    k = k.reshape(layers, B, T, C)          # merges leading axes: a bitcast
    v = v.reshape(layers, B, T, C)
    layer = jnp.int32(0)
    for i, n in zip(at, lead):
        layer = layer * n + jnp.asarray(i, jnp.int32)
    # block-diagonal query, row j·Hkv + h: head h·g + j in KV head h's columns
    qt = q.reshape(B, hkv, g, dh).transpose(0, 2, 1, 3)         # (B,g,Hkv,Dh)
    eye = jnp.eye(hkv, dtype=q.dtype)
    qbd = (qt[:, :, :, None, :] * eye[None, None, :, :, None]).reshape(B, H, C)

    def kv_map(b, j, at_ref, pos_ref):
        last = jnp.minimum(pos_ref[b], T - 1) // blk
        return at_ref[0], b, jnp.minimum(j, last), 0

    kv_spec = pl.BlockSpec((None, None, blk, C), kv_map)
    out = pl.pallas_call(
        functools.partial(_kernel, blk=blk, T=T, dh=dh, hkv=hkv, g=g,
                          scale=dh ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, T // blk),
            in_specs=[pl.BlockSpec((None, H, C), lambda b, j, *_: (b, 0, 0)),
                      kv_spec, kv_spec],
            out_specs=pl.BlockSpec((None, g, C), lambda b, j, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((H, 128), jnp.float32),   # m
                            pltpu.VMEM((H, 128), jnp.float32),   # l
                            pltpu.VMEM((H, C), jnp.float32)]),   # acc
        out_shape=jax.ShapeDtypeStruct((B, g, C), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attention",
    )(layer[None], positions.astype(jnp.int32), qbd, k, v)
    return out.reshape(B, g, hkv, dh).transpose(0, 2, 1, 3).reshape(B, 1, H, dh)
