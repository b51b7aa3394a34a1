"""Mamba2 SSD chunk scan — Pallas TPU kernel.

Grid (B, n_chunks) with the chunk dimension minor-most (sequential); the
inter-chunk state (H, P, N) is VMEM scratch carried across grid steps —
the streaming-pipeline structure again: each chunk is one SPSC hop, and the
heavy intra-chunk math is dense matmuls for the MXU:

  y_diag = (L ⊙ (C·Bᵀ)) · (dt·X)      — (l,l)×(l,P) per head
  y_off  = C · h_prevᵀ (decayed)       — (l,N)×(N,P)
  h_new  = decay·h_prev + Xᵀ·(decay·dt·B)

All recurrence state stays in fp32; inputs may be bf16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_scan"]


def _tril(l):
    """(l, l) mask of i >= j (inclusive lower triangle)."""
    return jax.lax.broadcasted_iota(jnp.int32, (l, l), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)


def _dot(a, b, contract):
    """2-D f32 matmul contracting ``contract = (lhs_dim, rhs_dim)``."""
    return jax.lax.dot_general(a, b, (((contract[0],), (contract[1],)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_scr,
                *, chunk: int, heads: int, head_dim: int):
    """One chunk of all heads.  Everything is a 2-D matmul or elementwise
    op, head by head: Mosaic has no cumsum (running sums are a matmul
    with the lower-triangular ones matrix) and no outer-product einsum."""
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    l, P = chunk, head_dim
    x = x_ref[0].astype(jnp.float32)          # (l, H*P)
    dt = dt_ref[0].astype(jnp.float32)        # (l, H)
    A = a_ref[...].astype(jnp.float32)        # (1, H)
    B = b_ref[0].astype(jnp.float32)          # (l, N)
    C = c_ref[0].astype(jnp.float32)          # (l, N)

    tril = _tril(l)
    ones = tril.astype(jnp.float32)
    dA = dt * A                               # (l, H)
    cum = _dot(ones, dA, (1, 0))              # (l, H) inclusive running sum
    cum_t = _dot(dA, ones, (0, 1))            # (H, l) the same, transposed
    scores = _dot(C, B, (1, 1))               # (l, l)
    for h in range(heads):
        cum_c = cum[:, h:h + 1]               # (l, 1)
        dt_c = dt[:, h:h + 1]
        x_h = x[:, h * P:(h + 1) * P]         # (l, P)
        h_prev = h_scr[h]                     # (P, N)
        L = jnp.where(tril, jnp.exp(cum_c - cum_t[h:h + 1, :]), 0.0)
        y_diag = _dot(scores * L, x_h * dt_c, (1, 0))               # (l, P)
        y_off = _dot(C, h_prev, (1, 1)) * jnp.exp(cum_c)           # (l, P)
        total = jnp.sum(dA[:, h:h + 1])       # scalar: the chunk's decay
        w = jnp.exp(total - cum_c) * dt_c                           # (l, 1)
        states = _dot(x_h, B * w, (0, 0))                           # (P, N)
        h_new = h_prev * jnp.exp(total) + states
        h_scr[h] = h_new
        y_ref[0, :, h * P:(h + 1) * P] = (y_diag + y_off).astype(y_ref.dtype)

    @pl.when(ci == pl.num_programs(1) - 1)
    def _emit():
        hout_ref[0] = h_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk: int = 256, interpret: bool = False):
    """x (b,T,H,P); dt (b,T,H); A (H,); B/C (b,T,N).
    Returns (y (b,T,H,P) fp32, h_final (b,H,P,N) fp32)."""
    b, T, H, P = x.shape
    N = B.shape[-1]
    l = min(chunk, T)
    assert T % l == 0
    nc = T // l
    kernel = functools.partial(_ssd_kernel, chunk=l, heads=H, head_dim=P)
    y, h = pl.pallas_call(
        kernel,
        grid=(b, nc),
        in_specs=[
            pl.BlockSpec((1, l, H * P), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, l, H), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, H), lambda bi, ci: (0, 0)),
            pl.BlockSpec((1, l, N), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, l, N), lambda bi, ci: (bi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, l, H * P), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, H, P, N), lambda bi, ci: (bi, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, T, H * P), jnp.float32),
            jax.ShapeDtypeStruct((b, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((H, P, N), jnp.float32)],
        # the unrolled heads keep several (l, l) f32 temporaries live:
        # more than the 16 MiB default scoped VMEM at l=256
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(x.reshape(b, T, H * P), dt, A.reshape(1, H), B, C)
    return y.reshape(b, T, H, P), h
