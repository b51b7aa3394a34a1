"""Smith-Waterman local alignment — Pallas TPU kernel.

Hardware adaptation (DESIGN.md §2): Farrar's SSE2 *striped* layout exists to
dodge SSE lane-shift latency and fixes F with a speculative "lazy-F" loop —
both pointless on TPU.  We keep the paper's *algorithmic* asset (the query
profile) and replace the SSE mechanics with TPU-native ones:

  * the **query axis is the 128-lane vector axis**; the whole query column
    state (H, E) lives in VMEM as (rows=Q/128 · sublanes, 128 lanes);
  * the subject **streams** through the kernel in HBM→SMEM tiles (the grid's
    sequential dimension — this kernel is itself a FastFlow pipeline: one
    SPSC hop per tile, state carried in VMEM scratch);
  * Farrar's lazy-F loop is replaced by a **closed-form prefix-max**: with
    gap_open ≥ gap_extend, F[i,j] = max_{k<i}(Ĥ[k,j] + k·ge) − go − (i−1)·ge
    where Ĥ is H computed without F — one associative scan on the VPU,
    exact, no data-dependent iteration (which TPUs hate);
  * each subject tile arrives as a ``(1, tile)`` block in SMEM, so a
    character is a scalar read; its substitution scores are one ``pl.ds``
    row read of the profile resident in VMEM (one sublane per char).

Limitations (documented): the within-column prefix-max runs over the padded
query length Qp; queries longer than one VMEM block (Qp ≤ 8192 comfortably)
would need a second-level carry, not implemented here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["sw_pallas", "DEFAULT_TILE"]

NEG = -1e9  # python float: keeps pallas kernels constant-free
DEFAULT_TILE = 512          # subject chars per grid step


def _prefix_max_exclusive(x: jnp.ndarray) -> jnp.ndarray:
    """Exclusive running max along the last axis (log-depth, VPU-friendly)."""
    n = x.shape[-1]
    x = jnp.concatenate([jnp.full(x.shape[:-1] + (1,), NEG, x.dtype), x[..., :-1]], -1)
    shift = 1
    while shift < n:
        pad = jnp.full(x.shape[:-1] + (shift,), NEG, x.dtype)
        x = jnp.maximum(x, jnp.concatenate([pad, x[..., :-shift]], -1))
        shift *= 2
    return x


def _sw_kernel(profile_ref, subject_ref, out_ref, h_ref, e_ref, best_ref,
               *, gap_open: float, gap_extend: float, tile: int, q_len: int):
    """Grid: (num_subject_tiles,) — sequential; column state in VMEM scratch.

    ``subject_ref`` is one ``(1, tile)`` row of the subject in SMEM: each
    character is a scalar read that selects one profile row (``pl.ds``)."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)
        e_ref[...] = jnp.full_like(e_ref, NEG)
        best_ref[...] = jnp.zeros_like(best_ref)

    n_codes, qp = profile_ref.shape
    lane = lax.broadcasted_iota(jnp.int32, (1, qp), 1)
    qmask = lane < q_len                         # padded query lanes
    idx = lane.astype(jnp.float32)

    def per_char(j, carry):
        h, e, best = carry                       # (1, Qp), (1, Qp), (1, 1)
        c = subject_ref[0, j]
        valid = c < n_codes
        s = profile_ref[pl.ds(jnp.minimum(c, n_codes - 1), 1), :]   # (1, Qp)
        e_new = jnp.maximum(h - gap_open, e - gap_extend)
        h_shift = jnp.concatenate([jnp.zeros((1, 1), h.dtype), h[:, :-1]], axis=1)
        h_hat = jnp.maximum(jnp.maximum(h_shift + s, e_new), 0.0)
        h_hat = jnp.where(qmask, h_hat, 0.0)
        # closed-form F: exclusive prefix-max over the query axis
        p = _prefix_max_exclusive(h_hat + idx * gap_extend)
        f = p - gap_open - (idx - 1.0) * gap_extend
        h_new = jnp.where(qmask, jnp.maximum(h_hat, f), 0.0)
        best_new = jnp.maximum(best, jnp.max(h_new, axis=1, keepdims=True))
        return (jnp.where(valid, h_new, h), jnp.where(valid, e_new, e),
                jnp.where(valid, best_new, best))

    h, e, best = lax.fori_loop(
        0, tile, per_char, (h_ref[...], e_ref[...], best_ref[...]))
    h_ref[...] = h
    e_ref[...] = e
    best_ref[...] = best

    @pl.when(t == pl.num_programs(0) - 1)
    def _emit():
        out_ref[...] = best_ref[...]


@functools.partial(jax.jit, static_argnames=("gap_open", "gap_extend", "tile",
                                             "interpret", "q_len"))
def sw_pallas(profile: jnp.ndarray, subject: jnp.ndarray, *, gap_open: float,
              gap_extend: float, q_len: int, tile: int = DEFAULT_TILE,
              interpret: bool = False) -> jnp.ndarray:
    """Best local-alignment score for one (query-profile, subject) pair.

    profile: (A, Qp) f32, Qp a multiple of 128; subject: (Dp,) int32 padded
    with value >= A.  q_len: true query length (<= Qp).
    """
    A, Qp = profile.shape
    Dp = subject.shape[0]
    assert Qp % 128 == 0, "query block must fill 128-lane registers"
    assert Dp % tile == 0, "subject must be padded to the tile size"
    grid = (Dp // tile,)
    kernel = functools.partial(_sw_kernel, gap_open=float(gap_open),
                               gap_extend=float(gap_extend), tile=tile,
                               q_len=q_len)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((A, Qp), lambda t: (0, 0)),          # profile: resident
            pl.BlockSpec((None, 1, tile), lambda t: (t, 0, 0),  # subject: streamed
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda t: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((1, Qp), jnp.float32),   # H column state
            pltpu.VMEM((1, Qp), jnp.float32),   # E column state
            pltpu.VMEM((1, 1), jnp.float32),    # running best
        ],
        interpret=interpret,
    )(profile, subject.reshape(Dp // tile, 1, tile))
    return out[0, 0]
