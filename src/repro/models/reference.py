"""Plain float32 reference of the dense family's forward pass.

Written apart from ``model.py``, in straightforward ``jax.numpy``, for the
tests that hold the serving path to it: one sequence, no cache, no
batching, no kernels, every matrix product at
``jax.default_matmul_precision("highest")`` (on a TPU a float32 product
otherwise runs in lower precision).  It reads the parameter tree of
``init_params`` for ``family="dense"``.

The block, as Phi-3-mini (arXiv:2404.14219) and the Llama family describe
it: pre-RMSNorm, multi-head or grouped-query attention with rotary
position embeddings and an optional sliding window, a residual, pre-RMSNorm,
a SwiGLU feed-forward, a residual; a final RMSNorm and an untied output
head.  Departures from the published description:

- the query/key/value and the gate/up projections are separate matrices,
  where Phi-3's checkpoints fuse them (the same products);
- rotary embeddings rotate the two halves of each head (the
  ``rotate_half`` convention of Phi-3 and Llama), with no long-context
  rescaling (Phi-3-mini-4k has none);
- attention heads padded for tensor parallelism (``n_heads_padded``) are
  dropped; they carry no weight in the model either;
- logits cover ``vocab_size`` ids, not the padded table.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig

__all__ = ["forward"]


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (S, H, Dh), pos (S,): rotate (x1, x2) halves by pos * theta^(-2i/Dh)."""
    half = x.shape[-1] // 2
    inv = theta ** (-np.arange(half, dtype=np.float32) * 2 / x.shape[-1])
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, window):
    """q (S, H, Dh), k/v (S, Hkv, Dh): causal, optionally windowed."""
    S, H, Dh = q.shape
    k = jnp.repeat(k, H // k.shape[1], axis=1)
    v = jnp.repeat(v, H // v.shape[1], axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(Dh)
    qpos, kpos = np.arange(S)[:, None], np.arange(S)[None, :]
    mask = kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None], scores, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)


def forward(params, tokens, cfg: ModelConfig) -> np.ndarray:
    """Logits (S, vocab_size), float32, of every position of ``tokens``."""
    if cfg.family != "dense":
        raise ValueError(f"the reference covers the dense family, not "
                         f"{cfg.family!r}")
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    tokens = jnp.asarray(tokens, jnp.int32)
    pos = jnp.arange(tokens.shape[0])
    h, kv = cfg.n_heads, cfg.n_kv_heads
    blocks = params["blocks"]
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"])[tokens]
        for i in range(cfg.n_layers):
            p = jax.tree.map(lambda a: f32(a[i]), blocks)
            y = _rms_norm(x, p["norm1"], cfg.norm_eps)
            q = _rope(jnp.einsum("sd,dhk->shk", y, p["wq"][:, :h]), pos,
                      cfg.rope_theta)
            k = _rope(jnp.einsum("sd,dhk->shk", y, p["wk"][:, :kv]), pos,
                      cfg.rope_theta)
            v = jnp.einsum("sd,dhk->shk", y, p["wv"][:, :kv])
            o = _attention(q, k, v, cfg.sliding_window)
            x = x + jnp.einsum("shk,hkd->sd", o, p["wo"][:h])
            y = _rms_norm(x, p["norm2"], cfg.norm_eps)
            m = p["mlp"]
            x = x + (jax.nn.silu(y @ m["w_gate"]) * (y @ m["w_up"])) @ m["w_down"]
        x = _rms_norm(x, f32(params["final_norm"]), cfg.norm_eps)
        logits = x @ f32(params["lm_head"]).T
    return np.asarray(logits[:, :cfg.vocab_size])
