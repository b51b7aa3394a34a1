"""Plain reference for ``phi3-mini-3.8b``, and the work a decode step needs.

``logits`` is the model's full forward pass over one sequence in float32,
every product at ``Precision.HIGHEST``, written from the published
description (arXiv:2404.14219; the source's ``config.json``): pre-RMSNorm,
multi-head attention with rotary embeddings over the two halves of each
head, a residual, pre-RMSNorm, a SwiGLU feed-forward, a residual; a final
RMSNorm and an untied head.  No cache, no batching, no kernel.  It imports
nothing from the program: the weights come in as arrays under the leaf
names of the program's ``init_params`` (``blocks`` stacked over layers,
``embed``, ``final_norm``, ``lm_head``), in bfloat16 on the chip.  It runs
layer by layer, and a layer's weights are upcast to float32 only while
that layer runs (about 0.45 GB), so it fits beside the weights.

Departures from the published model: separate q/k/v and gate/up
matrices where the checkpoint fuses them (the same products), and the
source's sliding window of 2047 left out (no compared sequence reaches
it).

``step_bytes`` and ``step_flops`` count what one decode step needs, from
the context length of each occupied slot: the weights once, the K/V of
each occupied slot's valid positions, nothing for free slots.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import numpy as np

BUCKET = 128          # sequences are padded to a multiple: few compiles


def widths(model: Dict) -> Dict[str, int]:
    d, h = model["hidden_size"], model["num_attention_heads"]
    return dict(layers=model["num_hidden_layers"], d=d, heads=h,
                kv_heads=model["num_key_value_heads"], head_dim=d // h,
                ffn=model["intermediate_size"], vocab=model["vocab_size"])


@functools.lru_cache(maxsize=None)
def _programs(heads: int, kv_heads: int, eps: float, theta: float):
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    f32 = jnp.float32

    def rms(x, scale):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
            scale.astype(f32)

    def rope(x):
        s, dh = x.shape[0], x.shape[-1]
        inv = theta ** (-jnp.arange(0, dh // 2, dtype=f32) * 2 / dh)
        ang = jnp.arange(s, dtype=f32)[:, None] * inv[None, :]
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    @jax.jit
    def layer(x, blocks, i):
        p = jax.tree.map(lambda a: lax.dynamic_index_in_dim(
            a, i, keepdims=False).astype(f32), blocks)
        y = rms(x, p["norm1"])
        q = rope(jnp.einsum("sd,dhk->shk", y, p["wq"], precision=hi))
        k = rope(jnp.einsum("sd,dhk->shk", y, p["wk"], precision=hi))
        v = jnp.einsum("sd,dhk->shk", y, p["wv"], precision=hi)
        k = jnp.repeat(k, heads // kv_heads, axis=1)
        v = jnp.repeat(v, heads // kv_heads, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=hi) / np.sqrt(q.shape[-1])
        causal = jnp.tril(jnp.ones((x.shape[0], x.shape[0]), bool))
        probs = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
        o = jnp.einsum("hqk,khd->qhd", probs, v, precision=hi)
        x = x + jnp.einsum("shk,hkd->sd", o, p["wo"], precision=hi)
        y = rms(x, p["norm2"])
        m = p["mlp"]
        gate = jnp.dot(y, m["w_gate"], precision=hi)
        up = jnp.dot(y, m["w_up"], precision=hi)
        return x + jnp.dot(jax.nn.silu(gate) * up, m["w_down"], precision=hi)

    @jax.jit
    def head(x, final_norm, lm_head):
        return jnp.dot(rms(x, final_norm), lm_head.astype(f32).T, precision=hi)

    return layer, head


def logits(weights, tokens: Sequence[int], model: Dict) -> np.ndarray:
    """Float32 logits ``(len(tokens), vocab_size)`` of every position."""
    import jax.numpy as jnp

    w = widths(model)
    layer, head = _programs(w["heads"], w["kv_heads"],
                            float(model["rms_norm_eps"]),
                            float(model["rope_theta"]))
    n = len(tokens)
    ids = np.zeros(-(-n // BUCKET) * BUCKET, np.int32)
    ids[:n] = tokens      # padding after the sequence: causal, never read
    x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for i in range(w["layers"]):
        x = layer(x, weights["blocks"], jnp.int32(i))
    out = head(x, weights["final_norm"], weights["lm_head"])
    return np.asarray(out[:n, :w["vocab"]])


def step_bytes(model: Dict, contexts: Sequence[int],
               weight_bytes: int = 2) -> int:
    """Bytes one decode step must move: every layer's weights, the final
    norm and the head once, an embedding row and the K/V of its
    ``context`` valid positions (the new one included) for each occupied
    slot."""
    w = widths(model)
    d, hd = w["d"], w["heads"] * w["head_dim"]
    kvd = w["kv_heads"] * w["head_dim"]
    per_layer = (2 * d * hd + 2 * d * kvd + 3 * d * w["ffn"]) * weight_bytes \
        + 2 * d * 4                              # two float32 norm scales
    weights = w["layers"] * per_layer + w["vocab"] * d * weight_bytes + d * 4
    kv = sum(contexts) * w["layers"] * 2 * kvd * weight_bytes
    return weights + kv + len(contexts) * d * weight_bytes


def step_flops(model: Dict, contexts: Sequence[int]) -> int:
    """Model FLOPs of one decode step: per occupied slot, two per weight
    of every matrix product, and the scores and weighted sum of attention
    over its ``context`` positions in each layer."""
    w = widths(model)
    d, hd = w["d"], w["heads"] * w["head_dim"]
    kvd = w["kv_heads"] * w["head_dim"]
    matmul = w["layers"] * (2 * d * hd + 2 * d * kvd + 3 * d * w["ffn"]) \
        + w["vocab"] * d
    attn = w["layers"] * 4 * hd
    return sum(2 * matmul + attn * c for c in contexts)
