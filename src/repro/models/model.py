"""Unified decoder LM covering all 10 assigned architectures.

One parameter tree + one forward, assembled from the block zoo
(self-attention / dense-MLP / MoE / Mamba2-SSD / cross-attention) according
to ``cfg.layer_kinds()``.  Execution is grouped into homogeneous segments
scanned with ``lax.scan`` (+ optional remat), so compile time is O(1) in
depth:

  * uniform   — dense / moe / ssm / audio: one stacked segment;
  * hybrid    — zamba2: groups of (attn_every-1) SSM blocks + 1 attention
                block whose parameters are *shared* across groups;
  * vlm       — llama-3.2-vision: groups of (cross_attn_every-1) self-attn
                blocks + 1 cross-attention block over vision embeddings.

Distribution: GSPMD constraints (`parallel.shard`) everywhere, except three
regions with hand-placed collectives under FULL-manual shard_map
(`parallel.manual_model`): embedding lookup, vocab-parallel cross-entropy,
and MoE dispatch (the farm) — with explicit ZeRO-3 gathers inside
(`fsdp_gather`).  Head/vocab padding makes every sharded dim divide the
mesh (padded heads are hard-masked so numerics equal the unpadded model).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels.ops import decode_attention_op, kv_block
from ..parallel.context import (current_ctx, fsdp_gather, manual_model,
                                psum_compat, shard)
from .attention import attention, cached_decode_attention, decode_attention
from .config import ModelConfig
from .layers import (dense_init, embed_init, rms_norm, apply_rope,
                     scan_unroll, swiglu)
from .moe import moe_apply, moe_init
from .ssm import init_ssm_cache, ssm_apply, ssm_decode, ssm_init

__all__ = [
    "init_params", "params_pspecs", "loss_fn", "prefill", "decode_step",
    "init_cache", "cache_pspecs", "batch_pspecs", "segment_counts",
    "reset_cache_slot",
]

Params = Dict[str, Any]


# ==========================================================================
# layout
# ==========================================================================
def segment_counts(cfg: ModelConfig) -> Dict[str, int]:
    kinds = cfg.layer_kinds()
    if cfg.family == "hybrid":
        n_groups = sum(1 for k in kinds if k in ("attn", "attn_shared"))
        inner = cfg.attn_every - 1
        assert n_groups * cfg.attn_every == cfg.n_layers
        return {"groups": n_groups, "ssm_per_group": inner}
    if cfg.family == "vlm":
        n_groups = sum(1 for k in kinds if k == "cross")
        inner = cfg.cross_attn_every - 1
        assert n_groups * cfg.cross_attn_every == cfg.n_layers
        return {"groups": n_groups, "self_per_group": inner}
    return {"blocks": cfg.n_layers}


def _kv_heads_alloc(cfg: ModelConfig) -> int:
    # MHA: pad kv together with q heads; GQA: keep kv unpadded (replicated)
    return cfg.n_heads_padded if cfg.n_kv_heads == cfg.n_heads else cfg.n_kv_heads


# ==========================================================================
# init (+ matching PartitionSpec token trees)
# ==========================================================================
def _attn_block_init(key, cfg: ModelConfig, cross: bool = False):
    d, hp, kv, dh = cfg.d_model, cfg.n_heads_padded, _kv_heads_alloc(cfg), cfg.hdim
    ks = jax.random.split(key, 8)
    p = {
        "norm1": jnp.ones((d,), jnp.float32),
        "wq": dense_init(ks[0], (d, hp, dh), d, cfg.param_dtype),
        "wk": dense_init(ks[1], (d, kv, dh), d, cfg.param_dtype),
        "wv": dense_init(ks[2], (d, kv, dh), d, cfg.param_dtype),
        "wo": dense_init(ks[3], (hp, dh, d), hp * dh, cfg.param_dtype),
    }
    if cfg.family == "moe" and not cross:
        p["norm2"] = jnp.ones((d,), jnp.float32)
        p["moe"] = moe_init(ks[4], cfg)
    elif cfg.d_ff:
        p["norm2"] = jnp.ones((d,), jnp.float32)
        p["mlp"] = {
            "w_gate": dense_init(ks[5], (d, cfg.d_ff), d, cfg.param_dtype),
            "w_up": dense_init(ks[6], (d, cfg.d_ff), d, cfg.param_dtype),
            "w_down": dense_init(ks[7], (cfg.d_ff, d), cfg.d_ff, cfg.param_dtype),
        }
    return p


def _fsdp_tok(cfg: ModelConfig):
    """'dp' for training (ZeRO-3); None for replicated-param serving."""
    return None if cfg.serve_params_replicated else "dp"


def _attn_block_specs(cfg: ModelConfig, mp_size: int, cross: bool = False):
    dp = _fsdp_tok(cfg)
    p = {
        "norm1": None,
        "wq": (dp, "mp", None),
        "wk": (dp, None, None),
        "wv": (dp, None, None),
        "wo": ("mp", None, dp),
    }
    if cfg.family == "moe" and not cross:
        ep = cfg.n_experts % max(mp_size, 1) == 0
        moe = {
            "router": (dp, None),
            "w_gate": ("mp", dp, None) if ep else (None, dp, "mp"),
            "w_up": ("mp", dp, None) if ep else (None, dp, "mp"),
            "w_down": ("mp", None, dp) if ep else (None, "mp", dp),
        }
        if cfg.n_shared_experts:
            moe["shared"] = {"w_gate": (dp, "mp"), "w_up": (dp, "mp"),
                             "w_down": ("mp", dp)}
        p["norm2"] = None
        p["moe"] = moe
    elif cfg.d_ff:
        p["norm2"] = None
        p["mlp"] = {"w_gate": (dp, "mp"), "w_up": (dp, "mp"),
                    "w_down": ("mp", dp)}
    return p


def _ssm_specs(cfg: ModelConfig):
    # SSD state mixes (H, P, N) non-separably with n_groups=1, so Mamba
    # params replicate over the model axis (documented limitation: Mamba TP
    # requires grouped B/C); FSDP over data still shards storage.
    dp = _fsdp_tok(cfg)
    return {
        "w_z": (dp, None), "w_xbc": (dp, None), "w_dt": (dp, None),
        "dt_bias": None, "A_log": None, "D": None,
        "conv_w": (None, dp), "norm": None, "w_out": (None, dp),
    }


def _stacked(init_fn, key, n: int):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def init_params(cfg: ModelConfig, key) -> Params:
    ks = jax.random.split(key, 10)
    segs = segment_counts(cfg)
    params: Params = {
        "embed": embed_init(ks[0], cfg.vocab_padded, cfg.d_model, cfg.param_dtype),
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if cfg.n_codebooks:
        params["lm_head"] = jax.vmap(
            lambda k: embed_init(k, cfg.vocab_padded, cfg.d_model, cfg.param_dtype)
        )(jax.random.split(ks[1], cfg.n_codebooks))
    else:
        params["lm_head"] = embed_init(ks[1], cfg.vocab_padded, cfg.d_model, cfg.param_dtype)

    if cfg.family == "hybrid":
        g, inner = segs["groups"], segs["ssm_per_group"]
        params["ssm"] = _stacked(lambda k: _stacked(partial(ssm_init, cfg=cfg), k, inner), ks[2], g)
        params["shared_attn"] = _attn_block_init(ks[3], cfg)   # ONE block, reused
    elif cfg.family == "vlm":
        g, inner = segs["groups"], segs["self_per_group"]
        params["self"] = _stacked(lambda k: _stacked(partial(_attn_block_init, cfg=cfg), k, inner), ks[2], g)
        params["cross"] = _stacked(partial(_attn_block_init, cfg=cfg, cross=True), ks[3], g)
        params["vision_proj"] = dense_init(ks[4], (cfg.vision_dim, cfg.d_model),
                                           cfg.vision_dim, cfg.param_dtype)
    elif cfg.family == "ssm":
        params["blocks"] = _stacked(partial(ssm_init, cfg=cfg), ks[2], segs["blocks"])
    else:
        params["blocks"] = _stacked(partial(_attn_block_init, cfg=cfg), ks[2], segs["blocks"])
    return params


def params_pspecs(cfg: ModelConfig, mp_size: int = 16) -> Params:
    """Same tree structure as init_params, with sharding-token tuples.
    Stacked segments get a leading ``None`` dim prepended per stack level."""
    def prepend(tree, n_lead: int):
        return jax.tree.map(
            lambda s: tuple([None] * n_lead) + (s if isinstance(s, tuple) else ()),
            tree, is_leaf=lambda v: v is None or type(v) is tuple)

    segs = segment_counts(cfg)
    dp = _fsdp_tok(cfg)
    specs: Params = {
        "embed": ("mp", dp),
        "final_norm": None,
        "lm_head": (None, "mp", dp) if cfg.n_codebooks else ("mp", dp),
    }
    if cfg.family == "hybrid":
        specs["ssm"] = prepend(_ssm_specs(cfg), 2)
        specs["shared_attn"] = _attn_block_specs(cfg, mp_size)
    elif cfg.family == "vlm":
        specs["self"] = prepend(_attn_block_specs(cfg, mp_size), 2)
        specs["cross"] = prepend(_attn_block_specs(cfg, mp_size, cross=True), 1)
        specs["vision_proj"] = (dp, None)
    elif cfg.family == "ssm":
        specs["blocks"] = prepend(_ssm_specs(cfg), 1)
    else:
        specs["blocks"] = prepend(_attn_block_specs(cfg, mp_size), 1)
    return specs


# ==========================================================================
# manual-collective regions (embedding, vocab-parallel CE)
# ==========================================================================
def _dp_tok(batch_size: int):
    """'dp' if the batch divides the dp axes, else replicated (B=1 cells)."""
    ctx = current_ctx()
    if ctx is None or batch_size % max(ctx.dp_size, 1) != 0:
        return None
    return "dp"


def _embed_lookup(table: jnp.ndarray, ids: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    ctx = current_ctx()
    if ctx is None:
        return table[ids]
    b = _dp_tok(ids.shape[0])

    tspec = ("mp", _fsdp_tok(cfg))

    def local(tbl, ids):
        tbl = fsdp_gather(tbl, tspec)
        v_loc = tbl.shape[0]
        me = lax.axis_index(ctx.model_axis)
        loc = ids - me * v_loc
        ok = (loc >= 0) & (loc < v_loc)
        emb = jnp.where(ok[..., None], tbl[jnp.clip(loc, 0, v_loc - 1)], 0)
        return psum_compat(emb, ctx.model_axis)

    return manual_model(local, [tspec, (b, None)],
                        (b, None, None))(table, ids)


def _vocab_ce(x: jnp.ndarray, head: jnp.ndarray, labels: jnp.ndarray,
              cfg: ModelConfig) -> jnp.ndarray:
    """Vocab-parallel cross entropy; x (B,S,d), head (V,d) model-sharded,
    labels (B,S).  Never materialises replicated (B,S,V) logits."""
    ctx = current_ctx()

    def chunk_loss(x_c, labels_c, head):
        if ctx is None:
            logits = (x_c.astype(jnp.float32) @ head.astype(jnp.float32).T)
            logits = logits[..., :cfg.vocab_size]
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            lab = jnp.take_along_axis(logits, labels_c[..., None], axis=-1)[..., 0]
            return lse - lab

        hspec = ("mp", _fsdp_tok(cfg))

        def local(x_c, head, labels_c):
            head = fsdp_gather(head, hspec)
            v_loc = head.shape[0]
            me = lax.axis_index(ctx.model_axis)
            logits = x_c.astype(jnp.float32) @ head.astype(jnp.float32).T  # (B,s,V/n)
            # mask vocab padding (global ids >= vocab_size)
            gid = me * v_loc + jnp.arange(v_loc)
            logits = jnp.where(gid < cfg.vocab_size, logits, -1e30)
            # stabiliser only — detach BEFORE pmax (pmax has no JVP rule;
            # with symbolic-zero tangents it is skipped by autodiff)
            gmax = lax.pmax(lax.stop_gradient(jnp.max(logits, axis=-1)),
                            ctx.model_axis)
            se = jnp.sum(jnp.exp(logits - gmax[..., None]), axis=-1)
            lse = jnp.log(lax.psum(se, ctx.model_axis)) + gmax
            loc = labels_c - me * v_loc
            ok = (loc >= 0) & (loc < v_loc)
            lab = jnp.where(ok, jnp.take_along_axis(
                logits, jnp.clip(loc, 0, v_loc - 1)[..., None], axis=-1)[..., 0], 0.0)
            lab = lax.psum(lab, ctx.model_axis)
            return lse - lab

        b = _dp_tok(x_c.shape[0])
        return manual_model(local, [(b, None, None), hspec, (b, None)],
                            (b, None))(x_c, head, labels_c)

    S = x.shape[1]
    csize = cfg.loss_chunk if cfg.loss_chunk and S % cfg.loss_chunk == 0 else S
    if csize == S:
        per_tok = chunk_loss(x, labels, head)
    else:
        nc = S // csize
        xs = x.reshape(x.shape[0], nc, csize, -1).transpose(1, 0, 2, 3)
        ls = labels.reshape(labels.shape[0], nc, csize).transpose(1, 0, 2)
        body = jax.checkpoint(lambda xc, lc: chunk_loss(xc, lc, head))
        _, per_tok = lax.scan(lambda c, args: (c, body(*args)), 0, (xs, ls),
                              unroll=scan_unroll())
        per_tok = per_tok.transpose(1, 0, 2).reshape(labels.shape)
    return per_tok


def _logits_full(x: jnp.ndarray, head: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Decode-time logits (B, V) — small, gathered replicated."""
    logits = jnp.einsum("bd,vd->bv", x.astype(jnp.float32), head.astype(jnp.float32))
    return logits[:, :cfg.vocab_size]


# ==========================================================================
# blocks
# ==========================================================================
def _head_mask(cfg: ModelConfig):
    hp = cfg.n_heads_padded
    if hp == cfg.n_heads:
        return None
    return (jnp.arange(hp) < cfg.n_heads).astype(cfg.param_dtype)


class LayerKV(NamedTuple):
    """The decode cache's K and V stacks, ``(*lead, B, T, kv·dh)``, and
    the indices ``at`` of one layer in their leading axes."""
    k: jnp.ndarray
    v: jnp.ndarray
    at: Tuple = ()


def _write_kv(kv: LayerKV, k, v, pos, rolling: bool) -> LayerKV:
    """Write each row's new K/V, (B, 1, kv, dh), into layer ``kv.at`` of
    the stacks at the row's own index (``pos % T`` for a rolling cache):
    one scatter a stack, in place, with heads and head dimension merged
    as the cache keeps them."""
    B = k.shape[0]
    idx = pos % kv.k.shape[-2] if rolling else pos
    at = kv.at + (jnp.arange(B), idx)
    return kv._replace(k=kv.k.at[at].set(k.reshape(B, -1).astype(kv.k.dtype)),
                       v=kv.v.at[at].set(v.reshape(B, -1).astype(kv.v.dtype)))


def _cached_attention(q, kv: LayerKV, pos):
    """Each row's decode self-attention over layer ``kv.at`` of the
    stacks, at the indices at or below its position ``pos``.

    Lowered for a TPU, the Pallas kernel reads the stacks where they lie
    and only each row's blocks up to its position; every other platform
    computes the plain ``cached_decode_attention`` over the layer.  So
    does a TPU where the kernel cannot tile the cache (``kv_block``) or
    under a mesh, where a Mosaic kernel is not partitioned."""
    def plain(q, k, v, pos, *at):
        return cached_decode_attention(q, k[at], v[at], pos)

    def kernel(q, k, v, pos, *at):
        return decode_attention_op(q, k, v, at, pos, interpret=False)

    T, C = kv.k.shape[-2:]
    if current_ctx() is not None or kv_block(T, C, kv.k.dtype.itemsize) is None:
        return plain(q, kv.k, kv.v, pos, *kv.at)
    return lax.platform_dependent(q, kv.k, kv.v, pos, *kv.at, tpu=kernel,
                                  default=plain)


def _attn_core(p, x, cfg: ModelConfig, *, positions, mode: str,
               kv_cache=None, rolling=False, ext_kv=None):
    """Shared attention path. Returns (delta, new cache or None): in
    decode mode the ``LayerKV`` with this layer's rows written, in
    prefill mode this layer's (k, v), each (B, S, kv·dh).

    In decode mode ``positions`` is (B, 1): each row's new token is written
    to its own cache index (``pos % T`` for a rolling cache) and attends to
    the indices at or below it."""
    B = x.shape[0]
    hp, dh = cfg.n_heads_padded, cfg.hdim
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    q = shard(q, "dp", None, "mp", None)
    if ext_kv is not None:  # cross-attention: kv from the vision stream
        k, v = ext_kv
        new_cache = None
    else:
        k = jnp.einsum("bsd,dhk->bshk", h, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if mode == "decode" and ext_kv is None:
        pos = positions[:, 0]
        new_cache = _write_kv(kv_cache, k, v, pos, rolling)
        attn = _cached_attention(q, new_cache, pos)
    elif mode == "decode":  # cross-attn decode: attend the cached vision kv
        attn = decode_attention(q, k, v, jnp.full((B,), k.shape[1] - 1))
        new_cache = None
    else:
        causal = ext_kv is None
        attn = attention(q, k, v, causal=causal, window=cfg.sliding_window,
                         impl=cfg.attn_impl, q_chunk=cfg.attn_q_chunk,
                         kv_chunk=cfg.attn_kv_chunk, causal_skip=cfg.causal_skip)
        if ext_kv is None and mode == "prefill":
            new_cache = (k.reshape(B, k.shape[1], -1), v.reshape(B, v.shape[1], -1))
        else:
            new_cache = None
    mask = _head_mask(cfg)
    if mask is not None:
        attn = attn * mask[None, None, :, None]
    out = jnp.einsum("bshk,hkd->bsd", attn.astype(x.dtype), p["wo"])
    out = shard(out, "dp", None, None)
    return out, new_cache


def _ffn_part(p, x, cfg: ModelConfig):
    """MLP or MoE sub-block (with pre-norm + residual). Returns (x, aux)."""
    if "moe" in p:
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        ctx = current_ctx()
        if ctx is None:
            delta, aux = moe_apply(h, p["moe"], cfg, axis_name=None)
        else:
            ep = cfg.n_experts % ctx.mp_size == 0
            dp = _fsdp_tok(cfg)
            espec = ("mp", dp, None) if ep else (None, dp, "mp")
            dspec = ("mp", None, dp) if ep else (None, "mp", dp)
            mspec = {"router": (dp, None),
                     "w_gate": espec, "w_up": espec, "w_down": dspec}
            if "shared" in p["moe"]:
                mspec["shared"] = {"w_gate": (dp, "mp"), "w_up": (dp, "mp"),
                                   "w_down": ("mp", dp)}
            b = _dp_tok(h.shape[0])

            def local(h_, m_):
                m_ = fsdp_gather(m_, mspec)          # explicit ZeRO-3 gather
                return moe_apply(h_, m_, cfg, axis_name=ctx.model_axis)

            fn = manual_model(local, [(b, None, None), mspec],
                              [(b, None, None), None])
            delta, aux = fn(h, p["moe"])
        return x + delta, aux
    if "mlp" in p:
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        m = p["mlp"]
        delta = swiglu(h, m["w_gate"], m["w_up"], m["w_down"])
        delta = shard(delta, "dp", None, None)
        return x + delta, jnp.float32(0)
    return x, jnp.float32(0)


def _attn_block(p, x, cfg, *, positions, mode, kv_cache=None, rolling=False,
                ext_kv=None):
    delta, new_cache = _attn_core(p, x, cfg, positions=positions, mode=mode,
                                  kv_cache=kv_cache, rolling=rolling,
                                  ext_kv=ext_kv)
    x = x + delta
    x, aux = _ffn_part(p, x, cfg)
    return x, aux, new_cache


# ==========================================================================
# forward
# ==========================================================================
def _vision_kv(params, vision_embeds, cfg: ModelConfig):
    """Project the (stub) vision embeddings once; per-cross-layer K/V are
    computed from this shared stream inside each cross block."""
    return (vision_embeds @ params["vision_proj"]).astype(params["vision_proj"].dtype)


def _layer_scan(body, carry, xs, kv: Optional[LayerKV], remat: bool):
    """``lax.scan`` of ``body(carry, xs, kv) -> (carry, ys, kv)`` over the
    leading axis of ``xs``; returns (carry, ys, kv).

    In decode mode ``kv`` holds the cache's K/V stacks.  They ride in the
    scan's carry, never in ``xs``/``ys``, beside the layer index, which
    ``body`` finds appended to ``kv.at``: each layer writes its rows into
    the stacks in place and attention reads its layer where it lies, so
    no step slices, restacks or copies a whole layer or stack.  A nested
    scan (vlm) passes on the ``kv`` its own body was given.  Elsewhere
    ``kv`` is None and ``body`` gets None."""
    if kv is None:
        def step(c, x):
            c, ys, _ = body(c, x, None)
            return c, ys
        carry, ys = lax.scan(jax.checkpoint(step) if remat else step, carry,
                             xs, unroll=scan_unroll())
        return carry, ys, None

    def step(c, x):
        c, k, v, i = c
        c, ys, out = body(c, x, LayerKV(k, v, kv.at + (i,)))
        return (c, out.k, out.v, i + 1), ys

    (carry, k, v, _), ys = lax.scan(
        jax.checkpoint(step) if remat else step,
        (carry, kv.k, kv.v, jnp.int32(0)), xs, unroll=scan_unroll())
    return carry, ys, kv._replace(k=k, v=v)


def forward_hidden(params: Params, x: jnp.ndarray, cfg: ModelConfig, *,
                   mode: str = "train", positions=None, cache=None,
                   vision_stream=None):
    """Run all blocks. x: (B,S,d) embeddings; positions (B,S), in decode
    mode each row's own cache position. Returns (x, aux, new_cache)."""
    aux_total = jnp.float32(0)
    new_cache: Dict[str, Any] = {}
    rolling = cfg.sliding_window is not None and mode == "decode"
    kv = LayerKV(cache["k"], cache["v"]) if mode == "decode" and \
        "k" in cache else None

    def attn(carry, p, kv):
        """One self-attention block as a ``_layer_scan`` body: its ys
        hold the layer's prefill K/V."""
        x, aux = carry
        x, a, nc = _attn_block(p, x, cfg, positions=positions, mode=mode,
                               kv_cache=kv, rolling=rolling)
        if mode == "prefill":
            return (x, aux + a), {"k": nc[0], "v": nc[1]}, None
        return (x, aux + a), {}, nc

    if cfg.family in ("dense", "moe", "audio"):
        (x, aux_total), new_cache, kv = _layer_scan(
            attn, (x, aux_total), params["blocks"], kv, cfg.remat)

    elif cfg.family == "ssm":
        x, aux_total, new_cache = _ssm_segment(params["blocks"], x, cfg, mode,
                                               cache, aux_total)

    elif cfg.family == "hybrid":
        def group(carry, xs, kv):
            x, aux = carry
            # inner ssm stack
            x, _, ssm_c = _ssm_segment_inner(xs["ssm"], x, cfg, mode,
                                             {"h": xs.get("h"), "conv": xs.get("conv")})
            # shared attention block
            carry, ys, kv = attn((x, aux), params["shared_attn"], kv)
            return carry, {**ssm_c, **ys}, kv

        xs = {"ssm": params["ssm"]}
        if mode == "decode":
            xs.update({"h": cache["h"], "conv": cache["conv"]})
        (x, aux_total), new_cache, kv = _layer_scan(
            group, (x, aux_total), xs, kv, cfg.remat)

    elif cfg.family == "vlm":
        def group(carry, xs, kv):
            # self-attention layers: a scan of their own, whose layer
            # index follows the group's in ``kv.at``
            carry, ys, kv = _layer_scan(attn, carry, xs["self"], kv, False)
            x, aux = carry
            # cross-attn over the vision stream
            pc = xs["cross"]
            kc = jnp.einsum("bpd,dhk->bphk", vision_stream, pc["wk"])
            vc = jnp.einsum("bpd,dhk->bphk", vision_stream, pc["wv"])
            x, a, _ = _attn_block(pc, x, cfg, positions=positions, mode=mode,
                                  ext_kv=(kc, vc))
            return (x, aux + a), ys, kv

        xs = {"self": params["self"], "cross": params["cross"]}
        (x, aux_total), new_cache, kv = _layer_scan(
            group, (x, aux_total), xs, kv, cfg.remat)
    else:
        raise ValueError(cfg.family)

    if kv is not None:
        new_cache.update(k=kv.k, v=kv.v)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux_total, new_cache


def _ssm_segment_inner(p_stack, x, cfg, mode, cache):
    """Scan a stacked ssm sub-segment. cache leaves may be None (train)."""
    def body(x, xs):
        if mode == "decode":
            delta, nc = ssm_decode(xs["p"], x, {"h": xs["h"], "conv": xs["conv"]}, cfg)
            return x + delta, nc
        if mode == "prefill":
            delta, nc = ssm_apply(xs["p"], x, cfg, return_cache=True)
            return x + delta, nc
        return x + ssm_apply(xs["p"], x, cfg), {}

    xs = {"p": p_stack}
    if mode == "decode":
        xs["h"], xs["conv"] = cache["h"], cache["conv"]
    x, ys = lax.scan(body, x, xs, unroll=scan_unroll())
    return x, jnp.float32(0), ys


def _ssm_segment(p_stack, x, cfg, mode, cache, aux):
    x, _, ys = _ssm_segment_inner(p_stack, x, cfg, mode, cache or {})
    return x, aux, ys


# ==========================================================================
# entry points
# ==========================================================================
def _embed_batch(params, batch, cfg: ModelConfig):
    if cfg.family == "audio":
        x = batch["frames"].astype(cfg.param_dtype)       # stub frontend
    else:
        x = _embed_lookup(params["embed"], batch["tokens"], cfg)
    x = shard(x, "dp", None, None)
    vision = None
    if cfg.family == "vlm":
        vision = _vision_kv(params, batch["vision_embeds"].astype(cfg.param_dtype), cfg)
    return x, vision


def loss_fn(params: Params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    x, vision = _embed_batch(params, batch, cfg)
    B, S = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    x, aux, _ = forward_hidden(params, x, cfg, mode="train",
                               positions=positions, vision_stream=vision)
    if cfg.n_codebooks:
        losses = []
        for cb in range(cfg.n_codebooks):
            per = _vocab_ce(x, params["lm_head"][cb], batch["labels"][:, cb], cfg)
            losses.append(per.mean())
        ce = sum(losses) / cfg.n_codebooks
    else:
        ce = _vocab_ce(x, params["lm_head"], batch["labels"], cfg).mean()
    loss = ce + 0.01 * aux
    return loss, {"ce": ce, "aux": aux}


def prefill(params: Params, batch, cfg: ModelConfig):
    """Forward pass that also returns the populated cache + last logits."""
    x, vision = _embed_batch(params, batch, cfg)
    B, S = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    x, _, new_cache = forward_hidden(params, x, cfg, mode="prefill",
                                     positions=positions, vision_stream=vision)
    if cfg.sliding_window is not None and "k" in new_cache:
        w = min(cfg.sliding_window, S)
        new_cache["k"] = new_cache["k"][..., -w:, :]
        new_cache["v"] = new_cache["v"][..., -w:, :]
    last = x[:, -1]
    if cfg.n_codebooks:
        logits = jnp.stack([_logits_full(last, params["lm_head"][cb], cfg)
                            for cb in range(cfg.n_codebooks)], axis=1)
    else:
        logits = _logits_full(last, params["lm_head"], cfg)
    return logits, new_cache


def decode_step(params: Params, batch, cache, positions, cfg: ModelConfig):
    """One token for every sequence in the batch.

    batch: {"tokens": (B,1)} (or {"frames": (B,1,d)} for audio);
    positions: (B,) int32 — each row's cache position, the number of tokens
    that row has already written.
    Returns (logits, new_cache)."""
    x, vision = _embed_batch(params, batch, cfg)
    pos = jnp.asarray(positions, jnp.int32)
    x, _, new_cache = forward_hidden(params, x, cfg, mode="decode",
                                     positions=pos[:, None], cache=cache,
                                     vision_stream=vision)
    last = x[:, -1]
    if cfg.n_codebooks:
        logits = jnp.stack([_logits_full(last, params["lm_head"][cb], cfg)
                            for cb in range(cfg.n_codebooks)], axis=1)
    else:
        logits = _logits_full(last, params["lm_head"], cfg)
    return logits, new_cache


# ==========================================================================
# caches & input specs
# ==========================================================================
def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    """Allocate an (empty) decode cache matching forward_hidden's layout.

    Each K and V stack keeps a layer's heads and head dimension merged in
    its minor axis, ``(layers, batch, T, kv·dh)`` (vlm: ``(groups, inner,
    batch, T, kv·dh)``): on a TPU that axis fills whole 128-lane tiles
    where a head dimension such as 96 would not, so the decode step's
    one-row writes and its attention read the cache in the layout it is
    stored in."""
    kvd = _kv_heads_alloc(cfg) * cfg.hdim
    T = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    dt = cfg.param_dtype
    segs = segment_counts(cfg)

    def kv_pair(*lead):
        k = jnp.zeros((*lead, batch, T, kvd), dt)
        return {"k": k, "v": jnp.zeros_like(k)}

    if cfg.family in ("dense", "moe", "audio"):
        return kv_pair(segs["blocks"])
    if cfg.family == "ssm":
        c = jax.vmap(lambda _: init_ssm_cache(batch, cfg, dt))(jnp.arange(segs["blocks"]))
        return c
    if cfg.family == "hybrid":
        g, inner = segs["groups"], segs["ssm_per_group"]
        ssm_c = jax.vmap(lambda _: jax.vmap(lambda __: init_ssm_cache(batch, cfg, dt))(jnp.arange(inner)))(jnp.arange(g))
        return {"h": ssm_c["h"], "conv": ssm_c["conv"], **kv_pair(g)}
    if cfg.family == "vlm":
        return kv_pair(segs["groups"], segs["self_per_group"])
    raise ValueError(cfg.family)


def cache_pspecs(cfg: ModelConfig, batch: int, dp_divisible: bool) -> Dict[str, Any]:
    b = "dp" if dp_divisible else None
    kv_spec = (None, b, "mp", None)
    if cfg.family in ("dense", "moe", "audio"):
        return {"k": kv_spec, "v": kv_spec}
    if cfg.family == "ssm":
        return {"h": (None, b, None, None, None), "conv": (None, b, None, None)}
    if cfg.family == "hybrid":
        return {"h": (None, None, b, None, None, None),
                "conv": (None, None, b, None, None),
                "k": kv_spec, "v": kv_spec}
    if cfg.family == "vlm":
        s = (None, None, b, "mp", None)
        return {"k": s, "v": s}
    raise ValueError(cfg.family)


def reset_cache_slot(cache, slot, cfg: ModelConfig):
    """Zero batch row ``slot`` of the recurrent state (SSM ``h`` and
    ``conv``), each leaf at its own batch axis as ``cache_pspecs`` places
    it.  K/V are left as they are: a row attends only to the indices at or
    below its position, which it has written itself."""
    specs = cache_pspecs(cfg, 1, dp_divisible=True)

    def zero(leaf, spec):
        ax = spec.index("dp")
        row = jnp.zeros(leaf.shape[:ax] + (1,) + leaf.shape[ax + 1:], leaf.dtype)
        return lax.dynamic_update_slice_in_dim(leaf, row, slot, axis=ax)

    return {name: leaf if name in ("k", "v") else zero(leaf, specs[name])
            for name, leaf in cache.items()}


def batch_pspecs(cfg: ModelConfig, batch: int, dp_size: int) -> Dict[str, Any]:
    b = "dp" if batch % max(dp_size, 1) == 0 else None
    out = {}
    if cfg.family == "audio":
        out["frames"] = (b, None, None)
        out["labels"] = (b, None, None)
    else:
        out["tokens"] = (b, None)
        out["labels"] = (b, None)
    if cfg.family == "vlm":
        out["vision_embeds"] = (b, None, None)
    return out
