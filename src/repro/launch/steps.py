"""Step builders: train / prefill / decode, with shardings attached.

These are the functions the dry-run lowers and the drivers execute.  All
sharding decisions funnel through ``parallel.rules``; input ShapeDtypeStructs
carry their shardings so ``jax.jit(...).lower(*specs)`` needs no separate
in_shardings (donation is still declared for the state arguments).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models import model as M
from ..models.config import ModelConfig
from ..optim import adamw_init, adamw_update, cosine_schedule
from ..parallel import rules
from ..parallel.context import MeshCtx, current_ctx

__all__ = ["make_train_step", "make_decode_step", "make_prefill_step",
           "train_state_specs", "input_specs"]


# --------------------------------------------------------------------------
# step functions (pure; trace under an active mesh_context)
# --------------------------------------------------------------------------
def make_train_step(cfg: ModelConfig, *, peak_lr: float = 3e-4,
                    warmup: int = 100, total_steps: int = 10_000):
    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            M.loss_fn, has_aux=True)(params, batch, cfg)
        lr = cosine_schedule(opt_state.step, peak_lr=peak_lr,
                             warmup_steps=warmup, total_steps=total_steps)
        params, opt_state, om = adamw_update(params, grads, opt_state, lr=lr)
        out_metrics = {"loss": loss, "lr": lr, **metrics, **om}
        return params, opt_state, out_metrics

    return train_step


def make_prefill_step(cfg: ModelConfig):
    def prefill_step(params, batch):
        return M.prefill(params, batch, cfg)
    return prefill_step


def make_decode_step(cfg: ModelConfig):
    def serve_step(params, batch, cache, positions):
        return M.decode_step(params, batch, cache, positions, cfg)
    return serve_step


# --------------------------------------------------------------------------
# specs (ShapeDtypeStruct stand-ins, shardings attached)
# --------------------------------------------------------------------------
def _sds(tree_shapes, tok_tree, ctx: Optional[MeshCtx]):
    """Attach resolved shardings to a ShapeDtypeStruct tree."""
    if ctx is None:
        return tree_shapes
    sh = rules.to_shardings(ctx, tok_tree)
    return jax.tree.map(
        lambda s, d: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=d),
        tree_shapes, sh)


def train_state_specs(cfg: ModelConfig, ctx: Optional[MeshCtx]):
    """(params, opt_state) ShapeDtypeStructs with shardings — no allocation."""
    p_shapes = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    mdt = jnp.dtype(cfg.optimizer_dtype)
    o_shapes = jax.eval_shape(lambda: adamw_init(p_shapes, mdt))
    p_tok = M.params_pspecs(cfg, ctx.mp_size if ctx else 1)
    o_tok = type(o_shapes)(step=None, mu=p_tok, nu=p_tok)
    return (_sds(p_shapes, p_tok, ctx),
            _sds(o_shapes, o_tok, ctx))


HBM_SERVE_BUDGET = 8e9   # bytes of params per chip we allow replicated-dp


def serve_cfg(cfg: ModelConfig, shape, ctx: Optional[MeshCtx]) -> ModelConfig:
    """For inference cells, replicate params over dp when they fit — kills
    the per-step ZeRO gathers that otherwise dominate the decode collective
    term (EXPERIMENTS §Perf, serving hillclimb)."""
    import os
    if shape.kind == "train" or ctx is None or \
            os.environ.get("REPRO_SERVE_FSDP") == "1":   # §Perf baseline knob
        return cfg
    from ..models import param_count
    per_chip = param_count(cfg) * 2 / max(ctx.mp_size, 1)   # bf16
    if per_chip <= HBM_SERVE_BUDGET:
        return cfg.replace(serve_params_replicated=True)
    return cfg


def input_specs(cfg: ModelConfig, shape, ctx: Optional[MeshCtx]) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for one assigned shape cell.

    Returns kwargs for the step function of the cell's kind:
      train   → {params, opt_state, batch}
      prefill → {params, batch}
      decode  → {params, batch, cache, positions}  (a position per row)
    """
    cfg = serve_cfg(cfg, shape, ctx)
    B, S = shape.global_batch, shape.seq_len
    dt = cfg.param_dtype
    dp = ctx.dp_size if ctx else 1

    def batch_of(seq):
        out = {}
        if cfg.family == "audio":
            out["frames"] = jax.ShapeDtypeStruct((B, seq, cfg.d_model), dt)
            out["labels"] = jax.ShapeDtypeStruct((B, cfg.n_codebooks, seq), jnp.int32)
        else:
            out["tokens"] = jax.ShapeDtypeStruct((B, seq), jnp.int32)
            out["labels"] = jax.ShapeDtypeStruct((B, seq), jnp.int32)
        if cfg.family == "vlm":
            out["vision_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.vision_patches, cfg.vision_dim), jnp.float32)
        if shape.kind != "train":
            out.pop("labels", None)
        return _sds(out, {k: v for k, v in M.batch_pspecs(cfg, B, dp).items()
                          if k in out}, ctx)

    params, opt = train_state_specs(cfg, ctx)
    if shape.kind == "train":
        return {"params": params, "opt_state": opt, "batch": batch_of(S)}
    if shape.kind == "prefill":
        return {"params": params, "batch": batch_of(S)}
    # decode: one new token against a cache of S
    cache_shapes = jax.eval_shape(lambda: M.init_cache(cfg, B, S))
    cache_tok = M.cache_pspecs(cfg, B, dp_divisible=(B % max(dp, 1) == 0))
    cache = _sds(cache_shapes, cache_tok, ctx)
    return {"params": params, "batch": batch_of(1), "cache": cache,
            "positions": jax.ShapeDtypeStruct((B,), jnp.int32)}


def step_fn_for(cfg: ModelConfig, shape, ctx: Optional[MeshCtx] = None):
    cfg = serve_cfg(cfg, shape, ctx)
    if shape.kind == "train":
        return make_train_step(cfg), ("params", "opt_state", "batch")
    if shape.kind == "prefill":
        return make_prefill_step(cfg), ("params", "batch")
    return make_decode_step(cfg), ("params", "batch", "cache", "positions")
