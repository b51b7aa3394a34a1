"""End-to-end training driver.

Composes every substrate in this repo: streaming data pipeline (Emitter →
SPSC ring), jitted train step (GSPMD + manual farm regions), async
checkpointing (Collector thread), fault-tolerant runner (restore-on-failure)
and deterministic replay.  On this CPU container it trains reduced configs
for real (examples/streaming_train.py runs a ~few-hundred-step job); on a
TPU pod the same driver runs the full configs via ``--arch``.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCHS
from ..data import make_batch_stream
from ..models import init_params
from ..optim import adamw_init
from ..parallel.context import mesh_context
from ..runtime.checkpoint import AsyncCheckpointer, latest_step, restore
from ..runtime.compile_cache import enable_compile_cache
from .steps import make_train_step


def train(cfg, *, steps: int, batch: int, seq: int, ckpt_dir: str | None,
          ckpt_every: int = 50, seed: int = 0, mesh=None, dp_axes=("data",),
          log_every: int = 10, peak_lr: float = 3e-4, inject_failure_at=None):
    """Returns (final_state, losses). Deterministic given (cfg, seed)."""
    key = jax.random.PRNGKey(seed)

    def build():
        params = init_params(cfg, key)
        opt = adamw_init(params, jnp.dtype(cfg.optimizer_dtype))
        return {"params": params, "opt": opt}

    ctx_mgr = mesh_context(mesh, dp_axes=dp_axes) if mesh is not None else None
    step_fn = make_train_step(cfg, peak_lr=peak_lr, total_steps=max(steps, 2))
    if ctx_mgr is not None:
        ctx_mgr.__enter__()
    try:
        jit_step = jax.jit(step_fn, donate_argnums=(0, 1))
        state = build()
        start = 0
        ckpt = None
        if ckpt_dir:
            ckpt = AsyncCheckpointer(ckpt_dir)
            last = latest_step(ckpt_dir)
            if last is not None:
                state = restore(state, ckpt_dir, last)
                start = last
                print(f"[train] restored step {start} from {ckpt_dir}")
        losses = []
        pipe = make_batch_stream(cfg, batch, seq, seed=seed, start_step=start,
                                 n_steps=steps - start)
        t0 = time.time()
        try:
            for step, np_batch in pipe:
                if inject_failure_at is not None and step == inject_failure_at:
                    inject_failure_at = None
                    raise RuntimeError("injected failure (test)")
                dev_batch = jax.tree.map(jnp.asarray, np_batch)
                params, opt, metrics = jit_step(state["params"], state["opt"], dev_batch)
                state = {"params": params, "opt": opt}
                loss = float(metrics["loss"])
                losses.append(loss)
                if step % log_every == 0:
                    dt = time.time() - t0
                    print(f"[train] step={step} loss={loss:.4f} "
                          f"gnorm={float(metrics['grad_norm']):.3f} "
                          f"lr={float(metrics['lr']):.2e} ({dt:.1f}s)", flush=True)
                if ckpt and (step + 1) % ckpt_every == 0:
                    ckpt.save(state, step + 1)
        finally:
            pipe.close()
            if ckpt:
                ckpt.wait()   # publish in-flight checkpoints even on failure
        if ckpt:
            ckpt.save(state, steps)
            ckpt.wait()
            ckpt.close()
        return state, losses
    finally:
        if ctx_mgr is not None:
            ctx_mgr.__exit__(None, None, None)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true",
                    help="train the reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()
    enable_compile_cache()
    cfg = ARCHS[args.arch]
    if args.smoke:
        cfg = cfg.smoke()
    _, losses = train(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                      ckpt_dir=args.ckpt_dir, seed=args.seed, peak_lr=args.lr)
    print(f"[train] done: first loss {losses[0]:.4f} → last {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
