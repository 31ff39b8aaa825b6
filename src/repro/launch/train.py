"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama_1_1b \
        --reduced --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt \
        --resume auto

Fault tolerance: checkpoints every ``--ckpt-every`` steps (atomic
manifests), auto-resume from the latest complete checkpoint, straggler
detection via step-time z-score, optional crash injection (--crash-at)
used by the restart test.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.data import DataConfig, synthetic_batches
from repro.launch.mesh import make_local_mesh
from jax import set_mesh
import repro.models as M
from repro.models.config import reduced
from repro.sharding import batch_shardings, param_shardings
from repro.train import (
    AdamWConfig,
    StragglerDetector,
    TrainConfig,
    latest_step,
    make_train_step,
    restore_checkpoint,
    save_checkpoint,
)
from repro.train.optimizer import adamw_init


def run(args) -> int:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.seq:
        cfg = dataclasses.replace(cfg, max_seq=args.seq)

    mesh = make_local_mesh()
    ctx = set_mesh(mesh)
    ctx.__enter__()

    key = jax.random.PRNGKey(args.seed)
    params = M.init_params(cfg, key, dtype=jnp.float32)
    axes = M.logical_axes(cfg)
    p_sh = param_shardings(axes, params, mesh)
    params = jax.tree.map(jax.device_put, params, p_sh)
    opt = adamw_init(params)

    tcfg = TrainConfig(
        microbatches=args.microbatches,
        opt=AdamWConfig(lr=args.lr, total_steps=args.steps),
    )
    step_fn = jax.jit(make_train_step(cfg, tcfg))

    start = 0
    if args.resume == "auto" and args.ckpt_dir:
        s = latest_step(args.ckpt_dir)
        if s is not None:
            params, opt, _ = restore_checkpoint(
                args.ckpt_dir, s, params, opt)
            start = s
            print(f"[train] resumed from step {s}", flush=True)

    dcfg = DataConfig(batch=args.batch, seq=args.seq or cfg.max_seq,
                      vocab=cfg.vocab, seed=args.seed)
    extra = None
    if cfg.family == "audio":
        extra = {"frames": lambda rng: rng.normal(
            size=(args.batch, cfg.encoder_seq, cfg.d_model)
        ).astype(np.float32) * 0.02}
    if cfg.rope_type == "mrope":
        s_len = args.seq or cfg.max_seq
        extra = {"positions": lambda rng: np.broadcast_to(
            np.arange(s_len, dtype=np.int32)[None, None],
            (args.batch, 3, s_len)).copy()}
    data = synthetic_batches(dcfg, start_step=start, extra=extra)

    det = StragglerDetector()
    losses = []
    for step in range(start, args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(data).items()}
        det.start()
        params, opt, metrics = step_fn(params, opt, batch)
        loss = float(metrics["loss"])
        if det.stop():
            print(f"[train] straggler step {step} detected", flush=True)
        losses.append(loss)
        if step % args.log_every == 0:
            print(f"[train] step {step} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f}", flush=True)
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, step + 1, params, opt,
                            extra=dict(arch=cfg.name))
        if args.crash_at is not None and step + 1 == args.crash_at:
            print("[train] injected crash", flush=True)
            os._exit(42)

    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, args.steps, params, opt,
                        extra=dict(arch=cfg.name))
    first = np.mean(losses[:5]) if len(losses) >= 5 else losses[0]
    last = np.mean(losses[-5:])
    print(f"[train] done: loss {first:.4f} -> {last:.4f} "
          f"({len(losses)} steps, stragglers={det.flagged})", flush=True)
    ctx.__exit__(None, None, None)
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", default="auto")
    ap.add_argument("--crash-at", type=int, default=None)
    sys.exit(run(ap.parse_args()))


if __name__ == "__main__":
    main()
