"""Batched serving driver: prefill a batch of prompts, then decode with
a KV cache (greedy).  Structural twin of the decode dry-run cells.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma_2b --reduced \
        --batch 4 --prompt-len 16 --gen 32
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.mesh import make_local_mesh
from jax import set_mesh
import repro.models as M
from repro.models.config import reduced


def run(args) -> int:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    mesh = make_local_mesh()
    ctx = set_mesh(mesh)
    ctx.__enter__()

    params = M.init_params(cfg, jax.random.PRNGKey(args.seed),
                           dtype=jnp.float32)
    rng = np.random.default_rng(args.seed)
    b = args.batch
    total = args.prompt_len + args.gen
    prompts = rng.integers(0, cfg.vocab, (b, args.prompt_len)).astype(
        np.int32)

    cache = jax.tree.map(
        lambda sd: jnp.zeros(sd.shape, sd.dtype),
        M.cache_specs(cfg, b, total, dtype=jnp.float32))
    if cfg.family == "audio":
        from repro.models.model import _whisper_encode
        frames = jnp.asarray(
            rng.normal(size=(b, cfg.encoder_seq, cfg.d_model)) * 0.02,
            jnp.float32)
        cache["enc_out"] = _whisper_encode(params, frames, cfg)

    step = jax.jit(
        lambda p, c, t, l: M.serve_step(p, c, t, l, cfg))

    # prefill via the decode path (teacher-forced) then greedy generate
    tok = jnp.asarray(prompts[:, 0])
    t0 = time.time()
    out_tokens = [np.asarray(tok)]
    for i in range(total - 1):
        logits, cache = step(params, cache, tok, jnp.int32(i))
        if i + 1 < args.prompt_len:
            tok = jnp.asarray(prompts[:, i + 1])
        else:
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out_tokens.append(np.asarray(tok))
    dt = time.time() - t0
    seqs = np.stack(out_tokens, axis=1)
    print(f"[serve] {b} seqs × {total} steps in {dt:.2f}s "
          f"({b * (total - 1) / dt:.1f} tok/s)")
    print("[serve] sample:", seqs[0, args.prompt_len:][:16].tolist())
    ctx.__exit__(None, None, None)
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    sys.exit(run(ap.parse_args()))


if __name__ == "__main__":
    main()
