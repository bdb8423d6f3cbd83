"""Batched serving driver: prefill a batch of prompts, then decode tokens.

Runs the reduced config on CPU (runnable example) or a full config on a
real mesh. Demonstrates the serve path the decode_* dry-run shapes lower.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2_5_3b --reduced \
      --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.cache import use_compile_cache
from repro.models.api import build_model


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_5_3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    bundle = build_model(cfg)
    rng = jax.random.key(0)
    params = bundle.init(rng)
    B, S = args.batch, args.prompt_len
    max_len = S + args.gen

    prompts = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": prompts}
    if cfg.family == "vlm":
        P = min(cfg.vision_patches, S)
        batch["patch_embeds"] = jnp.zeros((B, P, cfg.d_model))
        batch["positions"] = jnp.broadcast_to(
            jnp.arange(S)[None, None], (3, B, S)
        ).astype(jnp.int32)
    if cfg.family == "audio":
        batch["frames"] = jnp.zeros((B, cfg.frontend_len, cfg.d_model))

    cache = bundle.init_cache(B, max_len)
    t0 = time.time()
    logits, cache = jax.jit(bundle.prefill)(params, batch, cache)
    print(f"[serve] prefill {B}x{S} in {time.time()-t0:.2f}s")

    step = jax.jit(bundle.serve_step, donate_argnums=(1,))
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    generated = [tok]
    t0 = time.time()
    for i in range(args.gen - 1):
        logits, cache = step(params, cache, {"token": tok})
        if args.temperature > 0:
            rng, sub = jax.random.split(rng)
            tok = jax.random.categorical(
                sub, logits[:, -1] / args.temperature
            )[:, None].astype(jnp.int32)
        else:
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        generated.append(tok)
    dt = time.time() - t0
    out = jnp.concatenate(generated, axis=1)
    print(f"[serve] generated {args.gen} tokens/seq x {B} seqs in {dt:.2f}s "
          f"({B*args.gen/max(dt,1e-9):.1f} tok/s)")
    print("[serve] sample token ids:", out[0][:12].tolist())
    assert bool(jnp.all(jnp.isfinite(logits))), "non-finite logits"
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
