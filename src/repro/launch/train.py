"""End-to-end training driver.

Runs the TAG-driven federated train step (the paper's technique as a
first-class feature) for a chosen architecture on whatever devices exist,
one FL client per device: every device sits on the ``data`` axis, the
TAG's client axis. ``--reduced`` gives the CPU-sized config; ``--layers``
keeps the published widths and cuts only the depth, as one chip needs.
Data is the synthetic non-IID federated LM stream from ``repro.data``.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2_5_3b --reduced \
      --steps 50 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch qwen2_5_3b --layers 4 \
      --steps 5 --batch 8 --seq 512
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.checkpoint.checkpoint import save as save_checkpoint
from repro.configs import get_config
from repro.data.datasets import synthetic_lm_batches
from repro.fl.fedstep import FedStepConfig
from repro.launch import sharding as shd
from repro.launch.cache import use_compile_cache
from repro.launch.steps import build_train_step


def make_mesh_for_devices(devices=None):
    """One FL client per device: all ``devices`` (default: every device) on
    ``data``, ``model`` of size 1."""
    devices = list(devices or jax.devices())
    return jax.make_mesh(
        (len(devices), 1), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2, devices=devices,
    )


def make_batch(cfg, tokens: np.ndarray) -> dict:
    """The step's host-side input dict for one global batch of token ids."""
    B, S = tokens.shape
    batch = {"tokens": tokens}
    if cfg.family == "vlm":
        batch["patch_embeds"] = np.zeros(
            (B, cfg.vision_patches, cfg.d_model), np.float32
        )
        batch["positions"] = np.broadcast_to(
            np.arange(S, dtype=np.int32)[None, None], (3, B, S)
        )
    if cfg.family == "audio":
        batch["frames"] = np.zeros((B, cfg.frontend_len, cfg.d_model), np.float32)
    return batch


def setup_training(cfg, mesh, fed, rng, *, strategy_name=None,
                   cross_pod_wire="f32"):
    """Build the TAG-lowered step on ``mesh``, create params and server
    state in place with the step's ``in_shardings``, and jit the step with
    both donated. Returns ``(bundle, setup, params, state, step_fn)``."""
    bundle, setup = build_train_step(
        cfg, mesh, fed, cross_pod_wire=cross_pod_wire,
        strategy_name=strategy_name,
    )
    p_sh, s_sh = setup.in_shardings[:2]
    params = jax.jit(bundle.init, out_shardings=p_sh)(rng)
    state = jax.jit(setup.init_state, out_shardings=s_sh)(params)
    step_fn = jax.jit(
        setup.step, donate_argnums=(0, 1), out_shardings=setup.out_shardings
    )
    return bundle, setup, params, state, step_fn


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_5_3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="keep the published widths, cut the depth to N layers")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--local-lr", type=float, default=0.05)
    ap.add_argument("--strategy", default=None)
    ap.add_argument("--cross-pod-wire", default="f32")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    if args.layers:
        if args.layers % cfg.layer_period:
            ap.error(f"--layers must be a multiple of the layer period "
                     f"{cfg.layer_period}")
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    mesh = make_mesh_for_devices()
    fed = FedStepConfig(local_steps=args.local_steps, local_lr=args.local_lr)
    rng = jax.random.key(0)
    _, setup, params, state, step_fn = setup_training(
        cfg, mesh, fed, rng, strategy_name=args.strategy,
        cross_pod_wire=args.cross_pod_wire,
    )
    print(f"[train] arch={cfg.arch_id} layers={cfg.num_layers} "
          f"params={cfg.param_count():,} "
          f"mesh={dict(mesh.shape)} clients over {setup.client_axes} "
          f"tag={setup.tag.name if setup.tag else None}")

    data = synthetic_lm_batches(
        vocab=cfg.vocab_size, batch=args.batch, seq=args.seq, seed=0
    )
    t0 = time.time()
    losses = []
    for i in range(args.steps):
        batch = make_batch(cfg, next(data))
        batch = jax.device_put(
            batch, shd.batch_shardings(batch, cfg, mesh)
        )
        rng, sub = jax.random.split(rng)
        params, state, metrics = step_fn(params, state, batch, sub)
        losses.append(float(metrics["loss"]))
        if i % args.log_every == 0 or i == args.steps - 1:
            print(f"[train] step {i:4d} loss {losses[-1]:.4f} "
                  f"({(time.time()-t0)/(i+1):.2f}s/step)")
    if args.checkpoint:
        save_checkpoint(args.checkpoint, args.steps, params)
        print(f"[train] saved checkpoint to {args.checkpoint}")
    print(f"[train] done: first loss {losses[0]:.4f} -> last {losses[-1]:.4f}")
    assert losses[-1] < losses[0], "loss did not improve"
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
