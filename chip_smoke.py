"""Smoke run of the system's main path on a TPU, in one process.

From the repository root, on a machine with a TPU:

    python chip_smoke.py             # phases A-D on one chip
    python chip_smoke.py --chips 4   # only the four-client mesh step, on 4 chips

Phases, in order. Each prints what it measured; any failure raises, and the
script then exits non-zero without printing the result line.

A. The on-mesh FL train step, through the path of ``repro.launch.train``:
   qwen2.5-3b at its published widths with only the depth cut, on the
   ``(data=1, model=1)`` mesh with FedAdam, 5 steps on one fixed batch.
B. The aggregator fold on the device: ``weighted_mean``, ``StreamingMean``
   and the FedBuff batch flush over 8 client updates, each the size of one
   qwen2.5-3b decoder layer, bit for bit against the sequential numpy fold.
C. The ``int8_blocks`` wire codec at the same size, against
   ``repro.kernels.quant.ref``.
D. A seeded classical-FL TAG job on threads with ``fused_aggregation`` on,
   byte-identical to the same job with it off.

``--chips 4`` runs the step of phase A on a ``(data=4, model=1)`` mesh with
FedAvg (one FL client per chip) and compares it with the four clients' local
rounds run one after another on one chip and averaged.

The last line of output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.expansion import JobSpec  # noqa: E402
from repro.core.roles import (  # noqa: E402
    FUSED_AGG_MIN_ELEMS,
    StreamingMean,
    weighted_mean,
)
from repro.core.runtime import run_job  # noqa: E402
from repro.core.tag import DatasetSpec  # noqa: E402
from repro.core.topologies import classical_fl  # noqa: E402
from repro.data.datasets import synthetic_lm_batches  # noqa: E402
from repro.fl import strategies  # noqa: E402
from repro.fl.fedstep import FedStepConfig, local_round  # noqa: E402
from repro.kernels.agg import ops as agg_ops  # noqa: E402
from repro.kernels.quant.ops import BLOCK, quantize_flat  # noqa: E402
from repro.kernels.quant.ref import reference_quantize  # noqa: E402
from repro.launch import sharding as shd  # noqa: E402
from repro.launch.cache import use_compile_cache  # noqa: E402
from repro.launch.train import (  # noqa: E402
    make_batch,
    make_mesh_for_devices,
    setup_training,
)
from repro.models.api import build_model  # noqa: E402
from repro.transport.conformance import SeededSGDTrainer  # noqa: E402
from repro.transport.wire import Int8BlocksCodec  # noqa: E402

ARCH = "qwen2_5_3b"
# depth cut at published widths: with 4 layers (619 M parameters) the bf16
# params, FedAdam's m and v, and the step's temporaries at batch 8 x seq 512
# take about 13.3 GB of a v5e's 16 GB (compiled.memory_analysis); 6 layers
# at this batch need about 17 GB
LAYERS, BATCH, SEQ = 4, 8, 512
LOCAL_STEPS, LOCAL_LR = 2, 0.05
STEPS = 5
N_CLIENTS = 8
COLLECTIVES = r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)(?:-start)?\("


def log(msg: str) -> None:
    print(msg, flush=True)


def tree_bytes(tree) -> bytes:
    return b"|".join(
        np.asarray(leaf).tobytes() for leaf in jax.tree_util.tree_leaves(tree)
    )


def ulp_distance(a, b) -> np.ndarray:
    """Per-element distance in units in the last place between two float
    arrays of one dtype (f32 or bf16), counted across zero."""
    a, b = np.asarray(a), np.asarray(b)
    bits = {4: np.int32, 2: np.int16}[a.dtype.itemsize]

    def ordered(x):
        i = x.view(bits).astype(np.int64)
        return np.where(i < 0, np.iinfo(bits).min - i, i)

    return np.abs(ordered(a) - ordered(b))


def _mib(n) -> str:
    return f"{n / 2**20:.1f} MiB"


# --------------------------------------------------------------------- #
# A. the on-mesh FL train step
# --------------------------------------------------------------------- #
def cut_config(layers: int = LAYERS):
    """qwen2.5-3b at its published widths with only ``layers`` layers."""
    return dataclasses.replace(get_config(ARCH), num_layers=layers)


def phase_train(cfg, batch: int = BATCH, seq: int = SEQ,
                steps: int = STEPS) -> dict:
    mesh = make_mesh_for_devices(jax.devices()[:1])
    fed = FedStepConfig(local_steps=LOCAL_STEPS, local_lr=LOCAL_LR)
    rng = jax.random.key(0)
    _, _, params, state, step_fn = setup_training(cfg, mesh, fed, rng)
    tokens = next(synthetic_lm_batches(cfg.vocab_size, batch, seq, seed=0))
    host_batch = make_batch(cfg, tokens)
    dev_batch = jax.device_put(
        host_batch, shd.batch_shardings(host_batch, cfg, mesh)
    )
    t0 = time.perf_counter()
    compiled = step_fn.lower(params, state, dev_batch, rng).compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    log(f"[A] cut: {cfg.arch_id} {cfg.num_layers} layers (published "
        f"{get_config(ARCH).num_layers}), d_model {cfg.d_model}, heads "
        f"{cfg.num_heads}/{cfg.num_kv_heads} kv, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab_size}, {cfg.param_dtype} params {cfg.param_count():,}; "
        f"batch {batch} x seq {seq}, local_steps {LOCAL_STEPS}, "
        f"strategy {cfg.server_strategy}, mesh {dict(mesh.shape)}")
    if mem is not None:
        log(f"[A] memory_analysis: arguments {_mib(mem.argument_size_in_bytes)}"
            f", temporaries {_mib(mem.temp_size_in_bytes)}, outputs "
            f"{_mib(mem.output_size_in_bytes)} (aliased "
            f"{_mib(mem.alias_size_in_bytes)})")
    log(f"[A] compile {compile_s} s")
    losses, times = [], []
    for _ in range(steps):
        rng, sub = jax.random.split(rng)
        t = time.perf_counter()
        params, state, metrics = compiled(params, state, dev_batch, sub)
        jax.block_until_ready((params, state, metrics))
        times.append(time.perf_counter() - t)
        losses.append(float(metrics["loss"]))
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    steady = float(np.median(times[1:])) if steps > 1 else times[0]
    log(f"[A] losses {losses}")
    log(f"[A] step times (s) {times}; steady (median after warm-up) "
        f"{steady} s")
    log(f"[A] peak_bytes_in_use "
        f"{_mib(peak) if peak is not None else 'not reported'}")
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    return {"losses": losses, "compile_s": compile_s, "step_s": steady,
            "peak_bytes": peak}


# --------------------------------------------------------------------- #
# four chips: the mesh step against its one-chip FedAvg reference
# --------------------------------------------------------------------- #
def phase_mesh4(cfg, batch_per_client: int = BATCH, seq: int = SEQ) -> dict:
    """One FedAvg step on a ``(data=4, model=1)`` mesh, one client per
    device, against the four local rounds run one after another on one
    device and averaged.

    Tolerance, on the update Δ = new params − initial params: ‖Δ_mesh −
    Δ_ref‖₂ ≤ 0.2 ‖Δ_ref‖₂, and the mean loss within 1e-3 relative. With
    f32 params the two agree bit for bit (CPU, 4 virtual devices). With
    bf16 params the mesh program and the one-device program fuse the bf16
    ops differently, so a local update can round to the neighbouring bf16
    value: 0.068 at the reduced size on CPU, where averaging only 3 of the
    clients, one client, or summing instead of averaging gives 0.55 to
    0.86."""
    n = 4
    mesh = make_mesh_for_devices(jax.devices()[:n])
    fed = FedStepConfig(local_steps=LOCAL_STEPS, local_lr=LOCAL_LR)
    rng = jax.random.key(0)
    bundle, setup, params, state, step_fn = setup_training(
        cfg, mesh, fed, rng, strategy_name="fedavg"
    )
    if setup.client_axes != ("data",):
        raise AssertionError(f"clients on {setup.client_axes}, not data")
    tokens = next(
        synthetic_lm_batches(cfg.vocab_size, n * batch_per_client, seq, seed=0)
    )
    host_batch = make_batch(cfg, tokens)
    dev_batch = jax.device_put(
        host_batch, shd.batch_shardings(host_batch, cfg, mesh)
    )
    one = jax.devices()[0]
    # through the host: the step donates ``params``, and a device-to-device
    # put may alias the buffer
    params0 = jax.device_put(jax.device_get(params), one)
    rng, sub = jax.random.split(rng)
    t0 = time.perf_counter()
    compiled = step_fn.lower(params, state, dev_batch, sub).compile()
    log(f"[mesh4] {cfg.arch_id} {cfg.num_layers} layers, mesh "
        f"{dict(mesh.shape)}, {batch_per_client} x {seq} tokens per client, "
        f"compile {time.perf_counter() - t0} s")
    found = re.findall(COLLECTIVES, compiled.as_text())
    log(f"[mesh4] collectives in the compiled HLO: "
        f"{ {c: found.count(c) for c in sorted(set(found))} }")
    if "all-reduce" not in found:
        raise AssertionError("no all-reduce in the four-client step")
    t0 = time.perf_counter()
    new_params, _, metrics = compiled(params, state, dev_batch, sub)
    mesh_params = jax.device_get(new_params)
    log(f"[mesh4] mesh step {time.perf_counter() - t0} s (first call)")
    del new_params

    # the delta sum is donated from round to round, so one chip holds it,
    # params0 and one local round at a time
    @functools.partial(jax.jit, donate_argnums=3)
    def add_round(p, b, r, acc):
        local, loss, _ = local_round(bundle.loss_fn, p, b, r, fed)
        return jax.tree_util.tree_map(
            lambda a, lp, p0: a + (lp - p0).astype(jnp.float32), acc, local, p
        ), loss

    acc = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32, device=one), params0
    )
    ref_losses = []
    for i in range(n):
        shard = {
            k: v[:, i * batch_per_client:(i + 1) * batch_per_client]
            if k == "positions"
            else v[i * batch_per_client:(i + 1) * batch_per_client]
            for k, v in host_batch.items()
        }
        acc, loss = add_round(
            params0, jax.device_put(shard, one), jax.random.fold_in(sub, i), acc
        )
        ref_losses.append(float(loss))
    mean = jax.tree_util.tree_map(
        lambda a, p: (a / jnp.float32(n)).astype(p.dtype), acc, params0
    )
    ref_params, _ = strategies.get_strategy("fedavg").apply(params0, mean, ())
    ref_params = jax.device_get(ref_params)

    init = jax.device_get(params0)
    num = den = 0.0
    beyond, total = 0, 0
    for a, b, p in zip(*(jax.tree_util.tree_leaves(t)
                         for t in (mesh_params, ref_params, init))):
        # one step of the parameter dtype at the reference value
        shift = 2.0 ** (jnp.finfo(np.float32).nmant - jnp.finfo(b.dtype).nmant)
        a, b, p = (np.asarray(x, np.float32) for x in (a, b, p))
        num += float(np.sum(np.square(a - b)))
        den += float(np.sum(np.square(b - p)))
        beyond += int((np.abs(a - b) > np.spacing(np.abs(b)) * shift).sum())
        total += a.size
    rel = (num / den) ** 0.5
    mesh_loss, ref_loss = float(metrics["loss"]), float(np.mean(ref_losses))
    log(f"[mesh4] vs one-chip FedAvg of the 4 local rounds: update relative "
        f"L2 error {rel}; {beyond} of {total} parameters more than one step "
        f"apart; loss {mesh_loss} vs {ref_loss}")
    if not rel <= 0.2:
        raise AssertionError("mesh step disagrees with the FedAvg reference")
    if abs(mesh_loss - ref_loss) > 1e-3 * abs(ref_loss):
        raise AssertionError("mesh loss disagrees with the FedAvg reference")
    return {"update_rel_l2": rel, "beyond_one_step": beyond, "total": total}


# --------------------------------------------------------------------- #
# B. the aggregator fold
# --------------------------------------------------------------------- #
def layer_shapes(cfg):
    """Leaf shapes of one decoder layer of ``cfg``, from the model's init."""
    one = dataclasses.replace(cfg, num_layers=1, scan_layers=True)
    shapes = jax.eval_shape(build_model(one).init, jax.random.key(0))
    (layer,) = shapes["groups"]
    return jax.tree_util.tree_map(lambda s: s.shape[1:], layer,
                                  is_leaf=lambda s: hasattr(s, "shape"))


def make_updates(shapes, n_clients: int, seed: int):
    """``n_clients`` f32 update trees of ``shapes`` with sample counts."""
    rng = np.random.default_rng(seed)
    is_shape = lambda s: isinstance(s, tuple)  # noqa: E731
    return [
        (jax.tree_util.tree_map(
            lambda s: rng.standard_normal(s, dtype=np.float32), shapes,
            is_leaf=is_shape),
         float(rng.integers(1, 40)))
        for _ in range(n_clients)
    ]


def sequential_fold(updates):
    """The sequential numpy fold the roles' fused path must reproduce:
    scale each update by its sample count, add in client order, divide once
    by the Python-float total. Returns ``(mean, sum, total)``."""
    total, acc = 0.0, None
    for tree, n in updates:
        total += n
        scaled = jax.tree_util.tree_map(lambda x: x * n, tree)
        acc = scaled if acc is None else jax.tree_util.tree_map(np.add, acc, scaled)
    return jax.tree_util.tree_map(lambda x: x / total, acc), acc, total


def phase_fold(updates, fused=None) -> dict:
    """``fused=None`` is the roles' own size-based dispatch."""
    leaves = jax.tree_util.tree_leaves(updates[0][0])
    elems = sum(leaf.size for leaf in leaves)
    n = len(updates)
    auto_fused = agg_ops.fused_dispatch_default() and elems >= FUSED_AGG_MIN_ELEMS
    log(f"[B] {n} client updates of {elems:,} f32 elements "
        f"({_mib(4 * elems)} each); auto dispatch picks the fused path: "
        f"{auto_fused}")
    ref_mean, ref_sum, ref_total = sequential_fold(updates)

    compiled_before = agg_ops._fold_flat._cache_size()
    t = time.perf_counter()
    mean, total = weighted_mean(updates, fused=fused)
    t_mean = time.perf_counter() - t
    fold_ran = agg_ops._fold_flat._cache_size() > compiled_before
    if total != ref_total or tree_bytes(mean) != tree_bytes(ref_mean):
        raise AssertionError("weighted_mean differs from the sequential fold")
    log(f"[B] weighted_mean: bit-identical to the sequential numpy fold; "
        f"device fold ran: {fold_ran}; {t_mean} s host clock")

    stream = StreamingMean(fused=fused)
    t = time.perf_counter()
    for tree, w in updates:
        stream.fold(tree, w)
    smean, stotal = stream.finalize()
    t_stream = time.perf_counter() - t
    if stotal != ref_total or tree_bytes(smean) != tree_bytes(ref_mean):
        raise AssertionError("StreamingMean differs from the sequential fold")
    log(f"[B] StreamingMean: bit-identical; {t_stream} s host clock")

    buff = strategies.get_strategy("fedbuff", buffer_size=n)
    stale = [c % 4 for c in range(n)]
    zeros = jax.tree_util.tree_map(np.zeros_like, updates[0][0])
    t = time.perf_counter()
    flushed = buff.accumulate_batch(
        buff.init(zeros), [tree for tree, _ in updates], stale, fused=fused
    )
    t_flush = time.perf_counter() - t
    acc = zeros
    for (tree, _), s in zip(updates, stale):
        w = np.asarray(buff.staleness_weight(np.int32(s)))
        acc = jax.tree_util.tree_map(lambda a, d: a + w * d, acc, tree)
    if tree_bytes(flushed["acc"]) != tree_bytes(acc):
        raise AssertionError("FedBuff batch flush differs from the numpy chain")
    log(f"[B] FedBuff batch flush: bit-identical; {t_flush} s host clock")

    # why the exact fold divides on the host: the device's f32 division
    big = max(jax.tree_util.tree_leaves(ref_sum), key=lambda x: x.size)
    on_device = np.asarray(jnp.asarray(big) / jnp.float32(ref_total))
    ulps = ulp_distance(on_device, big / ref_total)
    log(f"[B] device f32 divide of the fold's sum vs numpy: max "
        f"{int(ulps.max())} ulp, {int((ulps > 0).sum())} of {ulps.size} "
        f"elements differ")

    hlo = agg_ops._fold_flat.lower(
        jax.ShapeDtypeStruct((n, elems), jnp.float32), interpret=None
    ).compile().as_text()
    kernel = "tpu_custom_call" in hlo
    log(f"[B] compiled fold contains tpu_custom_call: {kernel}")
    return {"auto_fused": auto_fused, "fold_ran": fold_ran, "kernel": kernel,
            "divide_max_ulp": int(ulps.max())}


# --------------------------------------------------------------------- #
# C. the int8_blocks wire codec
# --------------------------------------------------------------------- #
def phase_codec(tree) -> dict:
    codec = Int8BlocksCodec()
    t = time.perf_counter()
    coded = codec.encode(tree)
    t_enc = time.perf_counter() - t
    t = time.perf_counter()
    decoded = codec.decode(coded)
    t_dec = time.perf_counter() - t
    flat = np.concatenate(
        [np.asarray(x, np.float32).reshape(-1)
         for x in jax.tree_util.tree_leaves(tree)]
    )
    n = flat.size
    padded = np.pad(flat, (0, (-n) % BLOCK)).reshape(-1, BLOCK)
    q_ref, s_ref = jax.jit(reference_quantize)(padded)
    q_ref = np.asarray(q_ref).reshape(-1)[:n]
    s_ref = np.asarray(s_ref)
    q, scale = np.asarray(coded["q"]), np.asarray(coded["scale"])
    mismatched = int((q != q_ref).sum())
    scale_ulp = int(ulp_distance(scale, s_ref).max())
    log(f"[C] int8_blocks over {n:,} elements: encode {t_enc} s, decode "
        f"{t_dec} s host clock; vs ref.py: {mismatched} int8 codes "
        f"differ, scales within {scale_ulp} ulp")
    if mismatched or scale_ulp > 1:
        raise AssertionError("int8_blocks disagrees with kernels/quant/ref.py")
    # the same formula with numpy's correctly rounded division, for the record
    host_scale = np.maximum(np.abs(padded).max(1, keepdims=True),
                            np.float32(1e-30)) / np.float32(127.0)
    host_q = np.clip(np.round(padded / host_scale), -127, 127).astype(np.int8)
    log(f"[C] vs the numpy quantizer: "
        f"{int((host_q.reshape(-1)[:n] != q).sum())} int8 codes differ")
    expect = (np.pad(q, (0, (-n) % BLOCK)).reshape(-1, BLOCK).astype(np.float32)
              * scale).reshape(-1)[:n]
    got = np.concatenate(
        [np.asarray(x, np.float32).reshape(-1)
         for x in jax.tree_util.tree_leaves(decoded)]
    )
    if got.tobytes() != expect.tobytes():
        raise AssertionError("decode is not q * scale")
    err = np.abs(got - flat).reshape(-1)
    bound = np.repeat(scale.reshape(-1), BLOCK)[:n] * 0.5001 + 1e-7
    if not (err <= bound).all():
        raise AssertionError("round-trip error above half a quantization step")
    log(f"[C] decode == q * scale bit for bit; round-trip error within half "
        f"a step (max {float(err.max()):.3g})")
    hlo = quantize_flat.lower(
        jax.ShapeDtypeStruct(flat.shape, jnp.float32)
    ).compile().as_text()
    kernel = "tpu_custom_call" in hlo
    log(f"[C] compiled quantizer contains tpu_custom_call: {kernel}")
    return {"mismatched": mismatched, "scale_ulp": scale_ulp, "kernel": kernel}


# --------------------------------------------------------------------- #
# D. a TAG job with the device fold forced on
# --------------------------------------------------------------------- #
def phase_job(rounds: int = 3) -> dict:
    rng = np.random.default_rng(7)
    w0 = {
        "w": (0.01 * rng.normal(size=(32, 10))).astype(np.float32),
        "b": np.zeros((10,), np.float32),
    }
    weights = {}
    ran_before = strategies._add_scaled._cache_size()
    for fused in (True, False):
        job = JobSpec(
            tag=classical_fl(),
            datasets=tuple(DatasetSpec(name=f"d{i}") for i in range(4)),
            hyperparams={"rounds": rounds, "init_weights": w0,
                         "fused_aggregation": fused},
        )
        res = run_job(job, program_overrides={"trainer": SeededSGDTrainer},
                      timeout=120)
        if res.errors:
            raise AssertionError(f"job failed: {res.errors}")
        weights[fused] = res.global_weights()
    device_fold = strategies._add_scaled._cache_size() > ran_before
    same = tree_bytes(weights[True]) == tree_bytes(weights[False])
    log(f"[D] classical_fl, 4 trainers, {rounds} rounds: device fold ran: "
        f"{device_fold}; global weights fold-on == fold-off: {same}")
    if not same:
        raise AssertionError("fused job weights differ from the sequential job")
    return {"device_fold": device_fold}


# --------------------------------------------------------------------- #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-client mesh step")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found {dev.platform}")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: needs {args.chips} chips, found "
                         f"{len(devices)}")
    use_compile_cache()
    log(f"device: {dev.platform} {dev.device_kind} x {len(devices)}, "
        f"jax {jax.__version__}")
    cfg = cut_config()
    if args.chips == 4:
        phase_mesh4(cfg)
    else:
        phase_train(cfg)
        updates = make_updates(layer_shapes(cfg), N_CLIENTS, seed=0)
        fold = phase_fold(updates)
        if not (fold["auto_fused"] and fold["fold_ran"] and fold["kernel"]):
            raise AssertionError(f"the device fold did not run: {fold}")
        if not phase_codec(updates[0][0])["kernel"]:
            raise AssertionError("the int8 quantizer did not run as a kernel")
        del updates
        if not phase_job()["device_fold"]:
            raise AssertionError("the job's fold did not run on the device")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
