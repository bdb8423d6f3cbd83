"""Ahead-of-time compiles of the main path's Pallas kernels for a TPU v5e.

The TPU compiler is installed without the chip. Each test lowers a kernel
at a real size for one chip of a described ``v5e:2x2`` topology and
compiles it, which refuses what the chip would refuse: a primitive the TPU
lowering lacks, a block off the (8, 128) tiling, more scoped VMEM than a
kernel may take, more HBM than the chip has. Nothing runs.

Only one process at a time may load the TPU library, so the topology is
described inside a module fixture, never while a module is imported, and
these compiles live in this one file. The persistent compile cache is off
around them: an entry written for a described chip cannot be read back.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.agg.kernel import fold_scaled, weighted_aggregate
from repro.kernels.quant.kernel import dequantize_blocks, quantize_blocks

# parameters in one qwen2.5-3b decoder layer
LAYER = 77_076_992
QUANT_ELEMS = 64 * 2**20
# the fused attention kernels: forward with its residuals, dq and dkv
SPLASH_KERNELS = ("splash_mha_fwd", "splash_mha_dq", "splash_mha_dkv")
# one local step of the Qwen3-235B-A22B step cell: 2 sequences of 8,192
SEQS, SEQ = 2, 8192


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        # or the TPU compiler writes its logs under /tmp
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.experimental.compilation_cache import compilation_cache

        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 - any failure means no topology
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize(
    "clients,n",
    # 64 clients of a whole layer in f32 (19.7 GB) exceed one chip's HBM;
    # an eighth of a layer keeps the stack at the 8-client size
    [(8, LAYER), (64, LAYER // 8)],
)
def test_fold_scaled_compiles(one_chip, clients, n):
    compiled = _compile(
        lambda s: fold_scaled(s), _spec(one_chip, (clients, n))
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_weighted_aggregate_compiles(one_chip):
    compiled = _compile(
        lambda d, w, den: weighted_aggregate(d, w, den),
        _spec(one_chip, (8, LAYER)),
        _spec(one_chip, (8,)),
        _spec(one_chip, (1,)),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_quantize_blocks_compiles(one_chip):
    compiled = _compile(
        lambda x: quantize_blocks(x),
        _spec(one_chip, (QUANT_ELEMS // 4096, 4096)),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_dequantize_blocks_compiles(one_chip):
    nb = QUANT_ELEMS // 4096
    compiled = _compile(
        lambda q, s: dequantize_blocks(q, s),
        _spec(one_chip, (nb, 4096), jnp.int8),
        _spec(one_chip, (nb, 1)),
    )
    assert "tpu_custom_call" in compiled.as_text()


def _qwen3_moe_cell(**changes):
    """Qwen3-235B-A22B as the step cell runs it: 8 of 128 experts held, an
    eighth of the vocabulary, attention scanned in chunks of 256 queries
    where it is not fused, each layer rematerialised."""
    from repro.configs import get_config

    return dataclasses.replace(
        get_config("qwen3_moe_235b_a22b"), experts_held=8, vocab_size=18_992,
        fl_axes=("data",), scan_attn_chunks=True, q_chunk=256, remat=True,
        **changes)


def _assert_fused(text):
    for kernel in SPLASH_KERNELS:
        assert kernel in text, f"no {kernel} kernel in the compiled program"


def test_fused_attention_compiles_in_the_attention_block(one_chip):
    """Qwen3-235B-A22B's attention sub-block (64 query heads over 4 key
    heads of 128) at the step cell's 2 x 8,192 tokens, forward and backward:
    the TPU program runs the fused kernel and its two backward kernels."""
    from repro.models.attention import attn_init
    from repro.models.blocks import _attn_core_full

    cfg = _qwen3_moe_cell()
    params = jax.eval_shape(lambda k: attn_init(k, cfg, jnp.bfloat16),
                            jax.random.key(0))
    params = jax.tree_util.tree_map(
        lambda s: _spec(one_chip, s.shape, s.dtype), params)
    positions = _spec(one_chip, (SEQS, SEQ), jnp.int32)

    def loss(p, h, pos):
        out, _ = _attn_core_full(p, h, pos, None, cfg)
        return jnp.sum(out.astype(jnp.float32))

    compiled = _compile(jax.grad(loss, argnums=(0, 1)), params,
                        _spec(one_chip, (SEQS, SEQ, cfg.d_model), jnp.bfloat16),
                        positions)
    _assert_fused(compiled.as_text())


def test_fused_attention_compiles_in_the_fl_train_step(one_chip):
    """One layer of the step cell's TAG-lowered FedAvg train step (two local
    steps of 2 x 8,192 tokens): attention runs as the fused kernels inside
    the step's client shard_map, and no chunk's f32 scores, (..., 256,
    8,192), are left in the program."""
    from repro.fl.fedstep import FedStepConfig
    from repro.launch import sharding as shd
    from repro.launch.steps import build_train_step
    from repro.launch.train import make_batch, make_mesh_for_devices

    cfg = _qwen3_moe_cell(num_layers=1)
    mesh = make_mesh_for_devices(list(one_chip.device_set))
    bundle, setup = build_train_step(
        cfg, mesh, FedStepConfig(local_steps=2, local_lr=0.01),
        strategy_name="fedavg")
    assert setup.client_axes == ("data",)

    def with_shardings(shapes, shardings):
        return jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, shardings)

    p_sh, s_sh, _, rng_sh = setup.in_shardings
    params = jax.eval_shape(bundle.init, jax.random.key(0))
    state = jax.eval_shape(setup.init_state, params)
    host = make_batch(cfg, np.zeros((2 * SEQS, SEQ), np.int32))
    batch = with_shardings(host, shd.batch_shardings(host, cfg, mesh))
    rng = with_shardings(jax.eval_shape(lambda: jax.random.key(0)), rng_sh)
    compiled = jax.jit(setup.step, out_shardings=setup.out_shardings).lower(
        with_shardings(params, p_sh), with_shardings(state, s_sh), batch, rng,
    ).compile()
    text = compiled.as_text()
    _assert_fused(text)
    assert not re.search(r"f32\[[\d,]*256,8192\]", text)


def test_held_expert_layer_compiles(one_chip):
    """Qwen3-235B-A22B's expert layer at one chip's share (8 of 128 experts,
    4096 wide, 1536 per expert), forward and backward over one local step's
    16,384 tokens: the held picks run as the compiler's grouped-product
    kernels, and the dropless buffer (a row for every pick) fits the chip."""
    import dataclasses

    from repro.configs import get_config
    from repro.models.moe import moe_apply, moe_init

    cfg = dataclasses.replace(get_config("qwen3_moe_235b_a22b"), experts_held=8)
    params = jax.eval_shape(lambda k: moe_init(k, cfg, jnp.bfloat16), jax.random.key(0))
    params = jax.tree_util.tree_map(
        lambda s: _spec(one_chip, s.shape, s.dtype), params)

    def loss(p, x):
        y, stats = moe_apply(p, x, cfg)
        return jnp.sum(y.astype(jnp.float32)), stats

    compiled = _compile(jax.grad(loss, argnums=(0, 1), has_aux=True), params,
                        _spec(one_chip, (2, 8192, 4096), jnp.bfloat16))
    text = compiled.as_text()
    assert "ragged-dot" in text and "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
