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
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.agg.kernel import fold_scaled, weighted_aggregate
from repro.kernels.flash_attention.kernel import flash_attention_bhsd
from repro.kernels.quant.kernel import dequantize_blocks, quantize_blocks

# parameters in one qwen2.5-3b decoder layer
LAYER = 77_076_992
QUANT_ELEMS = 64 * 2**20


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


def test_flash_attention_compiles(one_chip):
    # qwen2.5-3b heads: 16 query heads over 2 KV heads of 128
    B, H, Hkv, S, D = 1, 16, 2, 4096, 128
    compiled = _compile(
        lambda q, k, v: flash_attention_bhsd(
            q, k, v, causal=True, window=0, block_q=128, block_k=128,
            interpret=False, kv_len=S,
        ),
        _spec(one_chip, (B, H, S, D), jnp.bfloat16),
        _spec(one_chip, (B, Hkv, S, D), jnp.bfloat16),
        _spec(one_chip, (B, Hkv, S, D), jnp.bfloat16),
    )
    assert "tpu_custom_call" in compiled.as_text()
