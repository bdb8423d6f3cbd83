"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.quant.ops import (
    compress_tree,
    decompress_tree,
    dequantize_flat,
    quantize_flat,
)
from repro.kernels.quant.ref import reference_quantize
from repro.models.attention import (
    attention,
    chunked_attention,
    fused_applies,
    fused_attention,
)


def _qkv(B, S, H, Hkv, D, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (B, S, H, D), dtype),
            jax.random.normal(ks[1], (B, S, Hkv, D), dtype),
            jax.random.normal(ks[2], (B, S, Hkv, D), dtype))


class TestFusedAttention:
    """The fused causal kernel (splash attention, interpret mode) against
    the chunked scan it replaces on the TPU."""

    @pytest.mark.parametrize(
        "B,S,H,Hkv,dtype,atol",
        [
            (2, 256, 4, 2, jnp.float32, 2e-5),     # GQA
            (1, 256, 4, 4, jnp.float32, 2e-5),     # MHA
            (1, 256, 8, 1, jnp.float32, 2e-5),     # MQA
            (1, 384, 4, 2, jnp.float32, 2e-5),     # GQA over 3 x 3 blocks
            (1, 256, 4, 2, jnp.bfloat16, 3e-2),    # bf16 operands
        ],
        ids=["gqa", "mha", "mqa", "gqa-three-blocks", "bf16"],
    )
    def test_forward_matches_chunked(self, B, S, H, Hkv, dtype, atol):
        q, k, v = _qkv(B, S, H, Hkv, 128, dtype)
        out = fused_attention(q, k, v, interpret=True)
        ref = chunked_attention(q, k, v, causal=True)
        assert out.dtype == dtype
        np.testing.assert_allclose(
            out.astype(np.float32), ref.astype(np.float32), atol=atol)

    @pytest.mark.parametrize(
        "B,S,H,Hkv", [(2, 256, 4, 2), (1, 256, 8, 1)], ids=["gqa", "mqa"])
    def test_gradients_match_chunked(self, B, S, H, Hkv):
        q, k, v = _qkv(B, S, H, Hkv, 128, seed=1)
        do = jax.random.normal(jax.random.key(2), q.shape)

        def grads(fn):
            return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * do),
                            argnums=(0, 1, 2))(q, k, v)

        fused = grads(lambda q, k, v: fused_attention(q, k, v, interpret=True))
        for got, want in zip(fused, grads(chunked_attention)):
            np.testing.assert_allclose(got, want, atol=5e-5)

    @pytest.mark.parametrize(
        "S,D,causal,window,q_offset,fused",
        [
            (256, 128, True, 0, 0, True),
            (256, 128, True, 64, 0, False),     # sliding window
            (320, 128, True, 0, 0, False),      # no block of whole lanes tiles it
            (256, 64, True, 0, 0, False),       # head size under a lane row
            (256, 128, False, 0, 0, False),     # bidirectional (encoder)
            (256, 128, True, 0, 128, False),    # queries after a prefix
        ],
        ids=["fused", "window", "no-lane-block", "dh64", "not-causal",
             "q-offset"],
    )
    def test_dispatch(self, S, D, causal, window, q_offset, fused):
        """Only the fused case stages the kernel out (for a TPU); each other
        case is the chunked scan alone, and on the CPU every case computes
        exactly what the chunked scan does."""
        q, k, v = _qkv(1, S, 4, 2, D, seed=3)
        kw = dict(causal=causal, window=window, q_offset=q_offset, q_chunk=128)
        assert fused_applies(q, k, causal=causal, window=window,
                             q_offset=q_offset) is fused
        staged = str(jax.make_jaxpr(lambda q, k, v: attention(q, k, v, **kw))(q, k, v))
        assert ("pallas_call" in staged) is fused
        np.testing.assert_array_equal(
            jax.jit(lambda q, k, v: attention(q, k, v, **kw))(q, k, v),
            jax.jit(lambda q, k, v: chunked_attention(q, k, v, **kw))(q, k, v))


class TestQuantKernel:
    def test_matches_reference(self):
        x = jax.random.normal(jax.random.key(0), (8192,)) * 3
        q, s = quantize_flat(x)
        xp = jnp.pad(x, (0, 0)).reshape(-1, 4096)
        qr, sr = reference_quantize(xp)
        assert bool(jnp.all(q == qr))
        np.testing.assert_allclose(s, sr, rtol=1e-6)

    def test_pallas_kernel_matches_reference_blocks(self):
        """The Pallas quant kernel (interpret mode) vs the jnp reference the
        ops layer dispatches to on CPU: quantized int8 values identical;
        scales within one ulp (the interpreted kernel's constant division
        may be strength-reduced); dequantization of identical inputs is
        bit-identical."""
        from repro.kernels.quant.kernel import dequantize_blocks, quantize_blocks
        from repro.kernels.quant.ref import reference_dequantize

        x = (jax.random.normal(jax.random.key(1), (12, 4096)) * 2.5).astype(
            jnp.float32
        )
        qk, sk = quantize_blocks(x, interpret=True)
        qr, sr = reference_quantize(x)
        assert bool(jnp.all(qk == qr))
        np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-6)
        dk = dequantize_blocks(qr, sr, interpret=True)
        dr = reference_dequantize(qr, sr)
        assert np.asarray(dk).tobytes() == np.asarray(dr).tobytes()

    @settings(max_examples=15, deadline=None)
    @given(
        n=st.integers(10, 9000),
        scale=st.floats(1e-3, 1e3),
    )
    def test_roundtrip_error_bound_property(self, n, scale):
        """|dequant(quant(x)) - x| <= absmax/127/2 + eps per block."""
        x = jax.random.normal(jax.random.key(n), (n,)) * scale
        q, s = quantize_flat(x)
        back = dequantize_flat(q, s, n)
        absmax = float(jnp.max(jnp.abs(x)))
        bound = absmax / 127.0 * 0.5001 + 1e-7
        assert float(jnp.max(jnp.abs(back - x))) <= bound

    def test_compress_tree_roundtrip(self):
        tree = {
            "w": jax.random.normal(jax.random.key(1), (33, 17)),
            "b": jnp.linspace(-2, 2, 11),
        }
        payload, spec = compress_tree(tree)
        assert payload["q"].dtype == jnp.int8
        back = decompress_tree(payload, spec)
        for a, b in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(back)):
            assert a.shape == b.shape
            np.testing.assert_allclose(a, b, atol=float(jnp.max(jnp.abs(a))) / 100)
