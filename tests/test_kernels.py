"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.agg.ops import aggregate_flat, aggregate_tree
from repro.kernels.agg.ref import reference_aggregate
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


class TestAggKernel:
    def test_against_reference(self):
        d = jax.random.normal(jax.random.key(0), (5, 1000))
        w = jnp.array([1.0, 2.0, 3.0, 4.0, 5.0])
        np.testing.assert_allclose(
            aggregate_flat(d, w), reference_aggregate(d, w), rtol=1e-6
        )

    def test_pallas_kernel_against_reference(self):
        """The actual Pallas matmul kernel (interpret mode), not the CPU
        jnp dispatch path."""
        d = jax.random.normal(jax.random.key(0), (5, 1000))
        w = jnp.array([1.0, 2.0, 3.0, 4.0, 5.0])
        np.testing.assert_allclose(
            aggregate_flat(d, w, interpret=True),
            reference_aggregate(d, w),
            rtol=1e-5, atol=1e-6,
        )

    def test_exact_fold_kernel_is_order_exact(self):
        """The add-only fold kernel (interpret mode) reproduces sequential
        IEEE accumulation bit-for-bit — the property the fused aggregator
        path is built on."""
        rng = np.random.default_rng(0)
        d = rng.normal(size=(6, 1000)).astype(np.float32)
        w = rng.uniform(1.0, 30.0, size=6).astype(np.float32)
        total = 0.0
        acc = None
        for c in range(6):
            scaled = d[c] * float(w[c])
            total += float(w[c])
            acc = scaled if acc is None else np.add(acc, scaled)
        seed = acc / total
        out = np.asarray(
            aggregate_flat(d, w, denom=total, exact=True, interpret=True)
        )
        assert out.tobytes() == seed.tobytes()

    def test_exact_fold_kernel_pads_to_whole_blocks(self):
        """At 64 clients the fold's block is 16,384 wide, so a parameter
        axis one block and 5 elements long runs two grid steps over a
        zero-padded tail; the padding never reaches the result."""
        from repro.kernels.agg.kernel import block_n_for, fold_scaled

        C = 64
        N = block_n_for(C) + 5
        d = np.random.default_rng(1).normal(size=(C, N)).astype(np.float32)
        acc = d[0]
        for c in range(1, C):
            acc = acc + d[c]
        out = np.asarray(fold_scaled(jnp.asarray(d), interpret=True))
        assert out.tobytes() == acc.tobytes()

    @settings(max_examples=15, deadline=None)
    @given(
        C=st.integers(1, 8),
        N=st.sampled_from([17, 256, 1000]),
        wmax=st.floats(0.1, 100),
    )
    def test_weighted_mean_property(self, C, N, wmax):
        ks = jax.random.split(jax.random.key(C * N), 2)
        d = jax.random.normal(ks[0], (C, N))
        w = jax.random.uniform(ks[1], (C,), minval=0.01, maxval=wmax)
        out = np.asarray(aggregate_flat(d, w))
        ref = np.asarray(reference_aggregate(d, w))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
        # the mean lies within the per-element min/max envelope
        assert (out <= np.max(np.asarray(d), 0) + 1e-5).all()
        assert (out >= np.min(np.asarray(d), 0) - 1e-5).all()

    @pytest.mark.parametrize("C", [1, 3, 8, 9, 64, 200])
    def test_block_width_fits_scoped_vmem(self, C):
        """The fold's block shrinks with the client count so that the
        double-buffered f32 (C, block) input block stays within budget."""
        from repro.kernels.agg.kernel import _IN_BLOCK_BUDGET, block_n_for

        block = block_n_for(C)
        rows = -(-C // 8) * 8
        assert block % 128 == 0 and block <= 65_536
        assert 2 * rows * block * 4 <= _IN_BLOCK_BUDGET
        # and it is the widest power of two that does
        assert block == 65_536 or 2 * rows * 2 * block * 4 > _IN_BLOCK_BUDGET

    def test_tree_roundtrip_shapes(self):
        tree = {"a": jnp.ones((4, 3, 5)), "b": jnp.zeros((4, 7))}
        out = aggregate_tree(tree, jnp.ones(4))
        assert out["a"].shape == (3, 5) and out["b"].shape == (7,)


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
