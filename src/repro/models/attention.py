"""GQA attention: fused and chunked training/prefill forms, single-token
decode form.

``attention`` is the training/prefill entry. Where the call is plain causal
self-attention over a whole sequence at kernel-aligned sizes, a TPU runs it
as one fused kernel with its own backward (``fused_attention``: Pallas
splash attention, which never writes the scores to HBM and skips the blocks
the causal mask empties). Every other call, and every call on another
platform, runs ``chunked_attention``: query chunks against the whole key
sequence with f32 scores, either unrolled (a python loop, so that every
FLOP is visible to ``cost_analysis``; while-loop bodies are counted once)
or as a ``lax.scan`` (one live score buffer).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.layers import dense_apply, dense_init, rmsnorm_apply, rmsnorm_init

Tree = Dict[str, jax.Array]

NEG_INF = -1e30

# the fused kernel's largest query and key block along the sequence: at
# 2 x 8,192 tokens, 64 query heads over 4 key heads of 128, one v5e ran the
# forward and backward in 193 ms with blocks of 256, 90 ms with 512 and 76
# ms with 1,024
FUSED_BLOCK = 1024
# the kernel's blocks and head size are whole rows of 128 vector lanes
LANES = 128


def attn_init(rng, cfg: ModelConfig, dtype) -> Tree:
    kq, kk, kv, ko = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(kq, cfg.d_model, cfg.q_dim, dtype, bias=cfg.qkv_bias),
        "wk": dense_init(kk, cfg.d_model, cfg.kv_dim, dtype, bias=cfg.qkv_bias),
        "wv": dense_init(kv, cfg.d_model, cfg.kv_dim, dtype, bias=cfg.qkv_bias),
        "wo": dense_init(ko, cfg.q_dim, cfg.d_model, dtype),
    }
    if cfg.qk_norm:  # one scale over head_dim, shared by every head
        p["q_norm"] = rmsnorm_init(cfg.head_dim, dtype)
        p["k_norm"] = rmsnorm_init(cfg.head_dim, dtype)
    return p


def project_q(p: Tree, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    B, S, _ = x.shape
    q = dense_apply(p["wq"], x).reshape(B, S, cfg.num_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm_apply(p["q_norm"], q, cfg.norm_eps)
    return q


def project_kv(p: Tree, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    B, S, _ = x.shape
    k = dense_apply(p["wk"], x).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = dense_apply(p["wv"], x).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        k = rmsnorm_apply(p["k_norm"], k, cfg.norm_eps)
    return k, v


def _grouped_scores(qc: jax.Array, k: jax.Array) -> jax.Array:
    """qc: (B, Cq, Hkv, G, Dh), k: (B, Skv, Hkv, Dh) -> (B, Hkv, G, Cq, Skv)."""
    return jnp.einsum(
        "bqhgd,bkhd->bhgqk", qc, k, preferred_element_type=jnp.float32
    )


def _grouped_out(w: jax.Array, v: jax.Array) -> jax.Array:
    """w: (B, Hkv, G, Cq, Skv), v: (B, Skv, Hkv, Dh) -> (B, Cq, Hkv, G, Dh)."""
    return jnp.einsum(
        "bhgqk,bkhd->bqhgd", w, v, preferred_element_type=jnp.float32
    )


def _attend_chunk(
    qc: jax.Array,  # (B, cq, Hkv, G, Dh)
    k: jax.Array,
    v: jax.Array,
    qpos: jax.Array,  # (cq,)
    *,
    causal: bool,
    window: int,
    scale: float,
) -> jax.Array:
    B, cq, Hkv, G, Dh = qc.shape
    Skv = k.shape[1]
    kpos = jnp.arange(Skv)
    scores = _grouped_scores(qc, k) * scale  # f32 (B,Hkv,G,cq,Skv)
    # additive f32 bias instead of a boolean where-mask: the (cq, Skv) bias
    # broadcasts into the softmax as a fused add — a pred mask materializes
    # at full (B, H, cq, Skv) in XLA CPU buffer assignment (hoisted out of
    # the chunk scan), which wrecks the dry-run memory proof
    bias = jnp.zeros((cq, Skv), jnp.float32)
    if causal:
        bias += jnp.where(kpos[None, :] <= qpos[:, None], 0.0, NEG_INF)
    if window:
        bias += jnp.where(kpos[None, :] > qpos[:, None] - window, 0.0, NEG_INF)
    scores = scores + bias[None, None, None]
    w = jax.nn.softmax(scores, axis=-1)
    # PV matmul reads V in its own dtype (f32 accumulate via the einsum's
    # preferred_element_type); a f32 `w` would upcast-materialize V
    out = _grouped_out(w.astype(v.dtype), v)
    return out.reshape(B, cq, Hkv * G, Dh)


def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_chunk: int = 1024,
    q_offset: int = 0,
    use_scan: bool = False,
) -> jax.Array:
    """Masked attention, blocked over query chunks.

    q: (B, Sq, H, Dh); k, v: (B, Skv, Hkv, Dh). Returns (B, Sq, H, Dh).
    ``window > 0`` restricts attention to the last ``window`` positions
    (sliding-window attention — the sub-quadratic long-context variant).
    ``use_scan`` drives the chunks with ``lax.scan`` (one live score buffer —
    the deployment path) instead of unrolling (exact HLO cost accounting —
    the dry-run cost path).
    """
    B, Sq, H, Dh = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = Dh**-0.5
    chunk = min(q_chunk, Sq)

    if use_scan and Sq % chunk == 0 and Sq > chunk:
        if B > 1:
            # one scan per sequence: with a batch dimension in the chunk's
            # f32 (B, Hkv, G, c, Skv) scores, the softmax a TPU v5e
            # compiles for 2 x 8,192 tokens takes 15x the time of two
            # sequences run one after the other
            return jnp.concatenate([
                chunked_attention(
                    q[b:b + 1], k[b:b + 1], v[b:b + 1], causal=causal,
                    window=window, q_chunk=q_chunk, q_offset=q_offset,
                    use_scan=True)
                for b in range(B)], axis=0)
        nc = Sq // chunk
        qs = jnp.moveaxis(
            q.reshape(B, nc, chunk, Hkv, G, Dh), 1, 0
        )  # (nc, B, c, Hkv, G, Dh)

        # jax.checkpoint: recompute scores/softmax in the backward (flash-
        # style) instead of stashing (nc, B, H, c, Skv) f32 residuals.
        @jax.checkpoint
        def chunk_fn(qc, lo):
            qpos = lo + jnp.arange(chunk)
            return _attend_chunk(
                qc, k, v, qpos, causal=causal, window=window, scale=scale
            ).astype(q.dtype)

        def body(lo, qc):
            # the chunk offset is loop-CARRIED (not an xs constant) so the
            # mask/bias computation cannot be hoisted out of the loop and
            # materialized for every chunk at once
            return lo + chunk, chunk_fn(qc, lo)

        _, outs = jax.lax.scan(
            body, jnp.int32(q_offset), qs
        )  # (nc, B, c, H, Dh)
        return jnp.moveaxis(outs, 0, 1).reshape(B, Sq, H, Dh)

    n_chunks = (Sq + chunk - 1) // chunk
    outs = []
    for i in range(n_chunks):
        lo = i * chunk
        cq = min(chunk, Sq - lo)
        qc = q[:, lo : lo + cq].reshape(B, cq, Hkv, G, Dh)
        qpos = q_offset + lo + jnp.arange(cq)
        out = _attend_chunk(
            qc, k, v, qpos, causal=causal, window=window, scale=scale
        )
        outs.append(out.astype(q.dtype))
    return jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]


def fused_applies(
    q: jax.Array, k: jax.Array, *, causal: bool, window: int, q_offset: int
) -> bool:
    """Whether the fused kernel computes this call: causal self-attention
    over the whole sequence (no window, no offset, as many keys as queries)
    in whole blocks of whole lanes, and a head size of whole lanes."""
    S, Dh = q.shape[1], q.shape[3]
    return (causal and not window and q_offset == 0 and k.shape[1] == S
            and _block(S) % LANES == 0 and Dh % LANES == 0)


def _block(seq: int) -> int:
    """The largest block of at most ``FUSED_BLOCK`` that tiles ``seq``."""
    return math.gcd(seq, FUSED_BLOCK)


def _heads_first(x: jax.Array) -> jax.Array:
    return jnp.swapaxes(x, 1, 2)  # (B, S, H, Dh) <-> (B, H, S, Dh)


def _splash_call(q: jax.Array, k: jax.Array, v: jax.Array, *, interpret: bool):
    S, H = q.shape[1], q.shape[2]
    block = _block(S)
    # two backward kernels: dq accumulates in f32 across the key blocks,
    # where the fused backward kernel sums one dq per key block in q's dtype
    sizes = splash.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=block, block_kv_dq=block,
    )
    # MHA form with fewer key heads: query head h reads key head h // G,
    # the grouping of ``_grouped_scores``
    kernel = splash.make_splash_mha(
        splash.MultiHeadMask([splash.CausalMask((S, S))] * H),
        block_sizes=sizes, head_shards=1, q_seq_shards=1, interpret=interpret,
    )
    out = jax.vmap(kernel)(_heads_first(q), _heads_first(k), _heads_first(v))
    return _heads_first(out)


def fused_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, interpret: bool = False
) -> jax.Array:
    """Causal attention as one fused kernel with its own dq and dkv
    backward kernels (Pallas splash attention), each sequence of the batch
    with its heads leading.

    q: (B, S, H, Dh); k, v: (B, S, Hkv, Dh). Returns (B, S, H, Dh). The
    kernel takes no scale: q is scaled by ``Dh**-0.5`` in f32 and rounded
    once to its dtype. Products take their operands in the inputs' dtype
    (the forward's probabilities and values in f32) and accumulate in f32;
    the softmax is exact. ``interpret`` runs the kernel in the Pallas
    interpreter (CPU tests).

    A Pallas kernel cannot be partitioned by the compiler, so under a mesh
    (the FL step's client ``shard_map`` leaves ``model`` to it) the call is
    manual over every axis the caller left automatic, each device
    attending over every head of its sequences.
    """
    Dh = q.shape[3]
    scaled = (q.astype(jnp.float32) * Dh**-0.5).astype(q.dtype)
    call = functools.partial(_splash_call, interpret=interpret)
    mesh = jax.sharding.get_abstract_mesh()
    auto = tuple(a for a in mesh.axis_names if a not in mesh.manual_axes)
    if auto:
        call = jax.shard_map(call, mesh=mesh, in_specs=P(), out_specs=P(),
                             axis_names=set(auto), check_vma=False)
    return call(scaled, k, v)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_chunk: int = 1024,
    q_offset: int = 0,
    use_scan: bool = False,
) -> jax.Array:
    """Training/prefill attention: ``fused_attention`` on a TPU where
    ``fused_applies``, else ``chunked_attention`` (same arguments).

    The platform is chosen when the program is lowered
    (``jax.lax.platform_dependent``), so a compile for a TPU takes the
    kernel while CPU runs take the chunked path."""
    chunked = functools.partial(
        chunked_attention, causal=causal, window=window, q_chunk=q_chunk,
        q_offset=q_offset, use_scan=use_scan,
    )
    if not fused_applies(q, k, causal=causal, window=window, q_offset=q_offset):
        return chunked(q, k, v)
    return jax.lax.platform_dependent(q, k, v, tpu=fused_attention,
                                      default=chunked)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    *,
    cache_len: jax.Array,
    window: int = 0,
) -> jax.Array:
    """One-token attention against a KV cache.

    q: (B, 1, H, Dh); caches: (B, S, Hkv, Dh); cache_len: () or (B,) — number
    of valid cache positions (the new token's k/v already written).
    """
    B, _, H, Dh = q.shape
    Skv, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    scale = Dh**-0.5
    qc = q.reshape(B, 1, Hkv, G, Dh)
    scores = _grouped_scores(qc, k_cache) * scale  # (B,Hkv,G,1,Skv)
    kpos = jnp.arange(Skv)
    valid = kpos[None, :] < jnp.reshape(cache_len, (-1, 1))  # (B or 1, Skv)
    if window:
        valid &= kpos[None, :] >= jnp.reshape(cache_len, (-1, 1)) - window
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = _grouped_out(w, v_cache)
    return out.reshape(B, 1, H, Dh).astype(q.dtype)


def attn_output(p: Tree, out: jax.Array, cfg: ModelConfig) -> jax.Array:
    B, S = out.shape[:2]
    return dense_apply(p["wo"], out.reshape(B, S, cfg.q_dim))
