"""Decoder-only language model assembled from ``repro.models.blocks``.

Layer stacking: the repeating kind pattern (``layer_kinds``) defines a
*period*; parameters are stacked per period-position with a leading
``n_groups`` dim and the stack is driven by ``lax.scan`` (``scan_layers``)
to keep HLO size and compile time bounded on 512-device dry-runs, or by a
python loop (smoke tests, per-layer inspection).

The model-level cache is ``{"len": int32 scalar, "layers"/"groups": ...}``;
decode positions derive from ``len``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.spans import LM_CE, scope
from repro.models.blocks import (
    block_apply,
    block_cache_init,
    block_init,
    layer_kinds,
)
from repro.models.config import ModelConfig
from repro.models.layers import (
    embed_apply,
    embed_init,
    rmsnorm_apply,
    rmsnorm_init,
    unembed_apply,
)
from repro.models.moe import zero_stats
from repro.models.shard_ctx import pin_activation, pin_stash

Tree = Any


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def _n_groups(cfg: ModelConfig) -> int:
    kinds = layer_kinds(cfg)
    assert cfg.num_layers % len(kinds) == 0, (cfg.num_layers, kinds)
    return cfg.num_layers // len(kinds)


# --------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------- #
def init_params(rng, cfg: ModelConfig) -> Tree:
    dtype = _dtype(cfg)
    kinds = layer_kinds(cfg)
    G = _n_groups(cfg)
    k_emb, k_blocks, k_un = jax.random.split(rng, 3)
    params: Dict[str, Tree] = {"embed": embed_init(k_emb, cfg.padded_vocab, cfg.d_model, dtype)}

    if cfg.scan_layers:
        # stack per period-position: each leaf leads with G
        def one_group(g_rng):
            ks = jax.random.split(g_rng, len(kinds))
            return tuple(block_init(ks[j], cfg, kind, dtype) for j, kind in enumerate(kinds))

        g_rngs = jax.random.split(k_blocks, G)
        groups = [one_group(r) for r in g_rngs]
        params["groups"] = tuple(
            jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *(g[j] for g in groups))
            for j in range(len(kinds))
        )
    else:
        ks = jax.random.split(k_blocks, cfg.num_layers)
        params["layers"] = tuple(
            block_init(ks[i], cfg, kinds[i % len(kinds)], dtype)
            for i in range(cfg.num_layers)
        )
    params["ln_f"] = rmsnorm_init(cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        params["unembed"] = embed_init(k_un, cfg.padded_vocab, cfg.d_model, dtype)
    return params


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None) -> Tree:
    dtype = dtype or _dtype(cfg)
    kinds = layer_kinds(cfg)
    G = _n_groups(cfg)
    cache: Dict[str, Tree] = {"len": jnp.zeros((), jnp.int32)}
    if cfg.scan_layers:
        def stack(kind):
            one = block_cache_init(cfg, kind, batch, max_len, dtype)
            return jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (G,) + x.shape), one
            )
        cache["groups"] = tuple(stack(kind) for kind in kinds)
    else:
        cache["layers"] = tuple(
            block_cache_init(cfg, kinds[i % len(kinds)], batch, max_len, dtype)
            for i in range(cfg.num_layers)
        )
    return cache


# --------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------- #
def default_positions(cfg: ModelConfig, batch: int, seq: int, offset=0) -> jax.Array:
    pos = offset + jnp.arange(seq)[None, :].astype(jnp.int32)
    pos = jnp.broadcast_to(pos, (batch, seq))
    if cfg.rope_type == "mrope":
        return jnp.broadcast_to(pos[None], (3, batch, seq))
    return pos


def _add_stats(total: Dict[str, jax.Array], stats: Dict[str, jax.Array]):
    """``total`` with a block's ``stats`` added, key by key."""
    return {k: v + stats[k] if k in stats else v for k, v in total.items()}


def apply_stack(
    params: Tree,
    h: jax.Array,
    positions: jax.Array,
    cache: Optional[Tree],
    mode: str,
    cfg: ModelConfig,
) -> Tuple[jax.Array, Optional[Tree], Dict[str, jax.Array]]:
    """Returns (h, new_cache, stats summed over layers)."""
    kinds = layer_kinds(cfg)
    stats = zero_stats() if cfg.is_moe else {}
    if cfg.scan_layers:
        c_groups = cache["groups"] if cache is not None else None

        def body(carry, xs):
            h, stats = carry
            h = pin_activation(h)  # scan carries lose the batch sharding
            if cache is not None:
                p_slices, c_slices = xs
            else:
                p_slices, c_slices = xs, None
            new_c = []
            for j, kind in enumerate(kinds):
                cj = None if c_slices is None else c_slices[j]
                h, cj_new, s = block_apply(p_slices[j], h, positions, cj, mode, cfg, kind)
                new_c.append(cj_new if cj_new is not None else 0)
                stats = _add_stats(stats, s)
            out = tuple(new_c) if cache is not None else 0
            # carries / remat residuals live in the (sequence-sharded)
            # stash layout between iterations
            return (pin_stash(h), stats), out

        if cfg.remat:
            body = jax.checkpoint(body)
        xs = (params["groups"], c_groups) if cache is not None else params["groups"]
        (h, stats), scanned = jax.lax.scan(body, (h, stats), xs)
        new_cache = None
        if cache is not None:
            new_cache = dict(cache)
            new_cache["groups"] = scanned
        return h, new_cache, stats

    new_layers = []
    for i, p in enumerate(params["layers"]):
        kind = kinds[i % len(kinds)]
        ci = cache["layers"][i] if cache is not None else None
        h, ci_new, s = block_apply(p, h, positions, ci, mode, cfg, kind)
        new_layers.append(ci_new)
        stats = _add_stats(stats, s)
    new_cache = None
    if cache is not None:
        new_cache = dict(cache)
        new_cache["layers"] = tuple(new_layers)
    return h, new_cache, stats


def forward_hidden(
    params: Tree,
    cfg: ModelConfig,
    tokens: Optional[jax.Array] = None,
    embeds: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,
    cache: Optional[Tree] = None,
    mode: str = "full",
) -> Tuple[jax.Array, Optional[Tree], jax.Array]:
    """Returns (final-norm hidden (B,S,d), new_cache, stats)."""
    if embeds is None:
        assert tokens is not None
        h = embed_apply(params["embed"], tokens)
    else:
        h = embeds
    h = pin_activation(h)  # embed gather output defaults to odd shardings
    B, S = h.shape[:2]
    if positions is None:
        offset = cache["len"] if (cache is not None and mode == "decode") else 0
        positions = default_positions(cfg, B, S, offset=offset)
    h, new_cache, stats = apply_stack(params, h, positions, cache, mode, cfg)
    if new_cache is not None:
        new_cache["len"] = (cache["len"] if cache is not None else 0) + S
    h = rmsnorm_apply(params["ln_f"], h, cfg.norm_eps)
    return h, new_cache, stats


def forward(
    params: Tree,
    cfg: ModelConfig,
    tokens: Optional[jax.Array] = None,
    embeds: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,
    cache: Optional[Tree] = None,
    mode: str = "full",
) -> Tuple[jax.Array, Optional[Tree], jax.Array]:
    """Returns (logits (B,S,V) f32, new_cache, stats)."""
    h, new_cache, stats = forward_hidden(
        params, cfg, tokens=tokens, embeds=embeds, positions=positions,
        cache=cache, mode=mode,
    )
    unemb = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed_apply(unemb, h)
    # API boundary: drop the vocab padding rows (cfg.padded_vocab)
    return logits[..., : cfg.vocab_size], new_cache, stats


def chunked_ce(
    h: jax.Array,  # (B, S, d) — hidden states for positions predicting t+1
    unemb: Tree,
    targets: jax.Array,  # (B, S) int32
    vocab_size: int,
    *,
    n_chunks: int = 16,
    use_scan: bool = True,
) -> jax.Array:
    """Mean cross-entropy over the first ``vocab_size`` rows of the
    unembedding without materializing full (B*S, V) f32 logits.

    The rows past ``vocab_size`` pad the table (``ModelConfig.padded_vocab``)
    and are masked out of the softmax, so they take no probability and get
    no gradient. Flattens tokens and scans over ``n_chunks`` blocks, padded
    with rows of weight 0 to a whole number of blocks: each block computes
    (chunk, V) logits, a log-sum-exp and the target gather, keeping one
    block's logits live (the f32 logits of a 1M-token global batch against a
    150k vocab would otherwise be hundreds of TB)."""
    B, S, d = h.shape
    N = B * S
    if not use_scan:
        n_chunks = 1
    pad = -N % n_chunks
    hf = jnp.pad(h.reshape(N, d), ((0, pad), (0, 0)))
    tf = jnp.pad(targets.reshape(N), (0, pad))
    valid = jnp.arange(N + pad) < N
    chunk = (N + pad) // n_chunks

    def chunk_nll(hc, tc, vc):
        logits = unembed_apply(unemb, hc)  # (chunk, V) f32
        real = jnp.arange(logits.shape[-1]) < vocab_size
        logits = jnp.where(real, logits, -jnp.inf)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(vc, lse - picked, 0.0))

    with scope(LM_CE):
        if n_chunks > 1:
            xs = (hf.reshape(n_chunks, chunk, d), tf.reshape(n_chunks, chunk),
                  valid.reshape(n_chunks, chunk))
            # recompute each chunk's logits in the backward instead of
            # stashing (n_chunks, chunk, V) f32 scan residuals
            ckpt_nll = jax.checkpoint(chunk_nll)

            def body(tot, xs):
                return tot + ckpt_nll(*xs), None

            total, _ = jax.lax.scan(body, jnp.float32(0.0), xs)
        else:
            total = chunk_nll(hf, tf, valid)
    return total / N


# --------------------------------------------------------------------- #
# losses / steps
# --------------------------------------------------------------------- #
def lm_loss(
    params: Tree,
    cfg: ModelConfig,
    tokens: jax.Array,
    embeds: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross-entropy (tokens shifted internally) + router aux, and
    the stack's counters (a MoE model's ``moe.COUNTERS``; none otherwise).

    Uses the chunked-CE path (scan) when the config is in deployment mode
    (``scan_attn_chunks``); the dry-run cost program unrolls to one matmul.
    """
    h, _, stats = forward_hidden(
        params, cfg, tokens=tokens, embeds=embeds, positions=positions
    )
    unemb = params["embed"] if cfg.tie_embeddings else params["unembed"]
    loss = chunked_ce(
        h[:, :-1], unemb, tokens[:, 1:], cfg.vocab_size,
        use_scan=cfg.scan_attn_chunks,
    )
    counters = dict(stats)
    aux = counters.pop("router_aux", None)
    if aux is not None:
        loss = loss + cfg.router_aux_weight * aux
    return loss, counters


def decode_step(
    params: Tree,
    cfg: ModelConfig,
    token: jax.Array,
    cache: Tree,
) -> Tuple[jax.Array, Tree]:
    """One serving step: token (B, 1) int32 -> (logits (B,1,V), new cache)."""
    logits, new_cache, _ = forward(
        params, cfg, tokens=token, cache=cache, mode="decode"
    )
    return logits, new_cache
