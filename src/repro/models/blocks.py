"""Per-family transformer blocks with a uniform (init, apply) interface.

Kinds
-----
``dense``  pre-norm GQA attention + gated MLP            (llama/glm/gemma/qwen)
``moe``    attention + top-k expert MLP (+shared expert) (qwen3-moe, llama4)
``hymba``  parallel attention heads + SSD (mamba) heads  (hymba)
``mlstm``  matrix-memory LSTM block, expand-2 projection (xlstm)
``slstm``  scalar-memory LSTM block                      (xlstm, every Nth)

``apply(p, x, positions, cache, mode, cfg)`` returns ``(y, new_cache, stats)``
(``stats``: what a MoE layer reports, ``moe.zero_stats()``'s keys; empty
for every other kind):

* mode ``"full"``   — causal self-attention / chunked scan over the whole
  sequence (training forward and prefill). If ``cache`` is not None it is
  filled and returned (prefill); otherwise no cache is materialized.
* mode ``"decode"`` — x is (B, 1, d); the per-layer cache carries the KV ring
  buffer / recurrent state plus the absolute position array.

Attention caches are *ring buffers* of ``window`` slots when the config uses
sliding-window attention (long_500k: O(window) memory per step), otherwise
full-length buffers. Keys are rotated (RoPE) at write time at their absolute
position, so decode never re-rotates the cache.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.spans import ATTN_CORE, ATTN_PROJ, scope
from repro.models import attention as attn
from repro.models.config import ModelConfig
from repro.models.layers import (
    apply_mrope,
    apply_rope,
    dense_apply,
    dense_init,
    mlp_apply,
    mlp_init,
    rmsnorm_apply,
    rmsnorm_init,
)
from repro.models.moe import moe_apply, moe_init
from repro.models.ssd import slstm_scan, ssd_chunked, ssd_decode_step

Tree = Dict[str, jax.Array]


# ===================================================================== #
# attention sub-block (shared by dense / moe / hymba)
# ===================================================================== #
def _rotate(x: jax.Array, positions: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.rope_type == "mrope":
        return apply_mrope(x, positions, cfg.mrope_sections, cfg.rope_theta)
    if cfg.rope_type == "rope":
        return apply_rope(x, positions, cfg.rope_theta)
    return x


def _text_positions(positions: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The scalar position stream used for masking (mrope: temporal)."""
    return positions[0] if cfg.rope_type == "mrope" else positions


def attn_cache_init(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Tree:
    window = cfg.sliding_window
    W = min(window, max_len) if window else max_len
    return {
        "k": jnp.zeros((batch, W, cfg.num_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, W, cfg.num_kv_heads, cfg.head_dim), dtype),
        "pos": jnp.full((W,), -1, jnp.int32),
    }


def _attn_core_full(
    p: Tree,
    h: jax.Array,
    positions: jax.Array,
    cache: Optional[Tree],
    cfg: ModelConfig,
) -> Tuple[jax.Array, Optional[Tree]]:
    """Full-sequence causal attention; optionally fills the cache (prefill)."""
    with scope(ATTN_PROJ):
        q = attn.project_q(p, h, cfg)
        k, v = attn.project_kv(p, h, cfg)
        q = _rotate(q, positions, cfg)
        k = _rotate(k, positions, cfg)
    with scope(ATTN_CORE):
        out = attn.attention(
            q, k, v, causal=True, window=cfg.sliding_window,
            q_chunk=cfg.q_chunk, use_scan=cfg.scan_attn_chunks,
        )
    new_cache = None
    if cache is not None:
        S = h.shape[1]
        W = cache["k"].shape[1]
        tpos = _text_positions(positions, cfg)
        if W >= S:
            new_cache = {
                "k": jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, 0, 0)),
                "v": jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, 0, 0)),
                "pos": cache["pos"].at[:S].set(tpos[0].astype(jnp.int32)),
            }
        else:  # ring buffer smaller than the prefill: keep the last W
            new_cache = {
                "k": k[:, -W:],
                "v": v[:, -W:],
                "pos": tpos[0, -W:].astype(jnp.int32),
            }
    with scope(ATTN_PROJ):
        out = attn.attn_output(p, out, cfg)
    return out, new_cache


def _attn_core_decode(
    p: Tree,
    h: jax.Array,
    positions: jax.Array,
    cache: Tree,
    cfg: ModelConfig,
) -> Tuple[jax.Array, Tree]:
    """One-token attention against the (ring) cache. h: (B, 1, d)."""
    q = attn.project_q(p, h, cfg)
    k, v = attn.project_kv(p, h, cfg)
    q = _rotate(q, positions, cfg)
    k = _rotate(k, positions, cfg)
    tpos = _text_positions(positions, cfg)
    cur = tpos[0, 0].astype(jnp.int32)  # absolute position of the new token
    W = cache["k"].shape[1]
    slot = jnp.mod(cur, W)
    k_cache = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
    pos = cache["pos"].at[slot].set(cur)
    # mask: slots holding positions in (cur - window, cur] (ring semantics)
    valid = (pos >= 0) & (pos <= cur)
    if cfg.sliding_window:
        valid &= pos > cur - cfg.sliding_window
    scale = cfg.head_dim**-0.5
    B = h.shape[0]
    qc = q.reshape(B, 1, cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads, -1)
    scores = attn._grouped_scores(qc, k_cache) * scale  # (B,Hkv,G,1,W) f32
    scores = jnp.where(valid[None, None, None, None, :], scores, attn.NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    # probabilities in the value dtype: a f32 `w` would promote (convert and
    # materialize) the whole bf16 V cache to f32 in the PV einsum
    out = attn._grouped_out(w.astype(v_cache.dtype), v_cache)
    out = out.reshape(B, 1, cfg.q_dim).astype(h.dtype)
    return (
        dense_apply(p["wo"], out),
        {"k": k_cache, "v": v_cache, "pos": pos},
    )


# ===================================================================== #
# SSD (mamba) sub-block — used by hymba's parallel SSM path
# ===================================================================== #
def ssd_init(rng, cfg: ModelConfig, dtype) -> Tree:
    d = cfg.d_model
    d_i = d * cfg.ssm_expand
    H = cfg.ssm_heads or max(1, d_i // 64)
    N = cfg.ssm_state
    k1, k2, k3, k4, k5 = jax.random.split(rng, 5)
    return {
        "in_xz": dense_init(k1, d, 2 * d_i, dtype),  # value path + gate z
        "in_bc": dense_init(k2, d, 2 * H * N, dtype),  # k (B) and q (C)
        "in_dt": dense_init(k3, d, H, dtype),
        "a_log": jnp.zeros((H,), jnp.float32),
        "d_skip": jnp.ones((H,), jnp.float32),
        "out": dense_init(k5, d_i, d, dtype),
    }


def ssd_state_init(cfg: ModelConfig, batch: int, dtype) -> Tree:
    d_i = cfg.d_model * cfg.ssm_expand
    H = cfg.ssm_heads or max(1, d_i // 64)
    P = d_i // H
    return {"state": jnp.zeros((batch, H, cfg.ssm_state, P), jnp.float32)}


def _ssd_project(p: Tree, h: jax.Array, cfg: ModelConfig):
    B, S, d = h.shape
    d_i = d * cfg.ssm_expand
    H = cfg.ssm_heads or max(1, d_i // 64)
    N = cfg.ssm_state
    xz = dense_apply(p["in_xz"], h)
    xv, z = jnp.split(xz, 2, axis=-1)  # (B,S,d_i) each
    bc = dense_apply(p["in_bc"], h).reshape(B, S, H, 2 * N)
    kk, qq = jnp.split(bc, 2, axis=-1)  # (B,S,H,N)
    dt = jax.nn.softplus(
        dense_apply(p["in_dt"], h).astype(jnp.float32)
    )  # (B,S,H) > 0
    a = -jnp.exp(p["a_log"])  # (H,) < 0
    log_decay = a * dt  # (B,S,H) < 0
    v = xv.reshape(B, S, H, d_i // H)
    return qq, kk, v, log_decay, dt, z, xv


def ssd_apply_full(
    p: Tree, h: jax.Array, cache: Optional[Tree], cfg: ModelConfig
) -> Tuple[jax.Array, Optional[Tree]]:
    qq, kk, v, log_decay, dt, z, xv = _ssd_project(p, h, cfg)
    init = cache["state"] if cache is not None else None
    y, final = ssd_chunked(qq, kk, v, log_decay, dt, chunk=cfg.ssd_chunk,
                           initial_state=init)
    B, S, H, P = y.shape
    y = y + xv.reshape(B, S, H, P) * p["d_skip"][None, None, :, None].astype(y.dtype)
    y = y.reshape(B, S, H * P) * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    out = dense_apply(p["out"], y)
    return out, ({"state": final} if cache is not None else None)


def ssd_apply_decode(
    p: Tree, h: jax.Array, cache: Tree, cfg: ModelConfig
) -> Tuple[jax.Array, Tree]:
    qq, kk, v, log_decay, dt, z, xv = _ssd_project(p, h, cfg)
    y, new_state = ssd_decode_step(
        cache["state"], qq[:, 0], kk[:, 0], v[:, 0], log_decay[:, 0], dt[:, 0]
    )
    B, H, P = y.shape
    y = y + xv[:, 0].reshape(B, H, P) * p["d_skip"][None, :, None].astype(y.dtype)
    y = y.reshape(B, 1, H * P) * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    return dense_apply(p["out"], y), {"state": new_state}


# ===================================================================== #
# block kinds
# ===================================================================== #
def block_init(rng, cfg: ModelConfig, kind: str, dtype) -> Tree:
    ks = jax.random.split(rng, 8)
    d = cfg.d_model
    if kind == "dense":
        return {
            "ln1": rmsnorm_init(d, dtype),
            "attn": attn.attn_init(ks[0], cfg, dtype),
            "ln2": rmsnorm_init(d, dtype),
            "mlp": mlp_init(ks[1], d, cfg.d_ff, dtype),
        }
    if kind == "moe":
        p: Tree = {
            "ln1": rmsnorm_init(d, dtype),
            "attn": attn.attn_init(ks[0], cfg, dtype),
            "ln2": rmsnorm_init(d, dtype),
            "moe": moe_init(ks[1], cfg, dtype),
        }
        return p
    if kind == "hymba":
        return {
            "ln1": rmsnorm_init(d, dtype),
            "attn": attn.attn_init(ks[0], cfg, dtype),
            "ssd": ssd_init(ks[1], cfg, dtype),
            "ln_attn": rmsnorm_init(d, dtype),
            "ln_ssm": rmsnorm_init(d, dtype),
            "ln2": rmsnorm_init(d, dtype),
            "mlp": mlp_init(ks[2], d, cfg.d_ff, dtype),
        }
    if kind == "mlstm":
        return {"ln1": rmsnorm_init(d, dtype), "ssd": ssd_init(ks[0], cfg, dtype)}
    if kind == "slstm":
        return {
            "ln1": rmsnorm_init(d, dtype),
            "gates": dense_init(ks[0], d, 4 * d, dtype),
            "out": dense_init(ks[1], d, d, dtype),
        }
    raise ValueError(f"unknown block kind {kind!r}")


def block_cache_init(
    cfg: ModelConfig, kind: str, batch: int, max_len: int, dtype
) -> Tree:
    if kind in ("dense", "moe"):
        return {"attn": attn_cache_init(cfg, batch, max_len, dtype)}
    if kind == "hymba":
        return {
            "attn": attn_cache_init(cfg, batch, max_len, dtype),
            "ssd": ssd_state_init(cfg, batch, dtype),
        }
    if kind == "mlstm":
        return {"ssd": ssd_state_init(cfg, batch, dtype)}
    if kind == "slstm":
        d = cfg.d_model
        zeros = jnp.zeros((batch, d), jnp.float32)
        return {"c": zeros, "n": zeros, "m": jnp.full((batch, d), -1e30, jnp.float32)}
    raise ValueError(f"unknown block kind {kind!r}")


def block_apply(
    p: Tree,
    x: jax.Array,
    positions: jax.Array,
    cache: Optional[Tree],
    mode: str,
    cfg: ModelConfig,
    kind: str,
) -> Tuple[jax.Array, Optional[Tree], jax.Array]:
    """Returns (y, new_cache, stats)."""
    decode = mode == "decode"
    stats: Dict[str, jax.Array] = {}
    new_cache: Optional[Tree] = dict(cache) if cache is not None else None

    if kind in ("dense", "moe"):
        h = rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
        if decode:
            a, ac = _attn_core_decode(p["attn"], h, positions, cache["attn"], cfg)
        else:
            a, ac = _attn_core_full(
                p["attn"], h, positions, cache["attn"] if cache else None, cfg
            )
        if new_cache is not None:
            new_cache["attn"] = ac
        x = x + a
        h = rmsnorm_apply(p["ln2"], x, cfg.norm_eps)
        if kind == "moe":
            m, stats = moe_apply(p["moe"], h, cfg)
        else:
            m = mlp_apply(p["mlp"], h, cfg.activation)
        return x + m, new_cache, stats

    if kind == "hymba":
        h = rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
        if decode:
            a, ac = _attn_core_decode(p["attn"], h, positions, cache["attn"], cfg)
            s, sc = ssd_apply_decode(p["ssd"], h, cache["ssd"], cfg)
        else:
            a, ac = _attn_core_full(
                p["attn"], h, positions, cache["attn"] if cache else None, cfg
            )
            s, sc = ssd_apply_full(
                p["ssd"], h, cache["ssd"] if cache else None, cfg
            )
        if new_cache is not None:
            new_cache["attn"], new_cache["ssd"] = ac, sc
        # paper (Hymba): per-path output norm, averaged fusion
        fused = 0.5 * (
            rmsnorm_apply(p["ln_attn"], a, cfg.norm_eps)
            + rmsnorm_apply(p["ln_ssm"], s, cfg.norm_eps)
        )
        x = x + fused
        h = rmsnorm_apply(p["ln2"], x, cfg.norm_eps)
        return x + mlp_apply(p["mlp"], h, cfg.activation), new_cache, stats

    if kind == "mlstm":
        h = rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
        if decode:
            s, sc = ssd_apply_decode(p["ssd"], h, cache["ssd"], cfg)
        else:
            s, sc = ssd_apply_full(p["ssd"], h, cache["ssd"] if cache else None, cfg)
        if new_cache is not None:
            new_cache["ssd"] = sc
        return x + s, new_cache, stats

    if kind == "slstm":
        h = rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
        gates = dense_apply(p["gates"], h)
        i_g, f_g, z_g, o_g = jnp.split(gates, 4, axis=-1)
        if decode:
            init = (cache["c"], cache["n"], cache["m"])
            hs, (c, n, m) = slstm_scan(i_g, f_g, z_g, o_g, initial=init)
            new_cache = {"c": c, "n": n, "m": m}
        else:
            init = (cache["c"], cache["n"], cache["m"]) if cache else None
            hs, carry = slstm_scan(i_g, f_g, z_g, o_g, initial=init)
            if new_cache is not None:
                new_cache = {"c": carry[0], "n": carry[1], "m": carry[2]}
        return x + dense_apply(p["out"], hs), new_cache, stats

    raise ValueError(f"unknown block kind {kind!r}")


def layer_kinds(cfg: ModelConfig) -> Tuple[str, ...]:
    """The repeating pattern of block kinds (one period of the layer stack)."""
    if cfg.family == "ssm":
        period = cfg.slstm_every or 1
        kinds = ["mlstm"] * period
        if cfg.slstm_every:
            kinds[-1] = "slstm"
        return tuple(kinds)
    if cfg.family == "hybrid":
        return ("hymba",)
    if cfg.is_moe:
        if cfg.moe_every > 1:
            pattern = ["dense"] * cfg.moe_every
            pattern[-1] = "moe"
            return tuple(pattern)
        return ("moe",)
    return ("dense",)
