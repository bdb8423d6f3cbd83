"""The benchmark's plain reference of the Qwen3-MoE decoder at one chip's
expert share (Qwen/Qwen3-235B-A22B, arXiv:2505.09388), and of one FL
client's local round of SGD on it.

It is the benchmark's own copy: it reads the program's parameter tree by
its names and nothing else of the program. The equations, per layer, with
u = RMSNorm(x):

* q = RMSNorm_q(u W_q), k = RMSNorm_k(u W_k) per head over ``head_dim``,
  then RoPE (rotate-half); v = u W_v; causal GQA attention with scale
  head_dim^-1/2; h = x + attn W_o.
* p = softmax(RMSNorm(h) W_r) over every expert; the top k weights
  renormalised to sum to one.
* y = h + sum over the chosen held experts of w_e W_down,e (silu(W_gate,e u')
  * W_up,e u'), u' = RMSNorm(h); picks of absent experts add nothing.

The loss is the mean next-token cross-entropy over the vocabulary slice
plus ``aux_weight`` times the layers' summed load-balancing losses
E sum_e f_e P_e (f_e the share of the layer's picks).

Everything is float32 with every matrix product at
``jax.default_matmul_precision("highest")``. To fit one chip at the
published widths it is computed in blocks, which change no arithmetic
order that matters: layer by layer (each layer's gradient from its input,
kept from the forward pass, and the gradient of its output), attention over
blocks of queries, the held experts one at a time over every token.

The round keeps the configuration's stated arithmetic: parameters in their
own dtype (bfloat16, the router float32), each step ``w - lr * g`` with the
gradient rounded to the parameter's dtype, as the program's SGD rounds it.

Three controls, each a reference that the step must not match:
``compute_dtype`` rounds both operands of every matrix product but the
router's to a lower precision (the nearest below the configuration's
bfloat16, ``float8_e4m3fn``), accumulating in float32 as the step does,
``expert_dtype`` with the held experts' alone rounded, and
``renormalise=False`` leaves the top-k weights as the softmax gave them.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 128  # queries a block of the reference's attention


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The sizes the reference needs, read from a configuration file."""

    d: int
    heads: int
    kv_heads: int
    head_dim: int
    experts: int
    top_k: int
    held: int
    shard: int
    ff: int
    vocab: int
    eps: float
    theta: float
    aux_weight: float

    @classmethod
    def of(cls, cfg: dict) -> "Sizes":
        return cls(d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
                   kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
                   experts=cfg["num_experts_total"], top_k=cfg["num_experts_per_tok"],
                   held=cfg["num_experts"], shard=cfg["expert_shard"],
                   ff=cfg["moe_intermediate_size"], vocab=cfg["vocab_size"],
                   eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
                   aux_weight=float(cfg["router_aux_loss_coef"]))


def layer_params(params: Any, i: int) -> Dict[str, jax.Array]:
    """Layer ``i`` of the program's scan-stacked tree, flat, as stored."""
    (g,) = params["groups"]
    a, m = g["attn"], g["moe"]
    return {"ln1": g["ln1"]["scale"][i], "ln2": g["ln2"]["scale"][i],
            "wq": a["wq"]["w"][i], "wk": a["wk"]["w"][i], "wv": a["wv"]["w"][i],
            "wo": a["wo"]["w"][i], "q_norm": a["q_norm"]["scale"][i],
            "k_norm": a["k_norm"]["scale"][i], "router": m["router"]["w"][i],
            "gate": m["gate"][i], "up": m["up"][i], "down": m["down"][i]}


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: (B, S, H, Dh) at positions 0..S-1."""
    S, Dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, Dh, 2, dtype=np.float32) / Dh)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : Dh // 2], x[..., Dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(lp, u, z: Sizes, dt=None):
    r = lambda x: as_f32(x, dt)  # noqa: E731 (the control's rounding)
    B, S, _ = u.shape
    H, Hkv, Dh = z.heads, z.kv_heads, z.head_dim
    q = rms_norm((r(u) @ lp["wq"]).reshape(B, S, H, Dh), lp["q_norm"], z.eps)
    k = rms_norm((r(u) @ lp["wk"]).reshape(B, S, Hkv, Dh), lp["k_norm"], z.eps)
    v = (r(u) @ lp["wv"]).reshape(B, S, Hkv, Dh)
    q, k = rope(q, z.theta), rope(k, z.theta)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    c = min(Q_BLOCK, S)
    assert S % c == 0, (S, c)

    @jax.checkpoint
    def block(args):
        qb, lo = args  # (B, c, H, Dh), first query position
        s = jnp.einsum("bqhd,bkhd->bhqk", r(qb), r(k)) / np.sqrt(Dh)
        keep = jnp.arange(S)[None, :] <= lo + jnp.arange(c)[:, None]
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", r(p), r(v))

    qs = jnp.moveaxis(q.reshape(B, S // c, c, H, Dh), 1, 0)
    out = jax.lax.map(block, (qs, jnp.arange(0, S, c)))
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, H * Dh)
    return r(out) @ lp["wo"]


def moe(lp, u2, z: Sizes, expert_dtype=None, renormalise=True, dt=None):
    """(N, d) normed tokens -> (expert output (N, d), aux loss, held picks).
    ``expert_dtype``, ``renormalise=False`` and ``dt`` make the controls."""
    probs = jax.nn.softmax(u2 @ lp["router"], axis=-1)
    top_w, top_e = jax.lax.top_k(probs, z.top_k)
    if renormalise:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    f = jnp.sum(jax.nn.one_hot(top_e, z.experts, dtype=F32), axis=(0, 1)) / top_e.size
    aux = z.experts * jnp.sum(f * jnp.mean(probs, axis=0))
    out = jnp.zeros_like(u2)
    held = jnp.int32(0)
    x = as_f32(u2, dt)
    for j in range(z.held):
        chosen = top_e == z.shard * z.held + j
        w = jnp.sum(jnp.where(chosen, top_w, 0.0), axis=-1)
        gate, up, down = (lp[n][j] for n in ("gate", "up", "down"))
        if expert_dtype is not None:  # the control: experts in a lower precision
            gate, up, down = (t.astype(expert_dtype).astype(F32) for t in (gate, up, down))
        h = as_f32(jax.nn.silu(x @ gate) * (x @ up), dt)
        out = out + w[:, None] * (h @ down)
        held = held + jnp.sum(chosen)
    return out, aux, held


def as_f32(a, dtype=None):
    """``a`` in float32; for the control, first rounded to ``dtype``."""
    if dtype is not None:
        a = a.astype(dtype)
    return a.astype(F32)


def make_layer(z: Sizes, expert_dtype=None, renormalise=True, compute_dtype=None):
    def layer(lp, x):
        # the router keeps the float32 the configuration gives it
        lp = {k: as_f32(a, None if k == "router" else compute_dtype)
              for k, a in lp.items()}
        B, S, d = x.shape
        with jax.default_matmul_precision("highest"):
            h = x + attention(lp, rms_norm(x, lp["ln1"], z.eps), z, compute_dtype)
            u2 = rms_norm(h, lp["ln2"], z.eps).reshape(B * S, d)
            out, aux, held = moe(lp, u2, z, expert_dtype, renormalise, compute_dtype)
        return h + out.reshape(B, S, d), aux, held
    return layer


def make_head(z: Sizes, compute_dtype=None):
    def head(norm, unembed, x, targets):
        """Mean cross-entropy of the last hidden states x (B, S-1, d)."""
        with jax.default_matmul_precision("highest"):
            h = rms_norm(x, norm.astype(F32), z.eps)
            logits = (as_f32(h, compute_dtype)
                      @ as_f32(unembed[: z.vocab], compute_dtype).T)
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
    return head


def sgd(w, g, lr):
    """The program's step: the gradient rounded to the parameter's dtype."""
    return w - lr * g.astype(w.dtype)


class Reference:
    """The reference at sizes ``z``, its blocks compiled once."""

    def __init__(self, z: Sizes, expert_dtype=None, renormalise=True,
                 compute_dtype=None):
        self.z = z
        self.compute_dtype = compute_dtype
        layer = make_layer(z, expert_dtype, renormalise, compute_dtype)
        head = make_head(z, compute_dtype)
        self.layer_fwd = jax.jit(layer)

        def layer_bwd(lp, x, dy):
            _, vjp = jax.vjp(lambda p, xi: layer(p, xi)[:2], lp, x)
            return vjp((dy, jnp.asarray(z.aux_weight, F32)))

        self.layer_bwd = jax.jit(layer_bwd)
        self.head = jax.jit(jax.value_and_grad(head, argnums=(0, 1, 2)))
        self.sgd = jax.jit(lambda t, g, lr: jax.tree_util.tree_map(
            lambda a, b: sgd(a, b, lr), t, g))
        # one client's mean update, in f32, applied in the parameter's dtype
        self.fedavg = jax.jit(lambda p, lp: jax.tree_util.tree_map(
            lambda a, b: a + (b.astype(F32) - a.astype(F32)).astype(a.dtype), p, lp))

    def step(self, params: Any, tokens: jax.Array, lr: float) -> Tuple[Any, float, int]:
        """One SGD step on ``tokens`` (B, S): (new params, loss, held picks).
        ``params`` is the program's tree; the result has its layout."""
        z = self.z
        (g,) = params["groups"]
        n_layers = g["ln1"]["scale"].shape[0]
        emb = params["embed"]["emb"]
        x = jnp.take(as_f32(emb, self.compute_dtype), tokens, axis=0)
        xs: List[jax.Array] = []
        aux_total, held = 0.0, 0
        for i in range(n_layers):
            xs.append(x)
            x, aux, h = self.layer_fwd(layer_params(params, i), x)
            aux_total += float(aux)
            held += int(h)
        ce, (d_norm, d_un, dx) = self.head(params["ln_f"]["scale"],
                                            params["unembed"]["emb"], x[:, :-1],
                                            tokens[:, 1:])
        dx = jnp.pad(dx, ((0, 0), (0, 1), (0, 0)))
        new_layers = [None] * n_layers
        for i in reversed(range(n_layers)):
            lp = layer_params(params, i)
            d_lp, dx = self.layer_bwd(lp, xs[i], dx)
            new_layers[i] = self.sgd(lp, d_lp, lr)
        d_emb = jnp.zeros(emb.shape, F32).at[tokens.reshape(-1)].add(
            dx.reshape(-1, z.d))
        out = jax.tree_util.tree_map(lambda a: a, params)
        out["embed"] = {"emb": self.sgd(emb, d_emb, lr)}
        out["unembed"] = {"emb": self.sgd(params["unembed"]["emb"], d_un, lr)}
        out["ln_f"] = {"scale": self.sgd(params["ln_f"]["scale"], d_norm, lr)}
        out["groups"] = (_stack(new_layers),)
        loss = float(ce) + z.aux_weight * aux_total
        return out, loss, held

    def round(self, params: Any, batches: List[jax.Array], lr: float
              ) -> Tuple[Any, List[float], int]:
        """A client's local round, one step a batch, then FedAvg over the
        one client: (global params after the round, each step's loss, held
        picks over the round)."""
        losses, held, local = [], 0, params
        for tokens in batches:
            local, loss, h = self.step(local, tokens, lr)
            losses.append(loss)
            held += h
        new = self.fedavg(params, local)
        return new, losses, held


def _stack(layers: List[Dict[str, jax.Array]]) -> Any:
    """Flat per-layer dicts back into the program's stacked group."""
    st = {k: jnp.stack([lp[k] for lp in layers]) for k in layers[0]}
    return {"ln1": {"scale": st["ln1"]}, "ln2": {"scale": st["ln2"]},
            "attn": {"wq": {"w": st["wq"]}, "wk": {"w": st["wk"]},
                     "wv": {"w": st["wv"]}, "wo": {"w": st["wo"]},
                     "q_norm": {"scale": st["q_norm"]},
                     "k_norm": {"scale": st["k_norm"]}},
            "moe": {"router": {"w": st["router"]}, "gate": st["gate"],
                    "up": st["up"], "down": st["down"]}}
