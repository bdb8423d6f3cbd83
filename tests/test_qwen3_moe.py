"""The Qwen3-MoE decoder (held experts, dropless, q/k norm) against its plain
float32 reference, ``repro.models.reference_qwen3_moe``, on seeded random
weights at a small size on the CPU.

Both sides compute in float32 here, so they differ only in the order of
their sums (chunked attention and a scanned cross-entropy against whole
matrices, grouped expert products against one product per expert): losses
agree to 1e-5 and gradients to 1e-4 relative, far below what a changed
equation moves (a dropped renormalisation or q/k norm moves the loss by
more than 1e-3).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.fl.fedstep import FedStepConfig
from repro.launch.train import make_mesh_for_devices, setup_training
from repro.models import reference_qwen3_moe as ref
from repro.models import transformer
from repro.models.api import build_model
from repro.models.blocks import block_apply
from repro.models.moe import buffer_rungs, moe_apply, moe_init

LOSS_RTOL = 1e-5  # float32 both sides: summation order only
GRAD_RTOL = 1e-4  # relative L2 per leaf, float32 both sides
B, S = 2, 32


def small(**kw):
    """Qwen3-MoE's mechanisms at a CPU size: 16 experts, top 4, this chip
    holding the second share of 4, a vocabulary of 500 (padded to 512)."""
    cfg = dataclasses.replace(
        get_config("qwen3_moe_235b_a22b"), num_layers=2, d_model=64,
        num_heads=4, num_kv_heads=2, head_dim=16, num_experts=16,
        experts_per_token=4, moe_d_ff=32, vocab_size=500, experts_held=4,
        expert_shard=1, param_dtype="float32", q_chunk=8,
        scan_attn_chunks=True, fl_axes=("data",), param_sharding="tp")
    return dataclasses.replace(cfg, **kw)


def _tokens(cfg, seed=1, batch=B):
    return jax.random.randint(jax.random.key(seed), (batch, S), 0, cfg.vocab_size)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.fixture(scope="module")
def case():
    cfg = small()
    bundle = build_model(cfg)
    params = bundle.init(jax.random.key(0))
    tokens = _tokens(cfg)
    (loss, counters), grads = jax.value_and_grad(bundle.loss_fn, has_aux=True)(
        params, {"tokens": tokens}, None)
    rp = ref.params_from_model(params, cfg)
    r_loss, r_stats, r_grads = ref.loss_and_grads(rp, tokens, cfg)
    return dict(cfg=cfg, params=params, tokens=tokens, loss=loss,
                counters=counters, grads=grads, r_loss=r_loss,
                r_stats=r_stats, r_grads=r_grads)


def test_the_configuration_is_the_published_one():
    cfg = get_config("qwen3_moe_235b_a22b")
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim) == (94, 4096, 64, 4, 128)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.moe_d_ff) == (128, 8, 1536)
    assert cfg.qk_norm and not cfg.qkv_bias and not cfg.shared_expert
    assert cfg.router_aux_weight == 0.001 and not cfg.tie_embeddings
    assert (cfg.vocab_size, cfg.rope_theta, cfg.norm_eps) == (151936, 1e6, 1e-6)


def test_one_chips_cut_has_the_counted_parameters():
    """4 layers, 8 of 128 experts held, an eighth of the vocabulary: 222,830,848
    a layer and 1,046,909,952 in all once the padding rows are left out."""
    cfg = dataclasses.replace(get_config("qwen3_moe_235b_a22b"), num_layers=4,
                              experts_held=8, vocab_size=18992)
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.key(0))
    total = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    (layer,) = shapes["groups"]
    assert sum(x.size for x in jax.tree_util.tree_leaves(layer)) == 4 * 222_830_848
    padding = 2 * (cfg.padded_vocab - cfg.vocab_size) * cfg.d_model
    assert total - padding == 1_046_909_952
    assert layer["moe"]["gate"].shape == (4, 8, 4096, 1536)
    assert layer["moe"]["router"]["w"].shape == (4, 4096, 128)


def test_qk_norm_stays_off_for_qwen25():
    """The benchmark's Qwen2.5-3B layer keeps its 77,076,992 elements."""
    cfg = dataclasses.replace(get_config("qwen2_5_3b"), num_layers=1)
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.key(0))
    (layer,) = shapes["groups"]
    assert not cfg.qk_norm and "q_norm" not in layer["attn"]
    assert sum(x.size for x in jax.tree_util.tree_leaves(layer)) == 77_076_992


def test_loss_matches_the_reference(case):
    np.testing.assert_allclose(case["loss"], case["r_loss"], rtol=LOSS_RTOL)


LEAVES = ["embed", "unembed", "norm"] + [
    f"layers.{n}" for n in ("ln1", "ln2", "wq", "wk", "wv", "wo", "q_norm",
                            "k_norm", "router", "gate", "up", "down")]


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradients_match_the_reference_leaf_by_leaf(case, leaf):
    got = ref.params_from_model(case["grads"], case["cfg"])
    want = case["r_grads"]
    if leaf.startswith("layers."):
        name = leaf.split(".")[1]
        pairs = [(g[name], w[name]) for g, w in zip(got["layers"], want["layers"])]
    else:
        pairs = [(got[leaf], want[leaf])]
    for g, w in pairs:
        assert float(jnp.linalg.norm(w)) > 0
        assert _rel(g, w) < GRAD_RTOL


def test_padding_rows_take_no_probability(case):
    """The rows past the vocabulary pad the table: they get no gradient, and
    filling them with large values leaves the loss as it was."""
    cfg, params = case["cfg"], case["params"]
    pad = case["grads"]["unembed"]["emb"][cfg.vocab_size:]
    assert pad.shape[0] == cfg.padded_vocab - cfg.vocab_size > 0
    assert float(jnp.max(jnp.abs(pad))) == 0.0
    loud = dict(params)
    loud["unembed"] = {"emb": params["unembed"]["emb"].at[cfg.vocab_size:].set(30.0)}
    loss, _ = build_model(cfg).loss_fn(loud, {"tokens": case["tokens"]}, None)
    np.testing.assert_allclose(loss, case["loss"], rtol=LOSS_RTOL)


@pytest.mark.parametrize("tokens", [31, 32, 33])
def test_chunked_ce_over_a_vocabulary_not_a_multiple_of_256(tokens):
    """The scanned cross-entropy, padded to whole chunks, against log-softmax
    over the first 500 of 512 rows."""
    rng = jax.random.split(jax.random.key(3), 3)
    h = jax.random.normal(rng[0], (1, tokens, 16))
    emb = jax.random.normal(rng[1], (512, 16))
    t = jax.random.randint(rng[2], (1, tokens), 0, 500)
    got = transformer.chunked_ce(h, {"emb": emb}, t, 500, n_chunks=4)
    logp = jax.nn.log_softmax(h[0] @ emb[:500].T, axis=-1)
    want = -jnp.mean(jnp.take_along_axis(logp, t[0][:, None], axis=-1))
    np.testing.assert_allclose(got, want, rtol=LOSS_RTOL)


def test_held_picks_match_the_reference(case):
    assert int(case["counters"]["moe_held_picks"]) == int(case["r_stats"]["moe_held_picks"])
    assert int(case["counters"]["moe_dropped"]) == 0
    assert 0 < int(case["counters"]["moe_held_picks"]) < 2 * B * S * 4


def test_a_share_holds_the_uncut_layers_experts():
    """Expert e's weights come from the key and e alone, so a share's are
    the uncut layer's rows for its experts."""
    full = moe_init(jax.random.key(5), small(experts_held=0, expert_shard=0), jnp.float32)
    part = moe_init(jax.random.key(5), small(), jnp.float32)
    for name in ("gate", "up", "down"):
        np.testing.assert_array_equal(part[name], full[name][4:8])
    np.testing.assert_array_equal(part["router"]["w"], full["router"]["w"])


def test_the_shares_add_up_to_the_uncut_layer():
    """Each of the four shares of 4 experts computes its part of a decoder
    layer; with attention counted once, the parts add up to the uncut
    reference layer."""
    uncut = small(experts_held=0, expert_shard=0)
    full = build_model(uncut).init(jax.random.key(0))
    layer = jax.tree_util.tree_map(lambda a: a[0], full["groups"][0])
    x = jax.random.normal(jax.random.key(2), (B, S, uncut.d_model))
    positions = transformer.default_positions(uncut, B, S)
    lp = ref.params_from_model(full, uncut)["layers"][0]
    with jax.default_matmul_precision("highest"):
        want, _ = ref.layer(lp, x, uncut)
        h = x + ref.attention(lp, ref.rms_norm(x, lp["ln1"], uncut.norm_eps), uncut)
    total = h
    for shard in range(4):
        cfg = small(expert_shard=shard)
        part = dict(layer, moe=dict(layer["moe"], **{
            n: layer["moe"][n][4 * shard: 4 * shard + 4] for n in ("gate", "up", "down")}))
        y, _, _ = block_apply(part, x, positions, None, "full", cfg, "moe")
        total = total + (y - h)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


def test_no_pick_is_dropped_under_total_imbalance():
    """Every token alike, and a router that ranks the held share first: all
    N·k picks land on the 4 held experts, N on each, and every one is
    computed."""
    cfg = small(expert_shard=0)
    p = moe_init(jax.random.key(0), cfg, jnp.float32)
    v = jax.random.normal(jax.random.key(1), (cfg.d_model,))
    x = jnp.broadcast_to(v, (B, S, cfg.d_model))
    u = v / jnp.linalg.norm(v) ** 2
    p["router"] = {"w": u[:, None] * (16.0 - jnp.arange(16.0))[None, :]}
    y, stats = moe_apply(p, x, cfg)
    n = B * S
    assert int(stats["moe_dropped"]) == 0
    assert int(stats["moe_held_picks"]) == n * cfg.experts_per_token
    assert int(stats["moe_load_max"]) == n
    assert int(stats["moe_rows"]) == n * min(cfg.experts_per_token, cfg.held_experts)
    lp = {"router": p["router"]["w"], "gate": p["gate"], "up": p["up"], "down": p["down"]}
    with jax.default_matmul_precision("highest"):
        top_e, top_w, _ = ref.route(lp, x.reshape(n, -1), cfg)
        want, held = ref.experts(lp, x.reshape(n, -1), top_e, top_w, cfg)
    assert int(held) == n * cfg.experts_per_token
    np.testing.assert_allclose(y.reshape(n, -1), want, rtol=1e-4, atol=1e-5)


def test_the_fl_round_through_the_tag_lowered_step_matches_the_reference():
    """``build_train_step`` on a (data=1, model=1) mesh lowers the classical
    TAG onto ``data`` and runs ``make_fl_train_step`` with FedAvg: one round
    of 2 local SGD steps equals two reference SGD steps, and the step's
    counters are the reference's."""
    cfg = small()
    fed = FedStepConfig(local_steps=2, local_lr=0.1)
    mesh = make_mesh_for_devices(jax.devices()[:1])
    _, setup, params, state, step = setup_training(
        cfg, mesh, fed, jax.random.key(0), strategy_name="fedavg")
    assert setup.client_axes == ("data",) and setup.tag is not None
    tokens = _tokens(cfg, batch=2 * B)
    rp = ref.params_from_model(params, cfg)
    held = 0
    for i in range(2):
        r_loss, st, g = ref.loss_and_grads(rp, tokens[i * B:(i + 1) * B], cfg)
        rp = jax.tree_util.tree_map(lambda w, d: w - 0.1 * d, rp, g)
        held += int(st["moe_held_picks"])
    new, _, metrics = step(params, state, {"tokens": tokens}, jax.random.key(1))
    np.testing.assert_allclose(metrics["loss"], r_loss, rtol=LOSS_RTOL)
    assert int(metrics["moe_held_picks"]) == held and int(metrics["moe_dropped"]) == 0
    got = ref.params_from_model(new, cfg)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(rp)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


def test_rows_past_the_groups_are_never_read(monkeypatch):
    """On the TPU the grouped product leaves the rows past its groups' total
    as the buffer held them; with NaN there the loss and every gradient
    still match the reference."""
    real = jax.lax.ragged_dot

    def garbage_past_groups(lhs, rhs, group_sizes, **kw):
        out = real(lhs, rhs, group_sizes, **kw)
        live = jnp.arange(out.shape[0]) < jnp.sum(group_sizes)
        return jnp.where(live[:, None], out, jnp.nan)

    monkeypatch.setattr(jax.lax, "ragged_dot", garbage_past_groups)
    cfg = small()
    bundle = build_model(cfg)
    params = bundle.init(jax.random.key(0))
    tokens = _tokens(cfg)
    (loss, counters), grads = jax.value_and_grad(bundle.loss_fn, has_aux=True)(
        params, {"tokens": tokens}, None)
    # both layers ran the smaller of the two rungs, (256, 128)
    assert buffer_rungs(B * S, 4, 16, 4) == (256, 128)
    assert int(counters["moe_rows"]) == cfg.num_layers * 128
    r_loss, _, r_grads = ref.loss_and_grads(ref.params_from_model(params, cfg), tokens, cfg)
    np.testing.assert_allclose(loss, r_loss, rtol=LOSS_RTOL)
    got = ref.params_from_model(grads, cfg)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(r_grads)):
        assert bool(jnp.all(jnp.isfinite(g))) and _rel(g, w) < GRAD_RTOL


# --------------------------------------------------------------------------- #
# the sorted buffer's ladder of rungs
# --------------------------------------------------------------------------- #
# 512 tokens, top 4 of 64 experts, the second share of 4 held: a row for
# each of the 2,048 picks that can land here, an even fill of 128
LADDER = dict(num_experts=64, experts_per_token=4, experts_held=4, expert_shard=1)
LADDER_TOKENS = (2, 256)
RUNGS = (2048, 1024, 512, 256)


@pytest.mark.parametrize("shape, rungs", [
    ((16384, 8, 128, 8), (131072, 65536, 32768, 16384)),  # the step cell
    ((512, 4, 64, 4), RUNGS),
    ((64, 4, 16, 4), (256, 128)),
    ((64, 4, 16, 16), (256,)),  # every expert held
    ((16384, 1, 128, 128), (16384,)),
    ((8, 8, 128, 8), (64,)),  # no halving is a whole tile smaller
    ((512, 4, 8, 4), (2048,)),  # the even fill passes a quarter of the rows
    ((96, 4, 64, 4), (384, 256, 128)),  # rounded up to whole tiles
])
def test_the_ladder_follows_the_shapes(shape, rungs):
    """(N, k, E, held) -> rungs: N·min(k, held), then halvings down to the
    last that holds twice the even fill N·k·held/E, in whole row tiles."""
    assert buffer_rungs(*shape) == rungs


def _routed(total, seed=0):
    """A layer of the ``LADDER`` shape, tokens and a router under which
    exactly ``total`` picks land on the held experts (4-7): a token of class
    j (its one-hot direction, plus a little noise) picks j held experts and
    4 - j absent ones, and the classes are spread over the tokens."""
    cfg = small(**LADDER)
    N, d, k = LADDER_TOKENS[0] * LADDER_TOKENS[1], cfg.d_model, cfg.experts_per_token
    p = moe_init(jax.random.key(seed), cfg, jnp.float32)
    w = np.zeros((d, cfg.num_experts), np.float32)
    for j in range(k + 1):
        chosen = [4 + i for i in range(j)] + [8 + i for i in range(k - j)]
        w[j, chosen] = 8.0 - np.arange(k)
    p["router"] = {"w": jnp.asarray(w)}
    full, rest = divmod(total, k)
    cls = np.array([k] * full + [rest] * (rest > 0) + [0] * (N - full - (rest > 0)))
    cls = np.random.default_rng(seed).permutation(cls)
    noise = jax.random.normal(jax.random.key(seed + 1), (N, d))
    x = (jax.nn.one_hot(cls, d) + 0.01 * noise).reshape(*LADDER_TOKENS, d)
    return cfg, p, x


def _layer_and_reference(cfg, p, x):
    """The layer's and the reference's sum(y · c) with its gradients in x,
    the router and the three expert weights, and the layer's counters."""
    n, d = x.shape[0] * x.shape[1], x.shape[-1]
    c = jax.random.normal(jax.random.key(9), (n, d))
    names = ("router", "gate", "up", "down")
    leaves = lambda p: (p["router"]["w"], p["gate"], p["up"], p["down"])  # noqa: E731

    def layer(x, r, gate, up, down):
        q = dict(p, router={"w": r}, gate=gate, up=up, down=down)
        y, stats = moe_apply(q, x, cfg)
        return jnp.sum(y.reshape(n, d) * c), stats

    def reference(x, r, gate, up, down):
        lp = dict(router=r, gate=gate, up=up, down=down)
        with jax.default_matmul_precision("highest"):
            top_e, top_w, _ = ref.route(lp, x.reshape(n, d), cfg)
            y, _ = ref.experts(lp, x.reshape(n, d), top_e, top_w, cfg)
        return jnp.sum(y * c)

    (got, stats), g_got = jax.value_and_grad(layer, argnums=range(5), has_aux=True)(
        x, *leaves(p))
    want, g_want = jax.value_and_grad(reference, argnums=range(5))(x, *leaves(p))
    return got, want, dict(zip(("x",) + names, g_got)), dict(zip(("x",) + names, g_want)), stats


@pytest.mark.parametrize("total, rung", [
    (0, 256), (256, 256), (257, 512), (512, 512), (513, 1024), (1024, 1024),
    (1025, 2048), (2048, 2048)])
def test_each_rung_matches_the_reference_at_its_boundaries(total, rung):
    """Held-pick totals at and just past each rung's rows run on the rung
    that holds them, and the layer's output and gradients equal the
    reference's."""
    assert buffer_rungs(512, 4, 64, 4) == RUNGS
    got, want, g_got, g_want, stats = _layer_and_reference(*_routed(total))
    assert int(stats["moe_held_picks"]) == total
    assert int(stats["moe_rows"]) == rung
    assert int(stats["moe_dropped"]) == 0
    np.testing.assert_allclose(got, want, rtol=LOSS_RTOL, atol=1e-6)
    for name, w in g_want.items():
        if total:
            assert float(jnp.linalg.norm(w)) > 0, name
            assert _rel(g_got[name], w) < GRAD_RTOL, name
        else:  # no pick reached a held expert: nothing gets a gradient
            assert float(jnp.max(jnp.abs(g_got[name]))) == 0.0, name


@pytest.mark.parametrize("total", [200, 700, 2048])
def test_rows_past_the_groups_are_never_read_on_any_rung(monkeypatch, total):
    """NaN past the groups' total on the rungs of 256, 1,024 and 2,048
    rows: the layer's output and gradients still equal the reference's."""
    real = jax.lax.ragged_dot

    def garbage_past_groups(lhs, rhs, group_sizes, **kw):
        out = real(lhs, rhs, group_sizes, **kw)
        live = jnp.arange(out.shape[0]) < jnp.sum(group_sizes)
        return jnp.where(live[:, None], out, jnp.nan)

    monkeypatch.setattr(jax.lax, "ragged_dot", garbage_past_groups)
    got, want, g_got, g_want, stats = _layer_and_reference(*_routed(total, seed=3))
    assert int(stats["moe_rows"]) == {200: 256, 700: 1024, 2048: 2048}[total]
    np.testing.assert_allclose(got, want, rtol=LOSS_RTOL, atol=1e-6)
    for name, w in g_want.items():
        assert bool(jnp.all(jnp.isfinite(g_got[name]))) and _rel(g_got[name], w) < GRAD_RTOL


def _wide_rows(jaxpr, branch, rows, widths):
    """Shapes of the equations in ``jaxpr`` and its inner jaxprs with
    ``rows`` rows (all but the last dimension) at one of ``widths``; of a
    ``cond``, only branch ``branch`` is read."""
    found = []
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            shape = getattr(v.aval, "shape", ())
            if shape and shape[-1] in widths and int(np.prod(shape[:-1])) == rows:
                found.append((eqn.primitive.name, shape))
        if eqn.primitive.name == "cond":
            inner = [eqn.params["branches"][branch]]
        else:
            inner = [q for v in eqn.params.values()
                     for q in (v if isinstance(v, (tuple, list)) else [v])
                     if isinstance(q, (jax.extend.core.ClosedJaxpr, jax.extend.core.Jaxpr))]
        for q in inner:
            found += _wide_rows(getattr(q, "jaxpr", q), branch, rows, widths)
    return found


def test_the_smallest_rung_holds_nothing_of_the_full_buffer():
    """The layer's forward and backward on the smallest rung hold no array
    of N·min(k, held) rows at the model's or the experts' width; on the
    full rung they do."""
    cfg, p, x = _routed(100)

    def loss(p, x):
        y, _ = moe_apply(p, x, cfg)
        return jnp.sum(y)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(p, x).jaxpr
    widths = (cfg.d_model, cfg.moe_d_ff)
    assert _wide_rows(jaxpr, len(RUNGS) - 1, RUNGS[0], widths) == []
    assert _wide_rows(jaxpr, len(RUNGS) - 1, RUNGS[-1], widths)
    assert _wide_rows(jaxpr, 0, RUNGS[0], widths)
