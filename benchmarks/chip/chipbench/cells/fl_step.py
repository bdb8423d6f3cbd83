"""Cells of kind ``fl_step``: rounds of the cross-silo FL train step.

The program's normal path: ``repro.launch.train.setup_training`` builds
``build_train_step``'s TAG-lowered step (the classical-FL TAG lowered onto
the client axis ``data`` of a (data=1, model=1) mesh, then
``make_fl_train_step`` with the configuration's server strategy), creates
the parameters from the seed and jits the step with both donated. A run
that finds the degenerate single-client branch instead fails.

A round is one call of the step: ``local_steps`` SGD steps of
``seqs_per_step`` sequences each, then the server update. Its tokens are
drawn for that round from the seed (``tokens``). Set-up builds the model,
compiles the step ahead of time (the window compiles nothing), runs one
warm-up round and keeps a host copy of the parameters the first measured
round starts from. The window is a closed loop of whole rounds until
``--seconds`` have passed; ``round_s`` is the window over its rounds.

``correct`` compares the first round of the window with the plain
reference (``chipbench.qwen3_moe_ref``), run on the same device afterwards
from the same parameters and tokens:

* ``loss_rel_err``: the loss the step returned (its last local step's)
  against the reference's;
* ``update_rel_l2_max``: the round's update, new minus old global
  parameters, leaf by leaf: the largest ‖Δ_step − Δ_ref‖ / ‖Δ_ref‖, with
  ‖Δ_ref‖ at least ``RESOLVED`` rounding steps of the leaf
  (``update_errors``);
* ``route_mismatch_share``: the held picks the step counted over the round
  against the reference's count, as a share of the reference's;
* ``dropped``: the picks the step dropped, over every round it ran.

A traced run also hands the readers the device time of each of the
program's scopes (``repro.core.spans``) in the window: each device
operation of the step is named by the scope in its op name, which the
compiled program keeps as metadata.
"""
from __future__ import annotations

import collections
import dataclasses
import re
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import moe_counts, weights
from chipbench.harness import Outcome, memory_peak, profile
from chipbench.qwen3_moe_ref import Reference, Sizes
from chipbench.trace import Trace, clip, device_ops, measure, op_name

SCOPES = ("attn/proj", "attn/core", "moe/route", "moe/dispatch", "moe/experts",
          "moe/combine", "lm/ce")
# The grouped matrix products the compiler builds for ``jax.lax.ragged_dot``
# are custom calls that carry the kernel's name as their op name, not the
# framework's; the program calls it under ``moe/experts`` alone.
KERNEL_SCOPES = {"ragged-dot": "moe/experts"}
MODULES_LINE = "XLA Modules"


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file: the cut sizes
    set explicitly, every other stated value checked against the program."""
    from repro.configs import get_config

    base = get_config(cfg["arch"])
    mc = dataclasses.replace(
        base,
        num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        num_experts=cfg["num_experts_total"],
        experts_held=cfg["num_experts"],
        expert_shard=cfg["expert_shard"],
        experts_per_token=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"],
        vocab_size=cfg["vocab_size"],
        param_dtype=cfg["torch_dtype"],
        # one silo is one FL client: the client axis is ``data``
        fl_axes=("data",),
        param_sharding="tp",
        **cfg["program"],
    )
    stated = {
        "family": "moe", "moe_every": 1, "shared_expert": False,
        "qkv_bias": cfg["attention_bias"], "qk_norm": True,
        "activation": "swiglu", "rope_type": "rope", "sliding_window": 0,
        "rope_theta": float(cfg["rope_theta"]), "norm_eps": float(cfg["rms_norm_eps"]),
        "tie_embeddings": cfg["tie_word_embeddings"],
        "router_aux_weight": float(cfg["router_aux_loss_coef"]),
    }
    for k, v in stated.items():
        if getattr(mc, k) != v:
            raise ValueError(f"{cfg['arch']}: program has {k}={getattr(mc, k)!r}, "
                             f"the configuration states {v!r}")
    return mc


def tokens(seed: int, round_: int, traffic: dict, vocab: int) -> np.ndarray:
    """Round ``round_``'s token ids, (local_steps · seqs_per_step, seq_len):
    Zipf ranks over the vocabulary, mapped to ids by the seed's permutation."""
    ids = np.random.default_rng([seed, 0x7E]).permutation(vocab).astype(np.int32)
    cdf = np.cumsum(1.0 / np.arange(1, vocab + 1) ** traffic["zipf_s"])
    shape = (traffic["local_steps"] * traffic["seqs_per_step"], traffic["seq_len"])
    u = np.random.default_rng([seed, 0xA11, round_]).random(shape) * cdf[-1]
    return ids[np.minimum(np.searchsorted(cdf, u, side="right"), vocab - 1)]


# ----------------------------------------------------------------------- #
# device operations named by the program's scopes
# ----------------------------------------------------------------------- #
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def scope_in(name: str) -> Optional[str]:
    """The program scope named in an op name: ``.../moe/experts/dot_general``
    or, in a backward pass, ``transpose(jvp(lm/ce))/...``."""
    for s in SCOPES:
        if re.search(r"(?:^|[/(])" + re.escape(s) + r"(?:[/)]|$)", name):
            return s
    return None


def instruction_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> scope, for one compiled module's text. An
    instruction takes the scope of its own op name; a fusion without one,
    the scope most of its fused computation's operations carry; a kernel
    custom call, the scope ``KERNEL_SCOPES`` gives its name."""
    own: Dict[str, Optional[str]] = {}
    calls: Dict[str, List[str]] = {}
    members: Dict[str, List[str]] = collections.defaultdict(list)
    comp = None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = _OP_NAME.search(rest)
        own[name] = scope_in(op.group(1)) if op else None
        calls[name] = _CALLS.findall(rest)
        if comp is not None:
            members[comp].append(name)
    out = {}
    for name, scope in own.items():
        if scope is None:
            votes = collections.Counter(own[i] for c in calls[name]
                                        for i in members.get(c, ()) if own[i])
            scope = votes.most_common(1)[0][0] if votes else None
        if scope is None:
            scope = next((s for k, s in KERNEL_SCOPES.items()
                          if name.startswith(k)), None)
        if scope is not None:
            out[name] = scope
    return out


def module_name(hlo_text: str) -> str:
    return re.match(r"HloModule\s+([\w.\-]+)", hlo_text).group(1)


def scope_device_s(trace: Trace, hlo_text: str, device: int = 0
                   ) -> Optional[Dict[str, float]]:
    """Seconds device ``device`` spent in each scope's operations of the
    step's module within the window; ``None`` where the trace holds no such
    device, and scopes with no operation left out."""
    if device not in trace.devices:
        return None
    names = instruction_scopes(hlo_text)
    module = module_name(hlo_text)
    plane = f"/device:TPU:{device}"
    runs = [(e.start_ns, e.end_ns) for e in trace.events
            if e.plane == plane and e.line == MODULES_LINE
            and e.name.split("(")[0].strip() == module]
    within = clip(runs, [trace.window]) if runs else [trace.window]
    by_scope: Dict[str, list] = collections.defaultdict(list)
    for e in device_ops(trace.events, device):
        scope = names.get(op_name(e.name).split(" ")[0])
        if scope is not None:
            by_scope[scope].append((e.start_ns, e.end_ns))
    out = {s: measure(clip(iv, within)) / 1e9 for s, iv in by_scope.items()}
    return {s: v for s, v in out.items() if v > 0}


# ----------------------------------------------------------------------- #
# the comparison
# ----------------------------------------------------------------------- #
RESOLVED = 1024  # one-unit rounding steps a leaf's update spans at least


def update_errors(old: Any, new: Any, ref: Any) -> Dict[str, float]:
    """Per leaf: ‖(new − old) − (ref − old)‖ over the larger of ‖ref − old‖
    and ``unit_floor(old)``, in float32.

    The floor keeps the ratio meaningful where the reference moves a leaf
    by a few rounding steps alone: a bfloat16 norm scale at 1.0 with a
    dozen elements one unit off reads a single flip of rounding as 1/√12 of
    its update (0.25 and 0.5 on a v5e), which says nothing of the step.
    Against the floor a flip reads 1/√``RESOLVED`` at most, and a leaf that
    only the step moves still reads as large as it moved."""
    out = {}
    flat_old = jax.tree_util.tree_flatten_with_path(old)[0]
    for (path, o), n, r in zip(flat_old, jax.tree_util.tree_leaves(new),
                               jax.tree_util.tree_leaves(ref)):
        floor = unit_floor(o)
        o, n, r = (jnp.asarray(x).astype(jnp.float32) for x in (o, n, r))
        err = float(jnp.linalg.norm(n - r))
        size = max(float(jnp.linalg.norm(r - o)), floor)
        out[jax.tree_util.keystr(path)] = err / size
    return out


def unit_floor(leaf: Any) -> float:
    """The norm of ``RESOLVED`` steps of one unit in the last place of the
    leaf's dtype, at its values (root mean square over the leaf)."""
    leaf = jnp.asarray(leaf)
    _, exp = jnp.frexp(leaf.astype(jnp.float32))
    unit = jnp.ldexp(jnp.float32(1.0), exp - (jnp.finfo(leaf.dtype).nmant + 1))
    return float(jnp.sqrt(RESOLVED * jnp.mean(jnp.square(unit))))


def run(cell, seed, seconds, trace, devices, t0) -> Outcome:
    from repro.fl.fedstep import FedStepConfig
    from repro.launch import sharding as shd
    from repro.launch.train import make_batch, make_mesh_for_devices, setup_training

    cfg, tr = cell.config, cell.traffic
    mc = model_config(cfg)
    if tr["clients"] != 1:
        raise ValueError("fl_step cells run one FL client on one device")
    mesh = make_mesh_for_devices(devices[:1])
    fed = FedStepConfig(local_steps=tr["local_steps"], local_lr=cfg["local_lr"])
    key = weights.seed_key(seed)
    _, setup, params, state, step_fn = setup_training(
        mc, mesh, fed, key, strategy_name=cfg["server_strategy"])
    if setup.client_axes != ("data",) or setup.tag is None:
        raise RuntimeError(f"the step took the single-client branch "
                           f"(client axes {setup.client_axes})")

    def batch(r):
        host = make_batch(mc, tokens(seed, r, tr, mc.vocab_size))
        return jax.device_put(host, shd.batch_shardings(host, mc, mesh))

    rng = lambda r: jax.random.fold_in(key, r)  # noqa: E731
    compiled = step_fn.lower(params, state, batch(0), rng(0)).compile()
    params, state, m = compiled(params, state, batch(0), rng(0))  # warm-up
    all_metrics = [m]
    old = jax.device_get(params)  # what the first measured round starts from
    setup_s = time.perf_counter() - t0

    rounds, snap = 0, None
    with profile(trace) as traced:
        with jax.profiler.TraceAnnotation("window"):
            t_start = time.perf_counter()
            nxt = batch(1)
            while True:
                r = rounds + 1
                params, state, m = compiled(params, state, nxt, rng(r))
                if rounds == 0:  # the compared round's result
                    snap = jax.tree_util.tree_map(jnp.copy, params)
                all_metrics.append(m)
                nxt = batch(r + 1)  # made on the host while the device works
                jax.block_until_ready(m)
                rounds += 1
                if time.perf_counter() - t_start >= seconds:
                    break
            t_end = time.perf_counter()
    peak = memory_peak(devices[:1])
    hlo = compiled.as_text() if trace else None
    new = jax.device_get(snap)
    del params, state, snap, compiled
    host_metrics = [jax.device_get(x) for x in all_metrics]
    first = host_metrics[1]

    ref = Reference(Sizes.of(cfg))
    toks = jnp.asarray(tokens(seed, 1, tr, mc.vocab_size))
    per = tr["seqs_per_step"]
    want, ref_losses, ref_held = ref.round(
        jax.device_put(old, devices[0]),
        [toks[i * per:(i + 1) * per] for i in range(tr["local_steps"])],
        cfg["local_lr"])
    errs = update_errors(old, new, jax.device_get(want))
    held_step = int(first["moe_held_picks"])
    numbers = {
        "loss_rel_err": abs(float(first["loss"]) - ref_losses[-1]) / abs(ref_losses[-1]),
        "update_rel_l2_max": max(errs.values()),
        "route_mismatch_share": abs(held_step - ref_held) / max(ref_held, 1),
        "dropped": float(sum(int(x["moe_dropped"]) for x in host_metrics)),
    }
    window = host_metrics[1:]
    counters: Dict[str, Any] = {
        "rounds": rounds,
        "round_flops": moe_counts.round_flops(cfg, tr),
        "expert_pick_flops": moe_counts.TRAIN * moe_counts.expert_pick_flops(cfg),
        "held_picks": sum(int(x["moe_held_picks"]) for x in window),
        "load_max": sum(int(x["moe_load_max"]) for x in window),
        "update_rel_l2": errs,
        "losses": [float(x["loss"]) for x in window],
    }
    out_trace = traced[0] if traced else None
    if out_trace is not None:
        scopes = scope_device_s(out_trace, hlo)
        if scopes:
            counters["scope_device_s"] = scopes
    return Outcome(
        metrics={"setup_s": setup_s, "round_s": (t_end - t_start) / rounds},
        attempted=rounds, failed=0, numbers=numbers, counters=counters,
        memory_peak_bytes=peak, trace=out_trace)
