"""Cells of kind ``fl_step``: the four readers of the step's per-layer
metrics on hand-worked fixtures, the counts they rest on, the benchmark's
copy of the reference, and a CPU-sized cell run end to end through the
harness: the timed path is correct, and the controls are not.

The CPU-sized cell keeps its parameters in float32, so the step and the
reference differ only in the order of their sums and the real limits of
``step.qwen3moe.ep16.s8192`` apply unchanged.
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_chip_cells import BENCH, write_root
from chipbench import moe_counts, peaks, qwen3_moe_ref
from chipbench.cells import fl_step
from chipbench.harness import run_workload
from chipbench.manifest import Manifest
from chipbench.trace import Event, Trace

PEAK = peaks.PEAKS["TPU v5 lite"]["bf16_flops"]
CELL = "step.qwen3moe.ep16.s8192"

TINY_MOE = {
    "name": "tiny-qwen3-moe", "arch": "qwen3_moe_235b_a22b",
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_experts": 4,
    "num_experts_total": 16, "expert_shard": 1, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "vocab_size": 500, "norm_topk_prob": True,
    "router_aux_loss_coef": 0.001, "tie_word_embeddings": False,
    "rope_theta": 1000000.0, "rms_norm_eps": 1e-06, "attention_bias": False,
    "torch_dtype": "float32", "server_strategy": "fedavg", "local_lr": 0.01,
    "program": {"scan_attn_chunks": True, "q_chunk": 16, "remat": True},
}
TINY_TRAFFIC = {"kind": "fl_step", "clients": 1, "local_steps": 2,
                "seqs_per_step": 2, "seq_len": 64, "zipf_s": 1.0}


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])


def real_limits():
    return Manifest().cell(CELL).limits


@pytest.fixture
def step_root(tmp_path):
    return write_root(tmp_path, {
        "step.tiny": (TINY_MOE, TINY_TRAFFIC, real_limits(), 1)})


def run(root, trace=False, seed=2**33 + 7):
    return run_workload(Manifest(root), "step.tiny", seed, 0.2, trace,
                        jax.devices(), time.perf_counter())


# ----------------------------------------------------------------------- #
# the readers, on hand-worked fixtures
# ----------------------------------------------------------------------- #
@dataclasses.dataclass
class FakeRun:
    counters: dict
    metrics: dict = dataclasses.field(default_factory=dict)
    device_kind: str = "TPU v5 lite"


SCOPED = {"attn/proj": 0.3, "attn/core": 2.7, "moe/route": 0.01,
          "moe/dispatch": 0.06, "moe/combine": 0.13, "moe/experts": 0.5,
          "lm/ce": 0.06}
HAND = {
    # 3 rounds: (0.3 + 2.7) s / 3 = 1,000 ms a round
    "attn_ms": (FakeRun({"rounds": 3, "scope_device_s": SCOPED}), 1000.0),
    # (0.01 + 0.06 + 0.13) s / 2 rounds = 100 ms a round
    "moe_dispatch_ms": (FakeRun({"rounds": 2, "scope_device_s": SCOPED}), 100.0),
    # 1e6 picks x 98.5e6 operations over 0.5 s at 197e12: 100 x 98.5e12 / 98.5e12
    "moe_expert_roofline": (FakeRun({"rounds": 2, "scope_device_s": SCOPED,
                                     "held_picks": 10**6,
                                     "expert_pick_flops": PEAK / 2e6}), 100.0),
    # 197e12 operations a round in 4 s at 197e12 a second: 25%
    "step_mfu": (FakeRun({"rounds": 2, "round_flops": PEAK},
                         metrics={"round_s": 4.0}), 25.0),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_each_step_reader_gives_the_hand_worked_value(name):
    fake, want = HAND[name]
    assert Manifest().reader(name)(fake) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("name", ["attn_ms", "moe_dispatch_ms", "moe_expert_roofline"])
def test_each_scope_reader_finds_nothing_without_its_scopes(name):
    read = Manifest().reader(name)
    assert read(FakeRun({"rounds": 2})) is None
    assert read(FakeRun({"rounds": 2, "scope_device_s": {"lm/ce": 0.1}})) is None


def test_step_mfu_finds_nothing_without_the_step_count():
    read = Manifest().reader("step_mfu")
    assert read(FakeRun({"rounds": 2}, metrics={"round_s": 4.0})) is None
    assert read(FakeRun({"round_flops": PEAK})) is None


# ----------------------------------------------------------------------- #
# counts and scopes
# ----------------------------------------------------------------------- #
def test_a_round_of_the_cell_counts_its_tokens_at_half_a_held_pick_each():
    m = Manifest().cell(CELL)
    cfg, tr = m.config, m.traffic
    assert moe_counts.round_tokens(tr) == 32768
    assert moe_counts.expert_pick_flops(cfg) == 2 * 3 * 4096 * 1536
    per_token = moe_counts.token_flops(cfg, tr["seq_len"])
    held = 0.5 * moe_counts.expert_pick_flops(cfg) * cfg["num_hidden_layers"]
    cfg0 = dict(cfg, num_experts=0)
    assert per_token - moe_counts.token_flops(cfg0, tr["seq_len"]) == pytest.approx(held)
    # about 4 GFLOP of model work a token, 130 TFLOP a round, trained
    assert 3.5e9 < per_token * moe_counts.TRAIN < 4.5e9
    assert 1.1e14 < moe_counts.round_flops(cfg, tr) < 1.5e14


@pytest.mark.parametrize("name,scope", [
    ("jit(step)/jvp(attn/core)/dot_general", "attn/core"),
    ("transpose(jvp(lm/ce))/reduce_max", "lm/ce"),
    ("jit(step)/moe/experts/ragged_dot", "moe/experts"),
    ("jit(step)/attn/projection", None),
    ("jit(step)/optimizer/add", None),
])
def test_an_op_name_gives_its_scope(name, scope):
    assert fl_step.scope_in(name) == scope


HLO = """HloModule jit_step, entry_computation_layout={()}

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %e = f32[4]{0} exponential(f32[4]{0} %p), metadata={op_name="jit(step)/attn/core/exp"}
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(f32[4]{0} %a), kind=kLoop, calls=%fused_computation
  %ragged-dot.3 = f32[4]{0} custom-call(f32[4]{0} %fusion.1), custom_call_target="x"
  ROOT %add.2 = f32[4]{0} add(f32[4]{0} %ragged-dot.3, f32[4]{0} %a), metadata={op_name="jit(step)/lm/ce/add"}
}
"""


def test_instructions_take_their_own_their_fusions_or_their_kernels_scope():
    assert fl_step.instruction_scopes(HLO) == {
        "e": "attn/core", "fusion.1": "attn/core", "ragged-dot.3": "moe/experts",
        "add.2": "lm/ce"}
    assert fl_step.module_name(HLO) == "jit_step"


def _ev(name, start, end, line="XLA Ops", plane="/device:TPU:0"):
    return Event(plane, line, name, float(start), float(end - start))


def test_scope_time_is_clipped_to_the_window_and_the_steps_module():
    events = [
        _ev("window", 1000, 9000, line="host", plane="/host:CPU"),
        _ev("jit_step(1)", 2000, 8000, line="XLA Modules"),
        _ev("fusion.1", 1500, 2500),  # half before the module's run
        _ev("ragged-dot.3", 3000, 4000),
        _ev("add.2", 8500, 9500),  # after the module's run
        _ev("copy.9", 5000, 6000),  # no scope
    ]
    trace = Trace.from_events(events)
    got = fl_step.scope_device_s(trace, HLO)
    assert got == pytest.approx({"attn/core": 500e-9, "moe/experts": 1000e-9})
    assert fl_step.scope_device_s(trace, HLO, device=1) is None


def test_tokens_are_fixed_by_the_seed_and_zipf_over_the_slice():
    tr = dict(TINY_TRAFFIC, seq_len=4096)
    a = fl_step.tokens(2**33 + 7, 1, tr, 500)
    assert a.shape == (4, 4096) and a.dtype == np.int32
    assert np.array_equal(a, fl_step.tokens(2**33 + 7, 1, tr, 500))
    assert not np.array_equal(a, fl_step.tokens(2**33 + 7, 2, tr, 500))
    assert not np.array_equal(a, fl_step.tokens(7, 1, tr, 500))
    assert 0 <= a.min() and a.max() < 500
    # the commonest id takes about 1 / H(500) = 15% of the tokens
    share = np.bincount(a.ravel()).max() / a.size
    assert 0.12 < share < 0.18


def _bf16(x):
    return np.asarray(x, np.float32).astype(jnp.bfloat16)


def test_an_update_of_a_few_rounding_steps_is_read_against_the_floor():
    """A norm scale at 1.0 (one unit 2**-7) that the reference moves by 16
    steps of 2**-8 and the step by 15 of them: against the floor of 1,024
    units, 32 x 2**-7 = 0.25, one flip reads 2**-8 / 0.25 = 1/64, not the
    1/4 of its 16-step update."""
    old = _bf16(np.ones((4, 4096)))
    ref = old.copy()
    ref.flat[:16] = _bf16(1 - 2**-8)
    new = ref.copy()
    new.flat[0] = _bf16(1.0)
    assert fl_step.unit_floor(old) == 0.25
    assert fl_step.update_errors({"s": old}, {"s": new}, {"s": ref}) == {
        "['s']": pytest.approx(1 / 64)}
    # a leaf only the step moves reads as far as it moved
    moved = _bf16(np.full((4, 4096), 1 - 2**-8))
    assert fl_step.update_errors({"s": old}, {"s": moved}, {"s": old})["['s']"] == (
        pytest.approx(np.sqrt(4 * 4096) * 2**-8 / 0.25))


def test_a_large_update_is_read_against_itself():
    rng = np.random.default_rng(0)
    old = _bf16(rng.normal(size=(512, 512)) * 0.02)
    ref = _bf16(np.asarray(old, np.float32) + rng.normal(size=old.shape) * 1e-3)
    new = _bf16(np.asarray(ref, np.float32) * 1.0)
    new.flat[:1000] = old.flat[:1000]
    d_ref = np.asarray(ref, np.float32) - np.asarray(old, np.float32)
    d_err = np.asarray(new, np.float32) - np.asarray(ref, np.float32)
    assert np.linalg.norm(d_ref) > 10 * fl_step.unit_floor(old)
    got = fl_step.update_errors({"w": old}, {"w": new}, {"w": ref})["['w']"]
    assert got == pytest.approx(np.linalg.norm(d_err) / np.linalg.norm(d_ref), rel=1e-5)


# ----------------------------------------------------------------------- #
# the benchmark's reference against the program's own
# ----------------------------------------------------------------------- #
def test_the_benchmarks_reference_is_the_programs_reference():
    from repro.models import reference_qwen3_moe as program_ref
    from repro.models.api import build_model

    mc = fl_step.model_config(TINY_MOE)
    params = build_model(mc).init(jax.random.key(3))
    toks = jnp.asarray(fl_step.tokens(5, 0, TINY_TRAFFIC, 500)[:2])
    ref = qwen3_moe_ref.Reference(qwen3_moe_ref.Sizes.of(TINY_MOE))
    _, loss, held = ref.step(params, toks, 0.01)
    want_loss, stats, _ = program_ref.loss_and_grads(
        program_ref.params_from_model(params, mc), toks, mc)
    assert loss == pytest.approx(float(want_loss), rel=1e-5)
    assert held == int(stats["moe_held_picks"])


# ----------------------------------------------------------------------- #
# whole runs of the CPU-sized cell
# ----------------------------------------------------------------------- #
@pytest.mark.parametrize("trace", [False, True])
def test_a_sound_step_run_is_correct(step_root, trace):
    r = run(step_root, trace)
    assert r["correct"], r["checks"]
    assert r["checks"]["dropped"]["value"] == 0
    assert r["attempted"] >= 1
    if trace:
        # the CPU trace holds no TPU plane: the scope readers find nothing
        assert set(r["metrics"]) == {"step_mfu"}
    else:
        assert set(r["metrics"]) == {"round_s", "setup_s"}
    assert list(r)[-1] == "checks"


def test_the_parameters_of_the_cut_are_the_configuration_files():
    m = Manifest().cell(CELL)
    mc = fl_step.model_config(m.config)
    assert (mc.num_layers, mc.num_experts, mc.experts_held, mc.vocab_size) == (
        4, 128, 8, 18992)
    assert mc.qk_norm and mc.router_aux_weight == 0.001


def test_a_configuration_that_states_what_the_program_lacks_is_refused():
    with pytest.raises(ValueError, match="attention_bias|qkv_bias"):
        fl_step.model_config(dict(TINY_MOE, attention_bias=True))


CONTROLS = {"fp8_compute": {"compute_dtype": jnp.float8_e4m3fn},
            "fp8_experts": {"expert_dtype": jnp.float8_e4m3fn},
            "no_renorm": {"renormalise": False}}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_the_controls_fail_their_limits(step_root, monkeypatch, control):
    """The reference in a precision below the step's (its matrix products'
    operands, or its experts alone, rounded to float8_e4m3fn), or without
    the top-k renormalisation, is not the step."""
    real = qwen3_moe_ref.Reference
    kw = CONTROLS[control]
    monkeypatch.setattr(fl_step, "Reference", lambda z: real(z, **kw))
    r = run(step_root)
    assert not r["correct"], r["checks"]


def test_a_dropped_pick_is_not_correct(step_root, monkeypatch):
    real = fl_step.run

    def run_dropping(*a, **kw):
        out = real(*a, **kw)
        out.numbers["dropped"] = 1.0
        return out

    monkeypatch.setattr(fl_step, "run", run_dropping)
    assert not run(step_root)["correct"]


def test_the_cells_limits_give_a_reason_each():
    for name, entry in real_limits().items():
        assert entry["why"] and entry["why"] != "provisional", name
    assert real_limits()["dropped"]["limit"] == 0


def test_the_bench_directory_holds_the_cell_files():
    for path in ("configs/fedavg-step-qwen3-moe-235b-a22b-ep16.json",
                 "traffic/xsilo.s8192.b2x2.json", f"limits/{CELL}.json",
                 "chipbench/cells/fl_step.py"):
        assert (BENCH / path).is_file(), path
