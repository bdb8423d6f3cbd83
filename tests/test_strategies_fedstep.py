"""Server strategies (Table 7) and the on-mesh TAG-lowered fed step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.mesh_lowering import (
    AggregationStage,
    lower_tag_to_mesh,
    stage_reduce_mean,
)
from repro.core.topologies import classical_fl, distributed_fl, hierarchical_fl
from repro.fl.fedstep import FedStepConfig, init_server_state, make_fl_train_step
from repro.fl.privacy import DPConfig
from repro.fl.strategies import get_strategy


def _mesh1():
    return jax.make_mesh((1,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))


PARAMS = {"w": jnp.array([1.0, 2.0]), "b": jnp.zeros((2, 2))}
DELTA = {"w": jnp.array([0.5, -0.5]), "b": jnp.ones((2, 2))}


class TestStrategies:
    def test_fedavg_applies_delta(self):
        s = get_strategy("fedavg")
        new, _ = s.apply(PARAMS, DELTA, s.init(PARAMS))
        np.testing.assert_allclose(new["w"], [1.5, 1.5])

    @pytest.mark.parametrize("name", ["fedadam", "fedadagrad", "fedyogi"])
    def test_adaptive_strategies_descend_quadratic(self, name):
        # server "delta" = -grad of f(w) = ||w||^2/2; strategies should shrink w
        s = get_strategy(name, lr=0.1)
        w = {"w": jnp.array([4.0, -3.0])}
        state = s.init(w)
        for _ in range(60):
            delta = jax.tree_util.tree_map(lambda x: -x, w)  # -grad
            w, state = s.apply(w, delta, state)
        # all adaptive servers descend the quadratic (adagrad's 1/sqrt(sum)
        # step shrinks over time so it is the slowest)
        assert float(jnp.abs(w["w"]).max()) < 0.9 * 4.0

    def test_fedprox_client_regularizer(self):
        s = get_strategy("fedprox", mu=0.1)
        extra = s.client_loss_extra(
            {"w": jnp.array([2.0])}, {"w": jnp.array([0.0])}, ()
        )
        assert float(extra) == pytest.approx(0.5 * 0.1 * 4.0)

    def test_feddyn_state_updates(self):
        s = get_strategy("feddyn", alpha=0.1)
        state = s.init(PARAMS)
        _, new_state = s.apply(PARAMS, DELTA, state)
        assert float(jnp.abs(new_state["h"]["w"]).sum()) > 0

    def test_fedbuff_buffers_then_applies(self):
        s = get_strategy("fedbuff", buffer_size=2, server_lr=1.0)
        state = s.init(PARAMS)
        state = s.accumulate(state, DELTA, jnp.int32(0))
        assert not bool(s.ready(state))
        state = s.accumulate(state, DELTA, jnp.int32(1))
        assert bool(s.ready(state))
        new, state2 = s.apply(PARAMS, None, state)
        assert float(state2["count"]) == 0  # reset
        assert float(new["w"][0]) > float(PARAMS["w"][0])

    @settings(max_examples=20, deadline=None)
    @given(st.lists(st.floats(-5, 5), min_size=2, max_size=6))
    def test_fedavg_identity_property(self, vals):
        """FedAvg with server_lr=1 and delta=d moves params by exactly d."""
        s = get_strategy("fedavg")
        p = {"w": jnp.zeros(len(vals))}
        d = {"w": jnp.array(vals, jnp.float32)}
        new, _ = s.apply(p, d, s.init(p))
        np.testing.assert_allclose(
            np.asarray(new["w"]), np.asarray(vals, np.float32),
            rtol=1e-6, atol=1e-6,
        )


class TestMeshLowering:
    def test_classical_plan_single_stage(self):
        plan = lower_tag_to_mesh(classical_fl(), ("data",))
        assert len(plan.stages) == 1
        assert plan.stages[0].axes == ("data",)

    def test_hierarchical_plan_two_stage(self):
        plan = lower_tag_to_mesh(hierarchical_fl(), ("data", "pod"))
        assert [s.axes for s in plan.stages] == [("data",), ("pod",)]
        assert plan.stages[0].channel == "param-channel"
        assert plan.stages[1].channel == "global-channel"

    def test_distributed_plan(self):
        plan = lower_tag_to_mesh(distributed_fl(), ("data",))
        assert plan.stages[0].channel == "ring-channel"

    def test_wire_dtype_carried(self):
        tag = hierarchical_fl(agg_wire_dtype="int8")
        plan = lower_tag_to_mesh(tag, ("data", "pod"))
        assert plan.stages[1].wire_dtype == "int8"

    @pytest.mark.parametrize("wire", ["f32", "bf16", "int8"])
    def test_stage_reduce_mean_wire_dtypes(self, wire):
        mesh = _mesh1()
        stage = AggregationStage(channel="c", axes=("data",), wire_dtype=wire)
        x = {"w": jnp.array([1.0, -2.0, 3.0])}

        def f(t):
            return stage_reduce_mean(t, stage)

        out = jax.shard_map(
            f, mesh=mesh,
            in_specs=(jax.sharding.PartitionSpec(),),
            out_specs=jax.sharding.PartitionSpec(),
            check_vma=False,
        )(x)
        tol = 0.05 if wire == "int8" else 1e-2
        np.testing.assert_allclose(out["w"], x["w"], atol=tol)


class TestFedStep:
    def _setup(self, wire="f32", dp=None, local_steps=2, strategy="fedavg"):
        mesh = _mesh1()
        tag = classical_fl(wire_dtype=wire)
        plan = lower_tag_to_mesh(tag, ("data",))
        strat = get_strategy(strategy)

        def loss_fn(p, batch, rng):
            pred = batch["x"] @ p["w"]
            return jnp.mean((pred - batch["y"]) ** 2), {}

        step = make_fl_train_step(
            loss_fn, strat, plan, mesh,
            FedStepConfig(local_steps=local_steps, local_lr=0.05, dp=dp),
        )
        params = {"w": jnp.zeros((3, 1))}
        state = init_server_state(strat, plan, params)
        rng = jax.random.key(0)
        k = jax.random.split(rng, 3)
        w_true = jnp.array([[1.0], [-2.0], [0.5]])
        x = jax.random.normal(k[0], (8, 3))
        batch = {"x": x, "y": x @ w_true}
        return step, params, state, batch, rng

    def test_loss_decreases(self):
        step, params, state, batch, rng = self._setup()
        losses = []
        for i in range(20):
            params, state, m = step(params, state, batch, jax.random.fold_in(rng, i))
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0] * 0.5

    @pytest.mark.parametrize("wire", ["bf16", "int8"])
    def test_wire_dtypes_still_converge(self, wire):
        step, params, state, batch, rng = self._setup(wire=wire)
        for i in range(20):
            params, state, m = step(params, state, batch, jax.random.fold_in(rng, i))
        assert float(m["loss"]) < 1.0

    def test_dp_clip_and_noise_runs(self):
        dp = DPConfig(clip_norm=0.5, noise_multiplier=0.01)
        step, params, state, batch, rng = self._setup(dp=dp)
        params, state, m = step(params, state, batch, rng)
        assert np.isfinite(float(m["loss"]))

    def test_fedadam_server(self):
        step, params, state, batch, rng = self._setup(strategy="fedadam")
        for i in range(25):
            params, state, m = step(params, state, batch, jax.random.fold_in(rng, i))
        assert float(m["loss"]) < 2.0
