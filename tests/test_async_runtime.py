"""RuntimePolicy: sync/deadline/async execution of the same TAG, plus
straggler/dropout/re-join emulation and the buffered-async server family."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.expansion import JobSpec
from repro.core.roles import Trainer
from repro.core.runtime import RuntimePolicy, run_job
from repro.core.tag import DatasetSpec
from repro.core.topologies import classical_fl
from repro.fl.strategies import get_strategy

W0 = {"w": np.full((8,), 2.0, np.float32), "b": np.zeros((2, 2), np.float32)}


class AddOneTrainer(Trainer):
    def train(self):
        if self.weights is not None:
            self.weights = {
                k: np.asarray(v) + 1.0 for k, v in self.weights.items()
            }


def _job(n_datasets=4, rounds=3):
    return JobSpec(
        tag=classical_fl(),
        datasets=tuple(DatasetSpec(name=f"d{i}") for i in range(n_datasets)),
        hyperparams={"rounds": rounds, "init_weights": W0},
    )


class TestPolicyValidation:
    def test_unknown_mode_rejected(self):
        with pytest.raises(ValueError):
            RuntimePolicy(mode="semi-sync")

    def test_rejoin_before_dropout_rejected(self):
        with pytest.raises(ValueError):
            RuntimePolicy(dropouts={"w": 2.0}, rejoins={"w": 1.0})


class TestSyncEquivalence:
    def test_sync_policy_matches_legacy_bit_for_bit(self):
        """mode='sync' must reproduce the pre-policy runtime exactly: same
        weights, same emulated wire bytes, same error surface."""
        legacy = run_job(
            _job(rounds=2), timeout=60,
            program_overrides={"trainer": AddOneTrainer},
        )
        policy = run_job(
            _job(rounds=2), timeout=60,
            program_overrides={"trainer": AddOneTrainer},
            policy=RuntimePolicy(mode="sync"),
        )
        assert not legacy.errors and not policy.errors
        np.testing.assert_array_equal(
            legacy.global_weights()["w"], policy.global_weights()["w"]
        )
        assert legacy.channel_bytes == policy.channel_bytes
        assert policy.dropped == {} and policy.events == []


class TestSameTagAllModes:
    """Acceptance: one TAG lowers to all three execution policies."""

    @pytest.mark.parametrize(
        "policy",
        [
            RuntimePolicy(mode="sync"),
            RuntimePolicy(mode="deadline", deadline=50.0, grace=2.0),
            RuntimePolicy(mode="async", buffer_size=2, grace=2.0),
        ],
        ids=["sync", "deadline", "async"],
    )
    def test_completes_and_progresses(self, policy):
        res = run_job(
            _job(rounds=3), timeout=60,
            program_overrides={"trainer": AddOneTrainer},
            policy=policy,
        )
        assert not res.errors, res.errors
        # every mode must move the global model off its initialization
        assert float(res.global_weights()["w"][0]) > float(W0["w"][0])


class TestDropout:
    def test_dropout_mid_round_excluded_and_recorded(self):
        pol = RuntimePolicy(
            mode="deadline", deadline=10.0, grace=1.0,
            dropouts={"trainer-2": 0.5},
        )
        res = run_job(
            _job(rounds=3), timeout=60, policy=pol,
            per_worker_hyperparams={"trainer-2": {"compute_time": 1.0}},
        )
        assert not res.errors, res.errors
        assert res.dropped == {"trainer-2": 0.5}
        assert (0.5, "dropout", "trainer-2") in res.events
        glob = res.program("global-aggregator-0")
        assert "trainer-2" not in glob.participation_log[0]["included"]
        # after the dropout the runtime stops expecting the dead worker
        assert "trainer-2" not in glob.participation_log[-1]["included"]
        assert "trainer-2" not in glob.participation_log[-1]["missing"]

    def test_async_job_survives_dropout(self):
        pol = RuntimePolicy(
            mode="async", buffer_size=2, grace=1.5,
            dropouts={"trainer-0": 0.5},
        )
        res = run_job(
            _job(rounds=4), timeout=60, policy=pol,
            per_worker_hyperparams={"trainer-0": {"compute_time": 1.0}},
        )
        assert not res.errors, res.errors
        assert res.dropped == {"trainer-0": 0.5}
        glob = res.program("global-aggregator-0")
        assert glob._version == 4  # server still reached its update target

    def test_on_time_update_from_doomed_worker_still_counts(self):
        """A worker that uploads before the deadline but is scheduled to drop
        before it must still have its update aggregated that round."""
        pol = RuntimePolicy(
            mode="deadline", deadline=2.0, grace=1.5,
            dropouts={"trainer-2": 1.5},
        )
        res = run_job(
            _job(n_datasets=3, rounds=2), timeout=60, policy=pol,
            per_worker_hyperparams={
                f"trainer-{i}": {"compute_time": 1.0} for i in range(3)
            },
        )
        assert not res.errors, res.errors
        glob = res.program("global-aggregator-0")
        assert "trainer-2" in glob.participation_log[0]["included"]
        assert "trainer-2" not in glob.participation_log[1]["included"]

    def test_rejoin_after_dropout(self):
        pol = RuntimePolicy(
            mode="deadline", deadline=10.0, grace=1.0,
            dropouts={"trainer-3": 0.5}, rejoins={"trainer-3": 1.5},
        )
        res = run_job(
            _job(rounds=4), timeout=60, policy=pol,
            per_worker_hyperparams={"trainer-3": {"compute_time": 1.0}},
        )
        assert not res.errors, res.errors
        assert (1.5, "rejoin", "trainer-3") in res.events
        glob = res.program("global-aggregator-0")
        assert "trainer-3" not in glob.participation_log[0]["included"]
        assert "trainer-3" in glob.participation_log[-1]["included"]


class TestStragglerDeadline:
    def test_straggler_past_deadline_excluded(self):
        pol = RuntimePolicy(mode="deadline", deadline=2.0, grace=1.5)
        res = run_job(
            _job(rounds=3), timeout=60, policy=pol,
            per_worker_hyperparams={"trainer-1": {"compute_time": 5.0}},
        )
        assert not res.errors, res.errors
        glob = res.program("global-aggregator-0")
        for entry in glob.participation_log:
            assert entry["excluded"] == ["trainer-1"]
            assert entry["round_time"] == pytest.approx(2.0)

    def test_min_participants_extends_past_deadline(self):
        pol = RuntimePolicy(
            mode="deadline", deadline=2.0, grace=1.5, min_participants=4
        )
        res = run_job(
            _job(rounds=2), timeout=60, policy=pol,
            per_worker_hyperparams={"trainer-1": {"compute_time": 5.0}},
        )
        assert not res.errors, res.errors
        glob = res.program("global-aggregator-0")
        # the floor re-admits the straggler: the round stretches to cover it
        assert "trainer-1" in glob.participation_log[0]["included"]
        assert glob.participation_log[0]["round_time"] >= 5.0

    def test_late_arrival_joins_async_job(self):
        pol = RuntimePolicy(
            mode="async", buffer_size=2, grace=2.0,
            arrivals={"trainer-1": 2.0},
        )
        res = run_job(_job(rounds=3), timeout=60, policy=pol)
        assert not res.errors, res.errors
        assert (2.0, "start", "trainer-1") in res.events


class TestFedBuffReference:
    def test_fedbuff_matches_sequential_reference(self):
        """Strategy-level: staleness-weighted buffered mean against a plain
        numpy reference implementation."""
        s = get_strategy(
            "fedbuff", buffer_size=3, server_lr=0.5, staleness_exp=0.5
        )
        params = {"w": jnp.ones((4,), jnp.float32)}
        state = s.init(params)
        deltas = [1.0, 2.0, 3.0]
        staleness = [0, 1, 2]
        for d, tau in zip(deltas, staleness):
            state = s.accumulate(
                state, {"w": jnp.full((4,), d, jnp.float32)}, jnp.int32(tau)
            )
            assert bool(s.ready(state)) == (tau == 2)
        new, reset = s.apply(params, None, state)
        ref = 1.0 + 0.5 * sum(
            d / (1.0 + t) ** 0.5 for d, t in zip(deltas, staleness)
        ) / 3.0
        np.testing.assert_allclose(np.asarray(new["w"]), ref, rtol=1e-6)
        assert int(reset["count"]) == 0

    def test_async_runtime_matches_sequential_reference(self):
        """End-to-end: one trainer + buffer_size=1 makes the async server a
        deterministic sequential process — AddOne per version with zero
        staleness must land exactly on W0 + rounds."""
        pol = RuntimePolicy(mode="async", buffer_size=1, grace=2.0)
        res = run_job(
            _job(n_datasets=1, rounds=3), timeout=60, policy=pol,
            program_overrides={"trainer": AddOneTrainer},
        )
        assert not res.errors, res.errors
        glob = res.program("global-aggregator-0")
        assert [e["staleness"] for e in glob.staleness_log] == [0, 0, 0]
        np.testing.assert_allclose(
            np.asarray(res.global_weights()["w"]), W0["w"] + 3.0, rtol=1e-6
        )

    def test_fedasync_strategy_applies_immediately(self):
        s = get_strategy("fedasync", alpha=0.5, staleness_exp=1.0)
        params = {"w": jnp.zeros((2,), jnp.float32)}
        state = s.init(params)
        state = s.accumulate(
            state, {"w": jnp.ones((2,), jnp.float32)}, jnp.int32(1)
        )
        assert bool(s.ready(state))
        new, _ = s.apply(params, None, state)
        # alpha * 1/(1+staleness) = 0.5 * 0.5
        np.testing.assert_allclose(np.asarray(new["w"]), 0.25, rtol=1e-6)


class TestDeadlineSelector:
    def test_predicted_stragglers_skipped_then_probed(self):
        from repro.fl.selection import get_selector

        sel = get_selector("deadline", deadline=1.0, probe_every=3)
        clients = ["c0", "c1", "c2"]
        sel.report("c1", 0.0, duration=5.0)  # past deadline
        picked = sel.select(clients, k=2, round_idx=0)
        assert picked == ["c0", "c2"]
        # after probe_every rounds the straggler is probed again
        picked = sel.select(clients, k=3, round_idx=3)
        assert "c1" in picked


class TestFedStepParticipation:
    def test_partial_participation_renormalizes(self):
        import jax
        from repro.core.mesh_lowering import lower_tag_to_mesh
        from repro.fl.fedstep import (
            FedStepConfig,
            init_server_state,
            make_fl_train_step,
        )

        mesh = jax.make_mesh((1,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
        plan = lower_tag_to_mesh(classical_fl(), ("data",))
        strat = get_strategy("fedavg")

        def loss_fn(p, batch, rng):
            return jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2)

        step = make_fl_train_step(
            loss_fn, strat, plan, mesh,
            FedStepConfig(local_steps=1, local_lr=0.05, participation=0.75),
        )
        params = {"w": jnp.zeros((3, 1))}
        state = init_server_state(strat, plan, params)
        rng = jax.random.key(0)
        x = jax.random.normal(rng, (8, 3))
        batch = {"x": x, "y": x @ jnp.array([[1.0], [-2.0], [0.5]])}
        participated = 0.0
        for i in range(30):
            params, state, m = step(
                params, state, batch, jax.random.fold_in(rng, i)
            )
            participated += float(m["participants"])
        # with a single client either it participates (renormalized to the
        # full mean) or the round is a no-op; loss still converges
        assert 0 < participated < 30
        assert float(m["loss"]) < 1.0
