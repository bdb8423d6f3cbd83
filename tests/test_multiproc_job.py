"""End-to-end jobs on the multi-process transport (driver + OS processes).

Marked ``multiproc``: CI runs these in a dedicated job with a hard timeout so
a hung child process can never wedge the main suite. All program classes are
module-level — spawned workers re-import them by qualified name.
"""
import os
import time

import numpy as np
import pytest

from repro.core.expansion import JobSpec
from repro.core.roles import GlobalAggregator, Trainer
from repro.core.runtime import RuntimePolicy, run_job
from repro.core.tag import DatasetSpec
from repro.core.topologies import classical_fl, hierarchical_fl
from repro.launch.spawn import MultiprocLauncher, run_job_multiproc
from repro.transport.conformance import SeededSGDTrainer  # noqa: F401 - spawn target

pytestmark = pytest.mark.multiproc

# shapes match the synthetic classification data SeededSGDTrainer trains on
_RNG = np.random.default_rng(7)
W0 = {
    "w": (0.01 * _RNG.normal(size=(32, 10))).astype(np.float32),
    "b": np.zeros((10,), np.float32),
}


class PlatformProbeTrainer(SeededSGDTrainer):
    """Records the JAX platforms its worker process may start."""

    def train(self):
        import jax

        self.metrics.append({"jax_platforms": jax.config.jax_platforms})
        super().train()


def _classical_job(rounds=3, n_datasets=3):
    tag = classical_fl(
        trainer_program="repro.transport.conformance.SeededSGDTrainer"
    )
    return JobSpec(
        tag=tag,
        datasets=tuple(DatasetSpec(name=f"d{i}") for i in range(n_datasets)),
        hyperparams={"rounds": rounds, "init_weights": W0},
    )


def _assert_trees_byte_identical(a, b):
    assert a is not None and b is not None
    assert sorted(a) == sorted(b)
    for k in a:
        assert np.asarray(a[k]).dtype == np.asarray(b[k]).dtype
        assert np.asarray(a[k]).tobytes() == np.asarray(b[k]).tobytes(), (
            f"leaf {k!r} differs between backends"
        )


class TestByteIdenticalAcrossBackends:
    def test_seeded_sync_fedavg_inproc_vs_multiproc(self):
        """The transport-layer acceptance criterion: same seeded sync job,
        byte-identical global weights and identical wire accounting on the
        threaded inproc runtime vs the real process tree."""
        job = _classical_job()
        res_in = run_job(job, timeout=60)
        assert not res_in.errors, res_in.errors
        res_mp = run_job_multiproc(job, timeout=120)
        assert not res_mp.errors, res_mp.errors
        _assert_trees_byte_identical(
            res_in.global_weights(), res_mp.global_weights()
        )
        assert res_in.channel_bytes == res_mp.channel_bytes
        # training actually happened (weights moved off the init)
        assert not np.array_equal(res_mp.global_weights()["w"], W0["w"])

    def test_hierarchical_sync_job_over_multiproc(self):
        tag = hierarchical_fl(
            groups=("west", "east"),
            dataset_groups={"west": ("d0", "d1"), "east": ("d2", "d3")},
            trainer_program="repro.transport.conformance.SeededSGDTrainer",
        )
        job = JobSpec(
            tag=tag,
            datasets=tuple(DatasetSpec(name=f"d{i}") for i in range(4)),
            hyperparams={"rounds": 2, "init_weights": W0},
        )
        res_in = run_job(job, timeout=60)
        assert not res_in.errors, res_in.errors
        res_mp = run_job_multiproc(job, timeout=120)
        assert not res_mp.errors, res_mp.errors
        _assert_trees_byte_identical(
            res_in.global_weights(), res_mp.global_weights()
        )
        # both tiers moved bytes over the hub
        assert res_mp.channel_bytes["param-channel"] > 0
        assert res_mp.channel_bytes["global-channel"] > 0


class FailingTrainer(Trainer):
    def load_data(self):
        raise RuntimeError("boom: load_data")


class FailingAggregator(GlobalAggregator):
    def initialize(self):
        raise RuntimeError("boom: initialize")


class SleepyTrainer(Trainer):
    def train(self):
        time.sleep(300.0)


class BadPreRunTrainer(Trainer):
    def pre_run(self):
        raise RuntimeError("boom: pre_run")


class HardCrashTrainer(Trainer):
    def pre_run(self):
        os._exit(3)  # dies before the barrier, reporting nothing


class TestFailureHandling:
    def test_worker_errors_marshalled_to_driver(self):
        res = run_job_multiproc(
            _classical_job(rounds=1, n_datasets=2),
            program_overrides={
                "trainer": FailingTrainer,
                "global-aggregator": FailingAggregator,
            },
            timeout=60,
        )
        assert set(res.errors) >= {"trainer-0", "trainer-1", "global-aggregator-0"}
        assert "boom: load_data" in str(res.errors["trainer-0"])
        assert "boom: initialize" in str(res.errors["global-aggregator-0"])

    def test_pre_barrier_failure_breaks_barrier_fast(self):
        """A worker dying before the start barrier aborts it, so healthy
        workers fail fast (BrokenBarrierError) instead of waiting out the
        whole job timeout for a party that will never arrive."""
        t0 = time.monotonic()
        res = run_job_multiproc(
            _classical_job(rounds=1, n_datasets=2),
            program_overrides={"trainer": BadPreRunTrainer},
            timeout=60,
        )
        assert "boom: pre_run" in str(res.errors["trainer-0"])
        assert "global-aggregator-0" in res.errors  # broken barrier, surfaced
        assert time.monotonic() - t0 < 30.0

    def test_hung_child_is_killed_not_wedged(self):
        t0 = time.monotonic()
        res = run_job_multiproc(
            _classical_job(rounds=1, n_datasets=2),
            program_overrides={"trainer": SleepyTrainer},
            timeout=8.0,
        )
        assert "__timeout__" in res.errors
        # the driver reclaimed the process tree well before the sleep ended
        assert time.monotonic() - t0 < 60.0

    def test_unknown_tier_role_rejected_up_front(self):
        """Policy modes now *run* over multiproc (see test_multiproc_policy);
        what is still rejected up front is a tiers entry naming a role the
        TAG does not have — same guard as the threaded runtime."""
        with pytest.raises(KeyError):
            MultiprocLauncher(
                _classical_job(),
                policy=RuntimePolicy(mode="async", tiers={"nope": "async"}),
            )

    def test_hard_crash_without_report_tears_tree_down(
        self, assert_children_reaped
    ):
        """Fast-fail hardening: a worker process dying pre-barrier without
        marshalling anything (os._exit skips the error reporting) must tear
        the whole process tree down promptly — no zombie children, no
        leaked hub — instead of wedging healthy peers on the start barrier
        for the full job timeout."""
        t0 = time.monotonic()
        res = run_job_multiproc(
            _classical_job(rounds=1, n_datasets=2),
            program_overrides={"trainer": HardCrashTrainer},
            timeout=60,
        )
        elapsed = time.monotonic() - t0
        assert elapsed < 30.0, f"fast-fail took {elapsed:.1f}s"
        assert "exited without a result" in str(res.errors["trainer-0"])
        # the healthy peers were reclaimed, not left to time out
        assert "global-aggregator-0" in res.errors
        # no zombie children: the driver reaped the whole tree
        assert_children_reaped()


class TestWorkersLeaveTheDeviceToTheDriver:
    @pytest.mark.parametrize("pool_size", [None, 1])
    def test_spawned_workers_are_pinned_to_cpu(self, monkeypatch, pool_size):
        """The driver process owns the accelerator; every spawned worker
        process (dedicated or pool host) starts pinned to the CPU, whatever
        environment it inherits."""
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        res = run_job_multiproc(
            _classical_job(rounds=1, n_datasets=2), timeout=120,
            program_overrides={"trainer": PlatformProbeTrainer},
            pool_size=pool_size,
        )
        assert not res.errors, res.errors
        seen = [
            m["jax_platforms"]
            for wid, prog in res.programs.items() if wid.startswith("trainer")
            for m in prog.metrics if "jax_platforms" in m
        ]
        assert seen and set(seen) == {"cpu"}
