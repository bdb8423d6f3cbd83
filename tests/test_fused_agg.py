"""The device fold vs the seed sequential accumulation.

The aggregator hot paths — sync/deadline ``weighted_mean`` and the FedBuff
/FedAsync ``accumulate_stream`` — fold on the device through the jitted
``scale``/``add`` pair of ``repro.core.fold`` where ``fold.device_fold``
says so. The scale and the add are separate XLA computations, so nothing
FMA-contracts and the device result must be **bit-identical** to the
per-client ``tree_map`` loop. These tests lock that equality, at the unit
level and through seeded jobs, forcing a path by patching
``fold.device_fold``.
"""
import jax
import numpy as np
import pytest

from repro.core import fold
from repro.core.expansion import JobSpec
from repro.core.roles import weighted_mean
from repro.core.runtime import RuntimePolicy, run_job
from repro.core.tag import DatasetSpec
from repro.core.topologies import classical_fl
from repro.fl.strategies import get_strategy
from repro.transport.conformance import SeededSGDTrainer


def _tree_bytes(tree):
    return b"|".join(
        np.asarray(leaf).tobytes() for leaf in jax.tree_util.tree_leaves(tree)
    )


def _updates(C, rng, shapes=((128, 130), (130,))):
    out = []
    for _ in range(C):
        tree = {
            f"l{i}": rng.normal(size=s).astype(np.float32)
            for i, s in enumerate(shapes)
        }
        out.append((tree, float(rng.integers(1, 40))))
    return out


@pytest.fixture
def force_fold(monkeypatch):
    """``force_fold(on)``: every fold after it takes the device path (on)
    or the host path; the jobs' threads see the patch too."""
    return lambda on: monkeypatch.setattr(fold, "device_fold", lambda tree: on)


def _seed_weighted_mean(updates):
    """The pre-fused-path accumulation, verbatim: sequential scaled adds,
    then one divide by the Python-float total."""
    total = 0.0
    acc = None
    for weights, n in updates:
        total += n
        scaled = jax.tree_util.tree_map(lambda x: np.asarray(x) * n, weights)
        acc = scaled if acc is None else jax.tree_util.tree_map(np.add, acc, scaled)
    return jax.tree_util.tree_map(lambda x: x / total, acc), total


class TestWeightedMeanBitEquality:
    @pytest.mark.parametrize("C", [2, 3, 7, 12])
    def test_fused_reproduces_seed_tree_map(self, C):
        rng = np.random.default_rng(C)
        updates = _updates(C, rng)
        fused, tf = weighted_mean(updates, fused=True)
        seed, ts = _seed_weighted_mean(updates)
        assert tf == ts
        assert _tree_bytes(fused) == _tree_bytes(seed)

    def test_sequential_path_is_the_seed_path(self):
        rng = np.random.default_rng(0)
        updates = _updates(4, rng, shapes=((16, 4), (4,)))
        seq, _ = weighted_mean(updates, fused=False)
        seed, _ = _seed_weighted_mean(updates)
        assert _tree_bytes(seq) == _tree_bytes(seed)

    def test_auto_dispatch_never_changes_bits(self):
        rng = np.random.default_rng(1)
        updates = _updates(5, rng)
        auto, _ = weighted_mean(updates)
        forced, _ = weighted_mean(updates, fused=True)
        assert _tree_bytes(auto) == _tree_bytes(forced)

    def test_signed_zero_columns_stay_bit_identical(self):
        """An all-(-0.0) element must keep its sign through the device fold
        (a zeros-seeded accumulator would flip it to +0.0): the fold starts
        from the first scaled update."""
        updates = [
            ({"w": np.array([-0.0, 5.0], np.float32)}, 1.0),
            ({"w": np.array([-0.0, 3.0], np.float32)}, 1.0),
        ]
        fused, _ = weighted_mean(updates, fused=True)
        seed, _ = _seed_weighted_mean(updates)
        assert _tree_bytes(fused) == _tree_bytes(seed)

    def test_mismatched_treedefs_fall_back_to_sequential_error(self):
        """Clients whose trees differ in *structure* (not just shape) must
        never be silently folded under the first client's keys — the device
        path rejects them with the host path's ValueError."""
        a = {"w1": np.ones((128, 130), np.float32)}
        b = {"w2": np.ones((128, 130), np.float32)}
        with pytest.raises(ValueError):
            weighted_mean([(a, 1.0), (b, 1.0)], fused=True)

    def test_ragged_clients_fall_back_gracefully(self):
        """Ragged updates are refused with the host path's ValueError even
        when the device fold is forced."""
        a = {"w": np.ones((4, 4), np.float32)}
        b = {"w": np.ones((2, 2), np.float32)}
        with pytest.raises(ValueError):
            # the seed loop itself cannot add ragged trees; the device fold
            # must refuse them before its jitted add, as numpy does
            weighted_mean([(a, 1.0), (b, 1.0)], fused=True)


class TestFedBuffFlushBitEquality:
    """``accumulate_stream`` on the device path reproduces the incremental
    ``accumulate`` chain byte for byte, buffer after buffer."""

    @staticmethod
    def _streamed(strat, state, deltas, staleness):
        for d, s in zip(deltas, staleness):
            state = strat.accumulate_stream(state, d, s)
        return state

    @pytest.mark.parametrize(
        "name,kwargs",
        [
            ("fedbuff", dict(buffer_size=4, server_lr=1.0, staleness_exp=0.5)),
            ("fedbuff", dict(buffer_size=3, server_lr=0.7, staleness_exp=1.0)),
            ("fedasync", dict(alpha=0.6, staleness_exp=0.5)),
        ],
    )
    def test_batched_flush_reproduces_incremental(self, force_fold, name, kwargs):
        force_fold(True)
        rng = np.random.default_rng(3)
        strat = get_strategy(name, **kwargs)
        params = {"w": rng.normal(size=(260, 64)).astype(np.float32)}
        n = kwargs.get("buffer_size", 1)
        deltas = [
            {"w": rng.normal(size=(260, 64)).astype(np.float32)}
            for _ in range(n)
        ]
        stals = [int(rng.integers(0, 4)) for _ in range(n)]
        inc = strat.init(params)
        for d, s in zip(deltas, stals):
            inc = strat.accumulate(inc, d, np.int32(s))
        dev = self._streamed(strat, strat.init(params), deltas, stals)
        assert int(np.asarray(dev["count"])) == int(np.asarray(inc["count"]))
        assert _tree_bytes(dev["acc"]) == _tree_bytes(inc["acc"])
        w_inc, _ = strat.apply(params, None, inc)
        w_dev, _ = strat.apply(params, None, dev)
        assert _tree_bytes(w_inc) == _tree_bytes(w_dev)

    def test_batched_flush_signed_zero_matches_incremental(self, force_fold):
        """Incremental FedBuff normalizes -0.0 via its leading ``0 + w*d``
        add; the device stream must reproduce that, not skip it."""
        force_fold(True)
        strat = get_strategy("fedbuff", buffer_size=2, server_lr=1.0,
                             staleness_exp=0.5)
        params = {"w": np.zeros(2, np.float32)}
        deltas = [
            {"w": np.array([-0.0, 1.0], np.float32)},
            {"w": np.array([-0.0, 2.0], np.float32)},
        ]
        inc = strat.init(params)
        for d in deltas:
            inc = strat.accumulate(inc, d, np.int32(0))
        dev = self._streamed(strat, strat.init(params), deltas, [0, 0])
        assert _tree_bytes(dev["acc"]) == _tree_bytes(inc["acc"])

    def test_nonzero_count_state_falls_back(self, force_fold):
        """A partially-filled state (count > 0) streams on the device into
        the bits of the sequential chain."""
        rng = np.random.default_rng(4)
        strat = get_strategy("fedbuff", buffer_size=3, server_lr=1.0,
                             staleness_exp=0.5)
        params = {"w": rng.normal(size=(300, 60)).astype(np.float32)}
        deltas = [
            {"w": rng.normal(size=(300, 60)).astype(np.float32)}
            for _ in range(3)
        ]
        pre = strat.accumulate(strat.init(params), deltas[0], np.int32(1))
        assert int(np.asarray(pre["count"])) == 1
        inc = pre
        for d in deltas[1:]:
            inc = strat.accumulate(inc, d, np.int32(0))
        force_fold(True)
        dev = self._streamed(strat, pre, deltas[1:], [0, 0])
        assert int(np.asarray(dev["count"])) == 3
        assert _tree_bytes(dev["acc"]) == _tree_bytes(inc["acc"])


class TestSeededJobBitEquality:
    """The device fold through real seeded jobs: forcing the device or the
    host path must never change a single byte of the global model."""

    def _job(self, rounds=3):
        rng = np.random.default_rng(7)
        w0 = {
            "w": (0.01 * rng.normal(size=(32, 10))).astype(np.float32),
            "b": np.zeros((10,), np.float32),
        }
        return JobSpec(
            tag=classical_fl(),
            datasets=tuple(DatasetSpec(name=f"d{i}") for i in range(4)),
            hyperparams={"rounds": rounds, "init_weights": w0},
        )

    def test_sync_job_fused_vs_sequential(self, force_fold):
        results = {}
        for fused in (True, False):
            force_fold(fused)
            res = run_job(
                self._job(), timeout=60,
                program_overrides={"trainer": SeededSGDTrainer},
            )
            assert not res.errors
            results[fused] = res.global_weights()
        assert _tree_bytes(results[True]) == _tree_bytes(results[False])

    def test_deadline_job_fused_vs_sequential(self, force_fold):
        results = {}
        for fused in (True, False):
            force_fold(fused)
            res = run_job(
                self._job(), timeout=60,
                program_overrides={"trainer": SeededSGDTrainer},
                policy=RuntimePolicy(mode="deadline", deadline=50.0, grace=2.0),
            )
            assert not res.errors
            results[fused] = res.global_weights()
        assert _tree_bytes(results[True]) == _tree_bytes(results[False])

    def test_fedbuff_job_fused_vs_sequential(self, force_fold):
        """Single trainer + buffer_size=2: the only deterministic FedBuff
        arrival order (multi-trainer async order is wall-clock reactive),
        so switching the fold's path must reproduce every byte."""
        rng = np.random.default_rng(7)
        w0 = {
            "w": (0.01 * rng.normal(size=(32, 10))).astype(np.float32),
            "b": np.zeros((10,), np.float32),
        }
        job = JobSpec(
            tag=classical_fl(),
            datasets=(DatasetSpec(name="d0"),),
            hyperparams={"rounds": 4, "init_weights": w0},
        )
        results = {}
        for fused in (True, False):
            force_fold(fused)
            res = run_job(
                job, timeout=60,
                program_overrides={"trainer": SeededSGDTrainer},
                policy=RuntimePolicy(mode="async", buffer_size=2, grace=2.0),
            )
            assert not res.errors
            glob = res.program("global-aggregator-0")
            # the buffered apply actually ran (buffer of 2, versions < uploads)
            assert len(glob.staleness_log) >= 2
            results[fused] = res.global_weights()
        assert _tree_bytes(results[True]) == _tree_bytes(results[False])
