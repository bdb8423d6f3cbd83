"""Streaming aggregation: O(1) server memory, bit-identical to the sequential fold.

The aggregator roles fold arriving updates one at a time through
``StreamingMean`` / ``ServerStrategy.accumulate_stream`` instead of
buffering every client tree and folding at round close. These tests pin the
two invariants the docs advertise (docs/ARCHITECTURE.md):

* **bit-identity** — for the same fold order, the streaming fold executes
  the exact IEEE op sequence of the sequential fold (scale each update, add
  into the accumulator, divide once by the total), so results match
  ``weighted_mean`` and the incremental ``accumulate`` chain byte for byte,
  on ragged pytrees included;
* **O(1) server memory** — the peak number of client update trees held at
  once is 1 regardless of client count (``peak_buffered``).
"""
import jax
import numpy as np
import pytest

from repro.core import fold
from repro.core.expansion import JobSpec
from repro.core.roles import StreamingMean, weighted_mean
from repro.core.runtime import run_job
from repro.core.tag import DatasetSpec
from repro.core.topologies import classical_fl
from repro.fl.strategies import FedAsync, FedBuff

_RNG = np.random.default_rng(17)
W0 = {
    "w": (0.01 * _RNG.normal(size=(32, 10))).astype(np.float32),
    "b": np.zeros((10,), np.float32),
}


def _ragged_tree(rng, scale=1.0):
    """A deliberately ragged pytree: mixed ranks, odd sizes, nested lists."""
    return {
        "w": (scale * rng.normal(size=(33, 7))).astype(np.float32),
        "b": (scale * rng.normal(size=(7,))).astype(np.float32),
        "blocks": [
            (scale * rng.normal(size=(5, 2, 2))).astype(np.float32),
            (scale * rng.normal(size=(11,))).astype(np.float32),
        ],
    }


def _leaves_bytes(tree):
    return [np.asarray(x).tobytes() for x in jax.tree_util.tree_leaves(tree)]


class TestStreamingMeanMatchesBatched:
    @pytest.mark.parametrize("n_clients", [1, 3, 17])
    def test_bitwise_equal_to_weighted_mean(self, n_clients):
        rng = np.random.default_rng(5 + n_clients)
        updates = [
            (_ragged_tree(rng), float(rng.integers(1, 9)))
            for _ in range(n_clients)
        ]
        batched, total_batched = weighted_mean(updates)
        acc = StreamingMean()
        for tree, n in updates:
            acc.fold(tree, n)
        streamed, total_streamed = acc.finalize()
        assert total_batched == total_streamed
        assert _leaves_bytes(batched) == _leaves_bytes(streamed)
        # O(1): one in-flight tree no matter how many clients were folded
        assert acc.peak_buffered == 1
        assert acc.count == n_clients

    def test_fused_matches_sequential_bitwise(self):
        """The jitted per-update scale/add pair (two jit entries, so no FMA
        contraction) must be byte-identical to the eager numpy fold in the
        same order."""
        rng = np.random.default_rng(9)
        updates = [(_ragged_tree(rng), float(i + 1)) for i in range(6)]
        seq = StreamingMean(fused=False)
        fused = StreamingMean(fused=True)
        for tree, n in updates:
            seq.fold(tree, n)
            fused.fold(tree, n)
        seq_mean, seq_total = seq.finalize()
        fused_mean, fused_total = fused.finalize()
        assert seq_total == fused_total
        assert _leaves_bytes(seq_mean) == _leaves_bytes(fused_mean)

    def test_empty_and_zero_weight_finalize_to_none(self):
        acc = StreamingMean()
        assert acc.finalize() == (None, 0.0)
        acc.fold({"w": np.ones((2,), np.float32)}, 0.0)
        assert acc.finalize() == (None, 0.0)


def _updates(n_clients, seed):
    rng = np.random.default_rng(seed)
    return [(_ragged_tree(rng), float(rng.integers(1, 9))) for _ in range(n_clients)]


def _folded(updates, fused):
    acc = StreamingMean(fused=fused)
    for tree, n in updates:
        acc.fold(tree, n)
    return acc


def _nbytes(tree):
    return sum(np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(tree))


class TestFusedAccumulatorStaysOnDevice:
    """On the fused path the accumulator is a device array between folds and
    crosses to the host once, when it is read; the bytes are the host
    path's."""

    @pytest.mark.parametrize("n_clients", [1, 2, 8])
    def test_pulled_once_at_finalize(self, n_clients):
        updates = _updates(n_clients, 31 + n_clients)
        nbytes = _nbytes(updates[0][0])
        fused = StreamingMean(fused=True)
        for tree, n in updates:
            fused.fold(tree, n)
            leaves = jax.tree_util.tree_leaves(fused._acc)
            assert all(isinstance(x, jax.Array) for x in leaves)
            assert fused.d2h_bytes == 0
        assert fused.h2d_bytes == n_clients * nbytes
        mean, total = fused.finalize()
        assert fused.d2h_bytes == nbytes
        host_mean, host_total = _folded(updates, fused=False).finalize()
        assert total == host_total
        assert all(isinstance(x, np.ndarray) for x in jax.tree_util.tree_leaves(mean))
        assert _leaves_bytes(mean) == _leaves_bytes(host_mean)

    @pytest.mark.parametrize("n_clients", [1, 2, 8])
    def test_partial_is_the_host_partial_in_numpy(self, n_clients):
        updates = _updates(n_clients, 41 + n_clients)
        fused = _folded(updates, fused=True)
        acc, total = fused.partial()
        host_acc, host_total = _folded(updates, fused=False).partial()
        assert total == host_total
        assert all(isinstance(x, np.ndarray) for x in jax.tree_util.tree_leaves(acc))
        assert _leaves_bytes(acc) == _leaves_bytes(host_acc)
        assert fused.d2h_bytes == _nbytes(acc)

    @pytest.mark.parametrize("n_clients", [1, 2, 8])
    def test_fold_then_fold_partial_gives_the_host_bytes(self, n_clients):
        updates = _updates(n_clients, 51 + n_clients)
        part, part_total = _folded(_updates(3, 7), fused=False).partial()
        fused = _folded(updates, fused=True)
        host = _folded(updates, fused=False)
        for acc in (fused, host):
            acc.fold_partial(part, part_total, count=3)
        assert fused.count == host.count == n_clients + 3
        (mean, total), (host_mean, host_total) = fused.finalize(), host.finalize()
        assert total == host_total
        assert _leaves_bytes(mean) == _leaves_bytes(host_mean)
        assert fused.d2h_bytes == _nbytes(part)

    def test_fold_after_fold_partial_copies_the_host_sum_in(self):
        updates = _updates(4, 61)
        part, part_total = _folded(_updates(2, 8), fused=False).partial()
        fused, host = StreamingMean(fused=True), StreamingMean(fused=False)
        for acc in (fused, host):
            acc.fold_partial(part, part_total, count=2)
            for tree, n in updates:
                acc.fold(tree, n)
        nbytes = _nbytes(part)
        assert fused.h2d_bytes == (len(updates) + 1) * nbytes
        (mean, _), (host_mean, _) = fused.finalize(), host.finalize()
        assert fused.d2h_bytes == nbytes
        assert _leaves_bytes(mean) == _leaves_bytes(host_mean)

    @pytest.mark.parametrize("n_clients", [1, 2, 8])
    def test_without_room_on_the_device_each_sum_goes_back(self, n_clients, monkeypatch):
        monkeypatch.setattr(fold, "_device_free_bytes", lambda: 0)
        updates = _updates(n_clients, 71 + n_clients)
        nbytes = _nbytes(updates[0][0])
        fused = StreamingMean(fused=True)
        for tree, n in updates:
            fused.fold(tree, n)
            leaves = jax.tree_util.tree_leaves(fused._acc)
            assert not any(isinstance(x, jax.Array) for x in leaves)
        assert fused.h2d_bytes == (2 * n_clients - 1) * nbytes
        assert fused.d2h_bytes == n_clients * nbytes
        mean, total = fused.finalize()
        assert fused.d2h_bytes == n_clients * nbytes
        host_mean, host_total = _folded(updates, fused=False).finalize()
        assert total == host_total
        assert _leaves_bytes(mean) == _leaves_bytes(host_mean)

    @pytest.mark.parametrize("spare, resident", [(0, True), (-1, False), (None, True)])
    def test_the_accumulator_stays_where_the_device_has_room(self, spare, resident,
                                                             monkeypatch):
        # room for the accumulator, a scaled update and two of its largest leaves
        updates = _updates(2, 81)
        sizes = [x.nbytes for x in jax.tree_util.tree_leaves(updates[0][0])]
        free = None if spare is None else 2 * sum(sizes) + 2 * max(sizes) + spare
        monkeypatch.setattr(fold, "_device_free_bytes", lambda: free)
        fused = _folded(updates, fused=True)
        leaves = jax.tree_util.tree_leaves(fused._acc)
        assert all(isinstance(x, jax.Array) == resident for x in leaves)


class TestStrategyStreamMatchesBatch:
    @pytest.mark.parametrize("strategy", [FedBuff(buffer_size=8), FedAsync()])
    def test_accumulate_stream_equals_accumulate_chain(self, strategy, monkeypatch):
        """On the device path, ``accumulate_stream`` gives the bytes of the
        incremental ``accumulate`` chain."""
        monkeypatch.setattr(fold, "device_fold", lambda tree: True)
        rng = np.random.default_rng(23)
        deltas = [_ragged_tree(rng, scale=0.1) for _ in range(5)]
        staleness = [0, 2, 1, 4, 0]
        params = _ragged_tree(np.random.default_rng(0))
        chain_state = strategy.init(params)
        stream_state = strategy.init(params)
        for delta, s in zip(deltas, staleness):
            chain_state = strategy.accumulate(chain_state, delta, np.int32(s))
            stream_state = strategy.accumulate_stream(stream_state, delta, s)
        assert int(chain_state["count"]) == int(stream_state["count"]) == 5
        assert _leaves_bytes(chain_state["acc"]) == _leaves_bytes(
            stream_state["acc"]
        )


class TestServerPeakBuffered:
    @pytest.mark.parametrize("n_clients", [2, 6])
    def test_sync_aggregator_peak_is_one(self, n_clients):
        """End-to-end: the sync global aggregator streams per-source in
        sorted-src order, so its server-side peak buffered-tree count is 1
        regardless of how many trainers report. The invariant is read off
        the job-result aggregation metrics — the same record a process
        deployment marshals back to the driver — not by poking at role
        internals."""
        job = JobSpec(
            tag=classical_fl(
                trainer_program="repro.transport.conformance.SeededSGDTrainer"
            ),
            datasets=tuple(DatasetSpec(name=f"d{i}") for i in range(n_clients)),
            hyperparams={"rounds": 2, "init_weights": W0},
        )
        res = run_job(job, timeout=60)
        assert not res.errors, res.errors
        glob = res.program("global-aggregator-0")
        agg = [m for m in glob.metrics if "agg_folds" in m]
        assert len(agg) == 2  # one record per round
        for m in agg:
            assert m["peak_buffered"] == 1
            assert m["agg_folds"] == n_clients
            # no reduce plan installed: one frame per trainer reached the server
            assert m["agg_frames"] == n_clients
        assert not np.array_equal(
            np.asarray(res.global_weights()["w"]), W0["w"]
        )
