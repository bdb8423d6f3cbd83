"""Whole runs of a CPU-sized cell through the harness, with the look for a
TPU left out: sound runs come out correct; each fault the cell can have,
planted under the timed path, comes out not correct; so does the control.
"""
import sys
import time

import jax
import numpy as np
import pytest

from bench_chip_cells import BENCH
from chipbench import peaks
from chipbench.harness import run_workload
from chipbench.manifest import Manifest

BIG = 2**33 + 7


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    # the per-layer readers need a peak for the device kind they ran on
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])


def run(root, name, trace=False, seed=BIG):
    return run_workload(Manifest(root), name, seed, 0.5, trace, jax.devices(),
                        time.perf_counter())


def test_run_py_refuses_a_host_without_a_tpu(capsys):
    sys.path.insert(0, str(BENCH))
    import run as run_py

    assert run_py.main(["--workload", "tag.sync.c8", "--seed", "1",
                        "--seconds", "1"]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "needs a TPU" in out.err


@pytest.mark.parametrize("trace", [False, True])
def test_a_sound_tag_run_is_correct(tiny_root, trace):
    r = run(tiny_root, "tag.tiny", trace)
    assert r["correct"], r["checks"]
    assert r["checks"]["mismatched"]["value"] == 0
    assert r["attempted"] >= 1
    want = {"agg_span_ms"} if trace else {"round_s", "setup_s"}
    assert set(r["metrics"]) == want
    assert list(r)[-1] == "checks"
    if trace:
        assert r["device"]["window_s"] > 0


def _broken_fold(monkeypatch, fault):
    from repro.core import protocols, roles

    real_aggregate = protocols.WeightSync.aggregate
    real_fold = roles.StreamingMean.fold

    def aggregate(self):
        if fault == "state_unchanged":
            return
        real_aggregate(self)
        if fault == "answer_altered":
            leaf = jax.tree_util.tree_leaves(self.role.weights)[0]
            leaf.reshape(-1)[0] += np.float32(1.0)

    def fold(self, weights, n):
        if fault == "half_batch" and self.count >= 1:
            return  # of the tiny cell's 3 updates, fold the first only
        real_fold(self, weights, n)

    monkeypatch.setattr(protocols.WeightSync, "aggregate", aggregate)
    monkeypatch.setattr(roles.StreamingMean, "fold", fold)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "answer_altered"])
def test_a_broken_fold_is_not_correct(tiny_root, monkeypatch, fault):
    _broken_fold(monkeypatch, fault)
    r = run(tiny_root, "tag.tiny")
    assert not r["correct"], (fault, r["checks"])
    assert r["checks"]["mismatched"]["value"] > 0


def test_the_controls_fail_their_limits(tiny_root):
    """The control of ``calibrate.py`` at a size a test run holds: the
    reference's fold in bfloat16 in place of the program's."""
    sys.path.insert(0, str(BENCH))
    import calibrate

    from chipbench import compare

    cell = Manifest(tiny_root).cell("tag.tiny")
    (line,) = calibrate.tag_readings(cell, jax.devices(), [BIG], 1, 0.3)
    assert compare.judge(line["program"], cell.limits)[0], line
    # the last round and two drawn among the others the window completed
    assert line["compared"] == min(2, line["rounds"] - 1) + 1, line
    assert line["program"]["mismatched"] == 0
    assert line["control"]["mismatched"] > 0
    assert not compare.judge(line["control"], cell.limits)[0], line
