"""The weighted fold module: its dispatch rule, its bits on edge values, the
jobs whose aggregators fold through it, and its place in the import graph.

Every aggregator computes its mean through ``repro.core.fold``. On the
device path the jitted ``scale``/``add`` pair runs; on the host path numpy
runs the same IEEE ops. Both must give the bytes of a plain numpy
sequential fold, with two exceptions on the device path, where the device
is XLA's CPU backend here and the TPU on the chip. It flushes subnormal
operands and results to zero, so on that path the numpy fold is run with
the same flush. And a NaN stays a NaN, but the TPU returns one NaN of its
own, whatever sign numpy's has, so on that path every NaN is compared as
one.
"""
import ast
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import fold
from repro.core.expansion import JobSpec
from repro.core.roles_coord import CoordTrainer
from repro.core.runtime import run_job
from repro.core.tag import DatasetSpec
from repro.core.topologies import classical_fl, coordinated_fl
from repro.transport.conformance import SeededSGDTrainer

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"


# --------------------------------------------------------------------- #
# the dispatch rule
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("leaves", [1, 3])
@pytest.mark.parametrize("elems", [16_383, 16_384])
@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_device_fold_rule(monkeypatch, backend, elems, leaves):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    sizes = [elems // leaves] * (leaves - 1)
    sizes.append(elems - sum(sizes))
    tree = {f"l{i}": np.zeros((size,), np.float32) for i, size in enumerate(sizes)}
    assert fold.device_fold(tree) == (backend == "tpu" and elems == 16_384)


# --------------------------------------------------------------------- #
# edge values, on both paths
# --------------------------------------------------------------------- #
EDGES = {
    "signed_zeros": [([-0.0, 0.0, -0.0, 2.0], 1.0), ([-0.0, -0.0, 0.0, -2.0], 3.0)],
    # subnormal inputs, and normal products whose sum is subnormal
    "subnormals": [([1e-40, -1e-40, 1e-45, 2e-38], 1.0),
                   ([3e-39, 1e-40, -1e-45, -1.5e-38], 2.0)],
    "infinities": [([np.inf, -np.inf, 1.0, np.inf], 1.0),
                   ([np.inf, np.inf, -np.inf, 2.0], 2.0)],
    "nan": [([np.nan, 1.0, -np.nan, 3.0], 2.0), ([1.0, np.nan, 2.0, 4.0], 1.0)],
    # 1 is lost beside 1e8 in float32: the order of the adds decides the sum
    "cancellation": [([1e8, 1.0, -3e7, 1.0], 1.0), ([1.0, 1e8, 1.0, 3e7], 1.0),
                     ([-1e8, -1e8, 3e7, 1.0], 1.0)],
    "single_client": [([0.1, -3.7, 1e30, 7.0], 3.0)],
}


def _flush(x):
    """Subnormals to a zero of their sign."""
    x = np.asarray(x, np.float32)
    tiny = np.finfo(np.float32).tiny
    return np.where(np.abs(x) < tiny, np.copysign(np.float32(0.0), x), x)


def _one_nan(x):
    return np.where(np.isnan(x), np.float32(np.nan), x)


def _numpy_fold(updates, flush):
    """The plain sequential fold: scale, add in list order, divide once by
    the Python-float total. ``flush`` flushes each operand and result."""
    f = _flush if flush else (lambda x: x)
    total, acc = 0.0, None
    for x, n in updates:
        total += n
        scaled = f(f(x) * n)
        acc = scaled if acc is None else f(f(acc) + scaled)
    return acc / total, total


@pytest.mark.parametrize("device", [True, False], ids=["device", "host"])
@pytest.mark.parametrize("kind", sorted(EDGES))
def test_fold_matches_the_numpy_fold_on_edge_values(kind, device):
    updates = [(np.asarray(v, np.float32), n) for v, n in EDGES[kind]]
    want, want_total = _numpy_fold(updates, flush=device)
    mean, total = fold.weighted_mean([({"w": x}, n) for x, n in updates], fused=device)
    got = mean["w"]
    if device:
        got, want = _one_nan(got), _one_nan(want)
    assert total == want_total
    assert got.tobytes() == want.tobytes(), (got, want)
    if kind == "cancellation":
        other, _ = _numpy_fold(updates[::2] + updates[1::2], flush=device)
        assert other.tobytes() != want.tobytes()


# --------------------------------------------------------------------- #
# jobs whose aggregators fold through the module, under forced paths
# --------------------------------------------------------------------- #
W0 = {
    "w": (0.01 * np.random.default_rng(7).normal(size=(32, 10))).astype(np.float32),
    "b": np.zeros((10,), np.float32),
}


class SeededCoordTrainer(CoordTrainer, SeededSGDTrainer):
    """A CO-FL trainer that trains like ``SeededSGDTrainer``."""


def _run_forced(monkeypatch, job, on, **kw):
    asked = []
    monkeypatch.setattr(fold, "device_fold", lambda tree: asked.append(1) or on)
    res = run_job(job, timeout=60, **kw)
    assert not res.errors, res.errors
    assert asked
    return res.global_weights()


def _bytes(tree):
    return [np.asarray(x).tobytes() for x in jax.tree_util.tree_leaves(tree)]


def test_cofl_job_is_bit_identical_on_both_paths(monkeypatch):
    """Four trainers round-robin over two aggregators: each aggregator and
    the global aggregator fold two updates, whose sum does not depend on
    their arrival order."""
    job = JobSpec(
        tag=coordinated_fl(dataset_groups={"default": ("d0", "d1", "d2", "d3")}),
        datasets=tuple(DatasetSpec(name=f"d{i}") for i in range(4)),
        hyperparams={"rounds": 3, "init_weights": W0},
    )
    got = [_run_forced(monkeypatch, job, on,
                       program_overrides={"trainer": SeededCoordTrainer})
           for on in (True, False)]
    assert _bytes(got[0]) == _bytes(got[1])
    assert _bytes(got[0]) != _bytes(W0)


def test_hub_reduced_sync_job_is_bit_identical_on_both_paths(monkeypatch):
    job = JobSpec(
        tag=classical_fl(trainer_program="repro.transport.conformance.SeededSGDTrainer"),
        datasets=tuple(DatasetSpec(name=f"d{i}") for i in range(4)),
        hyperparams={"rounds": 3, "init_weights": W0, "reduce_plan": 1},
    )
    got = [_run_forced(monkeypatch, job, on) for on in (True, False)]
    assert _bytes(got[0]) == _bytes(got[1])
    assert _bytes(got[0]) != _bytes(W0)


# --------------------------------------------------------------------- #
# the import graph
# --------------------------------------------------------------------- #
def _imports(path):
    tree = ast.parse(path.read_text())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add(node.module)
            names |= {f"{node.module}.{a.name}" for a in node.names}
    return names


def _under(name, packages):
    return any(name == p or name.startswith(p + ".") for p in packages)


@pytest.mark.parametrize("module, forbidden", [
    ("core/fold.py", ("repro.core.roles", "repro.core.channels",
                      "repro.core.protocols", "repro.fl", "repro.kernels")),
    ("fl/strategies.py", ("repro.core.roles",)),
    ("core/roles.py", ("repro.fl", "repro.kernels")),
])
def test_the_fold_sits_under_its_callers(module, forbidden):
    names = _imports(SRC / module)
    assert not [n for n in names if _under(n, forbidden)]
    if module == "core/fold.py":
        roots = {n for n in names if not _under(n, ("repro.core.spans",))}
        assert {n.split(".")[0] for n in roots} <= {"__future__", "typing", "jax", "numpy"}
