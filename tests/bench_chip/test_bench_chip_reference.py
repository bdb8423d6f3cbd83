"""The plain reference fold agrees with the program's fold bit for bit, and
its control does not."""
import numpy as np
import pytest

from chipbench import foldref


@pytest.fixture(scope="module")
def updates():
    rng = np.random.default_rng(3)
    shapes = {"a": (5, 7), "b": (33,)}
    return [({k: rng.standard_normal(s).astype(np.float32) for k, s in shapes.items()},
             float(rng.integers(1, 40))) for _ in range(4)]


@pytest.mark.parametrize("fused", [True, False])
def test_the_sequential_fold_is_the_programs_fold(updates, fused):
    from repro.core.roles import StreamingMean, weighted_mean

    want, total = foldref.sequential_fold(updates)
    stream = StreamingMean(fused=fused)
    for tree, n in updates:
        stream.fold(tree, n)
    got, got_total = stream.finalize()
    assert got_total == total
    assert foldref.mismatched(got, want) == 0
    mean, _ = weighted_mean(updates, fused=fused)
    assert foldref.mismatched(mean, want) == 0


def test_the_bfloat16_control_fold_is_not(updates):
    want, _ = foldref.sequential_fold(updates)
    control, _ = foldref.sequential_fold(updates, bfloat16=True)
    assert foldref.mismatched(control, want) > 0.9 * (35 + 33)
