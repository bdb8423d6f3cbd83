"""The traffic generator and the updates are fixed by ``--seed``: the same
seed gives the same inputs, another seed other values of the same sizes;
the compared rounds are drawn from the rounds a window completed."""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import generate, weights
from chipbench.cells.tag_round import Rounds

BIG = 2**33 + 7


def test_sample_counts_are_fixed_by_the_seed():
    assert generate.sample_count(BIG, 3, 5, 1, 40) == generate.sample_count(BIG, 3, 5, 1, 40)
    got = {generate.sample_count(BIG, c, r, 1, 40) for c in range(8) for r in range(8)}
    assert min(got) >= 1 and max(got) <= 40 and len(got) > 10


def compared(seed: int, rounds: int, picks: int = 2) -> list:
    r = Rounds(updates=[], seed=seed, samples=(1, 40), seconds=0.0, picks=picks)
    for i in range(rounds):
        r.complete(i, (f"aggregate {i}", float(i)))
    got = r.kept
    for i, (agg, total) in got.items():
        assert agg == f"aggregate {i}" and total == float(i)
    return sorted(got)


@pytest.mark.parametrize("rounds", [1, 2, 3, 7, 12, 30])
def test_compared_rounds_are_the_last_and_picks_among_the_completed(rounds):
    for seed in (BIG, 3, 2**31 + 11):
        got = compared(seed, rounds)
        assert got == compared(seed, rounds)
        assert got[-1] == rounds - 1
        assert len(got) == min(2, rounds - 1) + 1
        assert 0 <= got[0] and len(set(got)) == len(got)


def test_the_draw_is_uniform_over_the_completed_rounds():
    # 12 rounds: the 11 before the last are each drawn with probability 2/11
    hits = collections.Counter()
    seeds = range(3000)
    for seed in seeds:
        hits.update(compared(seed, 12)[:-1])
    assert set(hits) == set(range(11))
    for i in range(11):
        assert abs(hits[i] / len(seeds) - 2 / 11) < 0.03, (i, hits)


def test_updates_use_every_bit_of_a_large_seed():
    shapes = {"w": jax.ShapeDtypeStruct((16, 8), jnp.float32),
              "b": jax.ShapeDtypeStruct((8,), jnp.float32)}
    make = weights.updates_fn(shapes, 3)
    a = make(weights.seed_key(BIG))
    b = make(weights.seed_key(BIG))
    c = make(weights.seed_key(7))  # same low 32 bits as BIG
    assert len(a) == 3
    for x, y, z in zip(*(jax.tree_util.tree_leaves(t) for t in (a, b, c))):
        assert x.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert not np.array_equal(np.asarray(x), np.asarray(z))
    first, second = (np.asarray(t["w"]) for t in a[:2])
    assert not np.array_equal(first, second)
    assert 0.5 < first.std() < 2
