"""The one traffic generator: sample counts and compared rounds from a seed.

Every seed gets the same sizes; the seed only changes the values, so runs
with different seeds do the same work.
"""
from __future__ import annotations

import numpy as np


def sample_count(seed: int, client: int, round_: int, lo: int, hi: int) -> int:
    """The sample count client ``client`` reports in round ``round_``."""
    return int(np.random.default_rng([seed, client, round_]).integers(lo, hi + 1))


def reservoir_slot(seed: int, index: int) -> int:
    """Where the ``index``-th item of a stream lands in a reservoir drawn
    from the seed (Vitter's algorithm R): uniform in ``[0, index]``, and a
    slot only where it falls under the reservoir's size. Kept that way, a
    reservoir of ``k`` holds ``k`` items drawn uniformly from however many
    the stream had."""
    return int(np.random.default_rng([seed, 0x5A3, index]).integers(0, index + 1))
