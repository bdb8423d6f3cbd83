"""Shared fixtures of the on-chip benchmark's CPU tests.

``tiny_root`` builds a checkout-shaped directory whose ``BENCHMARK.json``
holds a CPU-sized TAG round cell, next to copies of the benchmark's metric
readers: the harness runs it end to end here, with the check for a TPU left
out (it lives in ``run.py``'s ``main``).
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
for p in (HERE, HERE.parents[1] / "benchmarks" / "chip"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench_chip_cells import TAG_TRAFFIC, TINY_LM, real_limits, write_root  # noqa: E402


@pytest.fixture
def tiny_root(tmp_path):
    return write_root(tmp_path, {
        "tag.tiny": (TINY_LM, TAG_TRAFFIC, real_limits("tag.sync.c8"), 1),
    })
