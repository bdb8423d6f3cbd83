"""The peak table and the byte counter, at the cell's real shapes."""
import json

import numpy as np
import pytest
from jax import tree_util

from chipbench import counts
from chipbench.cells.tag_round import layer_shapes
from chipbench.manifest import Manifest
from chipbench.peaks import peak


@pytest.fixture(scope="module")
def tag():
    return Manifest().cell("tag.sync.c8").config


def test_v5e_peaks_and_their_source():
    p = peak("TPU v5 lite")
    assert p["bf16_flops"] == 197e12
    assert p["int8_ops"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["source"] == "Google Cloud, TPU v5e"


def test_an_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peak("TPU v9 imaginary")


def test_one_decoder_layer_has_the_published_parameter_count(tag):
    assert counts.decoder_layer_params(tag) == 77_076_992


def test_the_programs_layer_has_the_counted_shapes(tag):
    leaves = tree_util.tree_leaves(layer_shapes(tag))
    assert sum(int(np.prod(s.shape)) for s in leaves) == 77_076_992
    assert {str(s.dtype) for s in leaves} == {"float32"}


def test_fold_least_bytes_of_eight_layer_updates():
    # 8 updates of 77,076,992 f32 elements read, the mean written once
    assert counts.fold_least_bytes(8, 77_076_992) == 9 * 4 * 77_076_992


def test_the_tag_config_states_the_update_the_counter_counts(tag):
    assert counts.decoder_layer_params(tag) == 77_076_992
    assert "77,076,992" in json.dumps(tag)
