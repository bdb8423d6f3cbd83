"""``chip_smoke.py``'s phases rehearsed on the CPU at reduced size.

The TPU check stays in ``main``; here the phase functions run directly on
the CPU paths (jnp references for the kernels) to catch wrong paths,
arguments and control flow before a chip run. The TPU-only facts (the
device fold chosen by the dispatch rule, ``tpu_custom_call`` in the
quantizer) are asserted by ``main`` on the chip.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import chip_smoke as cs  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.launch import cache  # noqa: E402


@pytest.fixture(scope="module")
def cfg():
    return get_config("qwen2_5_3b", reduced=True)


@pytest.fixture(scope="module")
def updates(cfg):
    return cs.make_updates(cs.layer_shapes(cfg), cs.N_CLIENTS, seed=0)


def test_main_refuses_a_host_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        cs.main([])
    assert "needs a TPU" in str(exc.value)
    assert '"ok"' not in capsys.readouterr().out


def test_layer_shapes_are_one_published_decoder_layer():
    shapes = cs.layer_shapes(get_config("qwen2_5_3b"))
    leaves = jax.tree_util.tree_leaves(
        shapes, is_leaf=lambda s: isinstance(s, tuple)
    )
    assert sum(int(np.prod(s)) for s in leaves) == 77_076_992


def test_phase_train_loss_falls(cfg):
    out = cs.phase_train(cfg, batch=4, seq=32)
    assert len(out["losses"]) == cs.STEPS
    assert out["losses"][-1] < out["losses"][0]


def test_phase_fold_is_bit_identical(updates, monkeypatch):
    monkeypatch.setattr(cs.fold, "device_fold", lambda tree: True)
    out = cs.phase_fold(updates)
    assert out["fold_ran"] and out["no_fma"]
    assert out["divide_max_ulp"] == 0  # the CPU's division is IEEE


def test_phase_codec_matches_reference(updates):
    out = cs.phase_codec(updates[0][0])
    # on the CPU the codec takes the jnp reference, not the kernel
    assert out == {"mismatched": 0, "scale_ulp": 0, "kernel": False}


def test_phase_job_fused_equals_sequential():
    cs.phase_job(rounds=2)


def test_mesh4_on_four_cpu_devices():
    """The ``--chips 4`` phase on four virtual CPU devices. The device count
    is fixed when a process first starts its JAX backend, which a test
    worker has done long before this test, so the phase runs in a fresh
    interpreter. In f32 the mesh step equals the one-device FedAvg of the
    four local rounds bit for bit."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = (
        "import jax, chip_smoke as cs\n"
        "from repro.configs import get_config\n"
        "assert len(jax.devices()) == 4, jax.devices()\n"
        "out = cs.phase_mesh4(get_config('qwen2_5_3b', reduced=True),\n"
        "                     batch_per_client=2, seq=32)\n"
        "assert out['update_rel_l2'] == 0.0, out\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "all-reduce" in proc.stdout


@pytest.mark.parametrize("env_dir", ["", "/elsewhere/cache"])
def test_compile_cache_location(monkeypatch, env_dir):
    """Entry points keep the cache in the checkout's ``.jax_cache/`` unless
    ``JAX_COMPILATION_CACHE_DIR`` is set, which JAX then reads itself."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    before = jax.config.jax_compilation_cache_dir
    try:
        cache.use_compile_cache()
        after = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if env_dir:
        assert after == before
    else:
        assert after == str(ROOT / ".jax_cache")
