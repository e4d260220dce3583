"""chip_smoke.py's phases at CPU size, and its refusal to run off a TPU.

The script itself runs only on a TPU; here each phase function runs at a
tiny size through the same ``build(RunSpec)`` path, so a broken phase shows
up before a chip call.  The chip-only check (``tpu_custom_call`` in the
stage HLO) is the one thing these cannot see.
"""
import importlib.util
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ModelSpec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "found cpu" in out.stderr
    assert '"ok"' not in out.stdout


def test_compile_clock_counts_a_compile(smoke):
    clock = smoke.CompileClock()
    try:
        jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()
    finally:
        jax.monitoring.unregister_event_duration_listener(clock._on)
    assert clock.seconds > 0


def test_phase_a_convex_two_track(smoke):
    res = smoke.phase_a(scale=0.05, n0=128, min_bytes=0)
    assert res["f"][-1] < res["f"][0]
    assert res["transfers"] <= res["stages"]


def test_phase_b_lm_trains_over_stages(smoke):
    res = smoke.phase_b(model=ModelSpec(arch="qwen3-0.6b"), batch=2,
                        seq_len=32, steps=3, stages=2, eval_rows=2)
    assert res["stages"] >= 2 and np.all(np.isfinite(res["losses"]))


def test_phase_c_mamba_through_the_kernel(smoke):
    res = smoke.phase_c(model=ModelSpec(arch="falcon-mamba-7b"), batch=1,
                        seq_len=32, steps=2, stages=2, eval_rows=1,
                        parity_shape=(1, 100, 256, 16))
    assert res["calls"] > 0
    assert all(res["parity"][k] <= smoke.SSM_TOL[k] for k in res["parity"])


def test_four_chip_phase_on_four_cpu_devices():
    """The --four-chips phase on 4 forced CPU devices: one lane per device
    and the single-host trajectory within the psum bound."""
    code = (
        "import importlib.util\n"
        "spec = importlib.util.spec_from_file_location('chip_smoke', "
        f"{os.path.join(ROOT, 'chip_smoke.py')!r})\n"
        "mod = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(mod)\n"
        "res = mod.four_chips(scale=0.05, shard_size=32)\n"
        "print('FOUR_OK', res['rel'])\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=480)
    assert "FOUR_OK" in out.stdout, (out.stdout, out.stderr)
