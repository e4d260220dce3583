"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode cannot see a slice the chip's tiling refuses or a block
that overflows VMEM; the TPU compiler, which is installed here, can, for a
chip that is described and not attached.  Each case compiles one kernel at
a published width with the blocks ``kernels/ops.py`` dispatches and checks
that Mosaic emitted the kernel (``tpu_custom_call``).

The topology is described only inside a fixture, after a test of this
file has started: describing it loads the TPU library, which one process
at a time may hold.
"""
import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import ops
from repro.kernels import rglru_scan as rg
from repro.kernels import ssm_scan as ss


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these out of any cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _ssm(S, di=8192, N=16, dtype=jnp.bfloat16):
    """falcon-mamba-7b: d_inner 8192, state 16."""
    ts, S_pad = ops._time_block(S)
    shapes = [((1, S_pad, di), dtype), ((1, S_pad, di), dtype),
              ((1, S_pad, N), dtype), ((1, S_pad, N), dtype),
              ((di, N), jnp.float32), ((di,), jnp.float32)]
    fn = lambda *a: ss.ssm_scan(*a, block_d=ops._lane_block(di, 256),
                                block_t=ts)
    return fn, shapes


def _rglru(S, W=4096, dtype=jnp.bfloat16):
    """recurrentgemma-9b: lru_width 4096."""
    ts, S_pad = ops._time_block(S)
    fn = lambda a, b: rg.rglru_scan(a, b, block_w=ops._lane_block(W, 256),
                                    block_t=ts)
    return fn, [((1, S_pad, W), dtype)] * 2


def _flash(S, hd, window, H=16, dtype=jnp.bfloat16):
    """qwen3-0.6b (hd 128, causal) and recurrentgemma-9b local attention
    (hd 256, window 2048)."""
    fn = lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                            window=window)
    return fn, [((1, H, S, hd), dtype)] * 3


CASES = {
    "ssm_scan-S2048-bf16": lambda: _ssm(2048),
    "ssm_scan-S4096-bf16": lambda: _ssm(4096),
    "ssm_scan-S2048-f32": lambda: _ssm(2048, dtype=jnp.float32),
    "rglru_scan-W4096-S2048-bf16": lambda: _rglru(2048),
    "rglru_scan-W4096-S4096-f32": lambda: _rglru(4096, dtype=jnp.float32),
    "flash_attention-hd128-causal-S2048": lambda: _flash(2048, 128, 0),
    "flash_attention-hd256-window2048-S4096": lambda: _flash(4096, 256, 2048),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
