"""RG-LRU gated diagonal recurrence Pallas kernel (recurrentgemma hot spot).

    h_t = a_t ⊙ h_{t-1} + b_t        (a_t, b_t precomputed per §rglru.py)

TPU adaptation: the CUDA version maps channels to threads with a
warp-parallel time loop; here the grid is (B, width/bw, S/ts) with the
time axis innermost and sequential, the (1, bw) state carried from one
time block to the next in VMEM scratch.  Within a block the kernel reads
G = 8 rows (16 for bf16) per aligned tile at ``pl.multiple_of(g*G, G)``,
unrolls the G steps in registers and writes the G output rows as one tile
— no access on the sublane axis is at an unaligned dynamic offset.

VMEM per program (S-independent), double-buffered, bw=256, ts=512, bf16:
a/b/y tiles 3 × 2 × 512·256·2 B = 1.5 MiB.  The v5e compile at width
4096 reports a scoped VMEM allocation of 1.50 MiB in bf16 and 3.00 MiB in
f32 at S=4096, inside Mosaic's default limit (tests/test_tpu_compile.py
compiles it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ssm_scan import rows_per_group


def _kernel(a_ref, b_ref, y_ref, h_scr, *, ts: int, G: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    rows = jax.lax.broadcasted_iota(jnp.int32, (G, h_scr.shape[1]), 0)

    def group(g, h):
        off = pl.multiple_of(g * G, G)
        a = a_ref[0, pl.ds(off, G), :].astype(jnp.float32)      # (G, bw)
        b = b_ref[0, pl.ds(off, G), :].astype(jnp.float32)
        y = jnp.zeros(a.shape, jnp.float32)
        for j in range(G):
            h = a[j:j + 1, :] * h + b[j:j + 1, :]                 # (1, bw)
            y = jnp.where(rows == j, h, y)
        y_ref[0, pl.ds(off, G), :] = y.astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, ts // G, group, h_scr[...])


@functools.partial(jax.jit,
                   static_argnames=("block_w", "block_t", "interpret"))
def rglru_scan(a, b, *, block_w: int = 256, block_t: int = 512,
               interpret: bool = False):
    """a, b: (B, S, W) -> h-trajectory y: (B, S, W) with h_0 = 0.

    ``S`` must divide by ``block_t``, a multiple of 128; ``block_w`` is a
    multiple of 128 dividing ``W``, or ``W`` itself (ops.py pads and picks
    both)."""
    B, S, W = a.shape
    bw, ts = block_w, block_t
    assert W % bw == 0 and S % ts == 0, (W, bw, S, ts)
    G = rows_per_group(a.dtype)
    return pl.pallas_call(
        functools.partial(_kernel, ts=ts, G=G),
        name="rglru_scan",
        grid=(B, W // bw, S // ts),
        in_specs=[
            pl.BlockSpec((1, ts, bw), lambda i, j, t: (i, t, j)),
            pl.BlockSpec((1, ts, bw), lambda i, j, t: (i, t, j)),
        ],
        out_specs=pl.BlockSpec((1, ts, bw), lambda i, j, t: (i, t, j)),
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
