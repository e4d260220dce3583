"""Mamba selective-scan Pallas kernel (falcon-mamba hot spot).

TPU adaptation of the CUDA selective-scan: instead of one threadblock per
(batch, channel-chunk) with warp-level time recurrence, the grid is
(B, d_inner/bd, S/ts).  The time axis is innermost and sequential: the
(N, bd) state lives in VMEM scratch and carries from one time block to the
next, so VMEM holds one (ts, bd) tile per tensor whatever S is.

Every access on the sublane (time) axis is an aligned tile: the kernel
walks its time block in groups of G = 8 rows (16 for bf16, whose native
tile packs 16 rows) at offsets ``pl.multiple_of(g*G, G)``, unrolls the G
recurrence steps in registers, and writes the G output rows back as one
tile.  The state is kept transposed, (N, bd), so a time step's rows of
``u``/``delta`` broadcast over N sublanes; ``B``/``C`` arrive transposed
to (N, S) and a step's column is picked by a one-hot lane reduction.

VMEM per program (S-independent), double-buffered, bd=256, ts=512, bf16:
u/delta/y tiles 3 × 2 × 512·256·2 B = 1.5 MiB, B/C 2 × 2 × 16·512·2 B =
64 KiB, plus the (16, 256) f32 state.  The v5e compile at falcon-mamba
widths reports a scoped VMEM allocation of 1.61 MiB in bf16 and 3.17 MiB
in f32 at S=4096, inside Mosaic's default limit
(tests/test_tpu_compile.py compiles it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def rows_per_group(dtype) -> int:
    """Sublane rows of one native tile: 8 for 32-bit, 16 for bf16."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def _kernel(u_ref, dt_ref, bt_ref, ct_ref, at_ref, d_ref, y_ref, h_scr, *,
            ts: int, G: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    AT = at_ref[...]                                      # (N, bd) = -exp(A)ᵀ
    D = d_ref[...]                                        # (1, bd)
    N, bd = h_scr.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (G, bd), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (N, LANES), 1)

    def group(g, h):
        off = pl.multiple_of(g * G, G)
        u = u_ref[0, pl.ds(off, G), :].astype(jnp.float32)     # (G, bd)
        dt = dt_ref[0, pl.ds(off, G), :].astype(jnp.float32)   # (G, bd)
        lane0 = pl.multiple_of((off // LANES) * LANES, LANES)
        bt = bt_ref[0, :, pl.ds(lane0, LANES)].astype(jnp.float32)  # (N, 128)
        ct = ct_ref[0, :, pl.ds(lane0, LANES)].astype(jnp.float32)
        y = jnp.zeros((G, bd), jnp.float32)
        for j in range(G):
            hot = lanes == off - lane0 + j
            b_t = jnp.sum(jnp.where(hot, bt, 0.0), axis=1, keepdims=True)
            c_t = jnp.sum(jnp.where(hot, ct, 0.0), axis=1, keepdims=True)
            u_t, d_t = u[j:j + 1, :], dt[j:j + 1, :]     # (1, bd)
            h = jnp.exp(d_t * AT) * h + (d_t * u_t) * b_t     # (N, bd)
            y_t = jnp.sum(h * c_t, axis=0, keepdims=True)     # (1, bd)
            y = jnp.where(rows == j, y_t, y)
        y_ref[0, pl.ds(off, G), :] = (y + u * D).astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, ts // G, group, h_scr[...])


@functools.partial(jax.jit,
                   static_argnames=("block_d", "block_t", "interpret"))
def ssm_scan(u, delta, B_ssm, C_ssm, A_log, D, *, block_d: int = 256,
             block_t: int = 512, interpret: bool = False):
    """u, delta: (B, S, di); B_ssm, C_ssm: (B, S, N); A_log: (di, N);
    D: (di,).  Returns y: (B, S, di) (including the u·D skip term).

    ``S`` must divide by ``block_t``, a multiple of 128; ``block_d`` is a
    multiple of 128 dividing ``di``, or ``di`` itself (ops.py pads and
    picks both)."""
    Bsz, S, di = u.shape
    N = B_ssm.shape[-1]
    bd, ts = block_d, block_t
    assert di % bd == 0 and S % ts == 0 and ts % LANES == 0, (di, bd, S, ts)
    G = rows_per_group(u.dtype)
    AT = -jnp.exp(A_log.astype(jnp.float32)).T               # (N, di)
    D2 = D.astype(jnp.float32)[None, :]                       # (1, di)
    Bt = jnp.swapaxes(B_ssm, 1, 2)                            # (B, N, S)
    Ct = jnp.swapaxes(C_ssm, 1, 2)
    return pl.pallas_call(
        functools.partial(_kernel, ts=ts, G=G),
        name="ssm_scan",
        grid=(Bsz, di // bd, S // ts),
        in_specs=[
            pl.BlockSpec((1, ts, bd), lambda b, i, t: (b, t, i)),
            pl.BlockSpec((1, ts, bd), lambda b, i, t: (b, t, i)),
            pl.BlockSpec((1, N, ts), lambda b, i, t: (b, 0, t)),
            pl.BlockSpec((1, N, ts), lambda b, i, t: (b, 0, t)),
            pl.BlockSpec((N, bd), lambda b, i, t: (0, i)),
            pl.BlockSpec((1, bd), lambda b, i, t: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, ts, bd), lambda b, i, t: (b, t, i)),
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        scratch_shapes=[pltpu.VMEM((N, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(u, delta, Bt, Ct, AT, D2)
