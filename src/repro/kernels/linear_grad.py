"""Fused linear-model loss/gradient Pallas kernel — the paper's convex
hot spot (DESIGN.md §4).

Each inner-optimizer iteration on a window of n points computes
    m = Xw  →  r = ℓ'(y·m)·y  →  g = Xᵀr,  L = Σℓ(y·m).
Two separate GEMV passes read X twice from HBM; this kernel streams X once
in (block_m × d) VMEM tiles, using each tile for both the forward dot and
the transposed accumulation — halving HBM traffic for the memory-bound
regime (arithmetic intensity 2d per element read, d ≫ 1).

TPU adaptation: the row-block grid is sequential per core, so the gradient
accumulates in a VMEM output tile that is zeroed by the first program —
the canonical Pallas reduction pattern (no atomics, unlike the CUDA
formulation this replaces).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, y_ref, w_ref, g_ref, l_ref, *, loss: str):
    pi = pl.program_id(0)

    @pl.when(pi == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)
        l_ref[...] = jnp.zeros_like(l_ref)

    X = x_ref[...].astype(jnp.float32)          # (bm, d)
    y = y_ref[...].astype(jnp.float32)          # (bm,)
    w = w_ref[...].astype(jnp.float32)          # (d,)
    m = y * (X @ w)                             # (bm,)
    if loss == "squared_hinge":
        hinge = jnp.maximum(0.0, 1.0 - m)
        li = hinge * hinge
        dm = -2.0 * hinge
    else:  # logistic
        li = jnp.logaddexp(0.0, -m)
        dm = -jax.nn.sigmoid(-m)
    r = dm * y                                   # (bm,)
    g_ref[...] += X.T @ r                        # (d,)
    l_ref[...] += jnp.sum(li)[None]


@functools.partial(jax.jit, static_argnames=("loss", "block_m", "interpret"))
def linear_value_grad(X, y, w, *, loss: str = "squared_hinge",
                      block_m: int = 128, interpret: bool = False):
    """Returns (Σ loss_i, ∇_w Σ loss_i).  X: (n, d) — n must divide by
    block_m (ops.py pads); w: (d,)."""
    n, d = X.shape
    assert n % block_m == 0, (n, block_m)
    grid = (n // block_m,)
    g, l = pl.pallas_call(
        functools.partial(_kernel, loss=loss),
        name="linear_grad",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i: (i, 0)),
            pl.BlockSpec((block_m,), lambda i: (i,)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d,), jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.float32),
        ],
        interpret=interpret,
    )(X, y, w)
    return l[0], g
