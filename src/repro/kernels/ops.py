"""jit'd public wrappers around the Pallas kernels: shape normalization
(padding to block multiples, GQA head expansion) + dispatch.

Interpret mode is chosen at dispatch time from the platform: the Pallas
interpreter runs the kernels only where ``jax.default_backend()`` is the
CPU (the tests, which ``tests/conftest.py`` pins there); on a TPU every
kernel is compiled by Mosaic.  Nothing reaches the chip interpreted.

The scan kernels (``ssm_scan``/``rglru_scan``) and ``flash_attention``
are differentiable: the Pallas kernel is the forward pass and the backward
is the VJP of the matching ``kernels.ref`` oracle recomputed from the
saved primal inputs — so the training path can route through the kernels
(``impl="pallas"`` end to end) without hand-written backward kernels.

``CALLS`` counts trace-time dispatches per kernel (reset with
``reset_calls``): the workload sweep uses it to prove a family's training
traffic actually routed through its kernel rather than the XLA fallback.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import linear_grad as _lg
from . import ref as _ref
from . import rglru_scan as _rg
from . import ssm_scan as _ss

CALLS: collections.Counter = collections.Counter()


def reset_calls() -> None:
    CALLS.clear()


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _lane_block(width: int, block: int) -> int:
    """The widest multiple of 128 lanes <= ``block`` that divides
    ``width``, or the whole width when it is not a multiple of 128."""
    if width % 128:
        return width
    bd = max(128, block - block % 128)
    while width % bd:
        bd -= 128
    return bd


def _time_block(S: int) -> tuple[int, int]:
    """(time block, S padded to a multiple of it) for the scan kernels: at
    most 512 rows, always a multiple of 128."""
    ts = min(512, -(-S // 128) * 128)
    return ts, -(-S // ts) * ts


def _pad_time(x, S_pad: int):
    S = x.shape[1]
    return x if S == S_pad else jnp.pad(
        x, ((0, 0), (0, S_pad - S)) + ((0, 0),) * (x.ndim - 2))


def linear_forward(X, w):
    # forward margins alone are a plain GEMV; the fused win is in value_grad
    return X @ w


def linear_value_grad(X, y, w, *, loss: str = "squared_hinge",
                      block_m: int = 128):
    n, d = X.shape
    pad = (-n) % block_m
    if pad:
        X = jnp.pad(X, ((0, pad), (0, 0)))
        y = jnp.pad(y, (0, pad), constant_values=1.0)  # margin 1·0 = 0 loss?
        # padded rows: y=1, Xw=0 -> squared hinge loss 1, grad -2·x = 0 (x=0)
        # loss contribution of pad rows is constant wrt w but nonzero; fix by
        # subtracting it below.
    L, g = _lg.linear_value_grad(X, y, w, loss=loss, block_m=block_m,
                                 interpret=_interpret())
    if pad:
        if loss == "squared_hinge":
            L = L - pad * 1.0          # each zero-row contributes ℓ(0) = 1
        else:
            L = L - pad * jnp.log(2.0)  # logistic ℓ(0) = log 2
    return L, g


# -------------------------------------------------------- flash attention
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fa_diff(qT, kT, vT, causal, window, bq, bk):
    return _fa.flash_attention(qT, kT, vT, causal=causal, window=window,
                               block_q=bq, block_k=bk, interpret=_interpret())


def _fa_fwd(qT, kT, vT, causal, window, bq, bk):
    return _fa_diff(qT, kT, vT, causal, window, bq, bk), (qT, kT, vT)


def _fa_bwd(causal, window, bq, bk, res, g):
    # padded rows/columns are safe: the caller slices padded outputs away,
    # so their cotangent is zero, and causal masking keeps padded keys out
    # of every real query's softmax
    _, vjp = jax.vjp(
        lambda q, k, v: _ref.flash_attention(q, k, v, causal=causal,
                                             window=window), *res)
    return vjp(g)


_fa_diff.defvjp(_fa_fwd, _fa_bwd)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128):
    """q: (B, S, H, hd); k, v: (B, S, KV, hd) — model layout (seq-major).
    Expands GQA KV heads and pads S to block multiples."""
    CALLS["flash_attention"] += 1
    B, S, H, hd = q.shape
    KV = k.shape[2]
    if KV != H:
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    # (B, S, H, hd) -> (B, H, S, hd)
    qT, kT, vT = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    bq = min(block_q, S)
    bk = min(block_k, S)
    pad = (-S) % max(bq, bk)
    if pad:
        qT = jnp.pad(qT, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kT = jnp.pad(kT, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vT = jnp.pad(vT, ((0, 0), (0, 0), (0, pad), (0, 0)))
    out = _fa_diff(qT, kT, vT, causal, window, bq, bk)
    if pad:
        out = out[:, :, :S]
    return jnp.swapaxes(out, 1, 2)      # back to (B, S, H, hd)


# --------------------------------------------------------------- ssm scan
@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssm_diff(u, delta, B_ssm, C_ssm, A_log, D, bd):
    # pad S to the kernel's time block: zero steps after the end never
    # reach a real output (the scan is causal), and are sliced back off
    S = u.shape[1]
    ts, S_pad = _time_block(S)
    y = _ss.ssm_scan(*(_pad_time(x, S_pad) for x in (u, delta, B_ssm, C_ssm)),
                     A_log, D, block_d=bd, block_t=ts,
                     interpret=_interpret())
    return y[:, :S]


def _ssm_fwd(u, delta, B_ssm, C_ssm, A_log, D, bd):
    y = _ssm_diff(u, delta, B_ssm, C_ssm, A_log, D, bd)
    return y, (u, delta, B_ssm, C_ssm, A_log, D)


def _ssm_bwd(bd, res, g):
    _, vjp = jax.vjp(_ref.ssm_scan, *res)
    return vjp(g)


_ssm_diff.defvjp(_ssm_fwd, _ssm_bwd)


def ssm_scan(u, delta, B_ssm, C_ssm, A_log, D, *, block_d: int = 256):
    CALLS["ssm_scan"] += 1
    return _ssm_diff(u, delta, B_ssm, C_ssm, A_log, D,
                     _lane_block(u.shape[-1], block_d))


# ------------------------------------------------------------- rglru scan
@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rglru_diff(a, b, bw):
    S = a.shape[1]
    ts, S_pad = _time_block(S)
    y = _rg.rglru_scan(_pad_time(a, S_pad), _pad_time(b, S_pad), block_w=bw,
                       block_t=ts, interpret=_interpret())
    return y[:, :S]


def _rglru_fwd(a, b, bw):
    return _rglru_diff(a, b, bw), (a, b)


def _rglru_bwd(bw, res, g):
    _, vjp = jax.vjp(_ref.rglru_scan, *res)
    return vjp(g)


_rglru_diff.defvjp(_rglru_fwd, _rglru_bwd)


def rglru_scan(a, b, *, block_w: int = 256):
    CALLS["rglru_scan"] += 1
    return _rglru_diff(a, b, _lane_block(a.shape[-1], block_w))
