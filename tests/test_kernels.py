"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, run by the
Pallas interpreter (ops.py picks it on the CPU)."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels import flash_attention as fa
from repro.kernels import linear_grad as lg
from repro.kernels import ssm_scan as ss

KEY = jax.random.key(42)


# ------------------------------------------------------------- linear_grad
@pytest.mark.parametrize("n,d", [(128, 16), (256, 300), (384, 64), (200, 32)])
@pytest.mark.parametrize("loss", ["squared_hinge", "logistic"])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_linear_grad_sweep(n, d, loss, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    X = jax.random.normal(k1, (n, d), dtype)
    y = jnp.sign(jax.random.normal(k2, (n,), dtype))
    w = 0.1 * jax.random.normal(k3, (d,), dtype)
    L, g = ops.linear_value_grad(X, y, w, loss=loss)
    Lr, gr = ref.linear_value_grad(X, y, w, loss=loss)
    assert jnp.allclose(L, Lr, rtol=1e-4, atol=1e-3)
    assert jnp.allclose(g, gr, rtol=1e-4, atol=1e-3)


def test_linear_grad_matches_autodiff():
    k1, k2, k3 = jax.random.split(KEY, 3)
    X = jax.random.normal(k1, (256, 40))
    y = jnp.sign(jax.random.normal(k2, (256,)))
    w = 0.1 * jax.random.normal(k3, (40,))
    _, g = ops.linear_value_grad(X, y, w)
    g_ad = jax.grad(lambda w: jnp.sum(
        jnp.maximum(0, 1 - y * (X @ w)) ** 2))(w)
    assert jnp.allclose(g, g_ad, rtol=1e-4, atol=1e-3)


# --------------------------------------------------------- flash attention
@pytest.mark.parametrize("B,H,KV,S,hd", [
    (1, 2, 2, 64, 32), (2, 4, 2, 128, 64), (1, 8, 1, 96, 64),
    (2, 3, 3, 160, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, H, KV, S, hd, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    kr = jnp.repeat(k, H // KV, axis=2)
    vr = jnp.repeat(v, H // KV, axis=2)
    want = jnp.swapaxes(ref.flash_attention(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(kr, 1, 2), jnp.swapaxes(vr, 1, 2),
        causal=True), 1, 2)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    assert jnp.allclose(out.astype(jnp.float32), want.astype(jnp.float32),
                        rtol=tol, atol=tol), float(
        jnp.max(jnp.abs(out.astype(jnp.float32) - want.astype(jnp.float32))))


@pytest.mark.parametrize("window", [16, 48])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 96, 2, 32))
    k = jax.random.normal(ks[1], (1, 96, 2, 32))
    v = jax.random.normal(ks[2], (1, 96, 2, 32))
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              block_q=32, block_k=32)
    want = jnp.swapaxes(ref.flash_attention(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        causal=True, window=window), 1, 2)
    assert jnp.allclose(out, want, rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------------- ssm scan
# S=37 and S=600 are no multiple of the kernel's 8/16-row time tiles; 600
# spans two 512-step time blocks, so the state carries across blocks
@pytest.mark.parametrize("B,S,di,N", [(1, 32, 64, 4), (2, 64, 128, 16),
                                      (1, 100, 96, 8), (2, 37, 256, 16),
                                      (1, 600, 128, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssm_scan_sweep(B, S, di, N, dtype):
    ks = jax.random.split(KEY, 4)
    u = jax.random.normal(ks[0], (B, S, di), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di))).astype(dtype)
    Bs = jax.random.normal(ks[2], (B, S, N), dtype)
    Cs = jax.random.normal(ks[3], (B, S, N), dtype)
    Al = jnp.log(jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32)[None],
                          (di, 1)))
    D = jnp.ones((di,), jnp.float32)
    out = ops.ssm_scan(u, dt, Bs, Cs, Al, D, block_d=32)
    want = ref.ssm_scan(u, dt, Bs, Cs, Al, D)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    assert jnp.allclose(out.astype(jnp.float32), want.astype(jnp.float32),
                        rtol=tol, atol=tol)


def test_ssm_scan_state_decay():
    """With large delta·|A|, the state forgets: output at t is dominated by
    recent inputs (recurrence stability sanity check)."""
    B, S, di, N = 1, 64, 32, 4
    u = jnp.zeros((B, S, di)).at[:, 0, :].set(100.0)   # impulse at t=0
    dt = jnp.ones((B, S, di)) * 2.0
    Bs = jnp.ones((B, S, N))
    Cs = jnp.ones((B, S, N))
    Al = jnp.zeros((di, N))                             # A = -1
    D = jnp.zeros((di,))
    y = ops.ssm_scan(u, dt, Bs, Cs, Al, D, block_d=32)
    assert float(jnp.abs(y[0, 0]).max()) > float(jnp.abs(y[0, -1]).max()) * 100


# -------------------------------------------------------------- rglru scan
@pytest.mark.parametrize("B,S,W", [(1, 32, 64), (2, 100, 96), (1, 64, 256),
                                   (1, 37, 256), (2, 600, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_scan_sweep(B, S, W, dtype):
    ks = jax.random.split(KEY, 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, W))).astype(dtype)
    b = jax.random.normal(ks[1], (B, S, W), dtype)
    out = ops.rglru_scan(a, b, block_w=32)
    want = ref.rglru_scan(a, b)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-5
    assert jnp.allclose(out.astype(jnp.float32), want.astype(jnp.float32),
                        rtol=tol, atol=tol)


def test_rglru_model_pallas_path_matches_xla():
    from repro import configs
    from repro.models import transformer as T
    cfg = configs.reduced(configs.get("recurrentgemma-9b"))
    params = T.init_params(cfg, jax.random.key(0))
    tok = jax.random.randint(jax.random.key(1), (2, 64), 0, 512)
    batch = {"tokens": tok, "labels": tok}
    l_x, _ = T.loss_fn(cfg, params, batch, impl="xla")
    l_p, _ = T.loss_fn(cfg, params, batch, impl="pallas", remat=False)
    assert abs(float(l_x) - float(l_p)) < 5e-2


# -------------------------------------------------- gradients (custom_vjp)
# The scan kernels carry training traffic (workload families mamba/rglru
# run impl="pallas" end to end), so their backward passes — the VJP of the
# ref oracle recomputed from the saved primals — must match differentiating
# the oracle directly.

@pytest.mark.parametrize("B,S,di,N", [(1, 32, 64, 4), (2, 48, 96, 8)])
@pytest.mark.parametrize("wrt", [0, 1, 2, 3, 4, 5])
def test_ssm_scan_grad_sweep(B, S, di, N, wrt):
    ks = jax.random.split(KEY, 4)
    args = [
        jax.random.normal(ks[0], (B, S, di)),                       # u
        jax.nn.softplus(jax.random.normal(ks[1], (B, S, di))),      # delta
        jax.random.normal(ks[2], (B, S, N)),                        # B
        jax.random.normal(ks[3], (B, S, N)),                        # C
        jnp.log(jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32)[None],
                         (di, 1))),                                 # A_log
        0.5 * jnp.ones((di,), jnp.float32),                         # D
    ]
    g = jax.grad(lambda *a: ops.ssm_scan(*a, block_d=32).sum(),
                 argnums=wrt)(*args)
    g_ref = jax.grad(lambda *a: ref.ssm_scan(*a).sum(), argnums=wrt)(*args)
    assert g.shape == args[wrt].shape
    assert jnp.allclose(g, g_ref, rtol=1e-4, atol=1e-4), float(
        jnp.max(jnp.abs(g - g_ref)))


@pytest.mark.parametrize("B,S,W", [(1, 32, 64), (2, 48, 96)])
@pytest.mark.parametrize("wrt", [0, 1])
def test_rglru_scan_grad_sweep(B, S, W, wrt):
    ks = jax.random.split(KEY, 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, W)))
    b = jax.random.normal(ks[1], (B, S, W))
    g = jax.grad(lambda a, b: ops.rglru_scan(a, b, block_w=32).sum(),
                 argnums=wrt)(a, b)
    g_ref = jax.grad(lambda a, b: ref.rglru_scan(a, b).sum(),
                     argnums=wrt)(a, b)
    assert g.shape == (a, b)[wrt].shape
    assert jnp.allclose(g, g_ref, rtol=1e-4, atol=1e-4), float(
        jnp.max(jnp.abs(g - g_ref)))


@pytest.mark.parametrize("window", [0, 16])
def test_flash_attention_grad(window):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 64, 2, 32))
    k = jax.random.normal(ks[1], (1, 64, 1, 32))
    v = jax.random.normal(ks[2], (1, 64, 1, 32))

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    def pallas(q, k, v):
        return ops.flash_attention(q, k, v, causal=True, window=window,
                                   block_q=32, block_k=32)

    def oracle(q, k, v):
        kr, vr = jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)
        return jnp.swapaxes(ref.flash_attention(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(kr, 1, 2),
            jnp.swapaxes(vr, 1, 2), causal=True, window=window), 1, 2)

    gq, gk, gv = jax.grad(loss(pallas), argnums=(0, 1, 2))(q, k, v)
    rq, rk, rv = jax.grad(loss(oracle), argnums=(0, 1, 2))(q, k, v)
    for got, want in ((gq, rq), (gk, rk), (gv, rv)):
        assert got.shape == want.shape
        assert jnp.allclose(got, want, rtol=1e-3, atol=1e-3), float(
            jnp.max(jnp.abs(got - want)))


def test_kernel_dispatch_counter():
    """ops.CALLS counts trace-time dispatches — the sweep's evidence that
    a family's training traffic routed through its kernel."""
    ops.reset_calls()
    ks = jax.random.split(KEY, 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (1, 16, 32)))
    b = jax.random.normal(ks[1], (1, 16, 32))
    ops.rglru_scan(a, b)
    assert ops.CALLS["rglru_scan"] == 1
    jax.jit(lambda a, b: ops.rglru_scan(a, b))(a, b)
    assert ops.CALLS["rglru_scan"] == 2
    ops.reset_calls()
    assert not ops.CALLS
