"""FusedLayerNorm vs plain-jnp layernorm — values and grads.

Mirrors the reference's tests/L0/run_fused_layer_norm/test_fused_layer_norm.py
(module vs torch.nn.LayerNorm, fp32 and fp16, values + backward grads).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.normalization import (FusedLayerNorm, fused_layer_norm,
                                    fused_layer_norm_affine)


def naive_ln(x, normalized_shape, weight=None, bias=None, eps=1e-5):
    axes = tuple(range(x.ndim - len(normalized_shape), x.ndim))
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    y = (xf - mean) / jnp.sqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(x.dtype)


@pytest.mark.parametrize("shape,ns", [((4, 16), (16,)),
                                      ((2, 3, 8, 32), (32,)),
                                      ((5, 4, 6), (4, 6))])
def test_forward_matches_naive(shape, ns):
    x = jnp.asarray(np.random.RandomState(0).randn(*shape), jnp.float32)
    got = fused_layer_norm(x, ns)
    # functions default to the reference's 1e-6; the MODULE keeps 1e-5
    want = naive_ln(x, ns, eps=1e-6)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_affine_forward_and_module():
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(4, 32), jnp.float32)
    w = jnp.asarray(rs.randn(32), jnp.float32)
    b = jnp.asarray(rs.randn(32), jnp.float32)
    got = fused_layer_norm_affine(x, (32,), w, b)
    want = naive_ln(x, (32,), w, b, eps=1e-6)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)

    ln = FusedLayerNorm(32)
    params = ln.init()
    y = ln.apply(params, x)  # weight=1 bias=0 -> plain ln
    np.testing.assert_allclose(y, naive_ln(x, (32,)), atol=1e-5, rtol=1e-5)


def test_grads_match_autodiff_of_naive():
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(6, 24), jnp.float32)
    w = jnp.asarray(rs.randn(24), jnp.float32)
    b = jnp.asarray(rs.randn(24), jnp.float32)

    def loss_fused(x, w, b):
        return jnp.sum(jnp.sin(fused_layer_norm_affine(x, (24,), w, b)))

    def loss_naive(x, w, b):
        return jnp.sum(jnp.sin(naive_ln(x, (24,), w, b, eps=1e-6)))

    g1 = jax.grad(loss_fused, argnums=(0, 1, 2))(x, w, b)
    g2 = jax.grad(loss_naive, argnums=(0, 1, 2))(x, w, b)
    for a, c in zip(g1, g2):
        np.testing.assert_allclose(a, c, atol=1e-4, rtol=1e-4)


def test_nonaffine_grad():
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(3, 5, 16), jnp.float32)
    # eps pinned on the oracle: the FUNCTIONS default to the reference's
    # 1e-6 (fused_layer_norm.py:64-67), the module to 1e-5
    g1 = jax.grad(lambda x: jnp.sum(fused_layer_norm(x, (16,)) ** 2))(x)
    g2 = jax.grad(lambda x: jnp.sum(
        naive_ln(x, (16,), eps=1e-6) ** 2))(x)
    np.testing.assert_allclose(g1, g2, atol=1e-4, rtol=1e-4)


def test_half_dtype_io():
    # bf16 storage, fp32 math — output dtype preserved (the reference runs
    # the same kernels on fp16 storage with float accumulation).
    x = jnp.asarray(np.random.RandomState(4).randn(8, 64), jnp.bfloat16)
    ln = FusedLayerNorm(64)
    y = ln.apply(ln.init(), x)
    assert y.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        y.astype(jnp.float32), naive_ln(x, (64,)).astype(jnp.float32),
        atol=3e-2, rtol=3e-2)


def test_under_jit_and_grad_jit():
    x = jnp.asarray(np.random.RandomState(5).randn(4, 16), jnp.float32)
    ln = FusedLayerNorm(16)
    params = ln.init()
    f = jax.jit(lambda p, x: jnp.sum(ln.apply(p, x)))
    _ = f(params, x)
    g = jax.jit(jax.grad(f))(params, x)
    assert g["weight"].shape == (16,)


def test_shape_mismatch_raises():
    x = jnp.zeros((4, 16))
    with pytest.raises(ValueError):
        fused_layer_norm(x, (8,))


def ln64(x, w=None, b=None, eps=1e-6, dy=None):
    """LayerNorm over the last axis of a 2-D ``x`` and the gradients under
    the cotangent ``dy`` (that of ``sum(y ** 2)`` where none is given), in
    float64 numpy: ``(y, dx, dw, db)``."""
    x = np.asarray(x, np.float64)
    mean = x.mean(-1, keepdims=True)
    invvar = 1.0 / np.sqrt(((x - mean) ** 2).mean(-1, keepdims=True) + eps)
    xhat = (x - mean) * invvar
    wf = 1.0 if w is None else np.asarray(w, np.float64)
    y = xhat * wf + (0.0 if b is None else np.asarray(b, np.float64))
    dy = 2.0 * y if dy is None else np.asarray(dy, np.float64)
    dxhat = dy * wf
    dx = invvar * (dxhat - dxhat.mean(-1, keepdims=True)
                   - xhat * (dxhat * xhat).mean(-1, keepdims=True))
    return y, dx, (dy * xhat).sum(0), dy.sum(0)


class TestWideAndHalfLayerNorm:
    """``fused_layer_norm`` / ``fused_layer_norm_affine`` past the widths the
    tests above reach (F to 16,384, a mean 1e5 standard deviations from zero,
    bf16 storage), forward and gradients against float64 numpy."""

    # 9344 = 73*128 is no power of two; (520, 9344) makes the batch
    # reduction of the gamma/beta grads long as well
    @pytest.mark.parametrize("rows,f", [(13, 9344), (13, 16384),
                                        (520, 9344)])
    def test_wide_f_affine(self, rows, f):
        k1, k2 = jax.random.split(jax.random.key(2))
        x = jax.random.normal(k1, (rows, f), jnp.float32)
        w = jax.random.normal(k2, (f,), jnp.float32) + 1.0
        b = jnp.linspace(-1, 1, f)

        def loss(x, w, b):
            return jnp.sum(fused_layer_norm_affine(x, (f,), w, b) ** 2)

        out = fused_layer_norm_affine(x, (f,), w, b)
        grads = jax.grad(loss, argnums=(0, 1, 2))(x, w, b)
        want, *g_want = ln64(x, w, b)
        np.testing.assert_allclose(np.asarray(out), want,
                                   rtol=2e-5, atol=2e-5)
        for a, r, name in zip(grads, g_want, ("dx", "dw", "db")):
            np.testing.assert_allclose(np.asarray(a), r, rtol=2e-4,
                                       atol=2e-4 * np.abs(r).max(),
                                       err_msg=name)

    def test_wide_f_large_mean_stability(self):
        # E[x^2]-E[x]^2 catastrophically cancels in fp32 when |mean| >> std
        # (x ~ 1000 +- 0.01 gives var off by orders of magnitude or NaN);
        # the variance of (x - mean) must stay accurate.
        f = 16384
        x = 1000.0 + 0.01 * jax.random.normal(
            jax.random.key(7), (9, f), jnp.float32)
        dy = jax.random.normal(jax.random.key(8), x.shape, jnp.float32)
        out, vjp = jax.vjp(lambda x: fused_layer_norm(x, (f,)), x)
        (dx,) = vjp(dy)
        want, dx_want, _, _ = ln64(x, dy=dy)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(np.asarray(out), want, atol=0.05)
        np.testing.assert_allclose(np.asarray(dx), dx_want, atol=5e-3
                                   * np.abs(dx_want).max())

    def test_wide_f_no_affine(self):
        f = 10240
        x = jax.random.normal(jax.random.key(3), (9, f), jnp.float32)
        # without gamma the gradient of sum(y ** 2) is zero but for eps
        dy = jax.random.normal(jax.random.key(4), x.shape, jnp.float32)
        out, vjp = jax.vjp(lambda x: fused_layer_norm(x, (f,)), x)
        (dx,) = vjp(dy)
        want, dx_want, _, _ = ln64(x, dy=dy)
        np.testing.assert_allclose(np.asarray(out), want,
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(dx), dx_want, atol=2e-4
                                   * np.abs(dx_want).max())

    def test_bf16_storage(self):
        k1, k2 = jax.random.split(jax.random.key(0))
        x = jax.random.normal(k1, (100, 256), jnp.bfloat16)
        w = jax.random.normal(k2, (256,), jnp.float32) + 1.0
        b = jnp.linspace(-1, 1, 256)

        def loss(x, w, b):
            y = fused_layer_norm_affine(x, (256,), w, b)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        out = fused_layer_norm_affine(x, (256,), w, b)
        dx, dw, db = jax.grad(loss, argnums=(0, 1, 2))(x, w, b)
        assert out.dtype == dx.dtype == jnp.bfloat16
        assert dw.dtype == db.dtype == jnp.float32
        want, dx_want, dw_want, db_want = ln64(x.astype(jnp.float32), w, b)
        # the output and dx round to bf16 (8 bits); dy = 2y carries the
        # output's rounding into the sums over 100 rows
        np.testing.assert_allclose(np.asarray(out, np.float32), want,
                                   rtol=1e-2, atol=1e-2)
        np.testing.assert_allclose(np.asarray(dx, np.float32), dx_want,
                                   rtol=2e-2, atol=2e-2
                                   * np.abs(dx_want).max())
        for a, r, name in ((dw, dw_want, "dw"), (db, db_want, "db")):
            np.testing.assert_allclose(np.asarray(a), r, atol=1e-2
                                       * np.abs(r).max(), err_msg=name)


@pytest.mark.parametrize("seed", range(6))
def test_fuzz_random_shapes_vs_torch(seed):
    """Randomized shape fuzz against the REAL torch.nn.LayerNorm oracle:
    random rank, random (possibly multi-axis, odd-sized, non-128) 
    normalized_shape, random eps, fp32 and bf16 storage — values AND
    input/weight/bias grads. The fixed cases above cover the
    lane-friendly shapes; this guards the ragged ones."""
    torch = pytest.importorskip("torch")
    rng = np.random.default_rng(6000 + seed)
    rank = int(rng.integers(2, 5))
    shape = tuple(int(rng.integers(1, 12)) for _ in range(rank - 1)) + \
        (int(rng.integers(3, 300)),)
    n_norm = int(rng.integers(1, 3))   # normalize over 1 or 2 axes
    ns = shape[-n_norm:]
    eps = float(10 ** rng.uniform(-8, -4))
    x_np = rng.normal(size=shape).astype(np.float32)
    w_np = rng.normal(size=ns).astype(np.float32)
    b_np = rng.normal(size=ns).astype(np.float32)
    dy_np = rng.normal(size=shape).astype(np.float32)

    # torch oracle with grads
    xt = torch.tensor(x_np, requires_grad=True)
    wt = torch.tensor(w_np, requires_grad=True)
    bt = torch.tensor(b_np, requires_grad=True)
    yt = torch.nn.functional.layer_norm(xt, ns, wt, bt, eps)
    yt.backward(torch.tensor(dy_np))

    x, w, b = map(jnp.asarray, (x_np, w_np, b_np))
    y = fused_layer_norm_affine(x, ns, w, b, eps)
    np.testing.assert_allclose(np.asarray(y), yt.detach().numpy(),
                               rtol=2e-5, atol=2e-5)
    gx, gw, gb = jax.vjp(
        lambda x, w, b: fused_layer_norm_affine(x, ns, w, b, eps),
        x, w, b)[1](jnp.asarray(dy_np))
    np.testing.assert_allclose(np.asarray(gx), xt.grad.numpy(),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(gw), wt.grad.numpy(),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(gb), bt.grad.numpy(),
                               rtol=2e-4, atol=2e-4)
    # bf16 storage: output matches the fp32 oracle to bf16 resolution
    y16 = fused_layer_norm_affine(x.astype(jnp.bfloat16), ns,
                                  w.astype(jnp.bfloat16),
                                  b.astype(jnp.bfloat16), eps)
    np.testing.assert_allclose(np.asarray(y16, np.float32),
                               yt.detach().numpy(), rtol=0.05, atol=0.05)
