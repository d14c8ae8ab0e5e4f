"""FusedLayerNorm — layer normalization with a hand-written VJP.

TPU-native counterpart of the reference's ``fused_layer_norm_cuda``
extension (reference: apex/normalization/fused_layer_norm.py:12-166,
csrc/layer_norm_cuda.cpp:7-98, csrc/layer_norm_cuda_kernel.cu:11-637).
The reference computes a single-pass Welford mean/invvar per row, saves
``(input, mean, invvar)`` for backward, and runs a two-stage reduction for
the gamma/beta grads. Here the same structure is expressed as a
``jax.custom_vjp``:

- forward normalizes in fp32 (``MATH_T = float`` in every reference kernel)
  over the trailing ``normalized_shape`` dims, saving (x, weight, mean,
  invvar) — mean/invvar in fp32 like the reference's
  ``at::ScalarType::Float`` save buffers (layer_norm_cuda.cpp:36-44);
- backward computes grad_input per row plus the full-batch reductions for
  grad_weight/grad_bias; XLA tiles/fuses the reductions, playing the role of
  the reference's hand-rolled warp-shuffle + shared-memory two-stage kernels
  (layer_norm_cuda_kernel.cu:403-637).

As in layer_norm_cuda.cpp:7-27 the trailing ``len(normalized_shape)`` dims
are the normalized axis; everything before is batch.

There is one implementation, this one, on every platform: on the v5e XLA's
fusion of it matched hand-written row kernels at F in {8192, 32768}
(0.96-0.98x) and won 7x at F=1024 x 8192 rows (docs/PERF.md r03), so no
kernel is kept beside it.
"""

from __future__ import annotations

import numbers
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def _norm_axes(x_shape: tuple[int, ...], normalized_shape: tuple[int, ...]):
    """Validate trailing dims; return the normalized axes tuple."""
    k = len(normalized_shape)
    if k == 0 or len(x_shape) < k or \
            tuple(x_shape[-k:]) != tuple(normalized_shape):
        raise ValueError(
            f"input trailing dims {x_shape[-k:] if k else ()} do not match "
            f"normalized_shape {normalized_shape}")
    return tuple(range(len(x_shape) - k, len(x_shape)))


def _canon_shape(normalized_shape) -> tuple[int, ...]:
    if isinstance(normalized_shape, numbers.Integral):
        return (int(normalized_shape),)
    return tuple(int(d) for d in normalized_shape)


def _ln_fwd_math(x, weight, bias, normalized_shape, eps):
    axes = _norm_axes(x.shape, normalized_shape)
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=axes, keepdims=True)
    invvar = jax.lax.rsqrt(var + eps)
    xhat = (xf - mean) * invvar
    if weight is not None:
        out = xhat * weight.astype(jnp.float32) + bias.astype(jnp.float32)
    else:
        out = xhat
    return out.astype(x.dtype), mean, invvar


# -- affine (weight + bias) -------------------------------------------------

def _ln_affine_call(x, weight, bias, normalized_shape, eps):
    out, _, _ = _ln_fwd_math(x, weight, bias, normalized_shape, eps)
    return out


def _ln_affine_fwd(x, weight, bias, normalized_shape, eps):
    out, mean, invvar = _ln_fwd_math(x, weight, bias, normalized_shape, eps)
    # ctx.save_for_backward(input, weight, bias, mean, invvar) — reference
    # fused_layer_norm.py:21-22; bias is kept only so its grad lands in the
    # bias dtype (it can differ from weight.dtype).
    return out, (x, weight, bias, mean, invvar)


def _ln_affine_bwd(normalized_shape, eps, res, dy):
    x, weight, bias, mean, invvar = res
    bias_dtype = bias.dtype
    axes = _norm_axes(x.shape, normalized_shape)
    batch_axes = tuple(range(len(x.shape) - len(normalized_shape)))

    xf = x.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    xhat = (xf - mean) * invvar

    # gamma/beta grads reduce over batch dims (the reference's two-stage
    # part-reduction, layer_norm_cuda_kernel.cu:403-560; XLA's reduce here).
    grad_weight = jnp.sum(dyf * xhat, axis=batch_axes).astype(weight.dtype)
    grad_bias = jnp.sum(dyf, axis=batch_axes).astype(bias_dtype)

    # grad_input per row (layer_norm_cuda_kernel.cu:561-637 math):
    # dxhat = dy*gamma; dx = invvar*(dxhat - mean(dxhat) - xhat*mean(dxhat*xhat))
    dxhat = dyf * weight.astype(jnp.float32)
    mean_dxhat = jnp.mean(dxhat, axis=axes, keepdims=True)
    mean_dxhat_xhat = jnp.mean(dxhat * xhat, axis=axes, keepdims=True)
    dx = invvar * (dxhat - mean_dxhat - xhat * mean_dxhat_xhat)
    return dx.astype(x.dtype), grad_weight, grad_bias


_affine = jax.custom_vjp(_ln_affine_call, nondiff_argnums=(3, 4))
_affine.defvjp(_ln_affine_fwd, _ln_affine_bwd)


# -- non-affine -------------------------------------------------------------

def _ln_plain_call(x, normalized_shape, eps):
    out, _, _ = _ln_fwd_math(x, None, None, normalized_shape, eps)
    return out


def _ln_plain_fwd(x, normalized_shape, eps):
    out, mean, invvar = _ln_fwd_math(x, None, None, normalized_shape, eps)
    return out, (x, mean, invvar)


def _ln_plain_bwd(normalized_shape, eps, res, dy):
    x, mean, invvar = res
    axes = _norm_axes(x.shape, normalized_shape)
    xf = x.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    xhat = (xf - mean) * invvar
    mean_dy = jnp.mean(dyf, axis=axes, keepdims=True)
    mean_dy_xhat = jnp.mean(dyf * xhat, axis=axes, keepdims=True)
    dx = invvar * (dyf - mean_dy - xhat * mean_dy_xhat)
    return (dx.astype(x.dtype),)


_plain = jax.custom_vjp(_ln_plain_call, nondiff_argnums=(1, 2))
_plain.defvjp(_ln_plain_fwd, _ln_plain_bwd)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def fused_layer_norm_affine(x, normalized_shape, weight, bias,
                            eps: float = 1e-6):
    """Functional affine layernorm. Signature matches the reference
    EXACTLY — (input, normalized_shape, weight, bias, eps=1e-6), the
    pre-0.1-apex order (apex/normalization/fused_layer_norm.py:64) — so
    positional migrations are drop-in."""
    ns = _canon_shape(normalized_shape)
    return _affine(x, weight, bias, ns, float(eps))


def fused_layer_norm(x, normalized_shape, eps: float = 1e-6):
    """Functional non-affine layernorm (reference:
    apex.normalization.fused_layer_norm, fused_layer_norm.py:67; same
    signature and 1e-6 default)."""
    ns = _canon_shape(normalized_shape)
    return _plain(x, ns, float(eps))


class FusedLayerNorm:
    """Module facade matching the reference ``FusedLayerNorm``
    (fused_layer_norm.py:12: normalized_shape, eps, elementwise_affine).

    Functional usage::

        ln = FusedLayerNorm(512)
        params = ln.init()
        y = ln.apply(params, x)
    """

    def __init__(self, normalized_shape, eps: float = 1e-5,
                 elementwise_affine: bool = True, param_dtype=jnp.float32):
        self.normalized_shape = _canon_shape(normalized_shape)
        self.eps = float(eps)
        self.elementwise_affine = bool(elementwise_affine)
        self.param_dtype = jnp.dtype(param_dtype)

    def init(self, rng: Optional[jax.Array] = None) -> dict:
        if not self.elementwise_affine:
            return {}
        # Reference reset: weight=1, bias=0 (fused_layer_norm.py:153-161).
        return {"weight": jnp.ones(self.normalized_shape, self.param_dtype),
                "bias": jnp.zeros(self.normalized_shape, self.param_dtype)}

    def apply(self, params: dict, x: jax.Array) -> jax.Array:
        if self.elementwise_affine:
            return fused_layer_norm_affine(
                x, self.normalized_shape, params["weight"],
                params["bias"], self.eps)
        return fused_layer_norm(x, self.normalized_shape, self.eps)

    def __call__(self, params: dict, x: jax.Array) -> jax.Array:
        return self.apply(params, x)
