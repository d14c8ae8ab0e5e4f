"""The arithmetic a plain reference computes in.

``float32``: every product at ``Precision.HIGHEST`` (on a TPU a float32
matmul is otherwise done in bfloat16 passes). ``fp8`` is the *control*
of a configuration that states bfloat16: the same mathematics with both
operands of every product rounded to float8 (e4m3, scaled per tensor to
its full range), the step a later PR would be tempted to take. The
rounding is straight-through for gradients, so the backward pass sees
the rounded values and nothing coarser: the mildest fp8 there is.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "fp8")
_HI = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def operand(x, precision: str):
    if precision == "float32":
        return x
    if precision == "fp8":
        return _fp8(x)
    raise ValueError(f"precision must be one of {PRECISIONS}")


def matmul(a, b, precision: str):
    return jnp.matmul(operand(a, precision), operand(b, precision),
                      precision=_HI)


def einsum(eq: str, a, b, precision: str):
    return jnp.einsum(eq, operand(a, precision), operand(b, precision),
                      precision=_HI)


def conv(x, w, strides, padding, precision: str):
    return jax.lax.conv_general_dilated(
        operand(x, precision), operand(w, precision), strides, padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=_HI)
