"""Fused softmax + cross-entropy with label smoothing.

TPU-native counterpart of the reference's ``xentropy_cuda`` extension
(reference: apex/contrib/xentropy/softmax_xentropy.py:4-37,
apex/contrib/csrc/xentropy/xentropy_kernel.cu). The defining trick is
memory: the kernel saves only the per-row ``max_log_sum_exp`` scalar
instead of the softmax output (xentropy_kernel.cu:429 "reserve max +
log_sum_exp for bprop") and the backward recomputes the probabilities from
logits + logsumexp. Here that is a ``jax.custom_vjp`` whose residuals are
(logits, logsumexp fp32, labels) — O(N) extra memory instead of O(N*C),
the same saving.

Loss formula with smoothing eps (xentropy_kernel.cu:428-433):
  loss_i = logsumexp_i - (1-eps) * x_i[y_i] - eps * mean_j(x_ij)
Backward (xentropy_kernel.cu:445-493):
  dx_ij = grad_i * (softmax_ij - (1-eps) * 1[j==y_i] - eps/C)

``padding_idx`` rows get zero loss and zero gradient (reference
softmax_xentropy.py:9,26: masked_fill on labels==padding_idx). The
reference defaults padding_idx=0, which silently drops class-0 rows —
kept here for drop-in parity, but pass ``padding_idx=None`` (our
extension) to disable masking.

XLA runs this on every platform: on the v5e its fusion of the logsumexp and
the recompute ran forward + backward ~1.2x faster than blocked kernels at 32k
and 256k classes (docs/PERF.md r03; the saved logsumexp already gives the
memory saving, a kernel added boundary cost and no fusion).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def select_label_logits(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """``logits[..., i, labels[i]]`` as a masked reduction.

    A row-gather on the minor axis lowers to a scalar-at-a-time TPU
    gather (~2 GB/s; the r4 trace measured 3 ms for 256 rows of it in
    the RN50 bench loss). The iota-compare + select fuses into the
    consumer's reduction and streams ``logits`` at full HBM bandwidth.
    """
    mask = jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, logits.ndim - 1) \
        == labels[..., None].astype(jnp.int32)
    return jnp.sum(jnp.where(mask, logits, 0).astype(jnp.float32), axis=-1)


def _fwd_math(logits, labels, smoothing):
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    target = select_label_logits(lf, labels)
    if smoothing > 0.0:
        mean_logits = jnp.mean(lf, axis=-1)
        losses = lse - (1.0 - smoothing) * target - smoothing * mean_logits
    else:
        losses = lse - target
    return losses, lse


def _xent_call(logits, labels, smoothing, padding_idx):
    losses, _ = _fwd_math(logits, labels, smoothing)
    if padding_idx is not None:
        losses = jnp.where(labels == padding_idx, 0.0, losses)
    return losses


def _xent_fwd(logits, labels, smoothing, padding_idx):
    losses, lse = _fwd_math(logits, labels, smoothing)
    if padding_idx is not None:
        losses = jnp.where(labels == padding_idx, 0.0, losses)
    # residuals: logits + per-row logsumexp, NOT the (N, C) softmax —
    # the reference's max_log_sum_exp memory saving.
    return losses, (logits, lse, labels)


def _xent_bwd(smoothing, padding_idx, res, grad_loss):
    logits, lse, labels = res
    classes = logits.shape[-1]
    g = grad_loss.astype(jnp.float32)
    if padding_idx is not None:
        g = jnp.where(labels == padding_idx, 0.0, g)
    # recompute softmax from saved logsumexp (the bprop epilogue,
    # xentropy_kernel.cu:445-493)
    probs = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    onehot = jax.nn.one_hot(labels, classes, dtype=jnp.float32)
    dx = probs - (1.0 - smoothing) * onehot
    if smoothing > 0.0:
        dx = dx - smoothing / classes
    dx = g[..., None] * dx
    return dx.astype(logits.dtype), None


_xent = jax.custom_vjp(_xent_call, nondiff_argnums=(2, 3))
_xent.defvjp(_xent_fwd, _xent_bwd)


def softmax_cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                               smoothing: float = 0.0,
                               padding_idx: Optional[int] = 0,
                               half_to_float: bool = False) -> jax.Array:
    """Per-row losses (no reduction), reference
    ``SoftmaxCrossEntropyLoss.apply`` (softmax_xentropy.py:5-20).

    ``half_to_float=True`` returns fp32 losses from half logits (the
    reference flag, xentropy_kernel.cu:580); the default False keeps the
    logit dtype, matching the reference Function's default
    (softmax_xentropy.py:6).
    """
    losses = _xent(logits, labels, float(smoothing), padding_idx)
    if not half_to_float:
        losses = losses.astype(logits.dtype)
    return losses


class SoftmaxCrossEntropyLoss:
    """Class facade mirroring the reference autograd Function's call
    signature (softmax_xentropy.py:4)."""

    @staticmethod
    def apply(logits, labels, smoothing=0.0, padding_idx=0,
              half_to_float=False):
        return softmax_cross_entropy_loss(logits, labels, smoothing,
                                          padding_idx, half_to_float)

    def __call__(self, logits, labels, **kw):
        return softmax_cross_entropy_loss(logits, labels, **kw)
