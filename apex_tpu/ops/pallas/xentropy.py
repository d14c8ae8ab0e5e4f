"""Pallas fused softmax-cross-entropy kernels (blocked vocab).

TPU twin of the reference's ``xentropy_cuda`` kernel
(apex/contrib/csrc/xentropy/xentropy_kernel.cu:429-493): the forward is an
online max/logsumexp sweep over vocab tiles (the flash-attention trick the
reference implements with ``blockReduceMax``/``blockReduceSum``), emitting
per-row loss and the ``max_log_sum_exp`` residual; the backward recomputes
the probabilities from logits + logsumexp tile by tile — O(N) residual
memory instead of the O(N*V) softmax, and for LM-vocab logits the fwd+bwd
HBM traffic is one read of the logits each way.

Loss with label smoothing eps (xentropy_kernel.cu:428-433):
  loss_i = lse_i - (1-eps) * x_i[y_i] - eps * mean_j(x_ij)
Backward (xentropy_kernel.cu:445-493):
  dx_ij = g_i * (softmax_ij - (1-eps)*1[j==y_i] - eps/V)

Grid: (row blocks, vocab blocks), vocab innermost; running (max, scaled
sumexp, target-logit, sum-logits) accumulators live in lane-replicated
output blocks revisited across the vocab sweep (TPU grids are sequential).
Vocab padding is masked with -inf for max/sumexp and 0 for sums.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.pallas._common import (LANES, block_rows as _block_rows_c,
                                         interpret_mode as _interpret,
                                         pad2d as _pad2d,
                                         vma as _vma)

VBLK = 2048
MIN_VOCAB = 512  # below this the pad-to-VBLK waste dwarfs the fusion win

_NEG = -1e30  # -inf stand-in that survives fp32 arithmetic


def _block_rows(n: int, streams: int) -> int:
    return _block_rows_c(n, VBLK, streams)


def supported(n_rows: int, vocab: int) -> bool:
    return n_rows > 0 and vocab >= MIN_VOCAB


def _cols(shape, j):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1) + j * shape[1]


def _fwd_kernel(vocab, smoothing, x_ref, lbl_ref,
                loss_ref, lse_ref, m_ref, s_ref, t_ref, sx_ref):
    # m/s/t/sx are VMEM scratch accumulators persisting across the
    # sequential vocab sweep (same idiom as the flash fwd kernel)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        s_ref[...] = jnp.zeros_like(s_ref)
        t_ref[...] = jnp.zeros_like(t_ref)
        sx_ref[...] = jnp.zeros_like(sx_ref)

    xf = x_ref[...].astype(jnp.float32)
    cols = _cols(xf.shape, j)
    valid = cols < vocab
    xneg = jnp.where(valid, xf, _NEG)

    m_old = m_ref[:, :1]
    m_new = jnp.maximum(m_old, jnp.max(xneg, axis=1, keepdims=True))
    scale = jnp.exp(m_old - m_new)
    s_new = s_ref[:, :1] * scale + \
        jnp.sum(jnp.exp(xneg - m_new), axis=1, keepdims=True)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    s_ref[...] = jnp.broadcast_to(s_new, s_ref.shape)

    lbl = lbl_ref[:, :1]
    t_ref[...] += jnp.broadcast_to(
        jnp.sum(jnp.where(cols == lbl, xf, 0.0), axis=1, keepdims=True),
        t_ref.shape)
    if smoothing > 0.0:
        sx_ref[...] += jnp.broadcast_to(
            jnp.sum(jnp.where(valid, xf, 0.0), axis=1, keepdims=True),
            sx_ref.shape)

    @pl.when(j == nj - 1)
    def _():
        lse = m_ref[:, :1] + jnp.log(s_ref[:, :1])
        loss = lse - (1.0 - smoothing) * t_ref[:, :1]
        if smoothing > 0.0:
            loss = loss - smoothing * sx_ref[:, :1] / vocab
        loss_ref[...] = jnp.broadcast_to(loss, loss_ref.shape)
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)


def xent_fwd(logits: jax.Array, labels: jax.Array, smoothing: float):
    """logits [N, V], labels [N] int. Returns (losses [N] f32, lse [N] f32).

    Rows whose loss must be masked (padding_idx) are handled by the caller
    — the kernel computes the raw loss for every row.
    """
    n, v = logits.shape
    rows = _block_rows(n, streams=1)
    rpad, vpad = (-n) % rows, (-v) % VBLK
    xx = _pad2d(logits, rpad, vpad)
    np_, vp_ = n + rpad, v + vpad
    lbl = jnp.broadcast_to(
        jnp.pad(labels.astype(jnp.int32), (0, rpad))[:, None], (np_, LANES))
    grid = (np_ // rows, vp_ // VBLK)
    vma = _vma(logits)

    loss, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, v, float(smoothing)),
        grid=grid,
        in_specs=[pl.BlockSpec((rows, VBLK), lambda i, j: (i, j)),
                  pl.BlockSpec((rows, LANES), lambda i, j: (i, 0))],
        out_specs=[pl.BlockSpec((rows, LANES), lambda i, j: (i, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((np_, LANES), jnp.float32,
                                        vma=vma)] * 2,
        scratch_shapes=[pltpu.VMEM((rows, LANES), jnp.float32)] * 4,
        interpret=_interpret(),
        name="apex_xent_fwd",
    )(xx, lbl)
    return loss[:n, 0], lse[:n, 0]


def _bwd_kernel(vocab, smoothing, x_ref, lbl_ref, lse_ref, g_ref, dx_ref):
    j = pl.program_id(1)
    xf = x_ref[...].astype(jnp.float32)
    cols = _cols(xf.shape, j)
    probs = jnp.exp(xf - lse_ref[:, :1])
    onehot = jnp.where(cols == lbl_ref[:, :1], 1.0, 0.0)
    dx = probs - (1.0 - smoothing) * onehot
    if smoothing > 0.0:
        dx = dx - smoothing / vocab
    dx_ref[...] = (g_ref[:, :1] * dx).astype(dx_ref.dtype)


def xent_bwd(logits, labels, lse, g, smoothing: float):
    """dx [N, V] in logits dtype. ``g`` must already be zero on padded
    rows (the caller applies the padding_idx mask)."""
    n, v = logits.shape
    rows = _block_rows(n, streams=2)
    rpad, vpad = (-n) % rows, (-v) % VBLK
    xx = _pad2d(logits, rpad, vpad)
    np_, vp_ = n + rpad, v + vpad
    lbl = jnp.broadcast_to(
        jnp.pad(labels.astype(jnp.int32), (0, rpad))[:, None], (np_, LANES))
    lse_l = jnp.broadcast_to(
        jnp.pad(lse, (0, rpad))[:, None], (np_, LANES))
    g_l = jnp.broadcast_to(
        jnp.pad(g.astype(jnp.float32), (0, rpad))[:, None], (np_, LANES))
    grid = (np_ // rows, vp_ // VBLK)
    vma = _vma(logits, g)

    dx = pl.pallas_call(
        functools.partial(_bwd_kernel, v, float(smoothing)),
        grid=grid,
        in_specs=[pl.BlockSpec((rows, VBLK), lambda i, j: (i, j)),
                  pl.BlockSpec((rows, LANES), lambda i, j: (i, 0)),
                  pl.BlockSpec((rows, LANES), lambda i, j: (i, 0)),
                  pl.BlockSpec((rows, LANES), lambda i, j: (i, 0))],
        out_specs=pl.BlockSpec((rows, VBLK), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((np_, vp_), logits.dtype, vma=vma),
        interpret=_interpret(),
        name="apex_xent_bwd",
    )(xx, lbl, lse_l, g_l)
    return dx[:n, :v]
