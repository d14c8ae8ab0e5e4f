"""Fused LM-head projection + softmax cross entropy, without the logits.

The standard LM loss materializes fp32 logits ``[N, V]`` (N = B*T): at
B=8, T=4095, V=32768 that is a 4 GB HLO temp plus a same-shaped backward
temp — the allocation that OOMed the round-4 ``lm_bench --seq 4096`` run
on a 16 GB chip. Neither op here builds that matrix. They walk it in
opposite directions, because a softmax's gradient needs the whole row's
logsumexp:

``linear_cross_entropy`` (per-row losses) scans the **vocabulary** in
chunks of ``chunk`` columns, keeping an online (max, sumexp) pair per row
— the same online-logsumexp recurrence the flash-attention kernel uses
over keys — plus the label's logit. A chunk of columns has no row's
logsumexp until the last chunk is done, and a per-row cotangent is not
known before the backward, so the backward makes each chunk's logits
again from the saved per-row logsumexp: FOUR vocabulary-wide matmuls a
step (logits, logits again, ``dh``, ``dW``), peak memory O(N*chunk).

``weighted_linear_cross_entropy`` (the reduced form, ``sum_n
row_weights[n] * loss_n``: what every training loss does with the rows at
once) walks blocks of **rows** and holds a block's whole-vocabulary
logits ``[R, V]``. The block has its rows' logsumexp, and the rows'
weights are data, so ``dz`` is known in the same pass: the forward rule
makes ``dh`` and ``dW`` beside the loss and the backward only scales them
by the scalar cotangent. THREE vocabulary-wide matmuls a step, peak
memory O(R*V) + a float32 ``[V, D]`` accumulator (R is 1,024 or 2,048 by
the vocabulary: ``_block_rows``); ``V`` need not divide by anything. This is the same sum in another order, not an
approximation: float32 logits, softmax and accumulation in both.

Loss/grad semantics match ``softmax_cross_entropy_loss`` exactly
(reference apex/contrib/xentropy label-smoothing convention:
``lse - (1-eps)*z_y - eps*mean(z)``), pinned by parity tests.

This is scan + MXU matmuls, not a Pallas kernel: each step is matmuls
XLA fuses the softmax passes around — the measured round-3 lesson
(docs/PERF.md r03: XLA beats hand kernels for everything it can fuse;
the win here is the algorithmic memory bound, which no per-op fusion can
deliver).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp


# Rows a step of the reduced op, at most: from the vocabulary, by where a
# block's float32 logits [R, V] live. XLA keeps a loop's temporary of up to
# 96 MiB in the v5e's VMEM (128 MiB; `S(1)` on its layout in the compiled
# step), and the three passes over the logits (sum-exp, and dz made inside
# the dh and the dW matmul) then cost no HBM time: 1,024 rows fit up to
# V = 24,576. A step also reads and writes the float32 [V, D] accumulator
# of dW (8 V D bytes of HBM) against the 2 R V D FLOPs of its matmul: on
# the v5e (197 TF/s, 819 GB/s) the bytes outlast the FLOPs below R ~ 960,
# so a block is never under 1,024 rows. A larger vocabulary's logits go to
# HBM at any such R, and there fewer, larger steps win: 2,048 (412 MB at
# V = 50,257). Read on the chip inside the traced step (PR 51), the
# head's ms a step at 1,024 | 2,048 | 4,096 rows: V 50,257 x D 2,048
# 34.3 | 31.1 | 35.7; V 18,992 x 2,048 23.8 | 28.6 | 27.3; V 20,480 x
# 2,304 29.2 | 35.6 | -. The steps come from N: the fewest of at most that
# many rows, the last step's missing rows (fewer than the steps) padded at
# weight zero, so a prime N (8,191 tokens a sequence) costs nothing.
VMEM_LOGITS = 96 << 20


def _block_rows(v: int) -> int:
    return 1024 if 4 * 1024 * v <= VMEM_LOGITS else 2048


def _validate(h, w, labels):
    if h.ndim != 2 or w.ndim != 2 or h.shape[1] != w.shape[1]:
        raise ValueError(f"expected h [N, D] and w [V, D] with matching D; "
                         f"got {h.shape} and {w.shape}")
    if labels.shape != (h.shape[0],):
        raise ValueError(f"labels must be [N]={h.shape[0]}, "
                         f"got {labels.shape}")


def _chunk_logits(h, w_c):
    # bf16 inputs ride the MXU; accumulate fp32.
    return jax.lax.dot_general(
        h, w_c, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _fwd_scan(h, w, labels, chunk):
    """Online logsumexp over vocab chunks.

    Returns (lse [N], zy [N] label logit, zsum [N] sum of logits)."""
    n, _ = h.shape
    v = w.shape[0]
    nc = v // chunk
    wc = w.reshape(nc, chunk, w.shape[1])
    lab = labels.astype(jnp.int32)

    def body(carry, xs):
        m, s, zy, zsum = carry
        i, w_c = xs
        z = _chunk_logits(h, w_c)                        # [N, C] fp32
        off = i * chunk
        m_new = jnp.maximum(m, jnp.max(z, axis=-1))
        s = s * jnp.exp(m - m_new) + \
            jnp.sum(jnp.exp(z - m_new[:, None]), axis=-1)
        # masked reduction, not take_along_axis: a minor-axis row-gather
        # is a ~2 GB/s scalar gather on TPU (see select_label_logits);
        # the global-column compare also subsumes the in-chunk test
        cols = off + jnp.arange(chunk, dtype=jnp.int32)[None, :]
        zy = zy + jnp.sum(jnp.where(cols == lab[:, None], z, 0.0), axis=-1)
        zsum = zsum + jnp.sum(z, axis=-1)
        return (m_new, s, zy, zsum), None

    init = (jnp.full((n,), -jnp.inf, jnp.float32),
            jnp.zeros((n,), jnp.float32),
            jnp.zeros((n,), jnp.float32),
            jnp.zeros((n,), jnp.float32))
    (m, s, zy, zsum), _ = jax.lax.scan(
        body, init, (jnp.arange(nc), wc))
    return m + jnp.log(s), zy, zsum


def _dlogits(z, lse, hit, v, smoothing):
    """d loss / d logits of rows with logsumexp ``lse`` whose label's
    column is where ``hit`` holds."""
    dz = jnp.exp(z - lse[:, None]) \
        - (1.0 - smoothing) * hit.astype(jnp.float32)
    if smoothing > 0.0:
        dz = dz - smoothing / v
    return dz


def _losses(lse, zy, zsum, v, smoothing):
    if smoothing > 0.0:
        return lse - (1.0 - smoothing) * zy - smoothing * (zsum / v)
    return lse - zy


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _linear_xent(h, w, labels, smoothing, padding_idx, chunk):
    lse, zy, zsum = _fwd_scan(h, w, labels, chunk)
    losses = _losses(lse, zy, zsum, w.shape[0], smoothing)
    if padding_idx is not None:
        losses = jnp.where(labels == padding_idx, 0.0, losses)
    return losses


def _linear_xent_fwd(h, w, labels, smoothing, padding_idx, chunk):
    lse, zy, zsum = _fwd_scan(h, w, labels, chunk)
    losses = _losses(lse, zy, zsum, w.shape[0], smoothing)
    if padding_idx is not None:
        losses = jnp.where(labels == padding_idx, 0.0, losses)
    # residuals: inputs + per-row lse only — never the [N, V] logits
    return losses, (h, w, labels, lse)


def _linear_xent_bwd(smoothing, padding_idx, chunk, res, g):
    h, w, labels, lse = res
    n, d = h.shape
    v = w.shape[0]
    nc = v // chunk
    wc = w.reshape(nc, chunk, d)
    lab = labels.astype(jnp.int32)
    g = g.astype(jnp.float32)
    if padding_idx is not None:
        g = jnp.where(labels == padding_idx, 0.0, g)

    def body(dh, xs):
        i, w_c = xs
        z = _chunk_logits(h, w_c)                        # recompute [N, C]
        off = i * chunk
        in_chunk = (lab >= off) & (lab < off + chunk)
        idx = jnp.clip(lab - off, 0, chunk - 1)
        onehot = (jnp.arange(chunk)[None, :] == idx[:, None]) & \
            in_chunk[:, None]
        dz = _dlogits(z, lse, onehot, v, smoothing) * g[:, None]
        dh = dh + jax.lax.dot_general(
            dz, w_c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [N, D]
        dw_c = jax.lax.dot_general(
            dz, h, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [C, D]
        return dh, dw_c.astype(w.dtype)

    dh, dwc = jax.lax.scan(body, jnp.zeros((n, d), jnp.float32),
                           (jnp.arange(nc), wc))
    return dh.astype(h.dtype), dwc.reshape(v, d), None


_linear_xent.defvjp(_linear_xent_fwd, _linear_xent_bwd)


def linear_cross_entropy(hidden: jax.Array, weight: jax.Array,
                         labels: jax.Array, *, smoothing: float = 0.0,
                         padding_idx: Optional[int] = None,
                         chunk: int = 8192) -> jax.Array:
    """Per-row ``xent(hidden @ weight.T, labels)`` without the logits.

    Args:
      hidden: ``[N, D]`` final hidden states (any float dtype; matmuls
        accumulate fp32).
      weight: ``[V, D]`` head weight — for tied embeddings pass the token
        embedding table directly.
      labels: ``[N]`` int class ids.
      smoothing: label smoothing epsilon (same convention as
        ``softmax_cross_entropy_loss``).
      padding_idx: rows whose label equals this id contribute zero loss
        and zero gradient.
      chunk: vocab columns per scan step (must divide V; clamped to V).
        Peak memory is O(N * chunk).

    Returns ``[N]`` fp32 losses. Differentiable wrt hidden and weight.
    """
    _validate(hidden, weight, labels)
    chunk = min(chunk, weight.shape[0])
    if weight.shape[0] % chunk:
        raise ValueError(f"chunk ({chunk}) must divide vocab "
                         f"({weight.shape[0]})")
    return _linear_xent(hidden, weight, labels, float(smoothing),
                        padding_idx, chunk)


def _block_loss(h, w, lab, rw, smoothing, padding_idx):
    """A block of rows against the whole vocabulary: its weighted loss,
    and what ``dz`` is made from (logits, logsumexp, label mask, the
    weights with the padded rows' at zero)."""
    v = w.shape[0]
    z = _chunk_logits(h, w)                              # [R, V] fp32
    m = jnp.max(z, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(z - m[:, None]), axis=-1))
    # masked reduction, not take_along_axis (see _fwd_scan)
    hit = jnp.arange(v, dtype=jnp.int32)[None, :] == lab[:, None]
    zy = jnp.sum(jnp.where(hit, z, 0.0), axis=-1)
    zsum = jnp.sum(z, axis=-1) if smoothing > 0.0 else None
    if padding_idx is not None:
        rw = jnp.where(lab == padding_idx, 0.0, rw)
    loss = jnp.sum(_losses(lse, zy, zsum, v, smoothing) * rw)
    return loss, (z, lse, hit, rw)


def _row_blocks(h, labels, row_weights, rows):
    """The operands as ``[steps, R, ...]``, rows past N at weight zero."""
    n = h.shape[0]
    steps = -(-n // rows)
    r = -(-n // steps)
    pad = steps * r - n
    return tuple(
        jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
            (steps, r) + x.shape[1:])
        for x in (h, labels.astype(jnp.int32),
                  row_weights.astype(jnp.float32)))


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _weighted_xent(h, w, labels, row_weights, smoothing, padding_idx, rows):
    def body(loss, xs):
        h_b, lab, rw = xs
        return loss + _block_loss(h_b, w, lab, rw, smoothing,
                                  padding_idx)[0], None

    return jax.lax.scan(body, jnp.zeros((), jnp.float32),
                        _row_blocks(h, labels, row_weights, rows))[0]


def _weighted_xent_fwd(h, w, labels, row_weights, smoothing, padding_idx,
                       rows):
    def body(carry, xs):
        loss, dw = carry
        h_b, lab, rw = xs
        loss_b, (z, lse, hit, rw) = _block_loss(h_b, w, lab, rw, smoothing,
                                                padding_idx)
        dz = _dlogits(z, lse, hit, w.shape[0], smoothing) * rw[:, None]
        dh_b = jax.lax.dot_general(
            dz, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [R, D]
        dw = dw + jax.lax.dot_general(
            dz, h_b, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [V, D]
        return (loss + loss_b, dw), dh_b

    (loss, dw), dh = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros(w.shape, jnp.float32)),
        _row_blocks(h, labels, row_weights, rows))
    dh = dh.reshape(-1, h.shape[1])[:h.shape[0]]
    # residuals: the gradients at cotangent 1, in float32, and the
    # operands' types (an empty array each)
    return loss, (dh, dw, h[:0], w[:0])


def _weighted_xent_bwd(smoothing, padding_idx, rows, res, g):
    dh, dw, like_h, like_w = res
    g = g.astype(jnp.float32)       # scaled in float32, rounded once
    return ((g * dh).astype(like_h.dtype), (g * dw).astype(like_w.dtype),
            None, None)


_weighted_xent.defvjp(_weighted_xent_fwd, _weighted_xent_bwd)


def weighted_linear_cross_entropy(hidden: jax.Array, weight: jax.Array,
                                  labels: jax.Array,
                                  row_weights: jax.Array, *,
                                  smoothing: float = 0.0,
                                  padding_idx: Optional[int] = None,
                                  _rows: Optional[int] = None) -> jax.Array:
    """``sum(row_weights * linear_cross_entropy(hidden, weight, labels))``
    in three vocabulary-wide matmuls where that expression takes four.

    Args:
      hidden, weight, labels, smoothing, padding_idx: as
        :func:`linear_cross_entropy`.
      row_weights: ``[N]`` float weights of the rows' losses (``1 / N``
        for a mean, a mask, ``masked / p``). Data: no gradient flows to
        them.

    Returns the float32 scalar. Differentiable wrt hidden and weight: the
    forward rule walks blocks of at most ``_block_rows(V)`` rows (1,024
    where their float32 logits fit VMEM, else 2,048; ``_rows`` is the
    tests' way to a block at toy sizes), holds a block's ``[R, V]``
    float32 logits and makes ``dh`` and the float32 ``dW`` beside the
    loss; the backward rule scales both by the cotangent in float32 and
    casts once. Not differentiated, it computes the loss alone.
    """
    _validate(hidden, weight, labels)
    if row_weights.shape != labels.shape:
        raise ValueError(f"row_weights must be [N]={labels.shape[0]}, "
                         f"got {row_weights.shape}")
    return _weighted_xent(hidden, weight, labels, row_weights,
                          float(smoothing), padding_idx,
                          _rows or _block_rows(weight.shape[0]))
