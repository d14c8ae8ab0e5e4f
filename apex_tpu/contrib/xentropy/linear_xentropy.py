"""Fused LM-head projection + softmax cross entropy, chunked over vocab.

The standard LM loss materializes fp32 logits ``[N, V]`` (N = B*T): at
B=8, T=4095, V=32768 that is a 4 GB HLO temp plus a same-shaped backward
temp — the allocation that OOMed the round-4 ``lm_bench --seq 4096`` run
on a 16 GB chip. This op never builds the full logits matrix: it scans
the vocabulary in chunks of ``chunk`` columns, keeping an online
(max, sumexp) pair per row — the same online-logsumexp recurrence the
flash-attention kernel uses over keys — plus the label's logit. Peak
memory drops from O(N*V) to O(N*chunk); the backward recomputes each
chunk's logits from the saved per-row logsumexp (one extra pass of the
head matmul, the standard remat trade).

Loss/grad semantics match ``softmax_cross_entropy_loss`` exactly
(reference apex/contrib/xentropy label-smoothing convention:
``lse - (1-eps)*z_y - eps*mean(z)``), pinned by a parity test.

This is scan + MXU matmuls, not a Pallas kernel: each chunk step is one
``[N, D] @ [D, C]`` matmul XLA fuses the online-softmax update into —
the measured round-3 lesson (docs/PERF.md r03: XLA beats hand kernels for
everything it can fuse; the win here is the algorithmic memory bound,
which no per-op fusion can deliver).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp


def _validate(h, w, labels, chunk):
    if h.ndim != 2 or w.ndim != 2 or h.shape[1] != w.shape[1]:
        raise ValueError(f"expected h [N, D] and w [V, D] with matching D; "
                         f"got {h.shape} and {w.shape}")
    if labels.shape != (h.shape[0],):
        raise ValueError(f"labels must be [N]={h.shape[0]}, "
                         f"got {labels.shape}")
    v = w.shape[0]
    chunk = min(chunk, v)
    if v % chunk:
        raise ValueError(f"chunk ({chunk}) must divide vocab ({v})")
    return chunk


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
        p = jnp.exp(z - lse[:, None])                    # softmax chunk
        off = i * chunk
        in_chunk = (lab >= off) & (lab < off + chunk)
        idx = jnp.clip(lab - off, 0, chunk - 1)
        onehot = (jnp.arange(chunk)[None, :] == idx[:, None]) & \
            in_chunk[:, None]
        dz = p - (1.0 - smoothing) * onehot.astype(jnp.float32)
        if smoothing > 0.0:
            dz = dz - smoothing / v
        dz = dz * g[:, None]
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
    chunk = _validate(hidden, weight, labels, chunk)
    return _linear_xent(hidden, weight, labels, float(smoothing),
                        padding_idx, chunk)
