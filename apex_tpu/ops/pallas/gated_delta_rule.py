"""The gated delta rule's loop over chunks as two Pallas kernels.

``ops/gated_delta_rule.py`` ``gated_delta_rule_chunked`` makes, chunk by
chunk, ``W`` and ``U`` (the inverse and its two float32 products: XLA's);
what is left is, from the chunk's queries, keys and running log-decay
``G``, the decayed queries ``Q = exp(G) q`` and keys ``K = exp(G_last - G)
k``, the masked ``M = (q k^T) exp(G_t - G_s)``, and a recurrence over the
chunks of a head,

    D  = U - W S
    O  = Q S + M D
    S' = exp(G_last) S + K^T D

whose ``dk x dv`` float32 state ``S`` an XLA ``while`` carries through its
iterations and whose ``Q``, ``K``, ``M`` it first writes to HBM. Here the
chunk axis is the sequential axis of a grid, the state of ``HEADS`` heads
stays in VMEM scratch from a head's first chunk to its last, and ``Q``,
``K``, ``M`` exist in VMEM only: :func:`chunk_scan` is that loop
(``apex_gdn_fwd``), and its ``custom_vjp`` the same loop backwards
(``apex_gdn_bwd``) with the state's cotangent in scratch, from the state
each chunk came in with (the forward's one residual of its own: what the
``lax.scan`` keeps as its carry). Several heads a grid step because one
head's products are a dependent chain of 64-row matmuls; the heads'
chains are independent.

The arithmetic is the ``lax.scan``'s: every product takes both operands in
the products' type and accumulates in float32; the state, its cotangent,
the decays and ``G`` are float32 and are rounded only where they enter a
product. ``W``'s and ``U``'s cotangents come out in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.pallas._common import interpret_mode, round_up, vma

__all__ = ["chunk_scan", "takes"]

HEADS = 8               # (batch x head) pairs a grid step
_F32 = jnp.float32


def takes(dk: int, dv: int, chunk: int) -> bool:
    """Whether these are the kernels' shapes: whole lanes in both head
    sizes, chunks of whole bfloat16 tiles."""
    return dk % 128 == 0 and dv % 128 == 0 and chunk in (64, 128)


def _dot(x, y, contract=((1,), (0,))):
    """``x y``, or with ``contract`` a transposed operand: float32 out."""
    return jax.lax.dot_general(x, y, (contract, ((), ())),
                               preferred_element_type=_F32)


_TN = ((0,), (0,))      # x^T y
_NT = ((1,), (1,))      # x y^T


def _decays(g_row):
    """From a chunk's running log-decay ``G [1, C]``: ``exp(G_t - G_s)``
    for ``s <= t`` and 0 above the diagonal ``[C, C]`` (masked before the
    exp: the differences above it are positive and overflow), ``exp(G)``
    and ``exp(G_last - G)`` as columns ``[C, 1]``, ``exp(G_last) [1, 1]``."""
    c = g_row.shape[-1]
    g_s = jnp.broadcast_to(g_row, (c, c))
    g_t = g_s.T
    lower = (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
             >= jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))
    last = g_row[:, c - 1:]
    return (jnp.exp(jnp.where(lower, g_t - g_s, -jnp.inf)),
            jnp.exp(g_t[:, :1]), jnp.exp(last - g_t[:, :1]), jnp.exp(last))


def _fwd_kernel(q_ref, k_ref, w_ref, u_ref, g_ref, o_ref, s0_ref, s_ref):
    first = pl.program_id(1) == 0

    @pl.when(first)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)
    dt = q_ref.dtype
    for h in range(HEADS):
        q, k = q_ref[h], k_ref[h]
        decay, into, out, through = _decays(g_ref[h])
        s = s0_ref[h] = s_ref[h]
        s_in = s.astype(dt)
        d = (u_ref[h].astype(_F32) - _dot(w_ref[h], s_in)).astype(dt)
        o_ref[h] = (
            _dot((q.astype(_F32) * into).astype(dt), s_in)
            + _dot((_dot(q, k, _NT) * decay).astype(dt), d)
        ).astype(o_ref.dtype)
        s_ref[h] = s * through + _dot((k.astype(_F32) * out).astype(dt), d,
                                      _TN)


def _bwd_kernel(q_ref, k_ref, w_ref, u_ref, g_ref, s0_ref, do_ref,
                dq_ref, dk_ref, dw_ref, du_ref, dg_ref, ds_ref):
    last = pl.program_id(1) == 0            # the chunks run backwards

    @pl.when(last)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)
    dt = q_ref.dtype
    c = q_ref.shape[1]
    for h in range(HEADS):
        q, k, w, do = q_ref[h], k_ref[h], w_ref[h], do_ref[h]
        decay, into, out, through = _decays(g_ref[h])
        s0, ds = s0_ref[h], ds_ref[h]
        s_in, ds_in = s0.astype(dt), ds.astype(dt)
        q_f, k_f = q.astype(_F32) * into, k.astype(_F32) * out
        q_in, k_out = q_f.astype(dt), k_f.astype(dt)
        qk_f = _dot(q, k, _NT)
        d = (u_ref[h].astype(_F32) - _dot(w, s_in)).astype(dt)
        # the recurrence's transposes
        dd = _dot((qk_f * decay).astype(dt), do, _TN) + _dot(k_out, ds_in)
        dd_in = dd.astype(dt)
        du_ref[h] = dd
        dw_ref[h] = -_dot(dd_in, s_in, _NT)
        dq_in = _dot(do, s_in, _NT)
        dk_out = _dot(d, ds_in, _NT)
        dqk = _dot(do, d, _NT)
        ds_ref[h] = ds * through + _dot(q_in, do, _TN) - _dot(w, dd_in, _TN)
        # through Q, K and M to q, k and G
        dm_f = dqk * decay
        dm = dm_f.astype(dt)
        dq_ref[h] = (dq_in * into + _dot(dm, k)).astype(dq_ref.dtype)
        dk_ref[h] = (dk_out * out + _dot(dm, q, _TN)).astype(dk_ref.dtype)
        dm_g = dm_f * qk_f                  # dM . M: G_t's gain, G_s's loss
        from_k = jnp.sum(dk_out * k_f, axis=1, keepdims=True)
        column = (jnp.sum(dq_in * q_f, axis=1, keepdims=True) - from_k
                  + jnp.sum(dm_g, axis=1, keepdims=True))
        # G_last: K's exp(G_last - G) and the state's decay, sum(S0 dS')
        at_last = jnp.sum(from_k, axis=0, keepdims=True) + through * jnp.sum(
            jnp.sum(s0 * ds, axis=0, keepdims=True), axis=1, keepdims=True)
        lanes = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)
        dg_ref[h] = (jnp.broadcast_to(column, (c, c)).T[:1]
                     - jnp.sum(dm_g, axis=0, keepdims=True)
                     + jnp.where(lanes == c - 1, at_last, 0.0))


def _spec(index, *tail):
    """A block of ``HEADS`` heads' one chunk, the chunk axis squeezed."""
    return pl.BlockSpec((HEADS, None) + tail,
                        lambda i, j: (i, index(j), 0, 0))


# heads in any order, a head's chunks one after the other
_SEQUENTIAL = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def _forward(q, k, w, u, g):
    bh, n, c, dk = q.shape
    dv = u.shape[-1]
    spec = functools.partial(_spec, lambda j: j)
    sds = functools.partial(jax.ShapeDtypeStruct, vma=vma(q, k, w, u, g))
    return pl.pallas_call(
        _fwd_kernel,
        grid=(bh // HEADS, n),
        in_specs=[spec(c, dk), spec(c, dk), spec(c, dk), spec(c, dv),
                  spec(1, c)],
        out_specs=[spec(c, dv), spec(dk, dv)],
        out_shape=[sds((bh, n, c, dv), u.dtype), sds((bh, n, dk, dv), _F32)],
        scratch_shapes=[pltpu.VMEM((HEADS, dk, dv), _F32)],
        compiler_params=_SEQUENTIAL,
        interpret=interpret_mode(),
        name="apex_gdn_fwd",
    )(q, k, w, u, g)


def _backward(q, k, w, u, g, s0, do):
    bh, n, c, dk = q.shape
    dv = u.shape[-1]
    spec = functools.partial(_spec, lambda j: n - 1 - j)
    sds = functools.partial(jax.ShapeDtypeStruct,
                            vma=vma(q, k, w, u, g, s0, do))
    return pl.pallas_call(
        _bwd_kernel,
        grid=(bh // HEADS, n),
        in_specs=[spec(c, dk), spec(c, dk), spec(c, dk), spec(c, dv),
                  spec(1, c), spec(dk, dv), spec(c, dv)],
        out_specs=[spec(c, dk), spec(c, dk), spec(c, dk), spec(c, dv),
                   spec(1, c)],
        out_shape=[sds(q.shape, q.dtype), sds(k.shape, k.dtype),
                   sds(w.shape, _F32), sds(u.shape, _F32),
                   sds(g.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((HEADS, dk, dv), _F32)],
        compiler_params=_SEQUENTIAL,
        interpret=interpret_mode(),
        name="apex_gdn_bwd",
    )(q, k, w, u, g, s0, do)


def _heads(x, pad):
    """``[B, H, n, ...]`` as ``[B H (+ pad), n, ...]``; the heads added
    are zero everywhere, so their state stays zero."""
    x = x.reshape((-1,) + x.shape[2:])
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) if pad else x


@jax.custom_vjp
def chunk_scan(q, k, w, u, gsum):
    """The chunks' outputs ``[B, H, n, C, dv]`` in ``q``'s type, the
    products', from the chunks' ``q, k [B, H, n, C, dk]``, float32 ``w [B,
    H, n, C, dk]`` and ``u [B, H, n, C, dv]`` (cast here, so that their
    cotangents leave the backward kernel in float32, rounded nowhere, and
    XLA has no pass to convert them) and the running log-decay inside each
    chunk, float32 ``gsum [B, H, n, C]``, with the state zero in front of a
    head's first chunk."""
    return _chunk_scan_fwd(q, k, w, u, gsum)[0]


def _chunk_scan_fwd(q, k, w, u, gsum):
    b, h = q.shape[:2]
    pad = round_up(b * h, HEADS) - b * h
    operands = tuple(_heads(x, pad) for x in (
        q, k, w.astype(q.dtype), u.astype(q.dtype), gsum[..., None, :]))
    o, s0 = _forward(*operands)
    return o[:b * h].reshape(u.shape), operands + (s0,)


def _chunk_scan_bwd(residuals, do):
    b, h = do.shape[:2]
    grads = _backward(*residuals, _heads(do, residuals[0].shape[0] - b * h))
    dq, dk, dw, du, dg = (x[:b * h].reshape((b, h) + x.shape[1:])
                          for x in grads)
    return dq, dk, dw, du, dg[..., 0, :]


chunk_scan.defvjp(_chunk_scan_fwd, _chunk_scan_bwd)
