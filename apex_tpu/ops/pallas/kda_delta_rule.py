"""The delta rule's loop over chunks under a decay a channel (Kimi Delta
Attention) as two Pallas kernels.

``ops/gated_delta_rule.py`` ``_chunked_vector`` makes, chunk by chunk and
in XLA, everything that holds a decay: the decayed queries ``Q = exp(G)
q`` and keys ``K = exp(G_last - G) k``, the masked ``M[t, s] = sum_c
q_t[c] k_s[c] exp(G_t[c] - G_s[c])`` (level by level, against reference
tokens), ``W``, ``U`` and the chunk's decay ``e = exp(G_last) [dk]``.
What is left is a recurrence over the chunks of a head,

    D  = U - W S
    O  = Q S + M D
    S' = Diag(e) S + K^T D

which holds no exponential at all. :func:`chunk_scan` is that loop
(``apex_kda_fwd``) with the chunk axis the sequential axis of a grid and
the state of ``HEADS`` heads in VMEM scratch from a head's first chunk to
its last, **transposed** (``S^T [dv, dk]``): the decay of a key channel
is then one number a lane, a row ``[1, dk]`` broadcast down the
sublanes, where ``S [dk, dv]`` would want it as a column. Its
``custom_vjp`` is the same loop backwards (``apex_kda_bwd``) with the
state's cotangent in scratch, from the state each chunk came in with
(the forward's one residual of its own), and hands every operand's
cotangent out in float32: ``Q``'s, ``K``'s and ``e``'s are what ``g``'s
cotangent is summed from in XLA, as differences. Several heads a grid
step because one head's products are a dependent chain of 64-row
matmuls; the heads' chains are independent.

The arithmetic is the ``lax.scan``'s (``gated_delta_rule._chunk_scan``):
every product takes both operands in the products' type and accumulates
in float32; the state, its cotangent and the decay are float32 and are
rounded only where they enter a product.
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


def _fwd_kernel(q_ref, k_ref, w_ref, u_ref, m_ref, e_ref, o_ref, s0_ref,
                s_ref):
    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)
    dt = q_ref.dtype

    def head(h, carry):
        st = s0_ref[h] = s_ref[h]                       # S^T [dv, dk]
        st_in = st.astype(dt)
        d = (u_ref[h].astype(_F32) - _dot(w_ref[h], st_in, _NT)).astype(dt)
        o_ref[h] = (_dot(q_ref[h], st_in, _NT)
                    + _dot(m_ref[h], d)).astype(o_ref.dtype)
        s_ref[h] = st * e_ref[h] + _dot(d, k_ref[h], _TN)
        return carry
    jax.lax.fori_loop(0, HEADS, head, 0)


def _bwd_kernel(q_ref, k_ref, w_ref, u_ref, m_ref, e_ref, s0_ref, do_ref,
                dq_ref, dk_ref, dw_ref, du_ref, dm_ref, de_ref, ds_ref):
    @pl.when(pl.program_id(1) == 0)         # the chunks run backwards
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)
    dt = q_ref.dtype

    def head(h, carry):
        w, do = w_ref[h], do_ref[h]
        st, dst = s0_ref[h], ds_ref[h]                  # [dv, dk] both
        st_in, dst_in = st.astype(dt), dst.astype(dt)
        d = (u_ref[h].astype(_F32) - _dot(w, st_in, _NT)).astype(dt)
        dd = _dot(m_ref[h], do, _TN) + _dot(k_ref[h], dst_in, _NT)
        dd_in = dd.astype(dt)
        du_ref[h] = dd
        dw_ref[h] = -_dot(dd_in, st_in)
        dq_ref[h] = _dot(do, st_in)
        dk_ref[h] = _dot(d, dst_in)
        dm_ref[h] = _dot(do, d, _NT)
        de_ref[h] = jnp.sum(st * dst, axis=0, keepdims=True)
        ds_ref[h] = (dst * e_ref[h] + _dot(do, q_ref[h], _TN)
                     - _dot(dd_in, w, _TN))
        return carry
    jax.lax.fori_loop(0, HEADS, head, 0)


def _spec(index, *tail):
    """A block of ``HEADS`` heads' one chunk, the chunk axis squeezed."""
    return pl.BlockSpec((HEADS, None) + tail,
                        lambda i, j: (i, index(j), 0, 0))


# heads in any order, a head's chunks one after the other
_SEQUENTIAL = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def _forward(q, k, w, u, m, e):
    bh, n, c, dk = q.shape
    dv = u.shape[-1]
    spec = functools.partial(_spec, lambda j: j)
    sds = functools.partial(jax.ShapeDtypeStruct, vma=vma(q, k, w, u, m, e))
    return pl.pallas_call(
        _fwd_kernel,
        grid=(bh // HEADS, n),
        in_specs=[spec(c, dk), spec(c, dk), spec(c, dk), spec(c, dv),
                  spec(c, c), spec(1, dk)],
        out_specs=[spec(c, dv), spec(dv, dk)],
        out_shape=[sds((bh, n, c, dv), u.dtype), sds((bh, n, dv, dk), _F32)],
        scratch_shapes=[pltpu.VMEM((HEADS, dv, dk), _F32)],
        compiler_params=_SEQUENTIAL,
        interpret=interpret_mode(),
        name="apex_kda_fwd",
    )(q, k, w, u, m, e)


def _backward(q, k, w, u, m, e, s0, do):
    bh, n, c, dk = q.shape
    dv = u.shape[-1]
    spec = functools.partial(_spec, lambda j: n - 1 - j)
    sds = functools.partial(jax.ShapeDtypeStruct,
                            vma=vma(q, k, w, u, m, e, s0, do))
    return pl.pallas_call(
        _bwd_kernel,
        grid=(bh // HEADS, n),
        in_specs=[spec(c, dk), spec(c, dk), spec(c, dk), spec(c, dv),
                  spec(c, c), spec(1, dk), spec(dv, dk), spec(c, dv)],
        out_specs=[spec(c, dk), spec(c, dk), spec(c, dk), spec(c, dv),
                   spec(c, c), spec(1, dk)],
        out_shape=[sds(q.shape, _F32), sds(k.shape, _F32),
                   sds(w.shape, _F32), sds(u.shape, _F32),
                   sds(m.shape, _F32), sds(e.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((HEADS, dv, dk), _F32)],
        compiler_params=_SEQUENTIAL,
        interpret=interpret_mode(),
        name="apex_kda_bwd",
    )(q, k, w, u, m, e, s0, do)


def _heads(x, pad):
    """``[B, H, n, ...]`` as ``[B H (+ pad), n, ...]``; the heads added
    are zero everywhere, so their state stays zero."""
    x = x.reshape((-1,) + x.shape[2:])
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) if pad else x


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def chunk_scan(dt, q, k, w, u, m, e):
    """The chunks' outputs ``[B, H, n, C, dv]`` in ``dt``, the products'
    type, from float32 ``q, k, w [B, H, n, C, dk]`` (``q`` and ``k``
    decayed), ``u [B, H, n, C, dv]``, ``m [B, H, n, C, C]`` (masked) and
    the chunks' decays ``e [B, H, n, dk]``, with the state zero in front of
    a head's first chunk. The operands are cast here, so that their
    cotangents leave the backward kernel in float32, rounded nowhere."""
    return _chunk_scan_fwd(dt, q, k, w, u, m, e)[0]


def _chunk_scan_fwd(dt, q, k, w, u, m, e):
    b, h = q.shape[:2]
    pad = round_up(b * h, HEADS) - b * h
    operands = tuple(_heads(x.astype(dt), pad) for x in (q, k, w, u, m)) \
        + (_heads(e[..., None, :], pad),)
    o, s0 = _forward(*operands)
    return o[:b * h].reshape(u.shape), operands + (s0,)


def _chunk_scan_bwd(dt, residuals, do):
    b, h = do.shape[:2]
    grads = _backward(*residuals, _heads(do, residuals[0].shape[0] - b * h))
    dq, dk, dw, du, dm, de = (x[:b * h].reshape((b, h) + x.shape[1:])
                              for x in grads)
    return dq, dk, dw, du, dm, de[..., 0, :]


chunk_scan.defvjp(_chunk_scan_fwd, _chunk_scan_bwd)
