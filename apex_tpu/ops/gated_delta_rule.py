"""The gated delta rule (Gated DeltaNet's linear-attention recurrence).

A head keeps a state ``S`` of ``dk x dv`` in float32, from zero, and for
each token ``t`` in order, with a log-decay ``g_t <= 0`` and a write
strength ``beta_t`` in (0, 1):

    S   = exp(g_t) S
    d_t = beta_t (v_t - S^T k_t)
    S   = S + k_t d_t^T
    o_t = S^T q_t

:func:`gated_delta_rule_recurrent` is that recurrence token by token, in
float32: the op's reference twin (``dispatch.backend("reference")``), and
what the chunked form is tested against.

:func:`gated_delta_rule_chunked` is the form a training step runs: the
sequence in chunks of ``chunk`` tokens, everything inside a chunk as
matrix products (the WY form: with ``G`` the running sum of ``g`` inside
the chunk and ``A[t, s] = beta_t exp(G_t - G_s) k_t.k_s`` for ``s < t``,
``T = (I + A)^-1``, ``U = T (beta V)``, ``W = T (beta exp(G) K)``, the
chunk's writes are ``D = U - W S0``), and only the ``dk x dv`` state
carried from chunk to chunk, in float32. The inverse and the two float32
products ``U`` and ``W`` are plain ``jax.numpy``, differentiated by JAX
(the inverse has a backward of its own). The rest, the decayed queries
``exp(G) q`` and keys ``exp(G_last - G) k``, the masked ``(q k^T) exp(G_t -
G_s)`` and the loop over chunks, is on the TPU at the kernels' shapes
(head sizes whole multiples of 128, chunks of 64 or 128) the Pallas pair
``apex_gdn_fwd`` / ``apex_gdn_bwd`` (``ops/pallas/gated_delta_rule.py``:
the state and those three never leave VMEM), and anywhere else
``jax.numpy`` with a ``lax.scan``, the kernels' oracle. Either way the
backward keeps one state a *chunk*, never one a token.

**A decay a channel** (Kimi Delta Attention): ``g [B, H, L, dk]`` in the
place of ``g [B, H, L]`` makes the first line ``S = Diag(exp(g_t)) S``,
row ``c`` of ``S`` times ``exp(g_t[c])``; with ``g_t`` the same in all
``dk`` channels it is the recurrence above. Its chunked form is
:func:`_chunked_vector`: with ``G [C, dk]`` the running sum inside a
chunk the decay sits **inside** the contraction, ``A[t, s] = beta_t sum_c
k_t[c] k_s[c] exp(G_t[c] - G_s[c])`` (and ``M`` the same with ``q_t``),
so the operands are decayed before the product and ``exp(-G_s)`` over a
whole chunk overflows. The products are taken level by level against
reference tokens (:func:`_local_products`; on the TPU at the kernels'
shapes the Pallas pair ``apex_kda_local_fwd`` / ``apex_kda_local_bwd``,
which read :func:`_levels` too and make a level's decayed rows and
columns in VMEM from ``q``, ``k``, ``G``: only the ``[C, C]`` products
and the ``[C, dk]`` cotangents leave it): the chunk halved and halved
again down to sub-blocks of ``SUB`` = 16 tokens, each off-diagonal block
against the first token ``r`` of its lower half (rows ``x_t exp(G_t -
G_r)``, columns ``k_s exp(G_r - G_s)``: every exponent <= 0), each
diagonal sub-block against its own first token (columns ``k_s exp(G_r -
G_s)`` with exponents >= 0 over at most 15 tokens). Then ``T = (I +
A)^-1``, ``U = T (beta V)``, ``W = T (beta exp(G) K)`` as above and, a
chunk at a time, ``D = U - W S0``, ``O = (exp(G) Q) S0 + M D``, ``S' =
Diag(exp(G_last)) S0 + (exp(G_last - G) K)^T D``: on the TPU at the
kernels' shapes the Pallas pair ``apex_kda_fwd`` / ``apex_kda_bwd``
(``ops/pallas/kda_delta_rule.py``, imported by this arm alone: the state
stays in VMEM, transposed, so that a channel's decay is a lane's, and the
decayed ``exp(G) Q``, ``exp(G_last - G) K`` and ``exp(G_last)`` are made
there from ``q``, ``k``, ``G``, the backward handing out ``dq``, ``dk`` and
one ``dG``), the same ``lax.scan`` over ``jax.numpy``'s decayed operands
anywhere else. The inverse, ``U``, ``W`` and the running sum ``G`` are
``jax.numpy`` on every platform. **Range:** the vector form equals the
recurrence while no channel decays by more than float32's largest
exponent (88.7 nats) over the 15 tokens of a sub-block, ``g >= -5.9`` a
token a channel held throughout; past that a diagonal column overflows
and the result is not finite. Over a whole chunk any decay is exact
(320 nats at ``g = -5`` and a chunk of 64; :func:`chunk_decay_nats` is
the number a model reports). ``g``'s cotangent comes out in float32
``[B, H, L, dk]``; nothing with two token axes and a channel axis is
formed anywhere.

Shapes: ``q, k [B, H, L, dk]``, ``v [B, H, L, dv]``, ``beta [B, H, L]``,
``g [B, H, L]`` or ``[B, H, L, dk]``; the result is ``[B, H, L, dv]`` in
``v``'s type. ``q`` and ``k``
come normalised and ``q`` scaled, as the caller's model has them. Any
``L``: the chunked form pads to a whole chunk with tokens that write
nothing (``beta`` 0, ``g`` 0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from apex_tpu.ops import dispatch
from apex_tpu.ops.pallas import gated_delta_rule as _kernels

__all__ = ["chunk_decay_nats", "gated_delta_rule",
           "gated_delta_rule_chunked", "gated_delta_rule_recurrent"]

_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32
SUB = 16                # tokens a diagonal sub-block of a vector gate's chunk


def gated_delta_rule_recurrent(q, k, v, g, beta):
    """The recurrence as written, one token at a time, in float32; ``g``
    a number or ``dk`` numbers a token."""
    out_dtype = v.dtype
    q, k, v, g, beta = (x.astype(_F32) for x in (q, k, v, g, beta))
    b, h, _, dk = q.shape
    if g.ndim == 3:     # one decay for every channel
        g = g[..., None]

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., None]
        d = b_t[..., None] * (v_t - jnp.einsum(
            "bhkv,bhk->bhv", s, k_t, precision=_HI))
        s = s + k_t[..., :, None] * d[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=_HI)

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]), _F32), xs)
    return jnp.moveaxis(o, 0, 2).astype(out_dtype)


def _mm_hi(x, y):
    return jnp.matmul(x, y, precision=_HI)


def _mm(dt, eq, x, y):
    """A product as the chunked form takes it: both operands in ``dt``,
    accumulated in float32."""
    return jnp.einsum(eq, x.astype(dt), y.astype(dt),
                      preferred_element_type=_F32)


def _inv_blocks(a):
    """``(I + a)^-1`` by matrix products alone: blocks of 16 by the finite
    series ``(I - a)(I + a^2)(I + a^4)(I + a^8)`` (``a^16 = 0``), then
    halves merged, ``[[P, 0], [C, Q]]^-1 = [[P', 0], [-Q' C P', Q']]``."""
    n = a.shape[-1]
    if n <= 16:
        eye = jnp.eye(n, dtype=a.dtype)
        out, p, k = eye - a, a, 2
        while k < n:
            p = _mm_hi(p, p)
            out = _mm_hi(out, eye + p)
            k *= 2
        return out
    half = n // 2
    p = _inv_blocks(a[..., :half, :half])
    q = _inv_blocks(a[..., half:, half:])
    low = -_mm_hi(_mm_hi(q, a[..., half:, :half]), p)
    return jnp.concatenate([
        jnp.concatenate([p, jnp.zeros_like(low)], -1),
        jnp.concatenate([low, q], -1)], -2)


@jax.custom_vjp
def _inv_unit_lower(a):
    """``T = (I + a)^-1`` for strictly lower-triangular ``a [..., n, n]``
    in float32. Its backward is ``da = -T^T dT T^T`` from ``T`` alone:
    nothing of the inversion's inside is kept (blocks of 16 x 16 pad to
    eight times their size in the chip's tiled memory)."""
    return _inv_blocks(a)


def _inv_unit_lower_fwd(a):
    t = _inv_blocks(a)
    return t, t


def _inv_unit_lower_bwd(t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    return (-_mm_hi(_mm_hi(tt, dt), tt),)


_inv_unit_lower.defvjp(_inv_unit_lower_fwd, _inv_unit_lower_bwd)


def gated_delta_rule_chunked(q, k, v, g, beta, *, chunk: int = 64):
    """The same result from chunks of ``chunk`` tokens (16, 32, 64 or
    128). Products take their operands in ``v``'s type and accumulate in
    float32; the inverse ``T``, the decays and the state are float32."""
    if chunk not in (16, 32, 64, 128):
        raise ValueError(f"chunk must be 16, 32, 64 or 128, got {chunk}")
    if g.ndim == 4:
        return _chunked_vector(q, k, v, g, beta, chunk)
    dt = v.dtype
    b, h, length, dk = q.shape
    pad = (-length) % chunk
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
                   for x in (q, k, v))
        g, beta = (jnp.pad(x, ((0, 0), (0, 0), (0, pad))) for x in (g, beta))
    n = (length + pad) // chunk

    def chunks(x):
        return x.reshape(b, h, n, chunk, *x.shape[3:])

    mm = functools.partial(_mm, dt)
    q, k, v = chunks(q), chunks(k), chunks(v)
    beta = chunks(beta.astype(_F32))[..., None]
    gsum = jnp.cumsum(chunks(g.astype(_F32)), axis=-1)      # G, per chunk
    idx = jnp.arange(chunk)
    lower = idx[:, None] >= idx[None, :]
    # exp(G_t - G_s) for s <= t, 0 above the diagonal (masked before the
    # exp: the differences above it are positive and overflow)
    decay = jnp.exp(jnp.where(lower, gsum[..., :, None] - gsum[..., None, :],
                              -jnp.inf))
    kk = mm("bhnck,bhnsk->bhncs", k, k)
    t_inv = _inv_unit_lower(jnp.where(idx[:, None] > idx[None, :],
                                      beta * kk * decay, 0.0))
    u = jnp.matmul(t_inv, beta * v.astype(_F32), precision=_HI)
    w = jnp.matmul(t_inv, beta * jnp.exp(gsum)[..., None] * k.astype(_F32),
                   precision=_HI)
    if dispatch.use_pallas() and _kernels.takes(dk, v.shape[-1], chunk):
        o = _kernels.chunk_scan(q.astype(dt), k.astype(dt), w, u, gsum)
    else:
        qk = mm("bhnck,bhnsk->bhncs", q, k) * decay
        q_in = q.astype(_F32) * jnp.exp(gsum)[..., None]
        last = gsum[..., -1:]
        k_out = k.astype(_F32) * jnp.exp(last - gsum)[..., None]
        o = _chunk_scan(*(x.astype(dt) for x in (w, u, q_in, k_out, qk)),
                        jnp.exp(last))
    return o.reshape(b, h, n * chunk, v.shape[-1])[:, :, :length]


def _levels(chunk: int):
    """The vector gate's products inside a chunk, level by level: ``(block,
    lower)`` with ``block`` the tokens of a block and ``lower`` where its
    lower half starts: the chunk's halves, their halves, ... down to
    ``SUB``, then the diagonal sub-blocks themselves (``lower`` 0)."""
    sub = min(SUB, chunk)
    out, block = (), chunk
    while block > sub:
        out += ((block, block // 2),)
        block //= 2
    return out + ((sub, 0),)


@jax.checkpoint
def _local_products(q, k, gsum):
    """``(qk, kk) [..., C, C]`` float32 from a chunk's ``q, k [..., C, dk]``
    and its running log-decay ``gsum [..., C, dk]``: ``qk[t, s] = sum_c
    q_t[c] k_s[c] exp(G_t[c] - G_s[c])`` for ``s <= t``, ``kk`` the same
    with ``k_t`` for ``s < t``, 0 elsewhere. A level (``_levels``) is one
    product of decayed rows with decayed columns, both in ``q``'s type: a
    block's lower half as rows against its upper half as columns, both
    decayed to the lower half's first token ``r`` (``exp(G_t - G_r)``,
    ``exp(G_r - G_s)``, exponents <= 0); a diagonal sub-block against its
    own first token (the columns' exponents >= 0 over ``SUB - 1`` tokens).
    Tokens with no part in a level enter it as zeros (masked before the
    exp). Recomputed in the backward: only ``q``, ``k``, ``gsum`` are kept."""
    dt = q.dtype
    c, dk = k.shape[-2:]
    lead = k.shape[:-2]
    x = jnp.stack([q, k], axis=-3).astype(_F32)         # [..., 2, C, dk]
    kf = k.astype(_F32)
    idx = jnp.arange(c)
    same = idx[:, None] >= idx[None, :]
    total = 0.0
    for block, lower in _levels(c):
        gb = gsum.reshape(*lead, c // block, block, dk)
        ref = jnp.broadcast_to(gb[..., lower:lower + 1, :],
                               gb.shape).reshape(gsum.shape)
        pos = idx % block
        row = (pos >= lower)[:, None]
        col = row if lower == 0 else ~row
        rows = x * jnp.exp(jnp.where(row, gsum - ref, -jnp.inf))[..., None,
                                                                 :, :]
        cols = kf * jnp.exp(jnp.where(col, ref - gsum, -jnp.inf))
        prod = jnp.einsum("...xck,...sk->...xcs", rows.astype(dt),
                          cols.astype(dt), preferred_element_type=_F32)
        here = same & (idx[:, None] // block == idx[None, :] // block)
        total = total + jnp.where(here, prod, 0.0)
    strict = idx[:, None] > idx[None, :]
    return total[..., 0, :, :], jnp.where(strict, total[..., 1, :, :], 0.0)


def chunk_decay_nats(g, chunk: int):
    """How far a channel decays inside one chunk, at the most: the largest
    ``-G_last[c]`` over everything of ``g [B, H, L, dk]`` (a float32
    scalar, no gradient). Past 88.7 a form that divides by ``exp(G)`` over
    a whole chunk is wrong; this one is not (the module's text)."""
    b, h, length, dk = g.shape
    g = jnp.pad(g.astype(_F32), ((0, 0), (0, 0), (0, (-length) % chunk),
                                 (0, 0)))
    return jax.lax.stop_gradient(-jnp.min(jnp.sum(
        g.reshape(b, h, -1, chunk, dk), axis=3)))


def _chunked_vector(q, k, v, g, beta, chunk: int):
    """The chunked form under a decay a channel, ``g [B, H, L, dk]`` (the
    module's text). The two Pallas pairs read the same ``q``, ``k`` (in the
    products' type) and ``G``; only the ``jax.numpy`` arm makes the scan's
    decayed operands."""
    dt = v.dtype
    b, h, length, dk = q.shape
    pad = (-length) % chunk
    if pad:
        q, k, v, g = (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
                      for x in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, 0), (0, pad)))
    n = (length + pad) // chunk

    def chunks(x):
        return x.reshape(b, h, n, chunk, *x.shape[3:])

    q, k, v = chunks(q.astype(dt)), chunks(k.astype(dt)), chunks(v)
    beta = chunks(beta.astype(_F32))[..., None]
    gsum = jnp.cumsum(chunks(g.astype(_F32)), axis=-2)      # G [C, dk]
    kernels = None
    if dispatch.use_pallas():       # this arm's alone: imported here
        from apex_tpu.ops.pallas import kda_delta_rule
        if kda_delta_rule.takes(dk, v.shape[-1], chunk):
            kernels = kda_delta_rule
    if kernels is not None:
        qk, kk = kernels.local_products(_levels(chunk), q, k, gsum)
    else:
        qk, kk = _local_products(q, k, gsum)
    t_inv = _inv_unit_lower(beta * kk)
    u = jnp.matmul(t_inv, beta * v.astype(_F32), precision=_HI)
    w = jnp.matmul(t_inv, beta * jnp.exp(gsum) * k.astype(_F32),
                   precision=_HI)
    if kernels is not None:     # the decayed operands are made in VMEM
        o = kernels.chunk_scan(q, k, gsum, w, u, qk)
    else:
        last = gsum[..., -1:, :]
        q_in = q.astype(_F32) * jnp.exp(gsum)
        k_out = k.astype(_F32) * jnp.exp(last - gsum)
        through = jnp.exp(last[..., 0, :])                  # [B, H, n, dk]
        o = _chunk_scan(*(x.astype(dt) for x in (w, u, q_in, k_out, qk)),
                        through)
    return o.reshape(b, h, n * chunk, v.shape[-1])[:, :, :length]


def _chunk_scan(w, u, q, k, qk, decay):
    """The loop over chunks as a ``lax.scan``: what runs off the TPU and at
    shapes the kernels do not take, and what they are tested against: from
    the products' operands ``w, q, k [B, H, n, C, dk]``, ``u [B, H, n, C,
    dv]``, ``qk [B, H, n, C, C]`` (``q`` and ``k`` decayed, ``qk`` masked)
    and the chunks' decays ``[B, H, n, 1]`` (a vector gate's: ``[B, H, n,
    dk]``, a row of the state each), the outputs ``[B, H, n, C, dv]``."""
    dt = u.dtype
    mm = functools.partial(_mm, dt)

    # the body is recomputed in the backward: the scan keeps its carry, one
    # state a chunk, and its inputs (in the products' type), nothing else
    @jax.checkpoint
    def step(s, x):
        w_i, u_i, q_i, k_i, qk_i, decay_i = x
        d = u_i - mm("bhck,bhkv->bhcv", w_i, s)
        o = mm("bhck,bhkv->bhcv", q_i, s) + mm("bhcs,bhsv->bhcv", qk_i, d)
        s = s * decay_i[..., None] + mm("bhck,bhcv->bhkv", k_i, d)
        return s, o.astype(dt)

    b, h, _, _, dk = w.shape
    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (w, u, q, k, qk, decay))
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, u.shape[-1]), _F32), xs)
    return jnp.moveaxis(o, 0, 2)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = 64):
    """The op as the models call it: the chunked form, or under
    ``dispatch.backend("reference")`` the recurrence."""
    if dispatch.get_backend() == "reference":
        return gated_delta_rule_recurrent(q, k, v, g, beta)
    return gated_delta_rule_chunked(q, k, v, g, beta, chunk=chunk)
