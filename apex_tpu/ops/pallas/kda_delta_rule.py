"""The delta rule under a decay a channel (Kimi Delta Attention): its
products inside a chunk and its loop over chunks, a Pallas pair each.

**Inside a chunk** (:func:`local_products`: ``apex_kda_local_fwd`` /
``apex_kda_local_bwd``): the masked ``M[t, s] = sum_c q_t[c] k_s[c]
exp(G_t[c] - G_s[c])`` and the same with ``k_t``, level by level against
reference tokens, as ``gated_delta_rule._local_products`` takes them in
``jax.numpy`` (the kernels' oracle, and its text the levels': the caller
hands ``gated_delta_rule._levels(C)`` in). A grid step holds ``CHUNKS``
chunks' ``q``, ``k``, ``G``, all chunks independent; a chunk makes each
level's decayed rows ``[2 C, dk]`` (``q``'s above ``k``'s) and columns
``[C, dk]`` in VMEM, masked before the ``exp``, rounds them to the
products' type, takes the whole-chunk product on the MXU into float32 and
keeps the level's blocks of it. The backward makes the levels again from
the same three residuals and hands out ``dq``, ``dk`` (the operands' type)
and ``dG`` (float32), a reference token's share of ``dG`` (its block's sum,
minus) placed on its row; the masked cotangent of a product is rounded to
the products' type where it enters a product, as JAX's transpose rounds
it, and nothing else is. No decayed operand and nothing with two token
axes but the two results and their cotangents is ever in HBM.

**Over the chunks.** ``ops/gated_delta_rule.py`` ``_chunked_vector``
makes in XLA the masked ``M`` (the local pair's ``qk``), ``U`` and ``W = T
(beta exp(G) k)`` (behind the inverse). What is left is, from the chunk's
``q``, ``k`` and running log-decay ``G [C, dk]``, the decayed queries ``Q
= exp(G) q`` and keys ``K = exp(G_last - G) k``, the chunk's decay ``e =
exp(G_last) [dk]`` (every exponent <= 0: ``G`` is a running sum of ``g <=
0``, so nothing overflows at any decay), and a recurrence over the chunks
of a head,

    D  = U - W S
    O  = Q S + M D
    S' = Diag(e) S + K^T D

:func:`chunk_scan` is that loop (``apex_kda_fwd``) with the chunk axis the
sequential axis of a grid, ``Q``, ``K`` and ``e`` made in VMEM from the
three arrays the local pair reads (the same float32 product and the one
rounding to the products' type that ``jax.numpy`` gives them: no decayed
operand is ever in HBM) and the state of ``HEADS`` heads in VMEM scratch
from a head's first chunk to its last, **transposed** (``S^T [dv, dk]``):
the decay of a key channel is then one number a lane, a row ``[1, dk]``
broadcast down the sublanes, where ``S [dk, dv]`` would want it as a
column. Its ``custom_vjp`` is the same loop backwards (``apex_kda_bwd``)
with the state's cotangent in scratch, from ``q``, ``k``, ``G`` again and
the state each chunk came in with (the forward's one residual of its own;
``q``, ``k``, ``G`` are the local pair's residuals too, ``w``, ``u``, ``m``
are kept in the products' type). It takes ``Q``'s, ``K``'s and ``e``'s
cotangents the rest of the way in VMEM and hands out ``dq``, ``dk`` in the
operands' type and **one** float32 ``dG [C, dk]``: ``dQ Q - dK K`` a
token, and on the chunk's last token also the column sums of ``dK K`` and
``e sum_v(S^T dS'^T)``; ``dw``, ``du``, ``dm`` leave in float32. Several
heads a grid step because one head's products are a dependent chain of
64-row matmuls; the heads' chains are independent.

The arithmetic is the ``lax.scan``'s (``gated_delta_rule._chunk_scan``
over ``jax.numpy``'s decayed operands): every product takes both operands
in the products' type and accumulates in float32; ``G``, every ``exp``,
the state, its cotangent and ``dG`` are float32, rounded only where they
enter a product.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.pallas._common import interpret_mode, round_up, vma

__all__ = ["chunk_scan", "local_products", "takes"]

HEADS = 8               # (batch x head) pairs a grid step of the scan pair
CHUNKS = 8              # chunks a grid step of the local pair
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


def _decayed(q_ref, k_ref, g_ref, h):
    """Head ``h``'s chunk from ``q``, ``k``, ``G``: ``Q = exp(G) q`` and ``K
    = exp(G_last - G) k`` in float32 ``[C, dk]``, the two decays, and ``e =
    exp(G_last) [1, dk]``. Every exponent is <= 0."""
    g = g_ref[h]
    last = g[-1:]
    into, out = jnp.exp(g), jnp.exp(last - g)
    return (q_ref[h].astype(_F32) * into, k_ref[h].astype(_F32) * out,
            into, out, jnp.exp(last))


def _fwd_kernel(q_ref, k_ref, g_ref, w_ref, u_ref, m_ref, o_ref, s0_ref,
                s_ref):
    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)
    dt = q_ref.dtype

    def head(h, carry):
        q, k, _, _, e = _decayed(q_ref, k_ref, g_ref, h)
        st = s0_ref[h] = s_ref[h]                       # S^T [dv, dk]
        st_in = st.astype(dt)
        d = (u_ref[h].astype(_F32) - _dot(w_ref[h], st_in, _NT)).astype(dt)
        o_ref[h] = (_dot(q.astype(dt), st_in, _NT)
                    + _dot(m_ref[h], d)).astype(o_ref.dtype)
        s_ref[h] = st * e + _dot(d, k.astype(dt), _TN)
        return carry
    jax.lax.fori_loop(0, HEADS, head, 0)


def _bwd_kernel(q_ref, k_ref, g_ref, w_ref, u_ref, m_ref, s0_ref, do_ref,
                dq_ref, dk_ref, dg_ref, dw_ref, du_ref, dm_ref, ds_ref):
    @pl.when(pl.program_id(1) == 0)         # the chunks run backwards
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)
    dt = q_ref.dtype

    def head(h, carry):
        w, do = w_ref[h], do_ref[h]
        q, k, into, out, e = _decayed(q_ref, k_ref, g_ref, h)
        st, dst = s0_ref[h], ds_ref[h]                  # [dv, dk] both
        st_in, dst_in = st.astype(dt), dst.astype(dt)
        d = (u_ref[h].astype(_F32) - _dot(w, st_in, _NT)).astype(dt)
        # the recurrence's transposes
        dd = _dot(m_ref[h], do, _TN) + _dot(k.astype(dt), dst_in, _NT)
        dd_in = dd.astype(dt)
        du_ref[h] = dd
        dw_ref[h] = -_dot(dd_in, st_in)
        dm_ref[h] = _dot(do, d, _NT)
        dq_in, dk_out = _dot(do, st_in), _dot(d, dst_in)
        ds_ref[h] = (dst * e + _dot(do, q.astype(dt), _TN)
                     - _dot(dd_in, w, _TN))
        # through Q, K and e to q, k and G
        dq_ref[h] = (dq_in * into).astype(dq_ref.dtype)
        dk_ref[h] = (dk_out * out).astype(dk_ref.dtype)
        from_k = dk_out * k
        # G_last: K's exp(G_last - G) and the state's decay, sum_v(S0 dS')
        at_last = jnp.sum(from_k, axis=0, keepdims=True) + e * jnp.sum(
            st * dst, axis=0, keepdims=True)
        rows = jax.lax.broadcasted_iota(jnp.int32, from_k.shape, 0)
        dg_ref[h] = dq_in * q - from_k + jnp.where(
            rows == from_k.shape[0] - 1, at_last, 0.0)
        return carry
    jax.lax.fori_loop(0, HEADS, head, 0)


def _spec(index, *tail):
    """A block of ``HEADS`` heads' one chunk, the chunk axis squeezed."""
    return pl.BlockSpec((HEADS, None) + tail,
                        lambda i, j: (i, index(j), 0, 0))


# heads in any order, a head's chunks one after the other
_SEQUENTIAL = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def _forward(q, k, g, w, u, m):
    bh, n, c, dk = q.shape
    dv = u.shape[-1]
    spec = functools.partial(_spec, lambda j: j)
    sds = functools.partial(jax.ShapeDtypeStruct, vma=vma(q, k, g, w, u, m))
    return pl.pallas_call(
        _fwd_kernel,
        grid=(bh // HEADS, n),
        in_specs=[spec(c, dk), spec(c, dk), spec(c, dk), spec(c, dk),
                  spec(c, dv), spec(c, c)],
        out_specs=[spec(c, dv), spec(dv, dk)],
        out_shape=[sds((bh, n, c, dv), u.dtype), sds((bh, n, dv, dk), _F32)],
        scratch_shapes=[pltpu.VMEM((HEADS, dv, dk), _F32)],
        compiler_params=_SEQUENTIAL,
        interpret=interpret_mode(),
        name="apex_kda_fwd",
    )(q, k, g, w, u, m)


def _backward(q, k, g, w, u, m, s0, do):
    bh, n, c, dk = q.shape
    dv = u.shape[-1]
    spec = functools.partial(_spec, lambda j: n - 1 - j)
    sds = functools.partial(jax.ShapeDtypeStruct,
                            vma=vma(q, k, g, w, u, m, s0, do))
    return pl.pallas_call(
        _bwd_kernel,
        grid=(bh // HEADS, n),
        in_specs=[spec(c, dk), spec(c, dk), spec(c, dk), spec(c, dk),
                  spec(c, dv), spec(c, c), spec(dv, dk), spec(c, dv)],
        out_specs=[spec(c, dk), spec(c, dk), spec(c, dk), spec(c, dk),
                   spec(c, dv), spec(c, c)],
        out_shape=[sds(q.shape, q.dtype), sds(k.shape, k.dtype),
                   sds(g.shape, _F32), sds(w.shape, _F32),
                   sds(u.shape, _F32), sds(m.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((HEADS, dv, dk), _F32)],
        compiler_params=_SEQUENTIAL,
        interpret=interpret_mode(),
        name="apex_kda_bwd",
    )(q, k, g, w, u, m, s0, do)


def _heads(x, pad):
    """``[B, H, n, ...]`` as ``[B H (+ pad), n, ...]``; the heads added
    are zero everywhere, so their state stays zero."""
    x = x.reshape((-1,) + x.shape[2:])
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) if pad else x


@jax.custom_vjp
def chunk_scan(q, k, g, w, u, m):
    """The chunks' outputs ``[B, H, n, C, dv]`` in ``q``'s type, the
    products', from the three arrays :func:`local_products` takes (``q, k
    [B, H, n, C, dk]`` and float32 ``g``, the running log-decay ``G``
    inside each chunk) and float32 ``w [B, H, n, C, dk]``, ``u [B, H, n, C,
    dv]``, ``m [B, H, n, C, C]`` (masked), with the state zero in front of
    a head's first chunk. ``w``, ``u``, ``m`` are cast here, so that their
    cotangents leave the backward kernel in float32, rounded nowhere;
    ``g``'s is float32 and ``q``'s, ``k``'s come in their own type."""
    return _chunk_scan_fwd(q, k, g, w, u, m)[0]


def _chunk_scan_fwd(q, k, g, w, u, m):
    b, h = q.shape[:2]
    pad = round_up(b * h, HEADS) - b * h
    kept = (q, k, g) + tuple(x.astype(q.dtype) for x in (w, u, m))
    o, s0 = _forward(*(_heads(x, pad) for x in kept))
    return o[:b * h].reshape(u.shape), kept + (s0,)


def _chunk_scan_bwd(residuals, do):
    b, h = do.shape[:2]
    *kept, s0 = residuals
    pad = s0.shape[0] - b * h
    grads = _backward(*(_heads(x, pad) for x in kept), s0, _heads(do, pad))
    return tuple(x[:b * h].reshape((b, h) + x.shape[1:]) for x in grads)


chunk_scan.defvjp(_chunk_scan_fwd, _chunk_scan_bwd)


# -- the products inside a chunk: apex_kda_local_fwd / apex_kda_local_bwd ----

def _decays(g, block, lower):
    """A level's decays ``[C, dk]`` from a chunk's ``G``, the rows' and the
    columns': every block of ``block`` tokens against its token ``lower``,
    masked before the ``exp`` (``gated_delta_rule._local_products``)."""
    c, dk = g.shape
    ref = jnp.concatenate([jnp.broadcast_to(g[r:r + 1], (block, dk))
                           for r in range(lower, c, block)], axis=0)
    if lower == 0:      # a diagonal sub-block: every token both ways
        return jnp.exp(g - ref), jnp.exp(ref - g)
    row = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0) % block >= lower
    return (jnp.exp(jnp.where(row, g - ref, -jnp.inf)),
            jnp.exp(jnp.where(row, -jnp.inf, ref - g)))


def _kept(c, block):
    """Where a level's product ``[2 C, C]`` (``q``'s rows above ``k``'s) is
    kept: inside a block, ``s <= t`` under ``q`` and ``s < t`` under ``k``."""
    t = jax.lax.broadcasted_iota(jnp.int32, (2 * c, c), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (2 * c, c), 1)
    of_k = (t >= c).astype(jnp.int32)
    t = t % c
    return ((t ^ s) < block) & (t - s >= of_k)


def _operands(q_ref, k_ref, g_ref, i):
    """Chunk ``i`` of a grid step: ``q`` above ``k`` ``[2 C, dk]``, ``k``
    and ``G`` ``[C, dk]``, all float32."""
    k = k_ref[i].astype(_F32)
    return jnp.concatenate([q_ref[i].astype(_F32), k], axis=0), k, g_ref[i]


def _local_fwd_kernel(levels, q_ref, k_ref, g_ref, qk_ref, kk_ref):
    dt = q_ref.dtype
    c = q_ref.shape[1]

    def chunk(i, carry):
        x, k, g = _operands(q_ref, k_ref, g_ref, i)
        total = jnp.zeros((2 * c, c), _F32)
        for block, lower in levels:
            er, ec = _decays(g, block, lower)
            rows = x * jnp.concatenate([er, er], axis=0)
            total += jnp.where(_kept(c, block), _dot(
                rows.astype(dt), (k * ec).astype(dt), _NT), 0.0)
        qk_ref[i] = total[:c]
        kk_ref[i] = total[c:]
        return carry
    jax.lax.fori_loop(0, q_ref.shape[0], chunk, 0)


def _on_reference(e, block, lower):
    """Every block's sum of ``e [C, dk]`` on its token ``lower``, zeros on
    the others: what the reference tokens collect."""
    dk = e.shape[1]
    at = jax.lax.broadcasted_iota(jnp.int32, (block, dk), 0) == lower
    return jnp.concatenate([
        jnp.where(at, jnp.sum(e[r:r + block], axis=0, keepdims=True), 0.0)
        for r in range(0, e.shape[0], block)], axis=0)


def _local_bwd_kernel(levels, q_ref, k_ref, g_ref, dqk_ref, dkk_ref,
                      dq_ref, dk_ref, dg_ref):
    dt = q_ref.dtype
    c = q_ref.shape[1]

    def chunk(i, carry):
        x, k, g = _operands(q_ref, k_ref, g_ref, i)
        dp = jnp.concatenate([dqk_ref[i], dkk_ref[i]], axis=0)
        dx, dcol, dg = jnp.zeros_like(x), jnp.zeros_like(k), jnp.zeros_like(g)
        for block, lower in levels:
            er, ec = _decays(g, block, lower)
            er = jnp.concatenate([er, er], axis=0)
            rows, cols = x * er, k * ec
            dp_here = jnp.where(_kept(c, block), dp, 0.0).astype(dt)
            drows = _dot(dp_here, cols.astype(dt))
            dcols = _dot(dp_here, rows.astype(dt), _TN)
            dx += drows * er
            dcol += dcols * ec
            # d(G_t - G_r) less d(G_r - G_s): a token's own, and the
            # block's sum, minus, on its reference token
            e = drows * rows
            e = e[:c] + e[c:] - dcols * cols
            dg += e - _on_reference(e, block, lower)
        dq_ref[i] = dx[:c].astype(dq_ref.dtype)
        dk_ref[i] = (dx[c:] + dcol).astype(dk_ref.dtype)
        dg_ref[i] = dg
        return carry
    jax.lax.fori_loop(0, q_ref.shape[0], chunk, 0)


def _chunks(x):
    """``[B, H, n, ...]`` as ``[B H n (+ pad), ...]``, a whole number of
    grid steps; the chunks added are zeros."""
    x = x.reshape((-1,) + x.shape[3:])
    pad = round_up(x.shape[0], CHUNKS) - x.shape[0]
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) if pad else x


def _blocks(operands, outs):
    """What a ``pallas_call`` over blocks of ``CHUNKS`` chunks, in any
    order, takes beside its body and its name: ``operands`` and the
    results ``outs`` (``(tail, dtype)``) are all ``[chunks, *tail]``."""
    n = operands[0].shape[0]
    spec = lambda tail: pl.BlockSpec((CHUNKS,) + tail, lambda i: (i, 0, 0))
    sds = functools.partial(jax.ShapeDtypeStruct, vma=vma(*operands))
    return dict(
        grid=(n // CHUNKS,),
        in_specs=[spec(x.shape[1:]) for x in operands],
        out_specs=[spec(tail) for tail, _ in outs],
        out_shape=[sds((n,) + tail, dtype) for tail, dtype in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret_mode())


def _unchunks(xs, lead):
    """``_chunks`` undone: ``[B, H, n, ...]`` again, the padding off."""
    return tuple(x[:lead[0] * lead[1] * lead[2]].reshape(lead + x.shape[1:])
                 for x in xs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def local_products(levels, q, k, g):
    """``gated_delta_rule._local_products``, whose text this is: ``(qk, kk)
    [B, H, n, C, C]`` float32 from ``q, k [B, H, n, C, dk]`` in the
    products' type and float32 ``g`` (``G``, the running log-decay), level
    by level (``levels``: ``gated_delta_rule._levels(C)``). The backward
    keeps ``q``, ``k``, ``g`` and nothing else."""
    return _local_products_fwd(levels, q, k, g)[0]


def _local_products_fwd(levels, q, k, g):
    c = q.shape[-2]
    operands = [_chunks(x) for x in (q, k, g)]
    products = pl.pallas_call(
        functools.partial(_local_fwd_kernel, levels),
        name="apex_kda_local_fwd",
        **_blocks(operands, [((c, c), _F32)] * 2))(*operands)
    return _unchunks(products, q.shape[:3]), (q, k, g)


def _local_products_bwd(levels, residuals, cotangents):
    operands = [_chunks(x) for x in residuals + tuple(cotangents)]
    grads = pl.pallas_call(
        functools.partial(_local_bwd_kernel, levels),
        name="apex_kda_local_bwd",
        **_blocks(operands, [(x.shape[-2:], x.dtype) for x in residuals]))(
            *operands)
    return _unchunks(grads, residuals[0].shape[:3])


local_products.defvjp(_local_products_fwd, _local_products_bwd)
