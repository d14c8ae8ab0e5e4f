"""The lightning indexer of learned sparse attention (DeepSeek-V3.2's, as
Keye-VL-2.0 carries it): which keys a query attends to, and the loss the
indexer learns from.

For a row of ``T`` tokens, ``H`` indexer heads of ``D`` over one shared
indexer key head, with ``qI [B, T, H, D]``, ``kI [B, T, D]`` and the
queries' head weights ``w [B, T, H]`` (float32, every scale folded in):

    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])       float32, s <= t

- :func:`select_keys`: ``S_t``, the ``min(t + 1, topk)`` keys ``s <= t``
  of largest ``I[t, s]``, **exactly that many**, among equal scores the
  lower key first (``jax.lax.top_k``'s order), as the packed set
  ``flash_attention(select=)`` reads (``pack_select``). No sort: the
  ``n``-th largest score of a query is found by bisection on the float's
  bits (a count of the scores at or above a candidate a pass), then the
  ties at that score by the same search over key positions. ``"fast"``:
  the kernel ``apex_idx_search``, which reads a block of queries' scores
  once, counts in VMEM a bit a pass over the keys the block can see, and
  writes the packed words; ``"reference"``: :func:`topk_mask`, two bits
  a pass over the chunk's scores in ``jax.numpy``, and ``pack_select``.
- :func:`index_loss`: with ``p[t, s]`` the head-mean of the main
  attention's probabilities over ``S_t`` (made again from its queries,
  keys and saved log-sum-exp, never as ``[heads, T, T]``) and ``qi[t, :] =
  softmax over S_t of I[t, :]``,

      L_I = mean_t sum_{s in S_t} p[t, s] (log p[t, s] - log qi[t, s])

  differentiable in ``qI``, ``kI`` and ``w`` alone. Its gradient is made
  in the forward pass, beside the value (``dL_I/dI = (sum_s p) qi - p`` on
  the set), and named ``SAVED_NAMES[1]``; the backward is a product with
  the cotangent.

Both work a chunk of queries at a time against all keys: ``[chunk, T]``
float32 arrays are the largest that stand in memory. ``impl="fast"``: the
sums over heads are the Pallas kernels ``apex_idx_scores``,
``apex_idx_probs`` and ``apex_idx_grad`` and the search is
``apex_idx_search`` (``ops/pallas/sparse_index.py``; interpreted off the
TPU); ``"reference"``: ``jax.numpy``, their oracle.
A ``jax.checkpoint`` whose policy saves ``SAVED_NAMES`` (the packed set,
33.5 MB a row of 16,384, and the indexer's gradient) runs neither the
search nor the loss again in its recomputed pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from apex_tpu.ops.key_set import pack_select, select_live, unpack_select
from apex_tpu.ops.pallas import sparse_index as _kernels
from apex_tpu.ops.pallas._common import LANES

__all__ = ["SAVED_NAMES", "index_loss", "index_scores", "live_tile_pct",
           "select_keys", "topk_mask"]

SAVED_NAMES = ("apex_idx_select", "apex_idx_grads")
CHUNK = 1024            # queries a pass: five [CHUNK, T] float32 arrays
_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
_RADIX = 2              # bits of the answer a pass of topk_mask settles


def _chunk(t: int, chunk) -> int:
    """Queries a pass: ``chunk``, or the largest power of two up to
    ``CHUNK`` that divides ``t`` (``t`` itself where none does)."""
    if chunk:
        if t % chunk:
            raise ValueError(f"chunk ({chunk}) must divide the sequence ({t})")
        return chunk
    return next((c for c in (CHUNK >> i for i in range(8)) if t % c == 0), t)


# -- the sums over heads, a chunk of queries against every key ---------------

def _lanes(x):
    """``[B, c, H]`` per-head scalars as the kernels read them: float32
    ``[B, c, 128]``, head ``h`` in lane ``h``."""
    return jnp.pad(x.astype(_F32), ((0, 0), (0, 0), (0, LANES - x.shape[-1])))


def _wide(x):
    """The last axis padded with zeros to whole lane tiles."""
    pad = (-x.shape[-1]) % LANES
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),))


def _seen(start, c: int, t: int):
    return (start + jnp.arange(c))[:, None] >= jnp.arange(t)[None, :]


def _scores(qh, kh, wl, start, impl: str):
    """``I [B, c, T]`` of a chunk: ``qh [B, H, c, D]``, ``kh [B, 1, T, D]``,
    ``wl [B, c, 128]``; ``-inf`` above the diagonal."""
    if impl == "fast":
        return _kernels.pair_sum(qh, kh, wl, start, probs=False)
    heads = qh.shape[1]
    s = jnp.einsum("bhcd,btd->bhct", qh.astype(_F32), kh[:, 0].astype(_F32),
                   precision=_HI)
    i = jnp.einsum("bhct,bch->bct", jax.nn.relu(s), wl[..., :heads],
                   precision=_HI)
    i = jnp.where(i == 0.0, 0.0, i)     # -0.0 and 0.0: one score to a top-k
    return jnp.where(_seen(start, qh.shape[2], kh.shape[2]), i, -jnp.inf)


def _probs(q, k, lse, start, scale: float, impl: str):
    """``P [B, c, T]``: the head-mean of ``exp(scale q . k - lse)``; ``q
    [B, H, c, D]``, ``k [B, G, T, D]``, ``lse [B, c, 128]``; 0 above the
    diagonal."""
    if impl == "fast":
        return _kernels.pair_sum(q, k, lse, start, probs=True, scale=scale)
    b, heads, c, d = q.shape
    g = k.shape[1]
    s = jnp.einsum("bgrcd,bgtd->bgrct", q.astype(_F32).reshape(
        b, g, heads // g, c, d), k.astype(_F32), precision=_HI) * scale
    p = jnp.exp(s.reshape(b, heads, c, -1)
                - lse[..., :heads].transpose(0, 2, 1)[..., None])
    return jnp.where(_seen(start, c, k.shape[2]), jnp.mean(p, 1), 0.0)


def _grads(qh, kh, wl, di, start, impl: str):
    """``(dqh, dwl, dkh [B, T, D])`` in float32 from ``di [B, c, T]``."""
    if impl == "fast":
        return _kernels.grad(qh, kh, wl, di, start)
    def visible(q, k, w):
        i = _scores(q, k, w, start, impl)
        return jnp.where(jnp.isfinite(i), i, 0.0)
    _, vjp = jax.vjp(visible, qh.astype(_F32), kh.astype(_F32), wl)
    dq, dk, dw = vjp(di)
    return dq, dw, dk[:, 0]


def index_scores(qi, ki, w, *, impl: str = "fast"):
    """``I [B, T, T]`` float32, ``-inf`` above the diagonal: for tests and
    small sizes (the ops below never hold it whole)."""
    qh, kh, wl = _operands(qi, ki, w)
    return _scores(qh, kh, wl, 0, impl)


def _operands(qi, ki, w):
    """The kernels' layouts: heads lead, the head width in whole lane
    tiles, a query's weights on the lanes of one tile."""
    return (_wide(qi).transpose(0, 2, 1, 3), _wide(ki)[:, None], _lanes(w))


def _rows(x, start, c: int, axis: int):
    return jax.lax.dynamic_slice_in_dim(x, start, c, axis)


# -- the exact top-k of a row, without a sort --------------------------------

def _ordered(x):
    """float32 -> uint32, order kept (``-inf`` lowest)."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    b = b ^ ((b >> 31) & 0x7FFFFFFF)
    return jax.lax.bitcast_convert_type(b, jnp.uint32) ^ jnp.uint32(1 << 31)


def _kth_largest(u, n, bits: int):
    """The ``n``-th largest of ``u [..., T]`` (uint32 below ``2**bits``;
    ``1 <= n [...] <= T``): the largest value with at least ``n`` elements
    at or above it, ``_RADIX`` bits a counting pass from the top bit
    down."""
    steps = jnp.arange(1, 1 << _RADIX, dtype=jnp.uint32)

    def one_pass(p, ans):
        shift = (bits - _RADIX * (p + 1)).astype(jnp.uint32)
        cands = ans[..., None] | (steps << shift)               # [..., 3]
        count = jnp.sum(u[..., None, :] >= cands[..., None], -1,
                        dtype=jnp.int32)
        # counts fall as the candidates rise: as many hold as the largest
        held = jnp.sum(count >= n[..., None], -1, dtype=jnp.uint32)
        return ans | (held << shift)
    return jax.lax.fori_loop(0, bits // _RADIX, one_pass,
                             jnp.zeros(u.shape[:-1], jnp.uint32))


def topk_mask(scores, n):
    """bool like ``scores [..., T]``: each row's ``n [...]`` largest
    entries, exactly ``n`` of them, among equals the lower index first
    (``jax.lax.top_k``'s choice). ``1 <= n <=`` the row's entries above
    ``-inf``."""
    t = scores.shape[-1]
    u = _ordered(scores)
    tau = _kth_largest(u, n, 32)[..., None]
    above, ties = u > tau, u == tau
    left = n - jnp.sum(above, -1, dtype=jnp.int32)      # ties to take: >= 1
    # the ties by position: the lower index is the larger key
    key = jnp.where(ties, jnp.uint32(t) - jnp.arange(t, dtype=jnp.uint32), 0)
    bits = -(-t.bit_length() // _RADIX) * _RADIX
    return above | (ties & (key >= _kth_largest(key, left, bits)[..., None]))


def _search(i, start, topk: int, impl: str):
    """The packed sets of a chunk's scores ``i [B, c, T]``, query ``r`` at
    position ``start + r``: its ``min(start + r + 1, topk)`` largest."""
    if impl == "fast":
        return _kernels.search(i, start, topk)
    n = jnp.minimum(start + jnp.arange(i.shape[1]) + 1, topk)
    return pack_select(topk_mask(i, jnp.broadcast_to(n, i.shape[:2])))


def select_keys(qi, ki, w, topk: int, *, chunk=None, impl: str = "fast"):
    """The packed key sets ``int32 [B, T, 128 * ceil(T / 4096)]`` (a bit a
    key, ``key_set.pack_select``): query ``t``'s ``min(t + 1,
    topk)`` best keys by ``I[t, :]``. Named ``SAVED_NAMES[0]``. Nothing is
    differentiated through it."""
    qi, ki, w = map(jax.lax.stop_gradient, (qi, ki, w))
    t = qi.shape[1]
    c = _chunk(t, chunk)
    qh, kh, wl = _operands(qi, ki, w)

    def one(start):
        return _search(_scores(_rows(qh, start, c, 2), kh,
                               _rows(wl, start, c, 1), start, impl),
                       start, topk, impl)
    words = jax.lax.map(one, jnp.arange(0, t, c))       # [chunks, B, c, W]
    words = words.transpose(1, 0, 2, 3).reshape(qi.shape[0], t, -1)
    return checkpoint_name(words, SAVED_NAMES[0])


def live_tile_pct(select, tile: int = 512):
    """Of the ``tile`` x ``tile`` tiles of scores at or below the diagonal,
    the share (%) that hold a selected key, fullest row of the batch:
    whether skipping tiles could pay at this selection (no kernel does)."""
    b, t, _ = select.shape
    bq = tile if t % tile == 0 else t
    live = select_live(select, bq, tile)                    # [B, nq, nk]
    nq, nk = live.shape[1:]
    first_key = jnp.arange(nk) * tile
    causal = (first_key[None, :] <= (jnp.arange(nq) * bq + bq - 1)[:, None]) \
        & (first_key[None, :] < t)
    held = jnp.sum(jnp.where(causal, live, 0), (1, 2))
    return 100.0 * jnp.max(held) / jnp.sum(causal)


# -- the indexer's loss, its gradient made beside it -------------------------

def _loss_and_grads(qi, ki, w, q, k, lse, select, scale, chunk, impl,
                    with_grads: bool):
    """``(L_I, (dqi, dki, dw) | None)``; the gradient in float32."""
    b, t, heads, d = qi.shape
    c = _chunk(t, chunk)
    qh, kh, wl = _operands(qi, ki, w)
    lse_l = _lanes(lse.transpose(0, 2, 1))                  # [B, T, 128]

    def one(carry, start):
        total, dkh = carry
        rows = functools.partial(_rows, start=start, c=c)
        qh_c, wl_c = rows(qh, axis=2), rows(wl, axis=1)
        keep = unpack_select(rows(select, axis=1), t)
        i = _scores(qh_c, kh, wl_c, start, impl)
        p = jnp.where(keep, _probs(rows(q, axis=2), k, rows(lse_l, axis=1),
                                   start, scale, impl), 0.0)
        logz = jax.nn.logsumexp(jnp.where(keep, i, -jnp.inf), -1,
                                keepdims=True)
        log_qi = jnp.where(keep, i - logz, 0.0)
        total = total + jnp.sum(jax.scipy.special.xlogy(p, p) - p * log_qi)
        if not with_grads:
            return (total, dkh), None
        di = (jnp.sum(p, -1, keepdims=True)
              * jnp.where(keep, jnp.exp(log_qi), 0.0) - p) * (1.0 / (b * t))
        dq_c, dw_c, dk = _grads(qh_c, kh, wl_c, di, start, impl)
        return (total, dkh + dk), (dq_c, dw_c)
    (total, dkh), per_chunk = jax.lax.scan(
        one, (jnp.zeros((), _F32), jnp.zeros(kh[:, 0].shape, _F32)),
        jnp.arange(0, t, c))
    loss = total / (b * t)
    if not with_grads:
        return loss, None
    dq, dw = per_chunk      # [chunks, B, H, c, Dp], [chunks, B, c, 128]
    dq = dq.transpose(1, 0, 3, 2, 4).reshape(b, t, heads, -1)[..., :d]
    dw = dw.transpose(1, 0, 2, 3).reshape(b, t, -1)[..., :heads]
    return loss, (dq, dkh[..., :d], dw)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _index_loss(qi, ki, w, q, k, lse, select, scale, chunk, impl):
    return _loss_and_grads(qi, ki, w, q, k, lse, select, scale, chunk, impl,
                           False)[0]


def _index_loss_fwd(qi, ki, w, q, k, lse, select, scale, chunk, impl):
    loss, grads = _loss_and_grads(qi, ki, w, q, k, lse, select, scale, chunk,
                                  impl, True)
    # in the operands' types: what a checkpoint keeps of a layer
    grads = tuple(checkpoint_name(g.astype(x.dtype), SAVED_NAMES[1])
                  for g, x in zip(grads, (qi, ki, w)))
    return loss, grads


def _index_loss_bwd(scale, chunk, impl, grads, ct):
    return tuple((ct * g.astype(_F32)).astype(g.dtype) for g in grads) \
        + (None,) * 4


_index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)


def index_loss(qi, ki, w, q, k, lse, select, *, scale: float, chunk=None,
               impl: str = "fast"):
    """``L_I``, the mean over the batch's queries of ``KL(p[t] ||
    qi[t])`` over the selected keys. ``q [B, Hq, T, Dq]``, ``k [B, G, T,
    Dq]`` and ``lse [B, Hq, T]`` are the main attention's queries, keys
    (head ``h`` reads key head ``h // (Hq / G)``) and log-sum-exp over the
    set ``select``; no gradient reaches them, nor ``select``: ``p`` is a
    target."""
    q, k, lse = map(jax.lax.stop_gradient, (q, k, lse))
    return _index_loss(qi, ki, w, q, k, lse, select, float(scale), chunk,
                       impl)
