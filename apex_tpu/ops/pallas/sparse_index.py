"""Pallas kernels of the sparse-attention indexer (``ops/sparse_index.py``):
sums over heads of a function of ``q_h . k`` for a chunk of queries against
every key, a [block_q, block_k] tile at a time, so that no array with a
heads axis beside the two sequence axes ever stands in HBM; and the exact
top-``n`` search over a chunk's scores, a block of queries held in VMEM.

- ``apex_idx_scores``: ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``
  in float32 (bf16 products, float32 sums), ``-inf`` above the diagonal.
- ``apex_idx_probs``: ``P[t, s] = mean_h exp(scale q[h, t] . k[g(h), s] -
  lse[h, t])``, the head-mean of the attention probabilities the flash
  backward makes again from its saved log-sum-exp, 0 above the diagonal.
- ``apex_idx_grad``: from ``dI [c, T]`` the cotangents of ``qI``, ``w``
  and (a partial sum a block of queries, summed by the caller) ``kI``.
- ``apex_idx_search``: from ``I [B, c, T]`` the packed key sets ``int32
  [B, c, 128 * ceil(T / 4096)]`` of each query's ``min(position + 1,
  topk)`` largest scores, among equals the lower key
  (``key_set.SELECT_SPAN``'s layout: bit ``b`` of lane ``j`` of tile ``u``
  is key ``4096 u + 128 b + j``, so a 128-key tile of choices is one bit
  of a word tile: shifts and ORs, no move across lanes). A grid step
  holds ``block_q`` queries' whole rows (128 at ``T`` = 16,384: 8 MB, read
  from HBM once), as order-keeping int32 in a VMEM scratch of ``[T / 512,
  block_q, 512]`` slabs; a counting pass walks the slabs that hold a key
  at or below the block's last query (from ``start``: above them every
  score is ``-inf``) and the words of the others are zeros. Neither mask
  nor counts stand in HBM.

Shapes: a chunk of ``c`` queries that starts at position ``start`` (a
traced scalar, in SMEM) against ``T`` keys; heads lead (``[B, H, c, D]``,
``[B, G, T, D]``, head ``h`` reads key head ``h // (H / G)``), ``D`` a
multiple of 128; a query's per-head scalars (``w``, ``lse``) ride the
lanes of one ``[B, c, 128]`` tile, head ``h`` in lane ``h``. A tile wholly
above the diagonal does no matmul.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.key_set import SELECT_SPAN
from apex_tpu.ops.pallas._common import LANES, interpret_mode

_F32 = jnp.float32
_PARALLEL = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"))
_ROWS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=48 << 20)


def blocks(c: int, t: int) -> tuple:
    """``(block_q, block_k)`` that tile a chunk of ``c`` queries and ``t``
    keys: up to 256 x 512, a divisor each (a short axis whole)."""
    bq = next((b for b in (256, 128, 64, 32, 16, 8) if c % b == 0), c)
    bk = next((b for b in (512, 256, 128) if t % b == 0), t)
    return bq, bk


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=_F32)


def _causal(start_ref, i, j, bq, bk):
    """``(the tile holds a visible pair, which of its pairs are)``."""
    q_lo, k_lo = start_ref[0] + i * bq, j * bk
    q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return k_lo <= q_lo + bq - 1, q_pos >= k_pos


def _pair_sum_kernel(probs: bool, heads: int, group: int, scale: float,
                     start_ref, q_ref, k_ref, stat_ref, o_ref):
    # program_id is read outside pl.when bodies (interpret mode)
    i, j = pl.program_id(1), pl.program_id(2)
    bq, bk = o_ref.shape[1:]
    live, seen = _causal(start_ref, i, j, bq, bk)
    fill = 0.0 if probs else -jnp.inf

    @pl.when(jnp.logical_not(live))
    def _dead():
        o_ref[0] = jnp.full((bq, bk), fill, _F32)

    @pl.when(live)
    def _body():
        stat = stat_ref[0]                          # [bq, 128], head a lane
        acc = jnp.zeros((bq, bk), _F32)
        for h in range(heads):
            s = _dot(q_ref[0, h], k_ref[0, h // group], ((1,), (1,)))
            if probs:
                acc += jnp.exp(s * scale - stat[:, h:h + 1])
            else:
                acc += stat[:, h:h + 1] * jnp.maximum(s, 0.0)
        if probs:
            acc = acc * (1.0 / heads)
        else:           # one zero: -0.0 and 0.0 are one score to a top-k
            acc = jnp.where(acc == 0.0, 0.0, acc)
        o_ref[0] = jnp.where(seen, acc, fill)


def pair_sum(q, k, stat, start, *, probs: bool, scale: float = 1.0):
    """``[B, c, T]`` float32: ``apex_idx_probs`` (``probs``: ``stat`` is
    the log-sum-exp) or ``apex_idx_scores`` (``stat`` is ``w``) of ``q [B,
    H, c, D]`` against ``k [B, G, T, D]``, ``stat [B, c, 128]``, the chunk's
    first position ``start``."""
    b, heads, c, d = q.shape
    g, t = k.shape[1], k.shape[2]
    bq, bk = blocks(c, t)
    kernel = functools.partial(_pair_sum_kernel, probs, heads, heads // g,
                               float(scale))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, c // bq, t // bk),
            in_specs=[
                pl.BlockSpec((1, heads, bq, d),
                             lambda b, i, j, _: (b, 0, i, 0)),
                pl.BlockSpec((1, g, bk, d), lambda b, i, j, _: (b, 0, j, 0)),
                pl.BlockSpec((1, bq, LANES), lambda b, i, j, _: (b, i, 0)),
            ],
            out_specs=pl.BlockSpec((1, bq, bk), lambda b, i, j, _: (b, i, j))),
        out_shape=jax.ShapeDtypeStruct((b, c, t), _F32),
        compiler_params=_PARALLEL,
        interpret=interpret_mode(),
        name="apex_idx_probs" if probs else "apex_idx_scores",
    )(jnp.asarray(start, jnp.int32).reshape(1), q, k, stat)


def _grad_kernel(heads: int, start_ref, q_ref, k_ref, w_ref, di_ref,
                 dq_ref, dw_ref, dk_ref, dq_acc, dw_acc):
    i, j = pl.program_id(1), pl.program_id(2)
    last = pl.num_programs(2) - 1
    bq, bk = di_ref.shape[1:]
    live, _ = _causal(start_ref, i, j, bq, bk)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)

    @pl.when(jnp.logical_not(live))
    def _dead():
        dk_ref[0, 0] = jnp.zeros(dk_ref.shape[2:], _F32)

    @pl.when(live)
    def _body():
        w, di, k = w_ref[0], di_ref[0], k_ref[0, 0]
        lane = jax.lax.broadcasted_iota(jnp.int32, (bq, LANES), 1)
        dk = jnp.zeros(dk_ref.shape[2:], _F32)
        dw = jnp.zeros((bq, LANES), _F32)
        for h in range(heads):
            qh = q_ref[0, h]
            s = _dot(qh, k, ((1,), (1,)))                       # [bq, bk]
            dw = dw + jnp.where(lane == h, jnp.sum(
                di * jnp.maximum(s, 0.0), axis=1, keepdims=True), 0.0)
            g = jnp.where(s > 0.0, di * w[:, h:h + 1], 0.0).astype(k.dtype)
            dq_acc[h] += _dot(g, k, ((1,), (0,)))               # [bq, D]
            dk = dk + _dot(g, qh, ((0,), (0,)))                 # [bk, D]
        dw_acc[...] += dw
        dk_ref[0, 0] = dk

    @pl.when(j == last)
    def _finalize():
        dq_ref[0] = dq_acc[...]
        dw_ref[0] = dw_acc[...]


def grad(q, k, w, di, start):
    """``apex_idx_grad``: ``(dq [B, H, c, D], dw [B, c, 128], dk [B, T,
    D])`` in float32 from ``di [B, c, T]``, the cotangent of
    ``apex_idx_scores``' result (zero wherever a pair is not selected)."""
    b, heads, c, d = q.shape
    t = k.shape[2]
    bq, bk = blocks(c, t)
    dq, dw, dk = pl.pallas_call(
        functools.partial(_grad_kernel, heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, c // bq, t // bk),
            in_specs=[
                pl.BlockSpec((1, heads, bq, d),
                             lambda b, i, j, _: (b, 0, i, 0)),
                pl.BlockSpec((1, 1, bk, d), lambda b, i, j, _: (b, 0, j, 0)),
                pl.BlockSpec((1, bq, LANES), lambda b, i, j, _: (b, i, 0)),
                pl.BlockSpec((1, bq, bk), lambda b, i, j, _: (b, i, j)),
            ],
            out_specs=[
                pl.BlockSpec((1, heads, bq, d),
                             lambda b, i, j, _: (b, 0, i, 0)),
                pl.BlockSpec((1, bq, LANES), lambda b, i, j, _: (b, i, 0)),
                pl.BlockSpec((1, 1, bk, d), lambda b, i, j, _: (b, i, j, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((heads, bq, d), _F32),
                            pltpu.VMEM((bq, LANES), _F32)]),
        out_shape=[jax.ShapeDtypeStruct((b, heads, c, d), _F32),
                   jax.ShapeDtypeStruct((b, c, LANES), _F32),
                   jax.ShapeDtypeStruct((b, c // bq, t, d), _F32)],
        compiler_params=_ROWS,
        interpret=interpret_mode(),
        name="apex_idx_grad",
    )(jnp.asarray(start, jnp.int32).reshape(1), q, k, w, di)
    return dq, dw, jnp.sum(dk, axis=1)


# -- the exact top-n of a block of queries, its scores held in VMEM ----------

_SEARCH = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=48 << 20)
_I32 = jnp.int32
_TOP = -(1 << 31)       # int32's lowest: the sign bit alone


def search_blocks(c: int, t: int) -> tuple:
    """``(block_q, slab)``: the queries a grid step holds (the largest of
    128 .. 8 that divides ``c`` and keeps a block's float32 scores at or
    under 8 MB; a short or odd chunk whole) and the keys a counting step
    reads (``t`` in whole lane tiles)."""
    bq = next((b for b in (128, 64, 32, 16, 8)
               if c % b == 0 and b * t * 4 <= 8 << 20), c)
    bk = next(b for b in (512, 256, 128) if t % b == 0)
    return bq, bk


def _search_kernel(topk: int, start_ref, i_ref, o_ref, u_ref, lim_ref):
    bq, t = i_ref.shape[1:]
    slabs, _, bk = u_ref.shape
    q_lo = start_ref[0] + pl.program_id(1) * bq
    # slabs that hold a key at or below the block's last query: above them
    # every score is -inf and no key is chosen
    live = jnp.minimum((q_lo + bq - 1) // bk + 1, slabs)
    n = jnp.minimum(q_lo + 1 + jax.lax.broadcasted_iota(_I32, (bq, 1), 0),
                    topk)

    def fill(s, _):
        # float32 -> int32, order kept (-inf lowest)
        b = jax.lax.bitcast_convert_type(
            i_ref[0, :, pl.ds(pl.multiple_of(s * bk, bk), bk)], _I32)
        u_ref[s] = b ^ ((b >> 31) & 0x7FFFFFFF)
    jax.lax.fori_loop(0, live, fill, None)

    def walk(lo, hi, each, init):
        """``each(carry, tile, first key)`` folded over the [bq, 128] tiles
        of slabs ``lo .. hi - 1``."""
        def slab(s, carry):
            for j in range(bk // LANES):
                carry = each(carry, u_ref[s, :, j * LANES:(j + 1) * LANES],
                             s * bk + j * LANES)
            return carry
        return jax.lax.fori_loop(lo, hi, slab, init)

    zeros = jnp.zeros((bq, LANES), _I32)

    def count(chosen):
        """``[bq, 1]``: a row's keys with ``chosen(tile, first key)``."""
        return jnp.sum(walk(0, live, lambda acc, tile, k_lo: acc + jnp.where(
            chosen(tile, k_lo), 1, 0), zeros), axis=1, keepdims=True)

    def kth_largest(bits: int, first, count_from, need):
        """The largest value with at least ``need [bq, 1]`` of a row's keys
        at or above it by ``count_from(candidate)``, a bit a counting pass
        from bit ``bits - 1`` down over ``first``; and that count."""
        def one_pass(p, carry):
            ans, held = carry
            cand = ans ^ jax.lax.shift_left(_I32(1), _I32(bits - 1) - p)
            got = count_from(cand)
            take = got >= need
            return jnp.where(take, cand, ans), jnp.where(take, got, held)
        return jax.lax.fori_loop(
            0, bits, one_pass, (jnp.full((bq, 1), first, _I32),
                                jnp.zeros((bq, 1), _I32)))

    def wide(x):
        return jnp.broadcast_to(x, (bq, LANES))

    def at_or_above(cand):
        cand = wide(cand)
        return count(lambda tile, _: tile >= cand)
    tau, at_tau = kth_largest(32, _TOP, at_or_above, n)
    tau_w = wide(tau)
    above = count(lambda tile, _: tile > tau_w)
    left, ties = n - above, at_tau - above      # ties to take: >= 1
    # the last key position a tie is taken at: every tie, unless a row
    # has more than it may take
    lim_ref[...] = jnp.full((bq, LANES), t, _I32)
    lane = jax.lax.broadcasted_iota(_I32, (bq, LANES), 1)

    @pl.when(jnp.max(ties - left) > 0)
    def _ties():
        # the same search over t - position among the ties: the lower key
        # is the larger
        def tied_at_or_above(cand):
            last = wide(t - cand)
            return count(lambda tile, k_lo: (tile == tau_w)
                         & (lane <= last - k_lo))
        kth, _ = kth_largest(t.bit_length(), 0, tied_at_or_above, left)
        lim_ref[...] = wide(t - kth)

    last = lim_ref[...]

    def span(u, _):
        # bit b of a span's word tile is its b-th tile of 128 keys
        def add(words, tile, k_lo):
            keep = (tile > tau_w) | ((tile == tau_w) & (lane <= last - k_lo))
            bit = jax.lax.shift_left(_I32(1),
                                     (k_lo - u * SELECT_SPAN) // LANES)
            return words | jnp.where(keep, bit, 0)
        per_span = SELECT_SPAN // bk
        o_ref[0, :, pl.ds(pl.multiple_of(u * LANES, LANES), LANES)] = walk(
            u * per_span, jnp.clip(live, u * per_span, (u + 1) * per_span),
            add, zeros)
    jax.lax.fori_loop(0, o_ref.shape[2] // LANES, span, None)


def search(scores, start, topk: int):
    """``apex_idx_search``: the packed key sets ``int32 [B, c, 128 *
    ceil(T / 4096)]`` (``key_set.SELECT_SPAN``'s layout) of the ``min(t +
    1, topk)`` largest of each query's ``scores [B, c, T]`` (float32,
    ``-inf`` above the diagonal; query ``r`` is position ``start + r``),
    among equals the lower key first: what ``pack_select(topk_mask(scores,
    n))`` gives. A block of queries' scores is read once and held in VMEM
    as order-keeping int32; the ``n``-th largest is found by bisection on
    its bits (a bit a counting pass), then, in a block where some row has
    more ties at that score than it may take, the ties by the same search
    over key positions. Only the keys at or below the block's last query
    are visited."""
    b, c, t = scores.shape
    pad = (-t) % LANES
    if pad:
        scores = jnp.pad(scores, ((0, 0), (0, 0), (0, pad)),
                         constant_values=-jnp.inf)
    bq, bk = search_blocks(c, t + pad)
    words = LANES * (-(-t // SELECT_SPAN))
    return pl.pallas_call(
        functools.partial(_search_kernel, int(topk)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, c // bq),
            in_specs=[pl.BlockSpec((1, bq, t + pad),
                                   lambda b, i, _: (b, i, 0))],
            out_specs=pl.BlockSpec((1, bq, words), lambda b, i, _: (b, i, 0)),
            scratch_shapes=[pltpu.VMEM(((t + pad) // bk, bq, bk), _I32),
                            pltpu.VMEM((bq, LANES), _I32)]),
        out_shape=jax.ShapeDtypeStruct((b, c, words), _I32),
        compiler_params=_SEARCH,
        interpret=interpret_mode(),
        name="apex_idx_search",
    )(jnp.asarray(start, jnp.int32).reshape(1), scores)
