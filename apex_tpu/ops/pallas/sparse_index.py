"""Pallas kernels of the sparse-attention indexer (``ops/sparse_index.py``):
sums over heads of a function of ``q_h . k`` for a chunk of queries against
every key, a [block_q, block_k] tile at a time, so that no array with a
heads axis beside the two sequence axes ever stands in HBM.

- ``apex_idx_scores``: ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``
  in float32 (bf16 products, float32 sums), ``-inf`` above the diagonal.
- ``apex_idx_probs``: ``P[t, s] = mean_h exp(scale q[h, t] . k[g(h), s] -
  lse[h, t])``, the head-mean of the attention probabilities the flash
  backward makes again from its saved log-sum-exp, 0 above the diagonal.
- ``apex_idx_grad``: from ``dI [c, T]`` the cotangents of ``qI``, ``w``
  and (a partial sum a block of queries, summed by the caller) ``kI``.

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
