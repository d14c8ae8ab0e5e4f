"""Grouped matmuls over a buffer of rows sorted by expert, as Pallas kernels
that look a tile's expert up instead of taking its weights gathered.

``contrib/moe/expert_layer.py`` sorts the (token, expert) pairs into a
buffer of whole tiles of ``TILE`` rows, every tile one expert's
(``tile_e [rows / TILE]``, never decreasing), of which only the first
``live`` hold a row. A batched matmul over tiles needs each tile's weights
as an operand of its own, ``w[tile_e]``: a written copy of one expert
matrix a tile, and in the backward one float32 gradient a tile added back
into its expert. Here ``tile_e`` and ``live`` are scalar-prefetch operands,
the tile axis is the grid's innermost, and a ``BlockSpec``'s index map
names the expert's block of ``w [held, K, N]``:

- :func:`grouped_matmul` ``out[t] = lhs[t] @ w[tile_e[t]]`` (``apex_moe_gmm``):
  consecutive tiles of one expert name the same block, which the pipeline
  does not fetch again, so an expert's weights are read once a pass. A
  layer's gate and up matrices share a call (one read of ``lhs``). With
  a ``scale`` a row (the down projection's: a pair's weight in the
  combine) the kernel stores ``(lhs[t] @ w) * scale[t]``, the float32
  product times the float32 scale while the tile is in VMEM, where XLA
  would read the result back and write it again. The
  same kernel contracts the blocks' other axis for the backward by rows,
  ``d lhs[t] = sum over the call's w of d out[t] @ w[tile_e[t]]^T``, in
  float32 and rounded once: no transposed copy of ``w``, no partial ``d
  lhs`` in HBM;
- the backward by experts (``apex_moe_tgmm``), ``d w[e] = sum over e's
  tiles of lhs[t]^T @ d out[t]``, accumulates in float32 scratch while the
  tiles name the same expert and is emitted once an expert, rounded once.
  The result starts as zeros that the call aliases, so an expert with no
  tile, whose block no grid step names, keeps them;
- a tile at or past ``live`` skips its products and (forward) writes
  zeros, what ``0 @ w`` gives; its index maps name the last live tile's
  blocks, which are resident, so it moves nothing either.

The arithmetic is the einsum's: operands in their own type (bfloat16 under
AMP), float32 accumulation; the cotangent is rounded to the operands' type
where it enters a product, as the MXU's default precision rounds it.

**Blocks.** A weight block is an expert's whole ``[K, N]`` where that
fits, else ``N`` (backward by rows: ``K``) is cut at the largest multiple
of 128 dividing it that does: 1408 = 11 x 128 has none between 128 and
itself, and cutting re-reads ``lhs`` once a cut. Two buffers of ``[2048,
1408]`` bfloat16 (11.5 MiB) beside the rows' do not fit v5e's default 16
MiB of scoped VMEM, so the calls **raise ``vmem_limit_bytes``** (to
``_VMEM_LIMIT`` of the chip's 128 MiB) rather than block the contraction,
which would fetch the weights once a tile again.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.pallas._common import LANES, interpret_mode, vma

__all__ = ["grouped_matmul", "takes"]

TILE = 128                  # rows a tile: ExpertLayer.tile
_SUB = 8                    # float32 rows a register: tiles a block of scales
_F32 = jnp.float32
_VMEM_LIMIT = 64 << 20
_BLOCK_BUDGET = 40 << 20    # what a call's blocks may take of it

_NN = ((1,), (0,))          # x y
_NT = ((1,), (1,))          # x y^T
_TN = ((0,), (0,))          # x^T y


def takes(hidden: int, ffn: int, tile: int) -> bool:
    """Whether these are the kernels' shapes: whole lanes in both widths,
    tiles of ``TILE`` rows."""
    return hidden % LANES == 0 and ffn % LANES == 0 and tile == TILE


def _dot(x, y, contract):
    return jax.lax.dot_general(x, y, (contract, ((), ())),
                               preferred_element_type=_F32)


def _cut(n: int, column_bytes: int, fixed_bytes: int) -> int:
    """The largest multiple of 128 dividing ``n`` whose blocks, at
    ``column_bytes`` a column beside ``fixed_bytes``, fit the budget."""
    room = (_BLOCK_BUDGET - fixed_bytes) // column_bytes
    return max(b for b in range(LANES, n + 1, LANES)
               if n % b == 0 and (b == LANES or b <= room))


def _live(t, live_ref):
    """The tile whose blocks step ``t`` names: itself, or the last live."""
    return jnp.maximum(jnp.minimum(t, live_ref[0] - 1), 0)


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT)


def _gmm_kernel(te_ref, live_ref, *refs, n: int, fan_in: bool,
                scaled: bool = False):
    """``n`` products a tile. Fanning out, ``refs`` are the tile's rows,
    ``n`` weight blocks, with ``scaled`` the scales of ``_SUB`` tiles (a
    tile a row: the array stays unpadded in HBM), and ``n`` results,
    ``o_i = (lhs w_i) * scale``; fanning in, ``n`` tiles of rows, ``n``
    blocks and one result, ``o = sum_i lhs_i w_i^T`` summed in float32
    and rounded once."""
    t = pl.program_id(1)
    live = t < live_ref[0]
    ins = 2 * n if fan_in else 1 + n
    outs = refs[ins + scaled:]

    @pl.when(live)
    def _():
        if fan_in:
            outs[0][...] = sum(
                _dot(lhs[...], w[...], _NT)
                for lhs, w in zip(refs[:n], refs[n:2 * n])).astype(
                    outs[0].dtype)
        else:
            if scaled:
                # the tile's scales lie along the lanes; one a row of the
                # product is the diagonal of their broadcast, summed a row
                # (one term and zeros: exact)
                at = [jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), a)
                      for a in (0, 1)]
                scale = jnp.sum(jnp.where(
                    at[0] == at[1], refs[ins][pl.ds(t % _SUB, 1), :], 0.0),
                    axis=1, keepdims=True)
            for w, o in zip(refs[1:1 + n], outs):
                out = _dot(refs[0][...], w[...], _NN)
                o[...] = (out * scale if scaled else out).astype(o.dtype)

    @pl.when(jnp.logical_not(live))
    def _():
        for o in outs:
            o[...] = jnp.zeros_like(o)


def _gmm(lhs: tuple, ws: tuple, tile_e, live, fan_in: bool, out_dtype,
         scale=None):
    """One ``lhs [rows, K]`` times each ``w[tile_e] [K, N]`` of ``ws``
    (each row times its ``scale [rows]`` float32 where there is one), or
    with ``fan_in`` the sum of each ``lhs [rows, N]`` times its
    ``w[tile_e]^T``, a tile at a time: a list of results."""
    n, (rows, depth) = len(ws), lhs[0].shape
    width = ws[0].shape[1 if fan_in else 2]
    outs = 1 if fan_in else n
    # a tile's scales a row, in whole blocks of _SUB tiles
    scales = () if scale is None else (jnp.pad(
        scale.reshape(rows // TILE, TILE),
        ((0, -(rows // TILE) % _SUB), (0, 0))),)
    # two buffers a block; a float32 product in front of each result
    cut = _cut(width, 2 * n * depth * ws[0].dtype.itemsize
               + 2 * TILE * outs * (4 + jnp.dtype(out_dtype).itemsize),
               2 * TILE * len(lhs) * depth * lhs[0].dtype.itemsize)
    if fan_in:
        w_spec = pl.BlockSpec((None, cut, depth), lambda j, t, te, lv: (
            te[_live(t, lv)], j, 0))
    else:
        w_spec = pl.BlockSpec((None, depth, cut), lambda j, t, te, lv: (
            te[_live(t, lv)], 0, j))
    lhs_spec = pl.BlockSpec((TILE, depth), lambda j, t, te, lv: (
        _live(t, lv), 0))
    scale_spec = pl.BlockSpec((_SUB, TILE), lambda j, t, te, lv: (
        _live(t, lv) // _SUB, 0))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, n=n, fan_in=fan_in,
                          scaled=bool(scales)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(width // cut, rows // TILE),
            in_specs=([lhs_spec] * len(lhs) + [w_spec] * n
                      + [scale_spec] * len(scales)),
            out_specs=[pl.BlockSpec((TILE, cut),
                                    lambda j, t, te, lv: (t, j))] * outs),
        out_shape=[jax.ShapeDtypeStruct((rows, width), out_dtype,
                                        vma=vma(*lhs, *ws))] * outs,
        compiler_params=_PARAMS,
        interpret=interpret_mode(),
        name="apex_moe_gmm",
    )(tile_e, live, *lhs, *ws, *scales)


def _tgmm_kernel(te_ref, live_ref, lhs_ref, do_ref, zeros_ref, o_ref,
                 acc_ref):
    del zeros_ref               # the result's own buffer, by the alias
    t, live, tiles = pl.program_id(1), live_ref[0], pl.num_programs(1)
    e = te_ref[t]
    first = jnp.logical_or(t == 0, te_ref[jnp.maximum(t - 1, 0)] != e)
    last = jnp.logical_or(
        t == live - 1, te_ref[jnp.minimum(t + 1, tiles - 1)] != e)

    @pl.when(t < live)
    def _():
        part = _dot(lhs_ref[...], do_ref[...], _TN)

        @pl.when(first)
        def _():
            acc_ref[...] = part

        @pl.when(jnp.logical_not(first))
        def _():
            acc_ref[...] += part

        @pl.when(last)
        def _():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    @pl.when(jnp.logical_and(t == 0, live == 0))
    def _():                    # no live tile: the block step 0 names
        o_ref[...] = jnp.zeros_like(o_ref)


def _tgmm(lhs, dout, tile_e, live, held: int, out_dtype):
    """``d w [held, K, N]``: each expert's ``lhs[t]^T @ dout[t]`` summed
    over its tiles; zeros for an expert without one."""
    rows, depth = lhs.shape
    width = dout.shape[1]
    size = lhs.dtype.itemsize
    # the float32 sum, a product to add to it, two buffers of the result
    cut = _cut(width, depth * (8 + 2 * jnp.dtype(out_dtype).itemsize)
               + 2 * TILE * size, 2 * TILE * depth * size)
    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(width // cut, rows // TILE),
            in_specs=[
                pl.BlockSpec((TILE, depth), lambda j, t, te, lv: (
                    _live(t, lv), 0)),
                pl.BlockSpec((TILE, cut), lambda j, t, te, lv: (
                    _live(t, lv), j)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, depth, cut), lambda j, t, te, lv: (
                te[_live(t, lv)], 0, j)),
            scratch_shapes=[pltpu.VMEM((depth, cut), _F32)]),
        out_shape=jax.ShapeDtypeStruct((held, depth, width), out_dtype,
                                       vma=vma(lhs, dout)),
        input_output_aliases={4: 0},
        compiler_params=_PARAMS,
        interpret=interpret_mode(),
        name="apex_moe_tgmm",
    )(tile_e, live, lhs, dout, jnp.zeros((held, depth, width), out_dtype))


@jax.custom_vjp
def grouped_matmul(lhs, ws: tuple, tile_e, live, scale=None):
    """For each ``w [held, K, N]`` of ``ws`` (one shape: a layer's gate
    and up matrices share a call and one read of ``lhs``) ``out [rows,
    N]`` float32 with ``out[t] = lhs[t] @ w[tile_e[t]]`` for each tile
    ``t`` of ``TILE`` rows of ``lhs [rows, K]``, ``tile_e [rows / TILE]``
    int32 never decreasing, and zeros in the tiles at or past ``live``
    (int32 scalar), whatever ``lhs`` holds there. With ``scale [rows]``
    or ``[rows, 1]`` float32, row ``r`` of every result is multiplied by
    ``scale[r]`` in float32 before it is stored. A row whose scale is 0
    gives and takes nothing, whatever finite values ``lhs`` holds there:
    zeros out, a zero row of ``d lhs``, nothing added to ``d ws``; that is
    how a dead row in a live tile's tail needs no mask.

    Differentiable in ``lhs``, ``ws`` and ``scale``; ``d lhs`` is summed
    over ``ws`` in float32 inside one call, from the cotangent times the
    scale. ``d scale[r] = sum(d out[r] * (lhs[r] @ w))`` takes the
    unscaled products again and a sum a row: dead code, which the compiler
    drops, where no gradient reaches ``scale`` (an expert layer's share,
    whose weights are constants in the backward)."""
    return tuple(_gmm((lhs,), ws, tile_e, live.reshape(1), False, _F32,
                      scale))


def _grouped_matmul_fwd(lhs, ws, tile_e, live, scale=None):
    return grouped_matmul(lhs, ws, tile_e, live, scale), (
        lhs, ws, tile_e, live, scale)


def _grouped_matmul_bwd(residuals, douts):
    lhs, ws, tile_e, live, scale = residuals
    live, d_scale = live.reshape(1), None
    if scale is not None:
        plain = _gmm((lhs,), ws, tile_e, live, False, _F32)
        d_scale = sum(jnp.sum(d * o, axis=1)
                      for d, o in zip(douts, plain)).reshape(scale.shape)
        douts = [d * scale.reshape(-1, 1) for d in douts]
    douts = tuple(d.astype(lhs.dtype) for d in douts)
    d_lhs, = _gmm(douts, ws, tile_e, live, True, lhs.dtype)
    return d_lhs, tuple(
        _tgmm(lhs, d, tile_e, live, w.shape[0], w.dtype)
        for d, w in zip(douts, ws)), None, None, d_scale


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)
