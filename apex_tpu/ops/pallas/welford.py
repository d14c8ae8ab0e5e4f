"""Pallas per-channel moment kernels for SyncBatchNorm.

TPU twin of the reference's welford kernel family (csrc/welford.cu:
``welford_mean_var`` :885 computes local per-channel mean/var;
``reduce_bn`` :325 the Kahan-summed backward partials). On TPU the
channels-last layout puts C on lanes, so both are column reductions over
the flattened ``[N*spatial, C]`` view — one grid sweep over row blocks
accumulating into a (1, C) output block (the TPU grid is sequential, so
cross-step accumulation into the same output block is safe; the cross-chip
part of the reference's welford_parallel merge stays a psum of moments in
the caller, SURVEY §3.4).

The forward emits raw (sum, sum_sq) rather than (mean, var): psum of raw
moments over the replica axis is exactly the Chan merge the reference does
(welford.cu:559-584) with fewer collectives. The ragged final row block is
handled by an iota mask (like ops/pallas/multi_tensor's reductions), so
padding waste is bounded at 7 rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.ops.pallas._common import (LANES, interpret_mode, round_up,
                                         vma as _vma)

# VMEM budget per streamed operand block; rows shrink as C grows so a
# (rows, C) fp32 block stays within it (the bwd kernel streams two).
_BLOCK_BYTES = 2 << 20
MAX_ROWS = 1024
MAX_C = 16384


def _block_rows(n: int, c: int) -> int:
    budget = max(8, (_BLOCK_BYTES // 4) // c // 8 * 8)
    return min(MAX_ROWS, budget, round_up(n, 8))


def supported(n_rows: int, c: int) -> bool:
    return c % LANES == 0 and 0 < c <= MAX_C and n_rows > 0


def _pad_rows(x2d, rows):
    n = x2d.shape[0]
    pad = (-n) % rows
    return (jnp.pad(x2d, ((0, pad), (0, 0))) if pad else x2d), n + pad


def _row_mask(shape, block_idx, nrows):
    """True on real rows of the (possibly ragged) final block."""
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + \
        block_idx * shape[0]
    return row < nrows


def _moments_kernel(nrows, x_ref, sum_ref, sq_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        sq_ref[...] = jnp.zeros_like(sq_ref)

    xf = x_ref[...].astype(jnp.float32)
    xf = jnp.where(_row_mask(xf.shape, i, nrows), xf, 0.0)
    sum_ref[...] += jnp.sum(xf, axis=0, keepdims=True)
    sq_ref[...] += jnp.sum(xf * xf, axis=0, keepdims=True)


def bn_moments(x2d: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x2d: [R, C] channels-last. Returns (sum[C], sum_sq[C]) fp32 —
    the local welford_mean_var pass (welford.cu:885) as raw moments."""
    n, c = x2d.shape
    rows = _block_rows(n, c)
    xx, np_ = _pad_rows(x2d, rows)
    vma = _vma(x2d)
    s, sq = pl.pallas_call(
        functools.partial(_moments_kernel, n),
        grid=(np_ // rows,),
        in_specs=[pl.BlockSpec((rows, c), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((1, c), lambda i: (0, 0)),
                   pl.BlockSpec((1, c), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, c), jnp.float32, vma=vma),
                   jax.ShapeDtypeStruct((1, c), jnp.float32, vma=vma)],
        interpret=interpret_mode(),
        name="apex_bn_moments",
    )(xx)
    return s[0], sq[0]


def _bwd_fused_reduce_kernel(nrows, has_out, dy_ref, x_ref, mean_ref,
                             invvar_ref, *rest):
    if has_out:
        out_ref, sdy_ref, sdx_ref = rest
    else:
        sdy_ref, sdx_ref = rest
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        sdy_ref[...] = jnp.zeros_like(sdy_ref)
        sdx_ref[...] = jnp.zeros_like(sdx_ref)

    dyf = dy_ref[...].astype(jnp.float32)
    if has_out:  # fused-relu mask: out==0 where the relu clipped
        # compare in fp32 — Mosaic cannot cmpf packed bf16 vectors
        dyf = jnp.where(out_ref[...].astype(jnp.float32) > 0, dyf, 0.0)
    dyf = jnp.where(_row_mask(dyf.shape, i, nrows), dyf, 0.0)
    xhat = (x_ref[...].astype(jnp.float32) - mean_ref[...]) * invvar_ref[...]
    sdy_ref[...] += jnp.sum(dyf, axis=0, keepdims=True)
    sdx_ref[...] += jnp.sum(dyf * xhat, axis=0, keepdims=True)


def bn_backward_fused_reduce(dy2d, x2d, mean, invvar, out2d=None):
    """Per-channel (sum_dy, sum_dy_xhat) straight from the saved input —
    the reduce_bn pass (welford.cu:325) WITHOUT materializing fp32 xhat /
    masked dy: x and dy stream in their storage dtype and xhat is
    recomputed in-kernel from (mean, invvar). ``out2d`` (the primal
    output) doubles as the fused-relu mask."""
    n, c = dy2d.shape
    streams = 3 if out2d is None else 4
    rows = _block_rows_n(n, c, streams)
    dd, np_ = _pad_rows(dy2d, rows)
    xx, _ = _pad_rows(x2d, rows)
    ops = [dd, xx, mean.reshape(1, c).astype(jnp.float32),
           invvar.reshape(1, c).astype(jnp.float32)]
    in_specs = [pl.BlockSpec((rows, c), lambda i: (i, 0)),
                pl.BlockSpec((rows, c), lambda i: (i, 0)),
                pl.BlockSpec((1, c), lambda i: (0, 0)),
                pl.BlockSpec((1, c), lambda i: (0, 0))]
    if out2d is not None:
        oo, _ = _pad_rows(out2d, rows)
        ops.append(oo)
        in_specs.append(pl.BlockSpec((rows, c), lambda i: (i, 0)))
    vma = _vma(dy2d, x2d)
    sdy, sdx = pl.pallas_call(
        functools.partial(_bwd_fused_reduce_kernel, n, out2d is not None),
        grid=(np_ // rows,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, c), lambda i: (0, 0)),
                   pl.BlockSpec((1, c), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, c), jnp.float32, vma=vma),
                   jax.ShapeDtypeStruct((1, c), jnp.float32, vma=vma)],
        interpret=interpret_mode(),
        name="apex_bn_bwd_fused_reduce",
    )(*ops)
    return sdy[0], sdx[0]


def _bwd_dx_kernel(has_out, emit_dz, dy_ref, x_ref, mean_ref, invvar_ref,
                   winv_ref, mdy_ref, mdx_ref, *rest):
    if has_out:
        out_ref, *outs = rest
    else:
        outs = list(rest)
    dx_ref = outs[0]
    dyf = dy_ref[...].astype(jnp.float32)
    if has_out:
        dyf = jnp.where(out_ref[...].astype(jnp.float32) > 0, dyf, 0.0)
    xhat = (x_ref[...].astype(jnp.float32) - mean_ref[...]) * invvar_ref[...]
    dx = winv_ref[...] * (dyf - mdy_ref[...] - xhat * mdx_ref[...])
    dx_ref[...] = dx.astype(dx_ref.dtype)
    if emit_dz:
        outs[1][...] = dyf.astype(outs[1].dtype)


def bn_backward_dx(dy2d, x2d, mean, invvar, winv, mean_dy, mean_dy_xhat,
                   out2d=None, emit_dz=False):
    """dx = invvar*w*(dy_masked - mean_dy - xhat*mean_dy_xhat) — the
    batchnorm_backward elementwise pass (welford.cu:387) fused with the
    relu mask and (optionally) the residual grad dz = masked dy, again
    with no fp32 intermediates in HBM. ``winv`` = invvar * weight."""
    n, c = dy2d.shape
    streams = (4 if out2d is None else 5) + (1 if emit_dz else 0)
    rows = _block_rows_n(n, c, streams)
    dd, np_ = _pad_rows(dy2d, rows)
    xx, _ = _pad_rows(x2d, rows)
    chan = [mean, invvar, winv, mean_dy, mean_dy_xhat]
    ops = [dd, xx] + [v.reshape(1, c).astype(jnp.float32) for v in chan]
    row_spec = pl.BlockSpec((rows, c), lambda i: (i, 0))
    chan_spec = pl.BlockSpec((1, c), lambda i: (0, 0))
    in_specs = [row_spec, row_spec] + [chan_spec] * 5
    if out2d is not None:
        oo, _ = _pad_rows(out2d, rows)
        ops.append(oo)
        in_specs.append(row_spec)
    vma = _vma(dy2d, x2d)
    out_shape = [jax.ShapeDtypeStruct((np_, c), x2d.dtype, vma=vma)]
    out_specs = [row_spec]
    if emit_dz:
        out_shape.append(jax.ShapeDtypeStruct((np_, c), x2d.dtype, vma=vma))
        out_specs.append(row_spec)
    res = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, out2d is not None, emit_dz),
        grid=(np_ // rows,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret_mode(),
        name="apex_bn_bwd_dx",
    )(*ops)
    dx = res[0][:n]
    dz = res[1][:n] if emit_dz else None
    return dx, dz


def _block_rows_n(n: int, c: int, streams: int) -> int:
    """Rows per block so `streams` (rows, c) fp32 operands fit the budget
    (delegates to the shared helper; conservative — streamed operands here
    are mostly 2-byte but budgeted as fp32)."""
    from apex_tpu.ops.pallas._common import block_rows
    return block_rows(n, c, streams, max_rows=MAX_ROWS)


def _bwd_reduce_kernel(nrows, dy_ref, xhat_ref, sdy_ref, sdx_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        sdy_ref[...] = jnp.zeros_like(sdy_ref)
        sdx_ref[...] = jnp.zeros_like(sdx_ref)

    dyf = dy_ref[...].astype(jnp.float32)
    dyf = jnp.where(_row_mask(dyf.shape, i, nrows), dyf, 0.0)
    sdy_ref[...] += jnp.sum(dyf, axis=0, keepdims=True)
    sdx_ref[...] += jnp.sum(dyf * xhat_ref[...].astype(jnp.float32),
                            axis=0, keepdims=True)


def bn_backward_reduce(dy2d, xhat2d):
    """Per-channel (sum_dy, sum_dy_xhat) — the reduce_bn partial pass
    (welford.cu:325). The caller already materializes xhat for the dx
    formula, so the kernel is a pure two-input row reduction."""
    n, c = dy2d.shape
    rows = _block_rows(n, c)
    dd, np_ = _pad_rows(dy2d, rows)
    xx, _ = _pad_rows(xhat2d, rows)
    vma = _vma(dy2d, xhat2d)
    sdy, sdx = pl.pallas_call(
        functools.partial(_bwd_reduce_kernel, n),
        grid=(np_ // rows,),
        in_specs=[pl.BlockSpec((rows, c), lambda i: (i, 0)),
                  pl.BlockSpec((rows, c), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((1, c), lambda i: (0, 0)),
                   pl.BlockSpec((1, c), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, c), jnp.float32, vma=vma),
                   jax.ShapeDtypeStruct((1, c), jnp.float32, vma=vma)],
        interpret=interpret_mode(),
        name="apex_bn_bwd_reduce",
    )(dd, xx)
    return sdy[0], sdx[0]
