"""Pallas TPU kernels for the multi-tensor op set (the amp_C equivalents).

Where the reference batches work over scattered tensor lists with one CUDA
kernel per op (reference: csrc/multi_tensor_apply.cuh:15-130 packs tensor
pointers + a block->(tensor, chunk) map; csrc/multi_tensor_*_kernel.cu), the
TPU design operates on ONE flat HBM buffer (see ``apex_tpu.ops.flat``) viewed
as ``(rows, 128)`` — rows are VPU lane groups, so every kernel is a plain 2-D
grid over row blocks with no pointer tables at all.

Conventions:
- buffers must have ``size % 128 == 0`` (the flat store guarantees this via
  its 128-element alignment); callers fall back to ``ops.reference``
  otherwise (see ``apex_tpu.ops.kernels``);
- all math in fp32 (the reference kernels' ``MATH_T``), storage dtype
  preserved on write;
- overflow flags are int32 scalars accumulated in SMEM across the sequential
  TPU grid — the analog of the device-side ``noop_flag`` write (reference:
  multi_tensor_scale_kernel.cu:108-109) without any host sync;
- the ragged final row-block is handled by Pallas write-masking; reduction
  kernels additionally mask out-of-range rows so garbage lanes never reach a
  scalar accumulator;
- the optimizer-step kernels update their state IN PLACE, as the reference
  kernels do (csrc/multi_tensor_adam.cu writes p, m, v where it read them):
  every state output aliases its input (``input_output_aliases``). Grid
  step ``i`` reads row-block ``i`` and writes row-block ``i``, so the
  pipeline's prefetch of block ``i+1`` never sees a written block. The
  contract for callers: DONATE the state or pay one copy — under a jit
  that donates p, m, v the program holds no copy of them; where the caller
  keeps its arrays XLA copies each in front of the kernel and they stay
  intact (what every call cost before the alias: three 5 ms copies a step
  at 409M parameters on a v5e, PERF.md PR 25);
- the overflow skip is inside the step kernels: ``skip`` (None, or a traced
  scalar — AMP's ``found_inf``) rides as one more SMEM scalar and, when set,
  every state output is the value read, bit for bit. With ``skip=None``
  nothing is passed and nothing is read. A select over whole buffers AFTER
  the kernel would keep the old state alive beside the new and bring the
  copies back;
- per-tensor (segment) semantics ride on the 128-alignment invariant: every
  flat row belongs to exactly one segment, so per-tensor reductions are a
  Pallas per-row pass plus a tiny XLA segment-sum over rows (the moral
  equivalent of the two-stage ``cleanup`` reduction in
  multi_tensor_l2norm_kernel.cu:197).

Numerics match ``apex_tpu.ops.reference`` (allclose, not bitwise — fp32
accumulation order differs between the VPU row reduction and XLA's global
reduce).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.pallas._common import LANES, interpret_mode

BLOCK_ROWS = 512  # 512x128 fp32 = 256 KiB per operand per block

_f32 = functools.partial(jnp.asarray, dtype=jnp.float32)


def supported(*arrays: jax.Array) -> bool:
    """True when every array can take the Pallas path."""
    return all(a.size > 0 and a.size % LANES == 0 for a in arrays)


def _rows(x: jax.Array) -> jax.Array:
    return x.reshape(x.size // LANES, LANES)


def _scalars(*vals) -> jax.Array:
    """Pack traced/host scalars into a (1, K) fp32 SMEM operand."""
    return jnp.stack([_f32(v) for v in vals]).reshape(1, -1)


def _smem_spec(k: int) -> pl.BlockSpec:
    return pl.BlockSpec((1, k), lambda i: (0, 0), memory_space=pltpu.SMEM)


def _row_spec() -> pl.BlockSpec:
    return pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)


def _col_spec() -> pl.BlockSpec:
    """Per-row scalar operand: (rows, 1) blocked along the grid."""
    return pl.BlockSpec((BLOCK_ROWS, 1), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)


def _flag_spec() -> pl.BlockSpec:
    return pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)


def _grid(nrows: int) -> tuple[int]:
    return (pl.cdiv(nrows, BLOCK_ROWS),)


def _step_scalars(*vals, skip):
    """A step kernel's SMEM operand, its BlockSpec, and where the overflow
    ``skip`` flag rides in it: last, or nowhere (None) when the caller
    passed none — then nothing is packed and nothing is read."""
    packed = _scalars(*vals, *(() if skip is None else (skip,)))
    return (packed, _smem_spec(packed.shape[1]),
            None if skip is None else len(vals))


def _skip_flag(s_ref, at):
    return None if at is None else s_ref[0, at] != 0.0


def _store(o_ref, old_ref, new, skip):
    """Write the new state over the state read — or, when ``skip`` is set,
    the very value read, so an overflowing step leaves it bit-for-bit."""
    new = new.astype(o_ref.dtype)
    o_ref[...] = new if skip is None else jnp.where(skip, old_ref[...], new)


def _valid(shape, block_idx: jax.Array, nrows: int) -> jax.Array:
    """Mask of in-range rows for the (possibly ragged) final block."""
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + block_idx * BLOCK_ROWS
    return row < nrows


# ---------------------------------------------------------------------------
# scale / axpby (amp_C.multi_tensor_scale / multi_tensor_axpby)
# ---------------------------------------------------------------------------

def _scale_kernel(nrows, s_ref, x_ref, o_ref, inf_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        inf_ref[0, 0] = 0

    xf = x_ref[...].astype(jnp.float32)
    o_ref[...] = (xf * s_ref[0, 0]).astype(o_ref.dtype)
    ok = jnp.isfinite(xf) | ~_valid(xf.shape, i, nrows)
    inf_ref[0, 0] = inf_ref[0, 0] | (~jnp.all(ok)).astype(jnp.int32)


def scale(x: jax.Array, scale_factor) -> tuple[jax.Array, jax.Array]:
    """out = x * scale + found_inf over the input (reference:
    multi_tensor_scale_kernel.cu:29-136; the finite check reads the input so
    a saturating unscale still reports overflow)."""
    x2 = _rows(x)
    nrows = x2.shape[0]
    out, inf = pl.pallas_call(
        functools.partial(_scale_kernel, nrows),
        grid=_grid(nrows),
        in_specs=[_smem_spec(1), _row_spec()],
        out_specs=[_row_spec(), _flag_spec()],
        out_shape=[jax.ShapeDtypeStruct(x2.shape, x.dtype),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        interpret=interpret_mode(),
        name="apex_mt_scale",
    )(_scalars(scale_factor), x2)
    return out.reshape(x.shape), inf[0, 0] > 0


def _axpby_kernel(nrows, arg_to_check, s_ref, x_ref, y_ref, o_ref, inf_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        inf_ref[0, 0] = 0

    xf = x_ref[...].astype(jnp.float32)
    yf = y_ref[...].astype(jnp.float32)
    o_ref[...] = (s_ref[0, 0] * xf + s_ref[0, 1] * yf).astype(o_ref.dtype)
    oob = ~_valid(xf.shape, i, nrows)
    if arg_to_check == 0:
        ok = jnp.isfinite(xf) | oob
    elif arg_to_check == 1:
        ok = jnp.isfinite(yf) | oob
    else:
        ok = (jnp.isfinite(xf) & jnp.isfinite(yf)) | oob
    inf_ref[0, 0] = inf_ref[0, 0] | (~jnp.all(ok)).astype(jnp.int32)


def axpby(a, x: jax.Array, b, y: jax.Array,
          arg_to_check: int = -1) -> tuple[jax.Array, jax.Array]:
    """out = a*x + b*y with selectable overflow check (reference:
    multi_tensor_axpby_kernel.cu:27-157)."""
    x2, y2 = _rows(x), _rows(y)
    nrows = x2.shape[0]
    out, inf = pl.pallas_call(
        functools.partial(_axpby_kernel, nrows, arg_to_check),
        grid=_grid(nrows),
        in_specs=[_smem_spec(2), _row_spec(), _row_spec()],
        out_specs=[_row_spec(), _flag_spec()],
        out_shape=[jax.ShapeDtypeStruct(x2.shape, jnp.result_type(x)),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        interpret=interpret_mode(),
        name="apex_mt_axpby",
    )(_scalars(a, b), x2, y2)
    return out.reshape(x.shape), inf[0, 0] > 0


# ---------------------------------------------------------------------------
# Norms (amp_C.multi_tensor_l2norm, global + per-row stage of per-tensor)
# ---------------------------------------------------------------------------

def _sumsq_kernel(nrows, x_ref, acc_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[0, 0] = 0.0

    xf = x_ref[...].astype(jnp.float32)
    xf = jnp.where(_valid(xf.shape, i, nrows), xf, 0.0)
    acc_ref[0, 0] += jnp.sum(xf * xf)


def l2norm(x: jax.Array) -> jax.Array:
    """Global L2 norm, fp32 accumulation (reference:
    multi_tensor_l2norm_kernel.cu:27-196)."""
    x2 = _rows(x)
    nrows = x2.shape[0]
    acc = pl.pallas_call(
        functools.partial(_sumsq_kernel, nrows),
        grid=_grid(nrows),
        in_specs=[_row_spec()],
        out_specs=_flag_spec(),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        interpret=interpret_mode(),
        name="apex_mt_l2norm",
    )(x2)
    return jnp.sqrt(acc[0, 0])


def _rowsumsq_kernel(x_ref, o_ref):
    xf = x_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.sum(xf * xf, axis=1, keepdims=True)


def rowsumsq(x: jax.Array) -> jax.Array:
    """Per-row sum of squares, fp32: the first stage of per-tensor norms.
    Garbage rows in the ragged final block map to out-of-range output rows,
    which Pallas write-masks — no explicit masking needed."""
    x2 = _rows(x)
    nrows = x2.shape[0]
    out = pl.pallas_call(
        _rowsumsq_kernel,
        grid=_grid(nrows),
        in_specs=[_row_spec()],
        out_specs=_col_spec(),
        out_shape=jax.ShapeDtypeStruct((nrows, 1), jnp.float32),
        interpret=interpret_mode(),
        name="apex_mt_rowsumsq",
    )(x2)
    return out[:, 0]


def _rowmaxabs_kernel(x_ref, o_ref):
    xf = x_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.max(jnp.abs(xf), axis=1, keepdims=True)


def rowmaxabs(x: jax.Array) -> jax.Array:
    """Per-row max-abs, first stage of per-tensor L-inf norms (reference:
    MaxNormFunctor, multi_tensor_l2norm_kernel.cu:113-196)."""
    x2 = _rows(x)
    nrows = x2.shape[0]
    out = pl.pallas_call(
        _rowmaxabs_kernel,
        grid=_grid(nrows),
        in_specs=[_row_spec()],
        out_specs=_col_spec(),
        out_shape=jax.ShapeDtypeStruct((nrows, 1), jnp.float32),
        interpret=interpret_mode(),
        name="apex_mt_rowmaxabs",
    )(x2)
    return out[:, 0]


def row_segment_ids(segment_ids: jax.Array) -> jax.Array:
    """Element-level segment ids -> per-row ids (valid because segments are
    128-aligned in the flat store, so a row never straddles segments)."""
    return segment_ids[::LANES]


def l2norm_per_segment(x: jax.Array, segment_ids: jax.Array,
                       num_segments: int) -> jax.Array:
    """Per-tensor L2 norms: Pallas row pass + dense masked segment-sum
    over rows (reference: multi_tensor_l2norm_cuda per_tensor=True; the
    row stage is the block reduction, the segment-sum is the ``cleanup``
    second pass, multi_tensor_l2norm_kernel.cu:197-355). The segment-sum
    is shared with the jnp twin (reference.segment_sum_dense) — a
    scatter-add here would serialize on TPU."""
    from apex_tpu.ops.reference import segment_sum_dense
    sq = segment_sum_dense(rowsumsq(x), row_segment_ids(segment_ids),
                           num_segments)
    return jnp.sqrt(sq)


def maxnorm_per_segment(x: jax.Array, segment_ids: jax.Array,
                        num_segments: int) -> jax.Array:
    return jax.ops.segment_max(rowmaxabs(x), row_segment_ids(segment_ids),
                               num_segments=num_segments)


# ---------------------------------------------------------------------------
# Optimizer steps
# ---------------------------------------------------------------------------

def _adam_kernel(mode, skip_at, s_ref, g_ref, p_ref, m_ref, v_ref,
                 po_ref, mo_ref, vo_ref):
    # (1-beta) arrives precomputed in float64 and rounded once to fp32 —
    # computing it in-kernel from the fp32 beta rounds differently
    # (1 - 0.9f = 0.10000002f vs fp32(0.1) = 0.10000000f) and was the one
    # source of >1-ulp divergence from the jnp reference path.
    lr, b1, b2, eps, bc1, bc2, wd, omb1, omb2 = (
        s_ref[0, k] for k in range(9))
    skip = _skip_flag(s_ref, skip_at)
    gf = g_ref[...].astype(jnp.float32)
    pf = p_ref[...].astype(jnp.float32)
    mf = m_ref[...].astype(jnp.float32)
    vf = v_ref[...].astype(jnp.float32)
    if mode == 0:  # L2: decay folded into the gradient
        gf = gf + wd * pf
    mf = b1 * mf + omb1 * gf
    vf = b2 * vf + omb2 * gf * gf
    update = (mf / bc1) / (jnp.sqrt(vf / bc2) + eps)
    if mode == 1:  # AdamW decoupled decay
        update = update + wd * pf
    _store(po_ref, p_ref, pf - lr * update, skip)
    _store(mo_ref, m_ref, mf, skip)
    _store(vo_ref, v_ref, vf, skip)


def adam_step(g, p, m, v, *, lr, beta1, beta2, eps, step, mode=0,
              bias_correction=True, weight_decay=0.0, skip=None):
    """Fused Adam/AdamW over the flat buffer (reference:
    multi_tensor_adam.cu:23-171). Bias corrections are precomputed scalars
    outside the kernel, exactly as the reference does host-side
    (multi_tensor_adam.cu:144-149). p, m and v are updated in place."""
    stepf = _f32(step)
    if bias_correction:
        bc1 = 1.0 - jnp.power(_f32(beta1), stepf)
        bc2 = 1.0 - jnp.power(_f32(beta2), stepf)
    else:
        bc1 = bc2 = _f32(1.0)
    g2, p2, m2, v2 = _rows(g), _rows(p), _rows(m), _rows(v)
    nrows = p2.shape[0]
    scalars, s_spec, skip_at = _step_scalars(
        lr, beta1, beta2, eps, bc1, bc2, weight_decay, 1.0 - beta1,
        1.0 - beta2, skip=skip)
    po, mo, vo = pl.pallas_call(
        functools.partial(_adam_kernel, mode, skip_at),
        grid=_grid(nrows),
        in_specs=[s_spec] + [_row_spec()] * 4,
        out_specs=[_row_spec()] * 3,
        out_shape=[jax.ShapeDtypeStruct(p2.shape, p.dtype),
                   jax.ShapeDtypeStruct(m2.shape, m.dtype),
                   jax.ShapeDtypeStruct(v2.shape, v.dtype)],
        input_output_aliases={2: 0, 3: 1, 4: 2},
        interpret=interpret_mode(),
        name="apex_mt_adam",
    )(scalars, g2, p2, m2, v2)
    return po.reshape(p.shape), mo.reshape(m.shape), vo.reshape(v.shape)


def _adagrad_kernel(mode, skip_at, s_ref, g_ref, p_ref, h_ref,
                    po_ref, ho_ref):
    lr, eps, wd = s_ref[0, 0], s_ref[0, 1], s_ref[0, 2]
    skip = _skip_flag(s_ref, skip_at)
    gf = g_ref[...].astype(jnp.float32)
    pf = p_ref[...].astype(jnp.float32)
    hf = h_ref[...].astype(jnp.float32)
    if mode == 0:
        gf = gf + wd * pf
        hf = hf + gf * gf
        pf = pf - lr * (gf / (jnp.sqrt(hf) + eps))
    else:
        hf = hf + gf * gf
        pf = pf - lr * (gf / (jnp.sqrt(hf) + eps) + wd * pf)
    _store(po_ref, p_ref, pf, skip)
    _store(ho_ref, h_ref, hf, skip)


def adagrad_step(g, p, h, *, lr, eps, mode=0, weight_decay=0.0, skip=None):
    """Fused Adagrad (reference: multi_tensor_adagrad.cu:24-85), p and h
    updated in place."""
    g2, p2, h2 = _rows(g), _rows(p), _rows(h)
    nrows = p2.shape[0]
    scalars, s_spec, skip_at = _step_scalars(lr, eps, weight_decay,
                                             skip=skip)
    po, ho = pl.pallas_call(
        functools.partial(_adagrad_kernel, mode, skip_at),
        grid=_grid(nrows),
        in_specs=[s_spec] + [_row_spec()] * 3,
        out_specs=[_row_spec()] * 2,
        out_shape=[jax.ShapeDtypeStruct(p2.shape, p.dtype),
                   jax.ShapeDtypeStruct(h2.shape, h.dtype)],
        input_output_aliases={2: 0, 3: 1},
        interpret=interpret_mode(),
        name="apex_mt_adagrad",
    )(scalars, g2, p2, h2)
    return po.reshape(p.shape), ho.reshape(h.shape)


def _sgd_kernel(momentum, dampening, nesterov, wd_after_momentum, skip_at,
                s_ref, g_ref, p_ref, m_ref, po_ref, mo_ref):
    wd, lr, scl, first_run = (s_ref[0, k] for k in range(4))
    skip = _skip_flag(s_ref, skip_at)
    gf = g_ref[...].astype(jnp.float32) * scl
    pf = p_ref[...].astype(jnp.float32)
    mf = m_ref[...].astype(jnp.float32)
    if not wd_after_momentum:
        gf = gf + wd * pf
    if momentum != 0.0:
        blended = mf * momentum + (1.0 - dampening) * gf
        mf = jnp.where(first_run > 0.0, gf, blended)
        gf = gf + momentum * mf if nesterov else mf
    if wd_after_momentum:
        gf = gf + wd * pf
    _store(po_ref, p_ref, pf - lr * gf, skip)
    _store(mo_ref, m_ref, mf, skip)


def sgd_step(g, p, mom, *, wd, momentum, dampening, lr, nesterov=False,
             first_run=False, wd_after_momentum=False, scale=1.0,
             skip=None):
    """Fused SGD with momentum/nesterov and folded grad unscale (reference:
    multi_tensor_sgd_kernel.cu:29-140; ``first_run`` initializes momentum to
    the incoming grad, :113-117). ``first_run`` may be traced. p and the
    momentum buffer are updated in place."""
    g2, p2, m2 = _rows(g), _rows(p), _rows(mom)
    nrows = p2.shape[0]
    first = jnp.asarray(first_run, jnp.float32)
    scalars, s_spec, skip_at = _step_scalars(wd, lr, scale, first, skip=skip)
    po, mo = pl.pallas_call(
        functools.partial(_sgd_kernel, float(momentum), float(dampening),
                          bool(nesterov), bool(wd_after_momentum), skip_at),
        grid=_grid(nrows),
        in_specs=[s_spec] + [_row_spec()] * 3,
        out_specs=[_row_spec()] * 2,
        out_shape=[jax.ShapeDtypeStruct(p2.shape, p.dtype),
                   jax.ShapeDtypeStruct(m2.shape, mom.dtype)],
        input_output_aliases={2: 0, 3: 1},
        interpret=interpret_mode(),
        name="apex_mt_sgd",
    )(scalars, g2, p2, m2)
    return po.reshape(p.shape), mo.reshape(mom.shape)


def _novograd_kernel(mode, grad_averaging, skip_at, s_ref, g_ref, p_ref,
                     m_ref, d_ref, po_ref, mo_ref):
    # omb1 = 1-beta1 precomputed host-side in float64 (see _adam_kernel)
    lr, b1, wd, bc1, omb1 = (s_ref[0, k] for k in range(5))
    skip = _skip_flag(s_ref, skip_at)
    gf = g_ref[...].astype(jnp.float32)
    pf = p_ref[...].astype(jnp.float32)
    mf = m_ref[...].astype(jnp.float32)
    denom = d_ref[...]  # (rows, 1) fp32, broadcasts over lanes
    beta3 = omb1 if grad_averaging else 1.0
    if mode == 0:
        gf = gf / denom + wd * pf
        mf = b1 * mf + beta3 * gf
        pf = pf - lr * (mf / bc1)
    else:
        mf = b1 * mf + beta3 * gf
        pf = pf - lr * ((mf / bc1) / denom + wd * pf)
    _store(po_ref, p_ref, pf, skip)
    _store(mo_ref, m_ref, mf, skip)


def novograd_step(g, p, m, v_norms, segment_ids, *, lr, beta1, beta2, eps,
                  step, bias_correction=True, weight_decay=0.0,
                  grad_averaging=True, mode=0, norm_type=2, skip=None):
    """Fused NovoGrad (reference: multi_tensor_novograd.cu:31-186): the
    per-tensor second-moment *norm* blend runs as a Pallas row pass +
    segment reduce; the elementwise update reads the per-row denominator.
    p and m are updated in place; the per-tensor norms are a few scalars
    and take ``skip`` as a plain select."""
    num_segments = v_norms.shape[0]
    row_ids = row_segment_ids(segment_ids)
    if norm_type == 0:
        new_norms = jax.ops.segment_max(rowmaxabs(g), row_ids,
                                        num_segments=num_segments)
        v_new = beta2 * v_norms + (1.0 - beta2) * new_norms
    else:
        from apex_tpu.ops.reference import segment_sum_dense
        sq = segment_sum_dense(rowsumsq(g), row_ids, num_segments)
        v_new = jnp.sqrt(beta2 * jnp.square(v_norms) + (1.0 - beta2) * sq)
    stepf = _f32(step)
    if bias_correction:
        bc1 = 1.0 - jnp.power(_f32(beta1), stepf)
        bc2 = jnp.sqrt(1.0 - jnp.power(_f32(beta2), stepf))
    else:
        bc1 = bc2 = _f32(1.0)
    denom = (v_new / bc2 + eps)[row_ids][:, None]  # (rows, 1)

    g2, p2, m2 = _rows(g), _rows(p), _rows(m)
    nrows = p2.shape[0]
    scalars, s_spec, skip_at = _step_scalars(lr, beta1, weight_decay, bc1,
                                             1.0 - beta1, skip=skip)
    po, mo = pl.pallas_call(
        functools.partial(_novograd_kernel, mode, bool(grad_averaging),
                          skip_at),
        grid=_grid(nrows),
        in_specs=[s_spec] + [_row_spec()] * 3 + [_col_spec()],
        out_specs=[_row_spec()] * 2,
        out_shape=[jax.ShapeDtypeStruct(p2.shape, p.dtype),
                   jax.ShapeDtypeStruct(m2.shape, m.dtype)],
        input_output_aliases={2: 0, 3: 1},
        interpret=interpret_mode(),
        name="apex_mt_novograd",
    )(scalars, g2, p2, m2, denom)
    from apex_tpu.ops.reference import keep_old
    return (po.reshape(p.shape), mo.reshape(m.shape),
            keep_old(skip, v_norms, v_new))
