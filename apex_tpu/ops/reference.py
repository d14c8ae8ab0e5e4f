"""Pure-jnp reference implementations of the multi-tensor op set.

These are the numerics contract of the framework: every Pallas kernel in
``apex_tpu.ops.pallas`` must agree with these functions (the analog of Apex's
Python-build vs CUDA-build bitwise L1 criterion, reference:
tests/L1/common/run_test.sh:57-137). They are also the execution path on CPU
and any platform without Pallas support.

Conventions shared with the reference kernels (reference: csrc/):
- all math is fp32 (``MATH_T = float`` in every csrc kernel) regardless of
  storage dtype; results are cast back to the storage dtype on write;
- overflow detection returns a ``found_inf`` bool scalar computed from the
  *inputs* (reference: multi_tensor_scale_kernel.cu:69, checks ``r_in``;
  multi_tensor_axpby_kernel.cu:105-111, checks args selected by
  ``arg_to_check``) rather than poisoning a global flag — callers thread it
  through jittable scaler state;
- ops take and return flat buffers (see ``apex_tpu.ops.flat``); per-tensor
  semantics use a segment-id vector.

Functions here never touch Python control flow on traced values, so they are
safe under jit/shard_map.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

MATH_DTYPE = jnp.float32

# Adam / Adagrad / LAMB weight-decay modes (reference: multi_tensor_adam.cu:16-19)
MODE_L2 = 0       # L2 regularization: decay folded into the gradient
MODE_DECOUPLED = 1  # AdamW-style decoupled weight decay

# Norm types (reference: multi_tensor_l2norm_kernel.cu MaxNormFunctor / L2NormFunctor)
NORM_LINF = 0
NORM_L2 = 2


def _f32(x: jax.Array) -> jax.Array:
    return x.astype(MATH_DTYPE)


def all_finite(*arrays: jax.Array) -> jax.Array:
    """True iff every element of every array is finite. Runs under the
    ``apex_overflow_check`` named scope so trace gaps bounded by the
    check attribute as ``overflow-check`` (prof/gaps.py), not
    ``unattributed``."""
    with jax.named_scope("apex_overflow_check"):
        ok = jnp.bool_(True)
        for a in arrays:
            ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(_f32(a))))
        return ok


# ---------------------------------------------------------------------------
# amp_C elementwise ops
# ---------------------------------------------------------------------------

def scale(x: jax.Array, scale_factor) -> tuple[jax.Array, jax.Array]:
    """out = x * scale, plus found_inf over the *input* (reference:
    multi_tensor_scale_kernel.cu:29-136; the finite check reads ``r_in`` so a
    saturating unscale still reports the overflow)."""
    out = (_f32(x) * scale_factor).astype(x.dtype)
    with jax.named_scope("apex_overflow_check"):
        found_inf = jnp.logical_not(jnp.all(jnp.isfinite(_f32(x))))
    return out, found_inf


def axpby(a, x: jax.Array, b, y: jax.Array,
          arg_to_check: int = -1) -> tuple[jax.Array, jax.Array]:
    """out = a*x + b*y with selectable overflow check (reference:
    multi_tensor_axpby_kernel.cu:27-157; arg_to_check -1 = both, 0 = x only,
    1 = y only — used for gradient accumulation across backward passes where
    the stashed master grads are known finite)."""
    out = (a * _f32(x) + b * _f32(y)).astype(jnp.result_type(x))
    with jax.named_scope("apex_overflow_check"):
        if arg_to_check == 0:
            bad = jnp.logical_not(jnp.all(jnp.isfinite(_f32(x))))
        elif arg_to_check == 1:
            bad = jnp.logical_not(jnp.all(jnp.isfinite(_f32(y))))
        else:
            bad = jnp.logical_not(
                jnp.logical_and(jnp.all(jnp.isfinite(_f32(x))),
                                jnp.all(jnp.isfinite(_f32(y)))))
    return out, bad


# ---------------------------------------------------------------------------
# Norms (global + per-segment)
# ---------------------------------------------------------------------------

def l2norm(x: jax.Array) -> jax.Array:
    """Global L2 norm, fp32 accumulation (reference:
    multi_tensor_l2norm_kernel.cu:27-196)."""
    return jnp.sqrt(jnp.sum(jnp.square(_f32(x))))


def segment_sum_dense(vals: jax.Array, ids: jax.Array,
                      num_segments: int) -> jax.Array:
    """Segment-sum as one fused masked column-reduction.

    ``jax.ops.segment_sum`` lowers to an XLA scatter-add, which the TPU
    executes one update at a time (~10 ms for 200k rows — measured as the
    dominant cost of a whole LAMB step, docs/PERF.md r03). For the few-hundred
    segment counts of an optimizer table, a dense (n, num_segments)
    masked reduce is exact per-segment fp32 tree summation (no
    long-running-cumsum cancellation), fully vectorized, and XLA fuses
    the broadcast so the mask never materializes in HBM (on the TPU
    fusion path; a CPU reference run may materialize the
    (n, num_segments) fp32 mask — fine at optimizer-table sizes, but
    callers with very large num_segments should mind it). Does not
    require sorted ids; out-of-range ids contribute nowhere."""
    cols = jnp.arange(num_segments, dtype=ids.dtype)
    return jnp.sum(jnp.where(ids[:, None] == cols[None, :],
                             vals[:, None], 0.0), axis=0)


def segment_sumsq_aligned(x: jax.Array, segment_ids: jax.Array,
                          num_segments: int) -> jax.Array:
    """Per-segment sums of squares over an ALIGN-aligned flat buffer (the
    flat-store invariant, ops/flat.py DEFAULT_ALIGN): a dense row
    reduction plus an ALIGN-x-smaller masked segment-sum — no element
    scatter. Shared by :func:`l2norm_per_segment` and the sharded LAMB's
    cross-device norms (which psum these partials before the sqrt)."""
    from apex_tpu.ops.flat import DEFAULT_ALIGN as ALIGN
    rows = jnp.sum(jnp.square(_f32(x)).reshape(-1, ALIGN), axis=1)
    return segment_sum_dense(rows, segment_ids[::ALIGN], num_segments)


def l2norm_per_segment(x: jax.Array, segment_ids: jax.Array,
                       num_segments: int, *,
                       aligned: bool = False) -> jax.Array:
    """Per-tensor L2 norms over a flat buffer (reference:
    multi_tensor_l2norm_cuda with per_tensor=True,
    multi_tensor_l2norm_kernel.cu:197-355). Padding must be zero.

    ``aligned=True`` asserts every segment boundary is ALIGN-aligned:
    see :func:`segment_sumsq_aligned`."""
    from apex_tpu.ops.flat import DEFAULT_ALIGN as ALIGN
    if aligned and x.size % ALIGN == 0:
        sq = segment_sumsq_aligned(x, segment_ids, num_segments)
    else:
        sq = jax.ops.segment_sum(jnp.square(_f32(x)), segment_ids,
                                 num_segments=num_segments)
    return jnp.sqrt(sq)


def maxnorm_per_segment(x: jax.Array, segment_ids: jax.Array,
                        num_segments: int, *,
                        aligned: bool = False) -> jax.Array:
    """Per-tensor L-inf norms (reference: MaxNormFunctor,
    multi_tensor_l2norm_kernel.cu:113-196). Padding zeros are harmless since
    |x| >= 0. ``aligned``: see :func:`l2norm_per_segment`. Segments absent
    from ``segment_ids`` report 0.0 on both paths (the fallback's
    segment_max identity is dtype-min; clamp to agree with the dense
    path's masked-0 identity)."""
    from apex_tpu.ops.flat import DEFAULT_ALIGN as ALIGN
    absx = jnp.abs(_f32(x))
    if aligned and x.size % ALIGN == 0:
        rows = jnp.max(absx.reshape(-1, ALIGN), axis=1)
        row_ids = segment_ids[::ALIGN]
        cols = jnp.arange(num_segments, dtype=row_ids.dtype)
        # dense masked column max (|x| >= 0 so 0 is the identity)
        return jnp.max(jnp.where(row_ids[:, None] == cols[None, :],
                                 rows[:, None], 0.0), axis=0)
    return jnp.maximum(jax.ops.segment_max(absx, segment_ids,
                                           num_segments=num_segments), 0.0)


def norm_out_blend(old_norms: jax.Array, new_norms: jax.Array,
                   alpha, beta, norm_type: int) -> jax.Array:
    """Blend per-tensor norms: L2: sqrt(a*old^2 + b*new^2); L-inf:
    a*old + b*new (reference: multi_tensor_l2norm_kernel.cu:361-368 comment +
    cleanup_v2). Used by NovoGrad's per-tensor second moment."""
    if norm_type == NORM_LINF:
        return alpha * old_norms + beta * new_norms
    return jnp.sqrt(alpha * jnp.square(old_norms) + beta * jnp.square(new_norms))


# ---------------------------------------------------------------------------
# Optimizer steps (flat-buffer, functional)
# ---------------------------------------------------------------------------

def keep_old(skip, old: jax.Array, new: jax.Array) -> jax.Array:
    """The overflow skip of every step below: ``new`` — or, where the traced
    ``skip`` is set, ``old`` bit-for-bit. With ``skip=None`` nothing is
    selected. The select fuses into the update, so a donated ``old`` is
    still overwritten in place."""
    return new if skip is None else jnp.where(skip, old, new)


def adam_step(g: jax.Array, p: jax.Array, m: jax.Array, v: jax.Array, *,
              lr, beta1: float, beta2: float, eps: float, step,
              mode: int = MODE_L2, bias_correction: bool = True,
              weight_decay: float = 0.0, skip=None,
              ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused Adam/AdamW step (reference: multi_tensor_adam.cu:23-171).

    mode 0 folds weight decay into the gradient (L2), mode 1 is decoupled
    AdamW. Bias corrections are plain ``1 - beta^t`` divisors applied to m,v
    (reference: multi_tensor_adam.cu:144-149). Returns (p, m, v).
    """
    gf, pf, mf, vf = _f32(g), _f32(p), _f32(m), _f32(v)
    step = jnp.asarray(step, MATH_DTYPE)
    if bias_correction:
        bc1 = 1.0 - jnp.power(jnp.asarray(beta1, MATH_DTYPE), step)
        bc2 = 1.0 - jnp.power(jnp.asarray(beta2, MATH_DTYPE), step)
    else:
        bc1 = bc2 = jnp.asarray(1.0, MATH_DTYPE)
    if mode == MODE_L2:
        gf = gf + weight_decay * pf
        mf = beta1 * mf + (1.0 - beta1) * gf
        vf = beta2 * vf + (1.0 - beta2) * gf * gf
        update = (mf / bc1) / (jnp.sqrt(vf / bc2) + eps)
    else:
        mf = beta1 * mf + (1.0 - beta1) * gf
        vf = beta2 * vf + (1.0 - beta2) * gf * gf
        update = (mf / bc1) / (jnp.sqrt(vf / bc2) + eps) + weight_decay * pf
    pf = pf - lr * update
    return (keep_old(skip, p, pf.astype(p.dtype)),
            keep_old(skip, m, mf.astype(m.dtype)),
            keep_old(skip, v, vf.astype(v.dtype)))


def adagrad_step(g: jax.Array, p: jax.Array, h: jax.Array, *,
                 lr, eps: float, mode: int = MODE_L2,
                 weight_decay: float = 0.0, skip=None,
                 ) -> tuple[jax.Array, jax.Array]:
    """Fused Adagrad step (reference: multi_tensor_adagrad.cu:24-85).
    Returns (p, h)."""
    gf, pf, hf = _f32(g), _f32(p), _f32(h)
    if mode == MODE_L2:
        gf = gf + weight_decay * pf
        hf = hf + gf * gf
        pf = pf - lr * (gf / (jnp.sqrt(hf) + eps))
    else:
        hf = hf + gf * gf
        pf = pf - lr * (gf / (jnp.sqrt(hf) + eps) + weight_decay * pf)
    return (keep_old(skip, p, pf.astype(p.dtype)),
            keep_old(skip, h, hf.astype(h.dtype)))


def sgd_step(g: jax.Array, p: jax.Array, mom: jax.Array, *,
             wd: float, momentum: float, dampening: float, lr,
             nesterov: bool = False, first_run: bool = False,
             wd_after_momentum: bool = False, scale: float = 1.0,
             skip=None) -> tuple[jax.Array, jax.Array]:
    """Fused SGD step (reference: multi_tensor_sgd_kernel.cu:29-140).

    ``scale`` folds AMP's grad unscale into the step (grads are multiplied by
    it before use); ``first_run`` initializes momentum to the incoming grad
    rather than blending (multi_tensor_sgd_kernel.cu:113-117). ``first_run``
    may be a traced bool. Returns (p, mom).
    """
    gf = _f32(g) * scale
    pf, mf = _f32(p), _f32(mom)
    if wd != 0.0 and not wd_after_momentum:
        gf = gf + wd * pf
    if momentum != 0.0:
        blended = mf * momentum + (1.0 - dampening) * gf
        mf = jnp.where(jnp.asarray(first_run), gf, blended)
        if nesterov:
            gf = gf + momentum * mf
        else:
            gf = mf
    if wd != 0.0 and wd_after_momentum:
        gf = gf + wd * pf
    pf = pf - lr * gf
    return (keep_old(skip, p, pf.astype(p.dtype)),
            keep_old(skip, mom, mf.astype(mom.dtype)))


def _broadcast_per_segment(vals: jax.Array, segment_ids: jax.Array,
                           n: int, aligned: bool) -> jax.Array:
    """vals[segment_ids] without the element-level gather when segments are
    ALIGN-aligned (the flat-store invariant, ops/flat.py): gather once per
    row, broadcast across lanes."""
    from apex_tpu.ops.flat import DEFAULT_ALIGN as ALIGN
    if aligned and n % ALIGN == 0:
        # masked reduction, not vals[row_ids]: a row-count-sized gather
        # runs as a ~2 GB/s kCustom scalar gather on TPU (r4 trace:
        # 1.6 ms x2 per LAMB step at RN50 scale); the compare+select
        # fuses and streams at VPU rate. Exactly one mask hit per row,
        # so the sum is exact.
        row_seg = segment_ids[::ALIGN]                           # [R]
        s = vals.shape[0]
        onehot = row_seg[:, None] == jnp.arange(
            s, dtype=row_seg.dtype)[None, :]                     # [R, S]
        rows = jnp.sum(jnp.where(onehot, vals[None, :], 0), axis=1)
        return jnp.broadcast_to(rows[:, None], (n // ALIGN, ALIGN)).reshape(n)
    return vals[segment_ids]


def novograd_step(g: jax.Array, p: jax.Array, m: jax.Array,
                  v_norms: jax.Array, segment_ids: jax.Array, *,
                  lr, beta1: float, beta2: float, eps: float, step,
                  bias_correction: bool = True, weight_decay: float = 0.0,
                  grad_averaging: bool = True, mode: int = MODE_L2,
                  norm_type: int = NORM_L2, aligned: bool = False,
                  skip=None) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused NovoGrad step (reference: multi_tensor_novograd.cu:31-186).

    ``v_norms`` is the per-tensor second-moment vector storing *norms* (not
    squares, reference: fused_novograd.py:157-158). The blend happens first:
    L2: v' = sqrt(beta2*v^2 + (1-beta2)*|g|^2); then the elementwise update
    uses denom = v'/bc2 + eps with bc2 = sqrt(1-beta2^t) (reference:
    multi_tensor_novograd.cu:148-152,107-126). Returns (p, m, v_norms).
    """
    num_segments = v_norms.shape[0]
    gf, pf, mf = _f32(g), _f32(p), _f32(m)
    step = jnp.asarray(step, MATH_DTYPE)
    if bias_correction:
        bc1 = 1.0 - jnp.power(jnp.asarray(beta1, MATH_DTYPE), step)
        bc2 = jnp.sqrt(1.0 - jnp.power(jnp.asarray(beta2, MATH_DTYPE), step))
    else:
        bc1 = bc2 = jnp.asarray(1.0, MATH_DTYPE)
    beta3 = (1.0 - beta1) if grad_averaging else 1.0

    if norm_type == NORM_LINF:
        new_norms = maxnorm_per_segment(gf, segment_ids, num_segments,
                                        aligned=aligned)
    else:
        new_norms = l2norm_per_segment(gf, segment_ids, num_segments,
                                       aligned=aligned)
    v_new = norm_out_blend(v_norms, new_norms, beta2, 1.0 - beta2, norm_type)

    per_elem_norm = _broadcast_per_segment(v_new, segment_ids, g.size,
                                           aligned)
    denom = per_elem_norm / bc2 + eps
    if mode == MODE_L2:
        gf = gf / denom + weight_decay * pf
        mf = beta1 * mf + beta3 * gf
        pf = pf - lr * (mf / bc1)
    else:
        mf = beta1 * mf + beta3 * gf
        update = (mf / bc1) / denom + weight_decay * pf
        pf = pf - lr * update
    return (keep_old(skip, p, pf.astype(p.dtype)),
            keep_old(skip, m, mf.astype(m.dtype)),
            keep_old(skip, v_norms, v_new))


def lamb_step(g: jax.Array, p: jax.Array, m: jax.Array, v: jax.Array,
              segment_ids: jax.Array, num_segments: int, *,
              lr, beta1: float, beta2: float, eps: float, step,
              bias_correction: bool = True, weight_decay: float = 0.0,
              grad_averaging: bool = True, mode: int = MODE_L2,
              global_grad_norm, max_grad_norm: float = 0.0,
              use_nvlamb: bool = False, aligned: bool = False, skip=None,
              ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused two-phase LAMB step (reference: multi_tensor_lamb.cu:40-413).

    Phase 1 computes the Adam-style update u (grads pre-scaled by the global
    clip factor ``norm/max_norm`` when norm > max_norm,
    multi_tensor_lamb.cu:66); phase 2 applies the per-tensor trust ratio
    ``||p|| / ||u||`` — only where decay != 0 unless use_nvlamb
    (multi_tensor_lamb.cu:256-263). Returns (p, m, v).
    """
    gf, pf, mf, vf = _f32(g), _f32(p), _f32(m), _f32(v)
    step = jnp.asarray(step, MATH_DTYPE)
    if bias_correction:
        bc1 = 1.0 - jnp.power(jnp.asarray(beta1, MATH_DTYPE), step)
        bc2 = 1.0 - jnp.power(jnp.asarray(beta2, MATH_DTYPE), step)
    else:
        bc1 = bc2 = jnp.asarray(1.0, MATH_DTYPE)
    beta3 = (1.0 - beta1) if grad_averaging else 1.0

    gg = jnp.asarray(global_grad_norm, MATH_DTYPE)
    clip = jnp.where(gg > max_grad_norm, gg / max_grad_norm,
                     jnp.asarray(1.0, MATH_DTYPE)) if max_grad_norm > 0 \
        else jnp.asarray(1.0, MATH_DTYPE)

    # Phase 1: update term (written over the grad buffer in the reference).
    param_norms = l2norm_per_segment(pf, segment_ids, num_segments,
                                     aligned=aligned)
    scaled_grad = gf / clip
    if mode == MODE_L2:
        scaled_grad = scaled_grad + weight_decay * pf
        mf = beta1 * mf + beta3 * scaled_grad
        vf = beta2 * vf + (1.0 - beta2) * scaled_grad * scaled_grad
        update = (mf / bc1) / (jnp.sqrt(vf / bc2) + eps)
    else:
        mf = beta1 * mf + beta3 * scaled_grad
        vf = beta2 * vf + (1.0 - beta2) * scaled_grad * scaled_grad
        update = (mf / bc1) / (jnp.sqrt(vf / bc2) + eps) + weight_decay * pf

    # Phase 2: per-tensor trust ratio.
    update_norms = l2norm_per_segment(update, segment_ids, num_segments,
                                      aligned=aligned)
    if use_nvlamb or weight_decay != 0.0:
        ratio = jnp.where(
            jnp.logical_and(update_norms != 0.0, param_norms != 0.0),
            lr * (param_norms / update_norms), jnp.asarray(lr, MATH_DTYPE))
    else:
        ratio = jnp.full((num_segments,), lr, MATH_DTYPE)
    pf = pf - _broadcast_per_segment(ratio, segment_ids, p.size,
                                     aligned) * update
    return (keep_old(skip, p, pf.astype(p.dtype)),
            keep_old(skip, m, mf.astype(m.dtype)),
            keep_old(skip, v, vf.astype(v.dtype)))
