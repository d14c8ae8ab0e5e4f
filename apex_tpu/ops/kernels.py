"""Dispatching op facade: Pallas kernels on TPU, jnp reference elsewhere.

This is the call surface the optimizers, the AMP scaler, and the fused
layers use — the single chokepoint the way ``multi_tensor_applier`` is in
the reference (apex/multi_tensor_apply/multi_tensor_apply.py:24). Unlike
the reference, which raises when the native extension is absent
(multi_tensor_apply.py:20-22), every op here degrades to the pure-jnp
reference implementation when the Pallas path does not apply (backend
forced to "reference", non-TPU platform without interpret value, empty or
non-128-aligned buffers).

Signatures mirror ``apex_tpu.ops.reference`` one-for-one, so the two layers
are interchangeable — the property the bitwise cross-check tests rely on
(the analog of the reference's Python-build vs CUDA-build L1 axis,
tests/L1/common/run_test.sh:57-137).
"""

from __future__ import annotations

from apex_tpu.ops import dispatch
from apex_tpu.ops import reference as R
from apex_tpu.ops.pallas import multi_tensor as P

MODE_L2 = R.MODE_L2
MODE_DECOUPLED = R.MODE_DECOUPLED
NORM_LINF = R.NORM_LINF
NORM_L2 = R.NORM_L2

all_finite = R.all_finite
keep_old = R.keep_old
norm_out_blend = R.norm_out_blend


def _pallas_ok(*arrays) -> bool:
    return dispatch.use_pallas() and P.supported(*arrays)


def scale(x, scale_factor):
    if _pallas_ok(x):
        return P.scale(x, scale_factor)
    return R.scale(x, scale_factor)


def axpby(a, x, b, y, arg_to_check: int = -1):
    if _pallas_ok(x, y):
        return P.axpby(a, x, b, y, arg_to_check)
    return R.axpby(a, x, b, y, arg_to_check)


def l2norm(x):
    if _pallas_ok(x):
        return P.l2norm(x)
    return R.l2norm(x)


def l2norm_per_segment(x, segment_ids, num_segments: int, *,
                       aligned_segments: bool = False):
    # The Pallas row trick needs every segment boundary 128-aligned (then a
    # flat row never straddles segments). segment_ids is traced, so the
    # property cannot be checked here — callers that built their buffers
    # through the flat store (apex_tpu/ops/flat.py DEFAULT_ALIGN) assert it
    # by passing aligned_segments=True; everyone else gets the reference
    # path, never silently-wrong norms.
    if aligned_segments and _pallas_ok(x):
        return P.l2norm_per_segment(x, segment_ids, num_segments)
    return R.l2norm_per_segment(x, segment_ids, num_segments,
                                aligned=aligned_segments)


def maxnorm_per_segment(x, segment_ids, num_segments: int, *,
                        aligned_segments: bool = False):
    if aligned_segments and _pallas_ok(x):
        return P.maxnorm_per_segment(x, segment_ids, num_segments)
    return R.maxnorm_per_segment(x, segment_ids, num_segments,
                                 aligned=aligned_segments)


def adam_step(g, p, m, v, **kw):
    if _pallas_ok(g, p, m, v):
        return P.adam_step(g, p, m, v, **kw)
    return R.adam_step(g, p, m, v, **kw)


def adagrad_step(g, p, h, **kw):
    if _pallas_ok(g, p, h):
        return P.adagrad_step(g, p, h, **kw)
    return R.adagrad_step(g, p, h, **kw)


def sgd_step(g, p, mom, **kw):
    if _pallas_ok(g, p, mom):
        return P.sgd_step(g, p, mom, **kw)
    return R.sgd_step(g, p, mom, **kw)


def novograd_step(g, p, m, v_norms, segment_ids, *,
                  aligned_segments: bool = False, **kw):
    if aligned_segments and _pallas_ok(g, p, m):
        return P.novograd_step(g, p, m, v_norms, segment_ids, **kw)
    return R.novograd_step(g, p, m, v_norms, segment_ids,
                           aligned=aligned_segments, **kw)


def lamb_step(g, p, m, v, segment_ids, num_segments, *,
              aligned_segments: bool = False, **kw):
    # LAMB has one side, XLA's, on every platform: on the v5e XLA fuses the
    # two phases into ~2 sweeps (4.3 ms for 25.6M params) where a two-kernel
    # composition paid per-kernel boundaries and skinny per-row norm outputs
    # (7.5-21 ms, docs/PERF.md r03; 0.67x, r05).
    return R.lamb_step(g, p, m, v, segment_ids, num_segments,
                       aligned=aligned_segments, **kw)
