"""SyncBatchNorm — cross-replica batch normalization.

TPU-native re-design of the reference's two implementations
(apex/parallel/sync_batchnorm.py:9-134 pure-python E[x]/E[x^2] allreduce
path; apex/parallel/optimized_sync_batchnorm*.py + csrc/welford.cu CUDA
welford path). The structure here follows the optimized path's collective
choreography with XLA collectives:

forward (training):
  local per-channel (count, sum, sum_sq)  ->  psum over the replica axis
  (the all_gather + ``welford_parallel`` merge, welford.cu:559-584, fused
  into one psum of moments — the python fallback's formulation,
  sync_batchnorm.py:68-81)  ->  normalize; running stats updated with the
  *unbiased* group variance (optimized_sync_batchnorm_kernel.py:47-50).

backward (custom_vjp, the ``reduce_bn`` + allreduce + ``batchnorm_backward``
pipeline, welford.cu:325-494, kernel.py:68-113):
  per-channel sum_dy / sum_dy_xhat  ->  psum  ->
  dx = invvar * w * (dy - mean_dy - xhat * mean_dy_xhat).

Layout: channels-last (NHWC / N...C) is the primary path — on TPU the
channel dim maps to lanes, which is why the reference's ``_c_last`` CUDA
variants (welford.cu:592-884) are the *default* here, not the special case.
Any channel axis is supported.

Group support (``create_syncbn_process_group``-style, reference
apex/parallel/__init__.py:58-95 and contrib groupbn's ``bn_group``):
pass ``axis_index_groups`` — stats sync only within each group.

Fused extras from the optimized/groupbn path: optional residual ``z`` added
pre-activation and ``fuse_relu`` (optimized_sync_batchnorm.py:70-85's
``z``/``fuse_relu`` args; batch_norm_add_relu.cu) — both differentiable
through the same custom_vjp.

``axis_name=None`` degrades to plain (single-replica) BatchNorm, the
equivalent of running the reference module outside DDP.

XLA runs the reductions on every platform: on the v5e RN50's 53 BNs cost
~16 ms a step this way against ~150 ms through hand-written welford kernels
(docs/PERF.md r03: a kernel boundary sends the activation through HBM once a
call and pays grid overhead 53 times, where XLA folds the reductions into
the adjacent convolutions' epilogues), so no kernel is kept beside them.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp


from apex_tpu.parallel.collectives import (grouped_psum as _psum,
                                           varies_over as _varies_over)


def _sum_pair(a, b, axes):
    """Sum two same-shape fp32 operands over ``axes`` as two plain
    jnp.sums. Two shapes that read better in a compile audit were
    measured on the chip at RN50 batch 384 and lost whole-step: one
    variadic ``lax.reduce`` over the pair (one fused input chain, no
    materialized fp32 upcast) 1868 img/s against 2172
    (BENCH_r05_builder.json vs BENCH_r05_bn_split.json), and moments from
    the raw storage dtype with ``sum(x*x)`` as an MXU self-contraction
    1749 (r05). The TPU emitter wants the pair of fused reductions
    (docs/PERF.md keeps the readings)."""
    return jnp.sum(a, axis=tuple(axes)), jnp.sum(b, axis=tuple(axes))


def _sum2(xf, axes):
    """(sum(x), sum(x^2)) — the BN moments pass — via _sum_pair."""
    return _sum_pair(xf, xf * xf, axes)


def _folded_upcast() -> bool:
    """Opt-in moments shape for the r06 convert-seam A/B
    (APEX_BN_FOLDED_UPCAST=1): each moments reduction consumes its OWN
    single-consumer input chain — sum(x) through an fp32-accumulating
    reduce, sum(x^2) squaring in the STORAGE dtype before its own fp32
    upcast — so no fp32 copy of the activation has two consumers and the
    emitter can sink each convert into its reduction fusion instead of
    materializing it (the r05b trace still carries 60 ms/capture of
    standalone jvp converts; prof.gaps attributes the seams). Numerics:
    identical for fp32 inputs; for bf16 the x^2 rounds to bf16 before
    accumulation (relative 2^-8 per element, pinned by the parity
    test). UNMEASURED on chip: stays opt-in until a window A/B decides
    it (docs/PERF.md r06 has the arm commands)."""
    import os
    return os.environ.get("APEX_BN_FOLDED_UPCAST") == "1"


def _reduce_axes(ndim: int, channel_axis: int) -> tuple[int, ...]:
    ca = channel_axis % ndim
    return tuple(i for i in range(ndim) if i != ca)


def _bcast_shape(ndim: int, channel_axis: int, c: int) -> tuple[int, ...]:
    ca = channel_axis % ndim
    return tuple(c if i == ca else 1 for i in range(ndim))


# -- training-mode core with hand-written VJP --------------------------------

def _bn_train_fwd_math(x, z, weight, bias, eps, axis_name, groups,
                       fuse_relu, channel_axis):
    ndim = x.ndim
    ca = channel_axis % ndim
    axes = _reduce_axes(ndim, ca)
    c = x.shape[ca]
    bshape = _bcast_shape(ndim, ca, c)

    local_count = jnp.asarray(
        jnp.prod(jnp.asarray([x.shape[i] for i in axes])), jnp.float32)
    count = _psum(local_count, axis_name, groups)
    if _folded_upcast():
        # per-reduction single-consumer upcasts (see _folded_upcast):
        # the square happens in storage dtype so each reduce owns its
        # whole input chain — no shared fp32 activation copy to
        # materialize at a fusion seam
        lsum = jnp.sum(x, axis=axes, dtype=jnp.float32)
        lsq = jnp.sum(jnp.square(x), axis=axes, dtype=jnp.float32)
    else:
        # (sum, sum-of-squares) via _sum_pair: two plain fused
        # reductions (see its note on the measured losers before
        # "re-fixing" the shared-upcast shape here).
        lsum, lsq = _sum2(x.astype(jnp.float32), axes)
    mean = _psum(lsum, axis_name, groups) / count
    mean_sq = _psum(lsq, axis_name, groups) / count
    var = mean_sq - jnp.square(mean)          # biased, over the whole group
    invvar = jax.lax.rsqrt(var + eps)

    # Normalize-apply reads the ORIGINAL x, not xf: with xf shared
    # between the moments reduction and this elementwise chain, XLA
    # materialized the fp32 copy of every activation as a top-level
    # convert (r4 trace: 12.7 ms/step, ~8.6 GB of pure convert traffic
    # across the 53 BNs). Folding (mean, invvar, weight, bias) into a
    # per-channel scale/shift keeps this chain's only big input bf16;
    # the bf16*fp32 promotion happens per-element inside the fusion.
    scale = invvar
    if weight is not None:
        scale = scale * weight.astype(jnp.float32)
    shift = -mean * scale
    if bias is not None:
        shift = shift + bias.astype(jnp.float32)
    out = x * scale.reshape(bshape) + shift.reshape(bshape)
    if z is not None:
        out = out + z.astype(jnp.float32)
    if fuse_relu:
        out = jnp.maximum(out, 0.0)
    return out.astype(x.dtype), mean, var, invvar, count


def _bn_train_call(x, z, weight, bias, eps, axis_name, groups, fuse_relu,
                   channel_axis):
    out, mean, var, _, count = _bn_train_fwd_math(
        x, z, weight, bias, eps, axis_name, groups, fuse_relu, channel_axis)
    return out, mean, var, count


def _bn_train_fwd(x, z, weight, bias, eps, axis_name, groups, fuse_relu,
                  channel_axis):
    out, mean, var, invvar, count = _bn_train_fwd_math(
        x, z, weight, bias, eps, axis_name, groups, fuse_relu, channel_axis)
    # save (input, weight, mean, invvar, count) — the reference saves the
    # same set (optimized_sync_batchnorm_kernel.py:52-55). For fuse_relu the
    # primal OUTPUT rides along as the relu mask (out==0 where clipped): a
    # primal output costs nothing as a residual (same buffer), unlike the
    # bool mask array this used to materialize.
    # bias is saved (not just a has-bias flag) so its grad lands in the bias
    # dtype, which can differ from weight.dtype.
    return (out, mean, var, count), (x, weight, bias, z is not None, mean,
                                     invvar, count,
                                     out if fuse_relu else None)


def _bn_train_bwd(eps, axis_name, groups, fuse_relu, channel_axis, res, cts):
    # mean/var/count are emitted ONLY for the running-stat update (buffer
    # semantics, never differentiated — the caller stop_gradients them);
    # their cotangents are discarded.
    dy, _d_mean, _d_var, _d_count = cts
    return _bn_train_bwd_out(eps, axis_name, groups, fuse_relu,
                             channel_axis, res, dy)


def _bn_train_bwd_out(eps, axis_name, groups, fuse_relu, channel_axis, res,
                      dy):
    x, weight, bias, has_z, mean, invvar, count, out = res
    has_bias = bias is not None
    ndim = x.ndim
    ca = channel_axis % ndim
    axes = _reduce_axes(ndim, ca)
    bshape = _bcast_shape(ndim, ca, x.shape[ca])

    # reduce_bn partial sums (welford.cu:325: per-channel sum_dy,
    # sum_dy_xmu -> grad_weight, grad_bias) + the two allreduces
    # (kernel.py:95-101), then the batchnorm_backward elementwise dx
    # (welford.cu:387).
    dyf = dy.astype(jnp.float32)
    if fuse_relu:
        dyf = jnp.where(out > 0, dyf, 0.0)
    xf = x.astype(jnp.float32)
    xhat = (xf - mean.reshape(bshape)) * invvar.reshape(bshape)
    sum_dy_local, sum_dy_xhat_local = _sum_pair(dyf, dyf * xhat, axes)
    # Param cotangents must match the primal's device-variance (jax vma
    # rules): a replicated weight gets globally-summed grads, so the psum
    # the reference leaves to DDP happens here, inside the vjp.
    # CONTRACT under check_vma=False (vma tracking off — any region with
    # a pallas_call in it): varies_over falls back to assume-varying, so
    # the psum does NOT happen here; classic semantics leave the grad
    # reduction to the caller's DDP.average_gradients, which psums in
    # that mode. The pair is consistent either way (pinned by
    # test_parallel.py's check_vma=False syncbn+ddp parity test).
    def _for_param(partial_sum):
        if axis_name is not None and weight is not None and \
                not _varies_over(weight, axis_name):
            # FULL-axis psum, not the grouped one the stats use: the
            # replicated weight's cotangent is the sum over ALL devices
            # (sum of group sums), and a group-psummed value is still
            # axis-varying — under check_vma=True the vjp would emit a
            # varying cotangent for an unvarying primal and be rejected
            # (caught by a grouped-BN + affine-grad drive, r5)
            return _psum(partial_sum, axis_name, None)
        return partial_sum
    grad_weight = (_for_param(sum_dy_xhat_local).astype(weight.dtype)
                   if weight is not None else None)
    grad_bias = (_for_param(sum_dy_local).astype(bias.dtype)
                 if has_bias else None)

    mean_dy = _psum(sum_dy_local, axis_name, groups) / count
    mean_dy_xhat = _psum(sum_dy_xhat_local, axis_name, groups) / count

    wvec = (weight.astype(jnp.float32) if weight is not None
            else jnp.ones_like(invvar))
    dz = dyf.astype(x.dtype) if has_z else None
    dx = ((invvar * wvec).reshape(bshape) *
          (dyf - mean_dy.reshape(bshape)
           - xhat * mean_dy_xhat.reshape(bshape))).astype(x.dtype)
    return dx, dz, grad_weight, grad_bias


_bn_train = jax.custom_vjp(_bn_train_call, nondiff_argnums=(4, 5, 6, 7, 8))
_bn_train.defvjp(_bn_train_fwd, _bn_train_bwd)


# -- module ------------------------------------------------------------------

class SyncBatchNorm:
    """Drop-in analog of ``apex.parallel.SyncBatchNorm``
    (optimized_sync_batchnorm.py:9: num_features, eps, momentum, affine,
    track_running_stats, process_group, channel_last).

    Functional usage::

        bn = SyncBatchNorm(64, axis_name="data")
        params, state = bn.init()
        y, state = bn.apply(params, state, x, training=True)  # in shard_map

    ``state`` carries (running_mean, running_var, num_batches_tracked);
    thread it like any other pytree. ``momentum=None`` selects cumulative
    moving average, matching torch BN semantics the reference inherits.
    """

    def __init__(self, num_features: int, eps: float = 1e-5,
                 momentum: Optional[float] = 0.1, affine: bool = True,
                 track_running_stats: bool = True,
                 process_group=None, channel_last: Optional[bool] = None,
                 fuse_relu: bool = False, *,
                 axis_name: Optional[str] = "data",
                 axis_index_groups=None,
                 channel_axis: int = -1,
                 param_dtype=jnp.float32):
        # Reference keyword aliases (optimized_sync_batchnorm.py:58, same
        # positional order through fuse_relu): ``process_group`` is the
        # output of create_syncbn_process_group — exactly our
        # axis_index_groups; ``channel_last`` maps onto channel_axis
        # (True -> -1 NHWC, False -> 1 NCHW; None -> use channel_axis,
        # whose TPU-native default is NHWC).
        if process_group is not None:
            if isinstance(process_group, str):
                # the 6th positional used to be axis_name — a stale
                # positional caller must fail loudly, not get their axis
                # name exploded into per-character "groups"
                raise TypeError(
                    f"process_group must be a sequence of rank groups "
                    f"(create_syncbn_process_group result), got "
                    f"{process_group!r}; axis_name is keyword-only "
                    f"(axis_name={process_group!r})")
            if axis_index_groups is not None:
                raise ValueError(
                    "pass process_group OR axis_index_groups, not both")
            axis_index_groups = process_group
        if channel_last is not None:
            channel_axis = -1 if channel_last else 1
        self.num_features = int(num_features)
        self.eps = float(eps)
        self.momentum = momentum
        self.affine = bool(affine)
        self.track_running_stats = bool(track_running_stats)
        self.axis_name = axis_name
        self.axis_index_groups = (tuple(tuple(g) for g in axis_index_groups)
                                  if axis_index_groups else None)
        self.channel_axis = int(channel_axis)
        self.fuse_relu = bool(fuse_relu)
        self.param_dtype = jnp.dtype(param_dtype)

    def init(self) -> tuple[dict, dict]:
        params = {}
        if self.affine:
            params = {"weight": jnp.ones((self.num_features,),
                                         self.param_dtype),
                      "bias": jnp.zeros((self.num_features,),
                                        self.param_dtype)}
        state = {}
        if self.track_running_stats:
            state = {"running_mean": jnp.zeros((self.num_features,),
                                               jnp.float32),
                     "running_var": jnp.ones((self.num_features,),
                                             jnp.float32),
                     "num_batches_tracked": jnp.asarray(0, jnp.int32)}
        return params, state

    def apply(self, params: dict, state: dict, x: jax.Array,
              z: Optional[jax.Array] = None, training: bool = True
              ) -> tuple[jax.Array, dict]:
        w = params.get("weight") if self.affine else None
        b = params.get("bias") if self.affine else None

        if not training and self.track_running_stats:
            # eval: normalize with running stats, no collectives
            # (optimized_sync_batchnorm_kernel.py:24-27 passes running stats
            # when not training).
            bshape = _bcast_shape(x.ndim, self.channel_axis,
                                  self.num_features)
            inv = jax.lax.rsqrt(state["running_var"] + self.eps)
            # scale/shift folding keeps the elementwise chain's big
            # input bf16 (see _bn_train_fwd_math); eval has no moments
            # pass but a materialized fp32 x is the same HBM cost
            scale = inv if w is None else inv * w.astype(jnp.float32)
            shift = -state["running_mean"] * scale
            if b is not None:
                shift = shift + b.astype(jnp.float32)
            out = x * scale.reshape(bshape) + shift.reshape(bshape)
            if z is not None:
                out = out + z.astype(jnp.float32)
            if self.fuse_relu:
                out = jnp.maximum(out, 0.0)
            return out.astype(x.dtype), state

        out, mean, var, count = _bn_train(
            x, z, w, b, self.eps, self.axis_name,
            self.axis_index_groups, self.fuse_relu, self.channel_axis)

        if not self.track_running_stats:
            return out, state

        # The group stats come out of the SAME custom_vjp call that
        # normalized (no second moments pass).
        # stop_gradient: running stats are buffers, never differentiated.
        # Unbiased var for running_var (kernel.py:47-50: var*count/(count-1)).
        mean = jax.lax.stop_gradient(mean)
        var = jax.lax.stop_gradient(var)
        count = jax.lax.stop_gradient(count)
        unbiased = var * (count / jnp.maximum(count - 1.0, 1.0))
        tracked = state["num_batches_tracked"] + 1
        if self.momentum is None:
            m = 1.0 / tracked.astype(jnp.float32)
        else:
            m = self.momentum
        new_state = {
            "running_mean": (1 - m) * state["running_mean"] + m * mean,
            "running_var": (1 - m) * state["running_var"] + m * unbiased,
            "num_batches_tracked": tracked,
        }
        return out, new_state

    def __call__(self, params, state, x, **kw):
        return self.apply(params, state, x, **kw)
