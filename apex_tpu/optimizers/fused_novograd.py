"""FusedNovoGrad — NovoGrad with per-tensor second moments.

Analog of the reference FusedNovoGrad (apex/optimizers/fused_novograd.py:
67-207): the second moment is ONE scalar per tensor, stored as a norm (not
a square, fused_novograd.py:157-158), blended before the elementwise update
(multi_tensor_novograd.cu:160-164). ``init_zero`` chooses zero-init vs
first-step-norm init (fused_novograd.py:159-172). L2 and L-inf norm modes.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from apex_tpu.optimizers.base import FusedOptimizer, GroupState
from apex_tpu.ops import kernels as R


class FusedNovoGrad(FusedOptimizer):
    _slot_names = ("exp_avg",)  # exp_avg_sq is per-tensor, added in _init_group

    def __init__(self, params, lr=1e-3, bias_correction=True,
                 betas=(0.9, 0.999), eps=1e-8, weight_decay=0.0,
                 amsgrad=False, reg_inside_moment=False,
                 grad_averaging=True, norm_type=2, init_zero=False,
                 set_grad_none=True, **kw):
        # positional order, defaults incl. betas=(0.9, 0.999) and
        # grad_averaging=True, and the amsgrad rejection all match the
        # reference exactly (fused_novograd.py:67-74)
        if amsgrad:
            raise RuntimeError(
                "FusedNovoGrad does not support the AMSGrad variant.")
        if norm_type not in (0, 2):
            raise RuntimeError("FusedNovoGrad only supports l2/inf norm.")
        defaults = dict(lr=lr, bias_correction=bias_correction, betas=betas,
                        eps=eps, weight_decay=weight_decay,
                        grad_averaging=grad_averaging, norm_type=norm_type)
        # moment_mode 0 = wd inside the moment (reference
        # fused_novograd.py:85: reg_inside_moment -> moment_mode 0)
        self.moment_mode = R.MODE_L2 if reg_inside_moment else R.MODE_DECOUPLED
        self.init_zero = init_zero
        super().__init__(params, defaults, set_grad_none=set_grad_none,
                         **kw)

    def _init_group(self, buf, table):
        gs = super()._init_group(buf, table)
        gs.slots["exp_avg_sq"] = jnp.full(
            (table.num_segments,), jnp.nan if not self.init_zero else 0.0,
            jnp.float32)
        return gs

    def _update_group(self, gidx, grad, gs: GroupState, hp, lr, extras,
                      skip):
        beta1, beta2 = hp["betas"]
        table = self._tables[gidx]
        seg = table.segment_ids()
        vnorms = gs.slots["exp_avg_sq"]
        if not self.init_zero:
            # First step: seed with the first grad norms so the first blend
            # is a no-op (reference fused_novograd.py:161-172). NaN marks
            # "uninitialized"; branchless substitution keeps this jittable.
            if hp["norm_type"] == 0:
                first = R.maxnorm_per_segment(grad, seg, table.num_segments,
                                              aligned_segments=True)
            else:
                first = R.l2norm_per_segment(grad, seg, table.num_segments,
                                             aligned_segments=True)
            vnorms = jnp.where(jnp.isnan(vnorms), first, vnorms)
        p, m, v = R.novograd_step(
            grad, gs.master, gs.slots["exp_avg"], vnorms, seg,
            aligned_segments=True,  # flat-store segments are 128-aligned
            lr=lr, beta1=beta1, beta2=beta2, eps=hp["eps"], step=gs.step,
            bias_correction=bool(hp["bias_correction"]),
            weight_decay=hp["weight_decay"],
            grad_averaging=bool(hp["grad_averaging"]),
            mode=self.moment_mode, norm_type=hp["norm_type"], skip=skip)
        if not self.init_zero:
            # a skipped first step must leave the NaN marker, not the
            # overflowing gradient's norms seeded above
            v = R.keep_old(skip, gs.slots["exp_avg_sq"], v)
        return dataclasses.replace(
            gs, master=p, slots={"exp_avg": m, "exp_avg_sq": v})
