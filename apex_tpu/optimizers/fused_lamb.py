"""FusedLAMB — layer-wise adaptive large-batch optimizer over flat buffers.

Analog of the reference FusedLAMB (apex/optimizers/fused_lamb.py:4,96-212):
the global gradient norm is computed across every param group (the
reference blends per-dtype-list norms, fused_lamb.py:122-135), then each
group runs the two-phase LAMB update (stage 1 Adam-style update term with
global clipping, per-tensor param/update norms, stage 2 trust-ratio apply —
multi_tensor_lamb.cu:40-413). Per-tensor norms ride the group's segment
table instead of the per-tensor kernel list.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from apex_tpu.optimizers.base import FusedOptimizer, GroupState
from apex_tpu.ops import kernels as R


class FusedLAMB(FusedOptimizer):
    _slot_names = ("exp_avg", "exp_avg_sq")

    def __init__(self, params, lr=1e-3, bias_correction=True,
                 betas=(0.9, 0.999), eps=1e-6, weight_decay=0.01,
                 amsgrad=False, adam_w_mode=True, grad_averaging=True,
                 set_grad_none=True, max_grad_norm=1.0, use_nvlamb=False,
                 **kw):
        if amsgrad:
            raise RuntimeError("FusedLAMB does not support the AMSGrad variant.")
        defaults = dict(lr=lr, bias_correction=bias_correction, betas=betas,
                        eps=eps, weight_decay=weight_decay,
                        grad_averaging=grad_averaging,
                        max_grad_norm=max_grad_norm)
        self.adam_w_mode = adam_w_mode
        self.use_nvlamb = use_nvlamb
        super().__init__(params, defaults, set_grad_none=set_grad_none,
                         **kw)

    def _pre_update(self, flat_grads, scale):
        # Global grad norm across ALL groups (reference fused_lamb.py:122-135
        # computes l2norm of the per-list norms — same value).
        sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                 for g in flat_grads)
        return {"global_grad_norm": jnp.sqrt(sq)}

    def _update_group(self, gidx, grad, gs: GroupState, hp, lr, extras,
                      skip):
        beta1, beta2 = hp["betas"]
        table = self._tables[gidx]
        p, m, v = R.lamb_step(
            grad, gs.master, gs.slots["exp_avg"], gs.slots["exp_avg_sq"],
            table.segment_ids(), table.num_segments,
            aligned_segments=True,  # flat-store segments are 128-aligned
            lr=lr, beta1=beta1, beta2=beta2, eps=hp["eps"], step=gs.step,
            bias_correction=bool(hp["bias_correction"]),
            weight_decay=hp["weight_decay"],
            grad_averaging=bool(hp["grad_averaging"]),
            mode=R.MODE_DECOUPLED if self.adam_w_mode else R.MODE_L2,
            global_grad_norm=extras["global_grad_norm"],
            max_grad_norm=hp["max_grad_norm"],
            use_nvlamb=self.use_nvlamb, skip=skip)
        return dataclasses.replace(
            gs, master=p, slots={"exp_avg": m, "exp_avg_sq": v})
