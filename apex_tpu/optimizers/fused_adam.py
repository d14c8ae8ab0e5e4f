"""FusedAdam — Adam/AdamW over flat buffers.

Drop-in analog of the reference FusedAdam (apex/optimizers/fused_adam.py:4,
89-169): one fused update per param group instead of one
``multi_tensor_adam`` launch per (group, dtype) list. ``adam_w_mode``
selects decoupled weight decay (multi_tensor_adam.cu:16-19).
"""

from __future__ import annotations

import dataclasses

from apex_tpu.optimizers.base import FusedOptimizer, GroupState
from apex_tpu.ops import kernels as R


class FusedAdam(FusedOptimizer):
    _slot_names = ("exp_avg", "exp_avg_sq")

    def __init__(self, params, lr=1e-3, bias_correction=True,
                 betas=(0.9, 0.999), eps=1e-8, adam_w_mode=True,
                 weight_decay=0.0, amsgrad=False, set_grad_none=True,
                 **kw):
        if amsgrad:
            raise RuntimeError("FusedAdam does not support the AMSGrad variant.")
        defaults = dict(lr=lr, bias_correction=bias_correction, betas=betas,
                        eps=eps, weight_decay=weight_decay)
        self.adam_w_mode = adam_w_mode
        super().__init__(params, defaults, set_grad_none=set_grad_none,
                         **kw)

    def _update_group(self, gidx, grad, gs: GroupState, hp, lr, extras,
                      skip):
        beta1, beta2 = hp["betas"]
        p, m, v = R.adam_step(
            grad, gs.master, gs.slots["exp_avg"], gs.slots["exp_avg_sq"],
            lr=lr, beta1=beta1, beta2=beta2, eps=hp["eps"], step=gs.step,
            mode=R.MODE_DECOUPLED if self.adam_w_mode else R.MODE_L2,
            bias_correction=bool(hp["bias_correction"]),
            weight_decay=hp["weight_decay"], skip=skip)
        return dataclasses.replace(
            gs, master=p, slots={"exp_avg": m, "exp_avg_sq": v})
