"""FusedAdagrad (reference: apex/optimizers/fused_adagrad.py:5-121)."""

from __future__ import annotations

import dataclasses

from apex_tpu.optimizers.base import FusedOptimizer, GroupState
from apex_tpu.ops import kernels as R


class FusedAdagrad(FusedOptimizer):
    _slot_names = ("sum",)

    def __init__(self, params, lr=1e-2, eps=1e-10, weight_decay=0.0,
                 set_grad_none=True, adagrad_w_mode=False, **kw):
        defaults = dict(lr=lr, eps=eps, weight_decay=weight_decay)
        self.adagrad_w_mode = adagrad_w_mode
        super().__init__(params, defaults, set_grad_none=set_grad_none,
                         **kw)

    def _update_group(self, gidx, grad, gs: GroupState, hp, lr, extras,
                      skip):
        p, h = R.adagrad_step(
            grad, gs.master, gs.slots["sum"], lr=lr, eps=hp["eps"],
            mode=R.MODE_DECOUPLED if self.adagrad_w_mode else R.MODE_L2,
            weight_decay=hp["weight_decay"], skip=skip)
        return dataclasses.replace(gs, master=p, slots={"sum": h})
