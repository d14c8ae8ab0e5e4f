"""FusedSGD — SGD with momentum/nesterov over flat buffers.

Analog of the reference FusedSGD (apex/optimizers/fused_sgd.py:76-217).
The reference's AMP specialization — consuming fp16 model grads directly
and writing fp32 master + fp16 model weights in one N=4 kernel
(multi_tensor_sgd_kernel.cu:61-66) — maps to the ``scale`` argument of
``step`` (grad unscale folded into the update) plus ``model_dtype`` on the
base class (half copy emitted from the same jitted computation).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from apex_tpu.optimizers.base import FusedOptimizer, GroupState
from apex_tpu.ops import kernels as R


class FusedSGD(FusedOptimizer):
    _slot_names = ("momentum_buffer",)

    def __init__(self, params, lr, momentum=0.0, dampening=0.0,
                 weight_decay=0.0, nesterov=False,
                 wd_after_momentum=False, materialize_master_grads=True,
                 **kw):
        # materialize_master_grads: accepted for drop-in parity
        # (fused_sgd.py:79). The flat store ALWAYS materializes fp32
        # master grads (they are the autodiff output buffer), so the
        # False mode has no analog — accepted, semantically always True.
        if nesterov and (momentum <= 0 or dampening != 0):
            raise ValueError(
                "Nesterov momentum requires a momentum and zero dampening")
        defaults = dict(lr=lr, momentum=momentum, dampening=dampening,
                        weight_decay=weight_decay, nesterov=nesterov)
        self.wd_after_momentum = wd_after_momentum
        super().__init__(params, defaults, **kw)

    def _update_group(self, gidx, grad, gs: GroupState, hp, lr, extras,
                      skip):
        # first_run initializes momentum to the incoming grad
        # (multi_tensor_sgd_kernel.cu:113-117); step was already incremented.
        first_run = gs.step == 1
        # grad unscaling (the reference kernel's ``scale`` arg) is applied
        # uniformly by the base class before this hook.
        p, mom = R.sgd_step(
            grad, gs.master, gs.slots["momentum_buffer"],
            wd=hp["weight_decay"], momentum=hp["momentum"],
            dampening=hp["dampening"], lr=lr, nesterov=hp["nesterov"],
            first_run=first_run, wd_after_momentum=self.wd_after_momentum,
            skip=skip)
        return dataclasses.replace(gs, master=p,
                                   slots={"momentum_buffer": mom})
