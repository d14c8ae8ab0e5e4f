"""The dense-LM training step the benchmark's drivers and ``chip_smoke.py``
build: the choice of optimizer and loss around the package's step builder
(``apex_tpu.train_step``). ``benchmarks/drivers/train_lm.py`` and
``train_hybrid_lm.py`` import :func:`build_train_step` and
:func:`place_for_plan` from here (ROADMAP D1b moves the choice into the
drivers); measure with ``python3 benchmarks/run.py --workload <cell>``.
"""

from __future__ import annotations

import os
import sys

# repo root importable without PYTHONPATH
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from apex_tpu.train_step import place_for_plan  # noqa: E402,F401


def build_train_step(lm, params, mesh, *, half, zero=False, lr=1e-4):
    """FusedAdam over flat fp32 masters on ``lm.loss``, parameters in
    ``half``: replicated, under DDP's buckets over a >1-device ``mesh``,
    or with ``zero`` the DistributedFusedAdam 1/n shards. Call under
    ``host_init()``: the optimizers flatten real arrays.

    Returns ``(opt, state, step, plan)``; ``step(state, toks) ->
    (state, loss)`` (``(state, (loss, counters))`` for a model that has
    ``loss_with_counters``) is the body ``compile_step_with_plan(body,
    plan)`` lowers (a 1-device plan is plain jit — the single-chip
    program), and :func:`place_for_plan` puts ``(state, toks)`` where it
    wants them. A model with state that no gradient reaches (``HybridLM``'s
    sigmoid router: ``router_state()`` is not ``None``) has it beside the
    optimizer's: ``state`` is ``(opt_state, router_bias)``, and the bias
    travels through ``build_step`` as a ResNet's batch statistics do, in
    with the batch and out in ``aux``."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu import train_step as T
    from apex_tpu.contrib.optimizers import DistributedFusedAdam
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import DistributedDataParallel

    n_dev = mesh.size
    # a model with counters of its own (HybridLM: pairs past the dispatch
    # bound, expert load) hands them out beside the loss: the step then
    # returns (state, (loss, counters)). On one chip only: across chips
    # each counter would need its own reduction (a sum, a maximum)
    counted = getattr(lm, "loss_with_counters", None)
    if counted is not None and (zero or n_dev > 1):
        raise NotImplementedError(
            f"{type(lm).__name__} hands counters out of its step, which "
            "only the one-chip FusedAdam step carries")
    if zero:
        opt = DistributedFusedAdam(
            params, lr=lr, axis_name="data", num_shards=n_dev,
            model_dtype=half or jnp.float32)
        return opt, opt.init_state(), \
            T.build_zero_step(opt, lm.loss, half=half), \
            T.step_plan(mesh, opt.state_pspec())
    opt = FusedAdam(params, lr=lr)
    router_bias = lm.router_state() if counted else None
    body = T.build_step(
        opt, (counted or lm.loss) if router_bias is None
        else lm.loss_with_router_state, half=half,
        ddp=DistributedDataParallel(axis_name="data") if n_dev > 1
        else None)

    def step(state, toks):
        state, _, loss, counters = body(state, None, toks)
        return state, (loss, counters) if counted else loss

    def step_with_router_state(state, toks):
        state, bias = state
        state, _, loss, (bias, counters) = body(state, None, bias, toks)
        return (state, bias), (loss, counters)

    plan = T.step_plan(mesh, P() if n_dev > 1 else None)
    # the constructor's own state goes to the caller, not a copy of it
    # beside it: weights + state + copy are 28 B a parameter at once, which
    # a model sized to the chip's memory does not have (ROADMAP S12)
    state, opt.state = opt.state, ()
    if router_bias is None:
        return opt, state, step, plan
    return opt, (state, router_bias), step_with_router_state, plan
