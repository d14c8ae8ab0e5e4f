"""The training step: how a step is differentiated with respect to the flat
fp32 master, once, for every program that trains.

The O2 master-weight pattern (reference ``_process_optimizer.py:321``)
with the copy fused into autodiff: the loss is differentiated with
respect to the optimizer's **flat fp32 master**, ``ops.flat.unflatten``'s
``dtype`` argument makes the half parameters in one fused convert, and
its transpose hands back **one flat fp32 gradient** (per-leaf casts and
flattens cost ~15 ms a step of per-op overhead at ResNet-50's 161
leaves). Under a :class:`~apex_tpu.parallel.DistributedDataParallel` the
master is cut where ``ddp.buckets`` says and differentiated with respect
to the buckets: each bucket's flat gradient is whole where the backward of
*its* leaves ends, so its ``psum`` runs under the backward of the layers
before them, and the pass that joins the sums into the optimizer's one
buffer divides by the world on the way. One device is one bucket, the
buffer itself: no slice, no ``psum``, no join.

:func:`build_step` is that body for a replicated fused optimizer, with an
AMP handle (dynamic loss scale, the skip inside the optimizer's kernel)
and a DDP policy as inputs, not switches: a caller that has none passes
none. :func:`build_zero_step` is the body of ZeRO's weight-update sharding
(``DistributedFusedAdam.shard_step`` behind its all-gather).
:func:`step_plan` and :func:`place_for_plan` are the
:class:`~apex_tpu.parallel.Plan` such a body compiles under
(``compile_step_with_plan``) and the placement of its arguments. The caller
chooses the optimizer and the loss; nothing here knows a model.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from apex_tpu.ops import flat as F
from apex_tpu.parallel.collectives import group_size
from apex_tpu.parallel.plan import Plan, place_with_specs
from apex_tpu.utils import ship

__all__ = ["build_step", "build_zero_step", "step_plan", "place_for_plan"]


def build_step(opt, loss_fn: Callable, *, half=None, handle=None,
               ddp=None) -> Callable:
    """The per-device body of one optimizer step of ``opt`` (a fused
    optimizer of one parameter group) on ``loss_fn(params, *batch) ->
    loss | (loss, aux)``, which sees the parameters in ``half`` (``None``:
    as the master holds them).

    Returns ``step(opt_state, amp_state, *batch) -> (opt_state, amp_state,
    loss, aux)``. ``aux`` (batch-norm state, a model's counters) passes
    through untouched, ``None`` where ``loss_fn`` gives none. With a
    ``handle`` (:class:`~apex_tpu.amp.AmpHandle`) the loss is scaled, the
    flat gradient unscaled, an overflowing step skipped inside the
    optimizer's kernel and the scaler updated; without one ``amp_state`` is
    handed through (pass ``None``). With a ``ddp`` the body runs inside
    ``shard_map`` over ``ddp.axis_name``: the gradient goes out in the
    policy's buckets, one ``psum`` each, and comes back averaged in one
    buffer (every reduction and the join under scope ``collective``); the
    loss is the devices' mean. The gradient is reduced before it is
    unscaled, so every device finds the same overflow."""
    table = opt._tables[0]
    # the flat master in buckets, runs of leaves (the DDP policy's; no
    # DDP: one bucket, the buffer itself)
    buckets = F.split_table(
        table, ddp.buckets(table.padded_sizes) if ddp is not None
        else (table.num_segments,))
    # DDP sums; the division by the world rides the join
    sums = None if ddp is None \
        else dataclasses.replace(ddp, gradient_average=False)

    def scaled_loss(masters, amp_state, batch):
        out = loss_fn(F.unflatten_split(masters, buckets, table.treedef,
                                        dtype=half), *batch)
        loss, aux = out if isinstance(out, tuple) else (out, None)
        scaled = loss if handle is None \
            else handle.scale_loss(loss, amp_state)
        return scaled, (loss, aux)

    def step(opt_state, amp_state, *batch):
        (_, (loss, aux)), fgs = jax.value_and_grad(
            scaled_loss, has_aux=True)(
                F.split(opt_state[0].master, buckets), amp_state, batch)
        if ddp is not None:
            fgs = sums.average_gradients(fgs)
            with jax.named_scope("collective"):     # prof.SCOPES
                fg = F.join(fgs, divisor=group_size(
                    ddp.axis_name, ddp.axis_index_groups)
                    if ddp.gradient_average else None)
            loss = lax.pmean(loss, ddp.axis_name)
        else:
            fg = F.join(fgs)
        if handle is None:
            return opt.apply_update(opt_state, [fg]), amp_state, loss, aux
        fg, found_inf = handle.unscale(fg, amp_state)
        return (opt.apply_update(opt_state, [fg], found_inf=found_inf),
                handle.update(amp_state, found_inf), loss, aux)

    return step


def build_zero_step(opt, loss_fn: Callable, *, half=None) -> Callable:
    """The per-device body of one step of ``opt``, a
    :class:`~apex_tpu.contrib.optimizers.DistributedFusedAdam` (ZeRO
    weight-update sharding), on ``loss_fn(params, *batch) -> loss``:
    ``step(state, *batch) -> (state, loss)`` inside ``shard_map`` over the
    optimizer's axis. The full parameters exist only transiently (the
    compressed all-gather, at ``opt.gather_dtype``); the flat gradient
    ``psum_scatter``s back to the 1/n shard inside ``shard_step``."""
    table = opt.table

    def step(state, *batch):
        with jax.named_scope("collective"):     # prof.SCOPES
            gathered = lax.all_gather(
                state.master.astype(opt.gather_dtype), opt.axis_name,
                tiled=True)
        loss, fg = jax.value_and_grad(
            lambda g: loss_fn(F.unflatten(g, table, dtype=half),
                              *batch))(gathered)
        new_state, _ = opt.shard_step(state, fg.astype(jnp.float32))
        return new_state, lax.pmean(loss, opt.axis_name)

    return step


def step_plan(mesh, state_spec: Optional[Any] = None) -> Plan:
    """The :class:`~apex_tpu.parallel.Plan` of a body ``step(state, batch)
    -> (state, out)`` with its state donated. ``state_spec`` is the
    state's layout over ``mesh`` inside ``shard_map`` (``P()`` replicated
    under DDP, ZeRO's ``opt.state_pspec()``), the batch split over the
    mesh's ``data`` axis and ``out`` replicated; ``None`` is the
    one-device program, plain jit."""
    if state_spec is None:
        return Plan(mesh=mesh, donate_argnums=(0,))
    return Plan(mesh=mesh, in_specs=(state_spec, P("data")),
                out_specs=(state_spec, P()), donate_argnums=(0,),
                # all_gather outputs aren't vma-provable replicated;
                # flash attention's pallas_call skips vma checks too
                check_vma=False)


def place_for_plan(state, batch, plan: Plan):
    """Place ``(state, batch)`` as ``plan`` declares them (ZeRO state in
    its 1/n shards, DDP state replicated, the batch split over the data
    axis), so the first call times no reshard and donation holds; a
    one-device plan gets one bulk transfer to its device."""
    if plan.in_specs is None:
        return ship((state, batch), plan.mesh.devices.flat[0])
    return place_with_specs((state, batch), plan.mesh, plan.in_specs)
