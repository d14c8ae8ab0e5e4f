"""Data-parallel gradient averaging — the DistributedDataParallel equivalent.

The reference DDP (apex/parallel/distributed.py:129-639) is ~600 lines of
bucketing machinery: per-param grad hooks, arrival-order bucket discovery,
rank-0 bucket-structure broadcast, flatten -> NCCL allreduce -> unflatten on
side CUDA streams, with knobs for fp32 allreduce and gradient predivision.
Under XLA the hooks and streams are the compiler's: a collective issued
inside a jitted step is placed by its scheduler, which can only move what
the program's data flow lets it. So the one thing kept of the machinery is
the **bucket**: a step that wants its gradient reduced under the backward
cuts the flat gradient where :meth:`DistributedDataParallel.buckets` says
and reduces a tuple, one ``psum`` a bucket, each depending on its own
leaves only (``apex_tpu.train_step.build_step`` does; on the TPU
``parallel/plan.py`` tells the compiler to run them asynchronously). What
must be preserved besides is the *semantics*:

- gradients averaged over the replica axis (allreduce ∘ /world);
- ``gradient_predivide_factor`` f: grads are divided by f before the
  allreduce and by world/f after (reference distributed.py:153-155,461-466)
  — a fp16-overflow guard for large worlds;
- ``allreduce_always_fp32``: upcast before the reduce, downcast after
  (reference distributed.py:455-459);
- rank-0 parameter broadcast at wrap time (reference distributed.py:253).

Two entry points, matching the reference's two classes:

- :class:`DistributedDataParallel` — wraps a ``grad_fn`` (or transforms a
  grads pytree) for use inside ``shard_map`` over a mesh axis;
- :class:`Reducer` — the manual variant ("allreduce when I say so",
  reference distributed.py:89-127): call it on a grads pytree.

Typical use (compiled through the sharding Plan layer — the single
compile path shared with the benches, see ``parallel/plan.py``)::

    mesh = make_mesh({"data": 8})
    ddp = DistributedDataParallel(axis_name="data")

    def train_step(params, batch):              # per-device body
        grads = jax.grad(loss_fn)(params, batch)
        grads = ddp.average_gradients(grads)    # psum with predivide
        ...

    step = ddp.compile_step(train_step, mesh,
                            in_specs=(P(), P("data")), out_specs=P())
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from apex_tpu.parallel.collectives import (grouped_psum as _grouped_psum,
                                           group_size as _group_size)


@dataclasses.dataclass(frozen=True)
class Reducer:
    """Manual gradient (or buffer) allreduce-mean over a mesh axis
    (reference: apex.parallel.Reducer, distributed.py:89-127 — "intended for
    advanced users, manually call reduce() during backward").

    Must be called inside ``shard_map``/``pmap`` where ``axis_name`` is
    bound. ``axis_index_groups`` restricts the reduction to sub-groups.
    """

    axis_name: str = "data"
    axis_index_groups: Optional[tuple[tuple[int, ...], ...]] = None

    def reduce(self, tree: Any) -> Any:
        n = _group_size(self.axis_name, self.axis_index_groups)
        return jax.tree_util.tree_map(
            lambda g: _grouped_psum(g, self.axis_name,
                                    self.axis_index_groups) / n, tree)

    def __call__(self, tree: Any) -> Any:
        return self.reduce(tree)


@dataclasses.dataclass(frozen=True)
class DistributedDataParallel:
    """Gradient-averaging policy over a mesh axis (reference:
    apex.parallel.DistributedDataParallel, distributed.py:129).

    Parameters mirror the reference knobs that affect numerics, and the
    two scheduling knobs that say how the gradient is cut (:meth:`buckets`):

    message_size : the least number of elements in a bucket (reference
        distributed.py:140-142; its default, 10,000,000, kept).
    delay_allreduce : one bucket of everything, reduced after the whole
        backward (reference distributed.py:143-146).

    The other scheduling knobs (shared_param, allreduce_trigger_params,
    num_allreduce_streams, allreduce_communicators,
    retain_allreduce_buffers, gradient_average_split_factor, prof —
    distributed.py:147-175) say when and on which stream a bucket goes
    out, which here the compiler decides: accepted and ignored for drop-in
    compatibility.

    gradient_average : divide by world size (reference
        ``gradient_average=True``, distributed.py:462-466).
    allreduce_always_fp32 : upcast half grads to fp32 for the reduction
        (distributed.py:455-459).
    gradient_predivide_factor : divide grads by f before the reduce and by
        world/f after (distributed.py:153-155).
    """

    axis_name: str = "data"
    gradient_average: bool = True
    allreduce_always_fp32: bool = False
    gradient_predivide_factor: float = 1.0
    axis_index_groups: Optional[tuple[tuple[int, ...], ...]] = None
    # how the flat gradient is cut into buckets (see ``buckets``)
    message_size: int = 10_000_000
    delay_allreduce: bool = False
    # accepted-and-ignored scheduling knobs (XLA owns scheduling) — with
    # the two above the COMPLETE reference kwarg list (distributed.py:
    # 162-175) so keyword migrations are drop-in:
    shared_param: Optional[Any] = None
    allreduce_trigger_params: Optional[Any] = None
    num_allreduce_streams: int = 1
    allreduce_communicators: Optional[Any] = None
    retain_allreduce_buffers: bool = False
    gradient_average_split_factor: Optional[float] = None
    prof: bool = False

    def buckets(self, sizes: Sequence[int]) -> tuple[int, ...]:
        """How a flat gradient whose leaves hold ``sizes`` elements, in the
        flat's order, is cut for the reduction: the number of leaves in
        each bucket. Walking the leaves in that order, a bucket closes once
        it holds ``message_size`` elements (reference distributed.py:
        140-142, "minimum number of elements in a communication bucket");
        what is left at the end is the last bucket. ``delay_allreduce`` is
        one bucket of everything.

        A step that differentiates with respect to the buckets
        (``ops.flat.split_table`` / ``split`` / ``unflatten_split``) and
        hands the tuple to :meth:`average_gradients` gets one ``psum`` a
        bucket, each depending on its own leaves only: XLA can start it
        where their backward ends and run it under the backward of the
        layers before them. Nothing here knows when a gradient arrives: a
        bucket is ready when the last of its leaves is. Where the step
        wants one flat gradient again, ``gradient_average=False`` and
        ``ops.flat.join(sums, divisor=world)`` divide in the pass that
        joins, not in one of their own."""
        if self.delay_allreduce:
            return (len(sizes),)
        counts, held = [0], 0
        for size in sizes:
            if held >= self.message_size:
                counts.append(0)
                held = 0
            counts[-1] += 1
            held += size
        return tuple(counts)

    @jax.named_scope("collective")      # prof.SCOPES: metadata only
    def average_gradients(self, grads: Any) -> Any:
        """psum-average a grads pytree. Call inside shard_map/pmap."""
        world = _group_size(self.axis_name, self.axis_index_groups)
        # per-region constant, hoisted out of the per-leaf loop (an
        # axis_index trace per gradient leaf is pure jaxpr bloat); under
        # check_vma=False every leaf has an empty vma, so without this
        # guard per-shard grads would read as "already psummed" and the
        # psum below would be silently skipped (r4 session-3 bug)
        from apex_tpu.parallel.collectives import vma_tracking_active
        tracking = vma_tracking_active(self.axis_name)

        def reduce_one(g):
            dtype = g.dtype
            # getattr guard (ADVICE r4): a leaf whose type carries no vma
            # info falls back to classic semantics (assume varying -> do
            # the psum) instead of raising inside a check_vma region.
            vma = getattr(jax.typeof(g), "vma", None)
            already_summed = tracking and vma is not None \
                and self.axis_name not in vma
            if self.allreduce_always_fp32:
                g = g.astype(jnp.float32)
            if already_summed:
                if self.axis_index_groups is not None:
                    # autodiff's implicit psum ran over the FULL axis; the
                    # per-group sums are unrecoverable from it.
                    raise ValueError(
                        "average_gradients with axis_index_groups requires "
                        "device-varying gradients; this gradient was already "
                        "globally summed by autodiff against replicated "
                        "params. Keep the loss per-device (do not psum it) "
                        "or shard the params so grads stay varying.")
                # autodiff against replicated params already psummed this
                # grad (see collectives.varies_over); finish the average.
                if self.gradient_average:
                    g = g / world
                return g.astype(dtype)
            if self.gradient_predivide_factor != 1.0:
                g = g / self.gradient_predivide_factor
            g = _grouped_psum(g, self.axis_name, self.axis_index_groups)
            if self.gradient_average:
                post = world / self.gradient_predivide_factor
                g = g / post
            elif self.gradient_predivide_factor != 1.0:
                g = g * self.gradient_predivide_factor
            return g.astype(dtype)

        return jax.tree_util.tree_map(reduce_one, grads)

    def value_and_grad(self, loss_fn: Callable, **vg_kwargs) -> Callable:
        """``jax.value_and_grad`` with the DDP grad transform applied —
        the "wrap the module and backward just works" experience of the
        reference (distributed.py:319-408's hook machinery)."""
        vg = jax.value_and_grad(loss_fn, **vg_kwargs)

        def wrapped(*args, **kwargs):
            loss, grads = vg(*args, **kwargs)
            return loss, self.average_gradients(grads)

        return wrapped

    def grad(self, loss_fn: Callable, **g_kwargs) -> Callable:
        gfn = jax.grad(loss_fn, **g_kwargs)

        def wrapped(*args, **kwargs):
            return self.average_gradients(gfn(*args, **kwargs))

        return wrapped

    def compile_step(self, body: Callable, mesh: Mesh, *, in_specs,
                     out_specs, donate_argnums=(), static_argnums=(),
                     check_vma: "bool | None" = False) -> Callable:
        """Compile a DDP train-step body through the sharding Plan layer
        (:func:`apex_tpu.parallel.plan.compile_step_with_plan`) — the
        one compile path shared with ``apex_tpu.train_step``'s plans,
        replacing the per-call-site ``jit(shard_map(...))`` stanzas.

        ``body`` is a per-device function (call ``average_gradients`` /
        ``value_and_grad`` inside it); ``in_specs``/``out_specs`` are
        shard_map-style spec trees over ``mesh``.
        """
        from apex_tpu.parallel.plan import Plan, compile_step_with_plan
        plan = Plan(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                    donate_argnums=tuple(donate_argnums),
                    static_argnums=tuple(static_argnums),
                    check_vma=check_vma)
        return compile_step_with_plan(body, plan)


def broadcast_params(params: Any, mesh: Mesh) -> Any:
    """Replicate a params pytree across the mesh — the ctor-time rank-0
    broadcast (reference distributed.py:253: ``flat_dist_call(...,
    dist.broadcast)``). Under SPMD this is just placing with a fully
    replicated sharding; XLA emits the broadcast."""
    sharding = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), params)


def flat_dist_call(tree: Any, op: Callable, axis_name: str = "data") -> Any:
    """Apply a collective to every leaf (the reference's coalesced
    ``flat_dist_call``, distributed.py:70-87 — coalescing is XLA's job)."""
    return jax.tree_util.tree_map(lambda x: op(x, axis_name), tree)
