"""ZeRO-style sharded optimizers: DistributedFusedAdam / DistributedFusedLAMB.

The reference pipeline (apex/contrib/optimizers/distributed_fused_adam.py:7,
§3.5 of SURVEY.md) keeps one flat fp16 grad buffer split into
block→chunk→shard, drives ``reduce_scatter`` / ``all_reduce`` on dedicated
process groups + CUDA streams, applies a monolithic Adam kernel to the local
fp32 (p, m, v) shard, and ``all_gather``s the new fp16 params
(distributed_fused_adam.py:319-407). ``DistributedFusedLAMB``
(distributed_fused_lamb.py:7) has the same shape plus per-tensor trust
ratios via dedicated kernels.

The TPU-native expression collapses all of the stream/process-group
machinery into three XLA collectives inside one shard_map'd train step
("weight-update sharding" — the ZeRO-on-XLA pattern):

    flat local grads [N]                          (from the local backward)
      └─ psum_scatter  → summed grad shard [N/n]  (reduce_scatter over ICI)
      └─ sharded Adam/LAMB update on (master, m, v)[N/n]
      └─ all_gather(model_dtype) → new params [N] (the fp16 allgather;
                                                   ``gather_dtype`` mirrors
                                                   the e5m2 compression knob,
                                                   distributed_fused_adam.py:50)

Overflow handling: the reference had to support *reverting* an applied step
(``maybe_adam_undo``, fused_adam_cuda.cpp:83) because its pipelined update
might land before a late overflow was discovered. Here the overflow flag is
an input to the branchless update (``found_inf`` selects old state), so no
undo path exists or is needed.

Usage (compiled through the sharding Plan layer, ``parallel/plan.py`` —
the optimizer's ``state_pspec()`` IS the plan's state sharding)::

    from apex_tpu.parallel import Plan, compile_step_with_plan

    opt = DistributedFusedAdam(params, lr=1e-3, axis_name="data",
                               num_shards=8)
    state = opt.init_state()        # full-size buffers; 1/n per device
                                    # once placed with state_pspec()

    def train_step(state, batch):             # per-device body
        grads = ...                           # local grads (pytree or
                                              # flat [N] buffer)
        new_state, params = opt.shard_step(state, grads)
        return new_state, ...

    step = compile_step_with_plan(train_step, Plan(
        mesh=mesh,
        in_specs=(opt.state_pspec(), P("data")),
        out_specs=(opt.state_pspec(), P()),
        # all_gather outputs can't be vma-proven replicated
        check_vma=False))

Checkpointing: ``opt.state_dict(state)`` is layout-independent (per-leaf
trees), so ``load_state_dict`` on an optimizer built with a DIFFERENT
``num_shards`` reshards the restore.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from apex_tpu.ops import flat as _flat
from apex_tpu.ops import reference as R

__all__ = ["DistributedFusedAdam", "DistributedFusedLAMB"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ShardedState:
    """Optimizer state over the flat buffer; shard axis 0 with P(axis) to
    get the per-device [N/n] view inside shard_map."""
    master: jax.Array
    slots: dict[str, jax.Array]
    step: jax.Array


class _DistributedBase:
    _slot_names: tuple = ()

    def __init__(self, params: Any, *, lr: float, axis_name: str = "data",
                 num_shards: int, model_dtype=jnp.bfloat16,
                 gather_dtype=None, weight_decay: float = 0.0,
                 gradient_predivide: bool = True,
                 replica_axis_name: Optional[str] = None, **hp):
        # Two-level hierarchy (the reference's ``dwu_group_size``,
        # distributed_fused_adam.py:95-98,335-341): optimizer state shards
        # over ``axis_name`` (the fast interconnect — ICI) and replicates
        # over ``replica_axis_name`` (the slow one — DCN across slices).
        # Gradients reduce_scatter within each replica group and psum
        # across groups ON THE SHARD ONLY — the cross-slice traffic is
        # 1/num_shards of the full gradient, exactly the reference's
        # "all_reduce per chunk across groups" pipeline shape. The replica
        # count is read from the mesh at trace time (lax.axis_size), so
        # the averaging cannot silently mis-scale.
        self.axis_name = axis_name
        self.replica_axis_name = replica_axis_name
        self.num_shards = int(num_shards)
        self.model_dtype = jnp.dtype(model_dtype)
        # reference: e5m2 compression of the param allgather
        # (distributed_fused_adam.py:50 dwu_e5m2_allgather); bf16 default.
        self.gather_dtype = jnp.dtype(gather_dtype) if gather_dtype \
            else self.model_dtype
        self.gradient_predivide = gradient_predivide
        self.hp = {"lr": lr, "weight_decay": weight_decay, **hp}
        # Align so every shard boundary AND every segment boundary stays
        # DEFAULT_ALIGN-aligned per shard (a multiple of n * DEFAULT_ALIGN
        # guarantees both) — _seg_l2's aligned fast path
        # (R.segment_sumsq_aligned) relies on this invariant.
        self._align = self.num_shards * _flat.DEFAULT_ALIGN
        buf, table = _flat.flatten(params, dtype=jnp.float32,
                                   align=self._align)
        pad = (-buf.size) % self._align
        if pad:  # total is a multiple of align already, but be safe
            buf = jnp.pad(buf, (0, pad))
        self.table = table
        self.total = buf.size
        self.shard_size = self.total // self.num_shards
        self._init_master = buf
        self._segment_ids = table.segment_ids()
        if self.total > self._segment_ids.size:
            self._segment_ids = jnp.pad(
                self._segment_ids, (0, self.total - self._segment_ids.size),
                constant_values=table.num_segments)

    # -- state plumbing ----------------------------------------------------
    def init_state(self) -> ShardedState:
        return ShardedState(
            master=self._init_master,
            slots={k: jnp.zeros_like(self._init_master)
                   for k in self._slot_names},
            step=jnp.asarray(0, jnp.int32))

    def state_pspec(self) -> ShardedState:
        """PartitionSpecs matching init_state() for shard_map in_specs."""
        return ShardedState(
            master=P(self.axis_name),
            slots={k: P(self.axis_name) for k in self._slot_names},
            step=P())

    def set_lr(self, lr: float):
        self.hp["lr"] = float(lr)

    # -- helpers (inside shard_map) ---------------------------------------
    def _local_ids(self):
        idx = lax.axis_index(self.axis_name)
        return lax.dynamic_slice(self._segment_ids,
                                 (idx * self.shard_size,),
                                 (self.shard_size,))

    @jax.named_scope("collective")      # prof.SCOPES: metadata only
    def _reduce_scatter(self, grads, scale):
        """grads: pytree (local, unsummed) or flat [N] buffer. Returns the
        summed-and-averaged local grad shard [N/n] in fp32 (the
        ``_pipeline_block_reductions`` reduce_scatter,
        distributed_fused_adam.py:319-341, minus the streams)."""
        if not isinstance(grads, jax.Array):
            flat = _flat.flatten(grads, table=self.table,
                                 dtype=jnp.float32)[0]
        else:
            flat = grads.astype(jnp.float32)
        if flat.size != self.total:
            flat = jnp.pad(flat, (0, self.total - flat.size))
        flat = flat * scale
        if self.gradient_predivide:
            world = self.num_shards
            if self.replica_axis_name is not None:
                world = world * lax.axis_size(self.replica_axis_name)
            flat = flat / world
        shard = lax.psum_scatter(flat, self.axis_name,
                                 scatter_dimension=0, tiled=True)
        if self.replica_axis_name is not None:
            # cross-group (DCN) reduction of the 1/n-sized shard
            shard = lax.psum(shard, self.replica_axis_name)
        return shard

    @jax.named_scope("collective")
    def _all_gather_params(self, master_shard):
        gathered = lax.all_gather(
            master_shard.astype(self.gather_dtype), self.axis_name,
            tiled=True)
        return _flat.unflatten(gathered.astype(self.model_dtype), self.table)

    def _finish(self, state, new_master, new_slots, found_inf):
        # master and slots already hold the old values where found_inf is
        # set (_update_shard's skip); only the counter is selected here
        new_step = state.step + 1
        if found_inf is not None:
            new_step = jnp.where(found_inf, state.step, new_step)
        return ShardedState(master=new_master, slots=new_slots,
                            step=new_step)

    def shard_step(self, state: ShardedState, grads, *, found_inf=None,
                   scale=1.0):
        """One sharded update. Call inside shard_map; ``state`` fields are
        the local [N/n] shards, ``grads`` the device-local grads (pytree or
        flat [N]). Returns (new_state, params_tree in model dtype)."""
        g_shard = self._reduce_scatter(grads, jnp.asarray(scale, jnp.float32))
        with jax.named_scope("optimizer"):
            new_master, new_slots = self._update_shard(state, g_shard,
                                                       found_inf)
            new_state = self._finish(state, new_master, new_slots, found_inf)
        return new_state, self._all_gather_params(new_state.master)

    def _update_shard(self, state, g_shard, skip):
        """(new master, new slots) of the local shard, updated in place;
        where the traced ``skip`` is set (None: never), the old ones
        bit-for-bit."""
        raise NotImplementedError

    # -- checkpoint --------------------------------------------------------
    def state_dict_specs(self):
        return {"hp": dict(self.hp), "total": self.total,
                "num_shards": self.num_shards}

    def state_dict_arrays(self, state: ShardedState) -> dict:
        """The device-side half of :meth:`state_dict`: the same
        layout-independent per-leaf trees, but as JAX arrays with NO
        host fetch — every unflatten is an async XLA dispatch. This is
        the async-snapshot payload (r17): hand it to
        ``runtime.SnapshotWriter.submit``, which stages device copies
        and fetches them on its background writer thread, keeping the
        ``state_dict`` sync off the step path (the
        ``snapshot-on-step-path`` lint contract)."""
        def unf(buf):
            return _flat.unflatten(buf, self.table)
        return {"format": "apex_tpu.zero_state/1",
                "master": unf(state.master),
                "slots": {k: unf(v) for k, v in state.slots.items()},
                "step": state.step,
                "hp": dict(self.hp),
                "num_shards": self.num_shards}

    def state_dict(self, state: ShardedState) -> dict:
        """Layout-independent checkpoint: master and slot buffers come
        back as per-leaf pytrees (unflattened through THIS optimizer's
        table), so a later :meth:`load_state_dict` may RESHARD — the
        flat layouts differ across shard counts (alignment is
        ``num_shards * DEFAULT_ALIGN``), the leaf values do not. Works
        on sharded state: outside shard_map the flat buffers read as
        one global array. Leaves come back as HOST numpy arrays (this
        is the serialization boundary — a later load must not inherit
        the saving mesh's device placement)."""
        import numpy as _np
        sd = self.state_dict_arrays(state)

        def conv(tree):
            return jax.tree_util.tree_map(_np.asarray, tree)
        return {**sd,
                "master": conv(sd["master"]),
                "slots": {k: conv(v) for k, v in sd["slots"].items()},
                "step": int(state.step)}

    def load_state_dict(self, sd: dict) -> ShardedState:
        """Rebuild a :class:`ShardedState` in THIS optimizer's flat
        layout from a :meth:`state_dict` saved under ANY shard count
        (the resharded-restore path the reference's rigid per-rank
        checkpoints could not do)."""
        if sd.get("format") != "apex_tpu.zero_state/1":
            raise ValueError(
                f"not a ZeRO state_dict (format={sd.get('format')!r})")
        master = _flat.flatten(sd["master"], table=self.table,
                               dtype=jnp.float32)[0]
        slots = {}
        for k in self._slot_names:
            slots[k] = _flat.flatten(sd["slots"][k], table=self.table,
                                     dtype=jnp.float32)[0]
        return ShardedState(master=master, slots=slots,
                            step=jnp.asarray(sd["step"], jnp.int32))


class DistributedFusedAdam(_DistributedBase):
    """Sharded Adam/AdamW (reference DistributedFusedAdam,
    distributed_fused_adam.py:7; v1/v2/v3 differ only in pipelining knobs
    that XLA owns here)."""

    _slot_names = ("m", "v")

    def __init__(self, params, lr=1e-3, betas=(0.9, 0.999), eps=1e-8,
                 weight_decay=0.0, adam_w_mode=True, **kw):
        super().__init__(params, lr=lr, weight_decay=weight_decay,
                         betas=tuple(betas), eps=eps,
                         adam_w_mode=bool(adam_w_mode), **kw)

    def _update_shard(self, state, g_shard, skip):
        hp = self.hp
        b1, b2 = hp["betas"]
        p, m, v = R.adam_step(
            g_shard, state.master, state.slots["m"], state.slots["v"],
            lr=jnp.asarray(hp["lr"], jnp.float32), beta1=b1, beta2=b2,
            eps=hp["eps"], step=state.step + 1,
            mode=R.MODE_DECOUPLED if hp["adam_w_mode"] else R.MODE_L2,
            weight_decay=hp["weight_decay"], skip=skip)
        return p, {"m": m, "v": v}


class DistributedFusedLAMB(_DistributedBase):
    """Sharded LAMB (reference DistributedFusedLAMB,
    distributed_fused_lamb.py:7,66 — the two-phase
    ``multi_tensor_lamb_compute_update_term`` /
    ``multi_tensor_lamb_update_weights`` pipeline). Per-tensor param/update
    norms become local segment partial sums + one psum over the shard axis
    (replacing the sharded-norm helper kernels,
    multi_tensor_distopt_lamb.cpp:29-32)."""

    _slot_names = ("m", "v")

    def __init__(self, params, lr=1e-3, betas=(0.9, 0.999), eps=1e-6,
                 weight_decay=0.01, max_grad_norm: float = 1.0,
                 use_nvlamb: bool = False, grad_averaging: bool = True,
                 bias_correction: bool = True, adam_w_mode: bool = True,
                 **kw):
        super().__init__(params, lr=lr, weight_decay=weight_decay,
                         betas=tuple(betas), eps=eps,
                         max_grad_norm=float(max_grad_norm),
                         use_nvlamb=bool(use_nvlamb),
                         grad_averaging=bool(grad_averaging),
                         bias_correction=bool(bias_correction),
                         adam_w_mode=bool(adam_w_mode), **kw)

    def _seg_l2(self, x, ids, num_seg):
        """Global per-segment L2 over the sharded flat buffer: local
        partial sq-sums + psum over the shard axis (state is replicated
        over any replica axis, so no second psum). Segments are
        (num_shards*ALIGN)-aligned, so the shard-local partials take the
        shared aligned fast path — an element-level segment_sum would be
        a serialized TPU scatter (docs/PERF.md r03)."""
        part = R.segment_sumsq_aligned(x, ids, num_seg + 1)
        return jnp.sqrt(lax.psum(part, self.axis_name))[:num_seg]

    def _update_shard(self, state, g_shard, skip):
        hp = self.hp
        b1, b2 = hp["betas"]
        num_seg = self.table.num_segments
        ids = self._local_ids()
        step = (state.step + 1).astype(jnp.float32)
        if hp["bias_correction"]:
            bc1 = 1.0 - jnp.power(jnp.asarray(b1, jnp.float32), step)
            bc2 = 1.0 - jnp.power(jnp.asarray(b2, jnp.float32), step)
        else:
            bc1 = bc2 = jnp.asarray(1.0, jnp.float32)
        beta3 = (1.0 - b1) if hp["grad_averaging"] else 1.0

        g = g_shard.astype(jnp.float32)
        p = state.master.astype(jnp.float32)
        m, v = state.slots["m"], state.slots["v"]
        wd, eps, lr = hp["weight_decay"], hp["eps"], \
            jnp.asarray(hp["lr"], jnp.float32)

        # global grad-norm clip (fused_lamb.py:122-135's three l2norm calls
        # become one local sq-sum + psum)
        gg = jnp.sqrt(lax.psum(jnp.sum(g * g), self.axis_name))
        if hp["max_grad_norm"] > 0:
            clip = jnp.where(gg > hp["max_grad_norm"],
                             gg / hp["max_grad_norm"], 1.0)
        else:
            clip = jnp.asarray(1.0, jnp.float32)

        param_norms = self._seg_l2(p, ids, num_seg)
        sg = g / clip
        if not hp["adam_w_mode"]:          # L2 mode: decay rides the grad
            sg = sg + wd * p
        m = b1 * m + beta3 * sg
        v = b2 * v + (1.0 - b2) * sg * sg
        update = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
        if hp["adam_w_mode"]:              # decoupled (AdamW) decay
            update = update + wd * p
        update_norms = self._seg_l2(update, ids, num_seg)

        if hp["use_nvlamb"] or wd != 0.0:
            ratio = jnp.where(
                jnp.logical_and(update_norms != 0.0, param_norms != 0.0),
                lr * (param_norms / update_norms), lr)
        else:
            ratio = jnp.full((num_seg,), lr, jnp.float32)
        # pad ratio for the out-of-range id used by padding elements
        ratio = jnp.concatenate([ratio, jnp.zeros((1,), jnp.float32)])
        new_p = (p - ratio[jnp.minimum(ids, num_seg)] * update).astype(
            state.master.dtype)
        return R.keep_old(skip, state.master, new_p), {
            "m": R.keep_old(skip, state.slots["m"], m),
            "v": R.keep_old(skip, state.slots["v"], v)}
