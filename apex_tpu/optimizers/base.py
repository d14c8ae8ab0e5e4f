"""Fused-optimizer base: a mutable, param-group facade over functional state.

The reference optimizers are ``torch.optim.Optimizer`` subclasses with
mutable param groups and lazily allocated per-param state
(reference: apex/optimizers/fused_adam.py:89-169). On a functional core the
same API shape is a thin stateful wrapper:

- construction flattens each param group into the flat-buffer data model
  (one fp32 master buffer + one SegmentTable per group — replacing the
  per-dtype tensor lists apex builds every step, fused_adam.py:110-140);
- ``step(grads, ...)`` runs ONE jitted update over the flat buffers,
  with AMP integration as explicit arguments: ``scale`` folds grad
  unscaling into the kernel (the FusedSGD ``scale`` arg,
  multi_tensor_sgd_kernel.cu:86), ``found_inf`` selects old-vs-new state
  branchlessly inside the step kernel (replacing amp.handle's "patch step
  into a no-op once" trick, apex/amp/handle.py:128-154);
- the step kernels update master and slots IN PLACE: a caller that donates
  its state (``step`` does; a jitted ``apply_update`` should) pays no copy,
  one that keeps the old state gets XLA's protective copy of it;
- hyperparameters that schedules mutate (lr) are traced scalars, so
  ``set_lr`` never retriggers compilation;
- ``state_dict``/``load_state_dict`` round-trip everything, including the
  step count (reference fused optimizers store ``step`` in group/state).

The functional core is exposed too (``init_state`` / ``apply_update``) for
users who keep optimizer state in their own train-state pytree.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.ops import flat as _flat


def _canon_hp(hp: dict) -> dict:
    """Canonicalize sequence hyperparams (betas, ...) to TUPLES at every
    entry point (ctor defaults/groups, add_param_group, load_state_dict).
    One invariant, three reasons: a caller-passed list (torch accepts
    ``betas=[0.9, 0.999]``) or a checkpoint-codec-rebuilt list
    (utils/checkpoint._set_deep emits lists for indexed sequences) would
    (a) make state_dict() trees differ structurally before vs after a
    restore (jax.tree.map then fails on the tuple-vs-list treedef), and
    (b) change the repr-based hyperparam cache key, silently retracing
    the jitted step."""
    return {k: tuple(v) if isinstance(v, list) else v
            for k, v in hp.items()}


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class GroupState:
    """Device state for one param group: flat master params + optimizer
    slots (contents depend on the optimizer) + step count."""
    master: jax.Array
    slots: dict[str, jax.Array]
    step: jax.Array  # i32 scalar


OptimizerState = tuple  # tuple[GroupState, ...]


class FusedOptimizer:
    """Base class; subclasses define ``_slot_names`` and ``_update_group``.

    Parameters
    ----------
    params : pytree | list[dict]
        A pytree of parameters (single group) or apex-style group dicts
        ``{"params": pytree, **per_group_hyperparams}``.
    model_dtype : optional dtype
        When set (O2-style), ``step`` also returns the params cast to this
        dtype in the same fused computation — the reference's "write an fp16
        model copy from the same kernel" trick
        (multi_tensor_sgd_kernel.cu:61-66,126-130).
    """

    _slot_names: Sequence[str] = ()

    def __init__(self, params, defaults: dict, *, model_dtype=None,
                 master_dtype=jnp.float32, align: int = 128,
                 set_grad_none: bool = True):
        # set_grad_none: accepted for drop-in parity with every reference
        # fused optimizer (e.g. fused_adam.py:64). In torch it controls
        # whether zero_grad() writes None into param.grad; grads here are
        # functional VALUES passed to step(), so there is nothing to
        # clear — stored, never read.
        self.set_grad_none = bool(set_grad_none)
        if isinstance(params, (list, tuple)) and params and \
                isinstance(params[0], dict):
            groups = [dict(g) for g in params]
        else:
            groups = [{"params": params}]
        self.defaults = _canon_hp(dict(defaults))
        self.model_dtype = None if model_dtype is None else jnp.dtype(model_dtype)
        self.master_dtype = jnp.dtype(master_dtype)
        self._align = align
        self.param_groups: list[dict] = []
        self._tables: list[_flat.SegmentTable] = []
        states = []
        for g in groups:
            tree = g.pop("params")
            hp = _canon_hp({**self.defaults, **g})
            buf, table = _flat.flatten(tree, dtype=self.master_dtype,
                                       align=align)
            self._tables.append(table)
            self.param_groups.append(hp)
            states.append(self._init_group(buf, table))
        self.state: OptimizerState = tuple(states)
        # hp_key is a static arg so mutating hyperparams (other than lr,
        # which is traced) correctly retriggers compilation.
        self._jit_step = jax.jit(self._step_impl, donate_argnums=(0,),
                                 static_argnums=(5,))

    # -- functional core ---------------------------------------------------
    def _init_group(self, buf: jax.Array, table: _flat.SegmentTable) -> GroupState:
        slots = {name: jnp.zeros_like(buf) for name in self._slot_names}
        return GroupState(master=buf, slots=slots,
                          step=jnp.asarray(0, jnp.int32))

    def _update_group(self, gidx: int, grad: jax.Array, gs: GroupState,
                      hp: dict, lr, extras: dict, skip) -> GroupState:
        """One group's update. ``skip`` (None, or the traced ``found_inf``)
        goes to the ``ops.kernels`` step as ``skip=``: master and slots come
        back bit-for-bit when it is set."""
        raise NotImplementedError

    def _pre_update(self, flat_grads: list[jax.Array], scale) -> dict:
        """Hook computing cross-group quantities (LAMB's global grad norm,
        reference fused_lamb.py:122-135). Returns extras passed to every
        group update."""
        return {}

    def _hp_key(self):
        # The backend is part of the key so tests that flip
        # reference<->pallas via dispatch.backend() retrace correctly.
        from apex_tpu.ops import dispatch
        return (dispatch.use_pallas(),) + tuple(
            tuple(sorted((k, repr(v)) for k, v in hp.items() if k != "lr"))
            for hp in self.param_groups)

    @jax.named_scope("optimizer")       # prof.SCOPES: metadata only
    def _step_impl(self, state: OptimizerState, flat_grads: list[jax.Array],
                   lrs: list[jax.Array], found_inf, scale, hp_key=None):
        # Fold AMP grad-unscaling into the update for every optimizer (the
        # reference only FusedSGD had this; here it is uniform). Scaling
        # before _pre_update keeps LAMB/NovoGrad norms in unscaled units.
        flat_grads = [(g.astype(jnp.float32) * scale).astype(g.dtype)
                      for g in flat_grads]
        extras = self._pre_update(flat_grads, scale)
        new_states = []
        for i, (gs, g) in enumerate(zip(state, flat_grads)):
            hp = self.param_groups[i]
            new_gs = self._update_group(i, g, dataclasses.replace(
                gs, step=gs.step + 1), hp, lrs[i], extras, found_inf)
            if found_inf is not None:
                # Branchless step-skip: on overflow the step kernel wrote
                # back the state it read (a select out here, over whole
                # buffers, would keep the old state alive beside the new
                # and cost a copy of each); the counter does not advance.
                new_gs = dataclasses.replace(new_gs, step=jnp.where(
                    found_inf, gs.step, new_gs.step))
            new_states.append(new_gs)
        return tuple(new_states)

    def init_state(self) -> OptimizerState:
        """A fresh copy of the current optimizer state for functional callers.

        Copied, not aliased: functional callers routinely donate this tree
        into their own jitted steps (which DELETES the donated buffers), and
        the stateful ``step()`` facade donates ``self.state`` the same way —
        either one invalidating the other's arrays is a crash at a distance.
        """
        return jax.tree.map(jnp.copy, self.state)

    def apply_update(self, state: OptimizerState,
                     flat_grads: list[jax.Array], *, found_inf=None,
                     scale=1.0) -> OptimizerState:
        """Pure functional update for callers managing their own state."""
        lrs = [jnp.asarray(hp.get("lr", self.defaults.get("lr", 1e-3)),
                           jnp.float32) for hp in self.param_groups]
        return self._step_impl(state, flat_grads, lrs, found_inf,
                               jnp.asarray(scale, jnp.float32))

    # -- stateful facade ---------------------------------------------------
    def flatten_grads(self, grads) -> list[jax.Array]:
        """grads: a pytree matching construction (single group), or — with
        multiple groups — a list of per-group pytrees. The group count
        disambiguates; array shapes are never inspected."""
        if len(self._tables) == 1:
            trees = [grads]
        else:
            if not isinstance(grads, (list, tuple)) or \
                    len(grads) != len(self._tables):
                raise ValueError(
                    f"optimizer has {len(self._tables)} param groups; pass a "
                    f"list of {len(self._tables)} grad pytrees")
            trees = list(grads)
        return [_flat.flatten(t, table=tab, dtype=self.master_dtype)[0]
                for t, tab in zip(trees, self._tables)]

    def step(self, grads, *, found_inf=None, scale=1.0):
        """Apply one update from a grads pytree (or list of per-group
        pytrees). Returns the new params (see ``params_tree``)."""
        return self.step_flat(self.flatten_grads(grads),
                              found_inf=found_inf, scale=scale)

    def step_flat(self, flat_grads: list[jax.Array], *, found_inf=None,
                  scale=1.0):
        """Apply one update from pre-flattened per-group grad buffers."""
        lrs = [jnp.asarray(hp.get("lr", self.defaults.get("lr", 1e-3)),
                           jnp.float32) for hp in self.param_groups]
        fi = None if found_inf is None else jnp.asarray(found_inf)
        self.state = self._jit_step(self.state, list(flat_grads), lrs, fi,
                                    jnp.asarray(scale, jnp.float32),
                                    self._hp_key())
        return self.params_tree()

    # -- views -------------------------------------------------------------
    def _trees(self, dtype=None):
        outs = []
        for gs, tab in zip(self.state, self._tables):
            outs.append(_flat.unflatten(gs.master, tab, dtype=dtype))
        return outs

    def params_tree(self):
        """Current params in model dtype (half under O2/O3, else master)."""
        trees = self._trees(dtype=self.model_dtype)
        return trees[0] if len(trees) == 1 else trees

    def master_params_tree(self):
        """fp32 master params (reference: amp.master_params,
        _amp_state.py:59-68)."""
        trees = self._trees(dtype=None)
        return trees[0] if len(trees) == 1 else trees

    def set_lr(self, lr: float, group: Optional[int] = None):
        """LR schedules mutate group['lr'] in the reference; traced here, so
        this is recompile-free."""
        if group is None:
            for hp in self.param_groups:
                hp["lr"] = float(lr)
        else:
            self.param_groups[group]["lr"] = float(lr)

    def add_param_group(self, group: dict):
        """Append a param group (reference _process_optimizer.py:411-487
        patches this for AMP; here it just extends the state tuple)."""
        g = dict(group)
        tree = g.pop("params")
        hp = _canon_hp({**self.defaults, **g})
        buf, table = _flat.flatten(tree, dtype=self.master_dtype,
                                   align=self._align)
        self._tables.append(table)
        self.param_groups.append(hp)
        self.state = (*self.state, self._init_group(buf, table))

    # -- checkpointing -----------------------------------------------------
    def state_dict(self) -> dict:
        out = {"param_groups": [dict(hp) for hp in self.param_groups],
               "groups": []}
        for gs in self.state:
            out["groups"].append({
                "master": np.asarray(gs.master),
                "slots": {k: np.asarray(v) for k, v in gs.slots.items()},
                "step": int(gs.step),
            })
        return out

    def load_state_dict(self, d: dict):
        self.param_groups = [_canon_hp(dict(hp))
                             for hp in d["param_groups"]]
        states = []
        for gs in d["groups"]:
            states.append(GroupState(
                master=jnp.asarray(gs["master"]),
                slots={k: jnp.asarray(v) for k, v in gs["slots"].items()},
                step=jnp.asarray(gs["step"], jnp.int32)))
        self.state = tuple(states)

    def zero_grad(self):
        """No-op provided for API familiarity: grads are function outputs in
        JAX, not buffers to clear (reference patches zero_grad to also clear
        master grads, _process_optimizer.py:366-382)."""
