"""AMP user API: ``initialize`` / loss-scaling handle / checkpoint facade.

Functional re-design of the reference frontend (apex/amp/frontend.py:195
``initialize``, apex/amp/handle.py:17 ``scale_loss``,
frontend.py:361-400 ``state_dict``/``load_state_dict``). The reference
mutates the model and optimizer in place; here ``initialize`` returns a
wrapped apply-fn plus an ``AmpHandle`` whose device state (the loss
scalers') is an explicit pytree the user threads through the jitted train
step — which is what keeps the overflow logic on device instead of syncing
to host every iteration (reference scaler.py:200).

Typical O2 flow::

    wrapped_apply, handle = amp.initialize(apply_fn, opt_level="O2")
    amp_state = handle.init_state()

    def train_step(master_params, opt_state, amp_state, batch):
        def loss_fn(p):
            out = wrapped_apply(p, batch["x"])      # casts p/inputs per policy
            return loss(out, batch["y"])
        def scaled(p):
            return handle.scale_loss(loss_fn(p), amp_state)
        grads = jax.grad(scaled)(master_params)
        ... unscale via handle.unscale, step optimizer with found_inf ...
        amp_state = handle.update(amp_state, found_inf)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from apex_tpu.amp.autocast import autocast as _autocast_fn
from apex_tpu.amp.policy import Policy, make_policy
from apex_tpu.amp.scaler import LossScaler, ScalerState
from apex_tpu.ops import flat as _flat


def _default_bn_predicate(path) -> bool:
    """True for parameters that stay fp32 under keep_batchnorm_fp32
    (reference fp16util.convert_network skips BN modules,
    fp16util.py:60-70). Matches flax naming conventions."""
    for p in path:
        name = getattr(p, "key", getattr(p, "name", str(p)))
        low = str(name).lower()
        if "batchnorm" in low or low in ("bn", "batch_stats") or low.startswith("bn_"):
            return True
    return False


def cast_model_params(params, dtype, keep_fp32_predicate=None,
                      coalesce=None):
    """Cast float params to ``dtype``, keeping BN params fp32 when a
    predicate matches (O2's convert_network semantics).

    Cast coalescing (r06): leaves headed for ``dtype`` that share one
    source dtype are packed into ONE flat buffer, converted once, and
    sliced back out — the r03 (docs/PERF.md) one-convert pattern
    ``apex_tpu.train_step`` uses for the master buffer, applied to the O2 wrapped-apply path the
    examples run. Under jit the step carries 1 param convert instead of
    one per leaf (161 for RN50, ~9 ms/step of per-op overhead on a
    v5e). Values are bit-identical to the per-leaf cast; opt out with
    ``coalesce=False`` or ``APEX_AMP_COALESCE_CAST=0`` (the A/B arm)."""
    import os
    pred = keep_fp32_predicate
    if coalesce is None:
        coalesce = os.environ.get("APEX_AMP_COALESCE_CAST") != "0"
    dtype = jnp.dtype(dtype)

    leaves_with_path, treedef = jax.tree_util.tree_flatten_with_path(params)

    def castable(path, leaf):
        return (jnp.issubdtype(jnp.result_type(leaf), jnp.floating)
                and not (pred is not None and pred(path))
                and jnp.result_type(leaf) != dtype)

    cast_idx = [i for i, (p, l) in enumerate(leaves_with_path)
                if castable(p, l)]
    src_dtypes = {jnp.result_type(leaves_with_path[i][1]).name
                  for i in cast_idx}
    out = []
    for path, leaf in leaves_with_path:
        if not jnp.issubdtype(jnp.result_type(leaf), jnp.floating):
            out.append(leaf)
        elif pred is not None and pred(path):
            out.append(jnp.asarray(leaf, jnp.float32))
        else:
            out.append(jnp.asarray(leaf))  # cast below (or no-op)

    if coalesce and len(cast_idx) >= 2 and len(src_dtypes) == 1:
        parts = [out[i] for i in cast_idx]
        table = _flat.make_table(parts)
        buf, _ = _flat.flatten(parts, table)      # concat, no converts
        recovered = _flat.unflatten(buf, table, dtype=dtype)  # 1 convert
        for i, leaf in zip(cast_idx, recovered):
            out[i] = leaf
    else:
        for i in cast_idx:
            out[i] = out[i].astype(dtype)
    return jax.tree_util.tree_unflatten(treedef, out)


def cast_inputs(tree, dtype):
    """Cast float inputs to the model dtype (the patched-forward input cast,
    reference _initialize.py:194-201)."""
    return jax.tree_util.tree_map(
        lambda x: jnp.asarray(x).astype(dtype)
        if jnp.issubdtype(jnp.result_type(x), jnp.floating) else x, tree)


def cast_outputs_fp32(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.asarray(x).astype(jnp.float32)
        if jnp.issubdtype(jnp.result_type(x), jnp.floating) else x, tree)


@dataclasses.dataclass
class AmpHandle:
    """Per-training-run AMP configuration + scaler ops.

    Device state lives in the pytree returned by ``init_state`` (a tuple of
    ScalerState, one per loss — reference _initialize.py:227-231 creates
    ``num_losses`` LossScalers).
    """

    policy: Policy
    scalers: Sequence[LossScaler]

    # -- state ------------------------------------------------------------
    def init_state(self) -> tuple[ScalerState, ...]:
        return tuple(s.init() for s in self.scalers)

    # -- per-step ops -----------------------------------------------------
    # each under prof.SCOPES' "amp_scale" (metadata only)
    @jax.named_scope("amp_scale")
    def scale_loss(self, loss, amp_state, loss_id: int = 0):
        return self.scalers[loss_id].scale_loss(loss, amp_state[loss_id])

    @jax.named_scope("amp_scale")
    def unscale(self, flat_grads, amp_state, loss_id: int = 0):
        return self.scalers[loss_id].unscale(flat_grads, amp_state[loss_id])

    @jax.named_scope("amp_scale")
    def unscale_with_stashed(self, flat_grads, stashed, amp_state,
                             loss_id: int = 0):
        return self.scalers[loss_id].unscale_with_stashed(
            flat_grads, stashed, amp_state[loss_id])

    @jax.named_scope("amp_scale")
    def update(self, amp_state, found_inf, loss_id: int = 0):
        new = self.scalers[loss_id].update(amp_state[loss_id], found_inf)
        return tuple(new if i == loss_id else s
                     for i, s in enumerate(amp_state))

    @jax.named_scope("amp_scale")
    def update_with_census(self, amp_state, found_inf, grads, census=None,
                           loss_id: int = 0, table=None):
        """:meth:`update` plus overflow provenance (r09 numerics — see
        :meth:`apex_tpu.amp.scaler.LossScaler.update_with_census`).
        Returns ``(new_amp_state, census_carry)``."""
        new, carry = self.scalers[loss_id].update_with_census(
            amp_state[loss_id], found_inf, grads, census, table=table)
        return tuple(new if i == loss_id else s
                     for i, s in enumerate(amp_state)), carry

    def loss_scale(self, amp_state, loss_id: int = 0):
        return amp_state[loss_id].scale

    def accumulate_grads(self, loss_fn, master, microbatches, amp_state,
                         loss_id: int = 0, average: bool = True):
        """Microbatch gradient accumulation under jit (the reference's
        multi-backward pattern: each backward's scaled grads fold into
        the running buffer via ``unscale_with_stashed``, overflow checked
        per FRESH microbatch — scaler.py:152-196).

        loss_fn : (flat_master, microbatch) -> scalar loss (UNscaled;
            scaling happens here).
        microbatches : pytree whose leaves have a leading microbatch
            axis (scanned over).
        Returns (flat_grads, found_inf, mean_loss) where flat_grads is
        the mean (``average=True``, the DDP/global-batch convention) or
        sum of per-microbatch gradients, already unscaled.
        """
        n = jax.tree.leaves(microbatches)[0].shape[0]

        def body(carry, mb):
            acc, fi = carry

            def scaled(m):
                loss = loss_fn(m, mb)
                return self.scale_loss(loss, amp_state, loss_id), loss

            fg, loss = jax.grad(scaled, has_aux=True)(master)
            acc, fi_new = self.unscale_with_stashed(fg, acc, amp_state,
                                                    loss_id)
            return (acc, jnp.maximum(fi, fi_new)), loss

        acc0 = jnp.zeros_like(master)
        fi0 = jnp.zeros((), jnp.float32)
        (acc, found_inf), losses = jax.lax.scan(body, (acc0, fi0),
                                                microbatches)
        if average:
            acc = acc / n
        return acc, found_inf, jnp.mean(losses)

    # -- checkpoint facade (reference frontend.py:361-400) ----------------
    def state_dict(self, amp_state) -> dict:
        return {f"loss_scaler{i}": s.state_dict(st)
                for i, (s, st) in enumerate(zip(self.scalers, amp_state))}

    def load_state_dict(self, d: dict) -> tuple[ScalerState, ...]:
        return tuple(s.load_state_dict(d[f"loss_scaler{i}"])
                     for i, s in enumerate(self.scalers))


def _as_jnp_dtype(d):
    """Accept jnp/np dtypes, strings, and torch dtype objects (whose str
    is 'torch.float16') — migrating callers pass any of these as
    ``cast_model_type``."""
    try:
        return jnp.dtype(d)          # jnp/np dtypes, scalar types, strings
    except TypeError:
        pass
    name = str(d)                    # e.g. 'torch.float16'
    if "." in name:
        name = name.rsplit(".", 1)[-1]
    if name == "half":
        name = "float16"
    return jnp.dtype(name)


def initialize(apply_fn: Optional[Callable] = None,
               opt_level: str = "O1",
               num_losses: int = 1,
               keep_fp32_predicate: Callable | None = None,
               verbosity: int = 1,
               cast_model_outputs=None,
               min_loss_scale: Optional[float] = None,
               max_loss_scale: float = 2.0 ** 24,
               **overrides) -> tuple[Any, AmpHandle]:
    """Resolve a policy and wrap a model apply-fn for it.

    Returns ``(wrapped_apply, handle)``. ``wrapped_apply(params, *args)``
    expects *master* (fp32) params for O0/O1/O2 and casts per policy:

    - O0: everything fp32;
    - O1: per-op autocast (params stay fp32, MXU ops run half);
    - O2: params cast to half except BN, inputs cast to half, outputs fp32,
      master weights kept by the optimizer;
    - O3: like O2 but BN is half too and no master weights.

    The reference's equivalent is amp.initialize's model patching
    (_initialize.py:145-246); optimizer wiring happens in
    apex_tpu.optimizers (master weights live in the optimizer's flat fp32
    buffer, as in _process_optimizer.py:28-91).
    """
    # Reference-name kwarg translation (frontend.py:195-210) so keyword
    # call sites migrate verbatim; None means "use the preset default",
    # exactly as in the reference.
    if not overrides.pop("enabled", True):
        # enabled=False returns everything un-amp'd (frontend.py:211-216)
        # — including no output cast: the disabled run must reproduce
        # the fp32 baseline exactly
        opt_level, overrides, cast_model_outputs = "O0", {}, None
    cmt = overrides.pop("cast_model_type", None)
    if cmt is not None:
        overrides["cast_model_dtype"] = _as_jnp_dtype(cmt)
    ptf = overrides.pop("patch_torch_functions", None)
    if ptf is not None:
        # the reference knob toggles O1's function patching; the analog
        # here is the per-op autocast transform
        overrides["autocast"] = bool(ptf)
    for k in ("keep_batchnorm_fp32", "master_weights", "loss_scale"):
        # reference semantics: an explicit None means "use the opt-level
        # preset" (frontend.py:200-204 defaults them all to None) — it
        # must not reach make_policy as a falsy OVERRIDE
        if k in overrides and overrides[k] is None:
            del overrides[k]

    policy = make_policy(opt_level, **overrides)
    handle = AmpHandle(policy=policy,
                       scalers=tuple(
                           LossScaler.from_policy(
                               policy, min_loss_scale=min_loss_scale,
                               max_loss_scale=max_loss_scale)
                           for _ in range(num_losses)))

    if apply_fn is None:
        return None, handle

    if policy.autocast:  # O1
        wrapped = _autocast_fn(apply_fn, policy.compute_dtype)
    elif policy.cast_model_dtype is not None and \
            policy.cast_model_dtype != jnp.dtype(jnp.float32):  # O2/O3
        dtype = policy.cast_model_dtype
        pred = keep_fp32_predicate
        if pred is None and policy.keep_batchnorm_fp32:
            pred = _default_bn_predicate

        def wrapped(params, *args, **kwargs):
            model_p = cast_model_params(params, dtype, pred)
            out = apply_fn(model_p, *cast_inputs(args, dtype),
                           **cast_inputs(kwargs, dtype))
            return cast_outputs_fp32(out)
    else:  # O0: force fp32 params/inputs (reference frontend.py:102-111)
        def wrapped(params, *args, **kwargs):
            return apply_fn(cast_model_params(params, jnp.float32),
                            *cast_inputs(args, jnp.float32),
                            **cast_inputs(kwargs, jnp.float32))

    if cast_model_outputs is not None:
        # reference: casts every float model output to this dtype
        # (_initialize.py:252-256, applied after the per-level wrapper)
        _inner, _odt = wrapped, _as_jnp_dtype(cast_model_outputs)

        def wrapped(params, *args, **kwargs):  # noqa: F811
            return cast_inputs(_inner(params, *args, **kwargs), _odt)

    if verbosity > 0:
        p = policy
        print(f"apex_tpu.amp: opt_level={p.opt_level}, "
              f"half_dtype={jnp.dtype(p.half_dtype).name}, "
              f"autocast={p.autocast}, cast_model_dtype={p.cast_model_dtype}, "
              f"keep_batchnorm_fp32={p.keep_batchnorm_fp32}, "
              f"master_weights={p.master_weights}, loss_scale={p.loss_scale}")
    return wrapped, handle


def master_params(optimizer):
    """Iterate fp32 master params from an apex_tpu optimizer (reference:
    _amp_state.master_params, _amp_state.py:59-68)."""
    tree = optimizer.master_params_tree()
    yield from jax.tree_util.tree_leaves(tree)
