"""Pipeline parallelism: GPipe schedule over a ``pipe`` mesh axis.

Beyond-reference capability (the reference has none; its parallelism is
data-parallel only — SURVEY §2.3). The TPU-native design runs the classic
GPipe fill/steady/drain schedule as ONE SPMD program inside ``shard_map``:

- every rank holds its stage's layer parameters (shard the stacked layer
  pytree with ``P('pipe')`` — see :func:`stack_layers`);
- a ``lax.scan`` over ``num_microbatches + num_stages - 1`` ticks carries
  the in-flight activation; each tick computes the local stage and
  rotates activations to the next rank with a single neighbor
  ``ppermute`` (ICI traffic only);
- rank 0 injects microbatches during the fill phase, the last rank
  collects outputs during the drain phase, and a final masked ``psum``
  broadcasts the collected outputs to every rank;
- the backward pass needs no extra code: autodiff of ``ppermute`` is the
  reverse permute and of ``psum`` the identity-broadcast, so grads flow
  stage-to-stage in reverse schedule order automatically.

Differentiation contract: take gradients OUTSIDE the ``shard_map`` (wrap
the shard-mapped forward in the loss) — jax then transposes the whole
SPMD program and per-stage grads come out exact. Differentiating INSIDE
the shard_map (each rank seeding its own replica of the loss) is also
exact UNDER THE DEFAULT ``check_vma`` mode: the vma system tracks the
psum-broadcast as replicated and its transpose stays a no-op. (Under
``check_vma=False`` that transpose degenerates to another psum and every
grad comes out inflated by ``num_stages`` — one more reason this module
keeps vma checking on. Pinned by
tests/test_pipeline.py::test_gpipe_grads_inside_shard_map.)

The schedule is plain GPipe (bubble fraction (S-1)/(M+S-1)); increase
``num_microbatches`` to amortize. Composes with a ``data`` axis outside
and GSPMD tensor parallelism inside a stage.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["gpipe", "stack_layers", "unstack_layers"]


def stack_layers(layer_params: list):
    """Stack a list of per-layer param pytrees into one pytree with a
    leading ``num_layers`` axis — shard it with ``P('pipe')`` so each rank
    holds ``num_layers // num_stages`` layers."""
    return jax.tree.map(lambda *ls: jnp.stack(ls), *layer_params)


def unstack_layers(stacked):
    """Inverse of :func:`stack_layers` (host-side convenience)."""
    n = jax.tree.leaves(stacked)[0].shape[0]
    return [jax.tree.map(lambda l: l[i], stacked) for i in range(n)]


def gpipe(layer_fn: Callable, local_layers, x: jax.Array, *,
          axis_name: str, num_stages: int, num_microbatches: int):
    """Run ``x`` through all ``num_stages * layers_per_stage`` layers,
    pipelined over ``axis_name``. Call inside ``shard_map``.

    layer_fn : (layer_params, h) -> h, the single-layer apply; input and
        output must have the same shape/dtype (transformer blocks do).
    local_layers : THIS rank's stacked layer params (leading axis =
        layers_per_stage) — pass the globally-stacked tree through
        ``shard_map`` with ``in_specs=P('pipe')``.
    x : [B, ...] the full (replicated) activation batch; B must divide by
        ``num_microbatches``.

    Returns [B, ...] outputs, valid on every rank.
    """
    s = num_stages
    m = num_microbatches
    b = x.shape[0]
    axis = lax.axis_size(axis_name)
    if axis != s:
        raise ValueError(
            f"num_stages={s} != size of mesh axis {axis_name!r} ({axis}); "
            f"a smaller ring would silently skip the extra ranks' layers")
    if b % m:
        raise ValueError(f"batch {b} not divisible by microbatches {m}")
    mb = b // m
    micro = x.reshape(m, mb, *x.shape[1:])
    rank = lax.axis_index(axis_name)
    last = s - 1
    fwd_perm = [(i, (i + 1) % s) for i in range(s)]

    def stage(h):
        def one(h, lp):
            return layer_fn(lp, h), None
        h, _ = lax.scan(one, h, local_layers)
        return h

    def tick(carry, t):
        h_in, out_buf = carry
        # fill: rank 0 reads microbatch t (clamped in the drain phase,
        # where its output is ignored anyway)
        inject = micro[jnp.clip(t, 0, m - 1)]
        h = jnp.where(rank == 0, inject, h_in)
        h_out = stage(h)
        # drain: the last rank owns microbatch t-(s-1) at tick t
        idx = t - last
        is_mine = jnp.logical_and(rank == last,
                                  jnp.logical_and(idx >= 0, idx < m))
        safe = jnp.clip(idx, 0, m - 1)
        cur = lax.dynamic_index_in_dim(out_buf, safe, 0, keepdims=False)
        out_buf = lax.dynamic_update_index_in_dim(
            out_buf, jnp.where(is_mine, h_out, cur), safe, 0)
        h_next = lax.ppermute(h_out, axis_name, fwd_perm)
        return (h_next, out_buf), None

    # the tick body makes both carries rank-dependent (varying over the
    # pipe axis); mark the zero-init carries varying up front so
    # shard_map's static replication checking (check_vma) accepts the
    # scan — the final psum restores a provably-replicated output
    h0 = lax.pcast(jnp.zeros_like(micro[0]), (axis_name,), to="varying")
    out0 = lax.pcast(jnp.zeros_like(micro), (axis_name,), to="varying")
    (_, out_buf), _ = lax.scan(tick, (h0, out0), jnp.arange(m + s - 1))
    # broadcast the last rank's collected outputs to every rank
    out = lax.psum(jnp.where(rank == last, out_buf, 0.0), axis_name)
    return out.reshape(b, *x.shape[1:])
