"""Single-query slot attention: the serve decode step's attention core.

``slot_decode_attention`` answers the continuous-batching engine's
per-step question — one query per slot against the slot's lanes of the
``[slots, H, max_len, hd]`` arena, masked to the slot's current length
— through the same two-tier shape as ``flash_attention``:

- ``reference_slot_decode_attention``: the lax/jnp twin, op-for-op the
  math ``reference_attention`` runs on the chunked prefill path (same
  finite ``NEG_INF`` masking, same max/exp/sum/divide sequence, fp32
  scores), so the fused decode step is bit-comparable with the
  per-slot vmapped ``_decode_one`` path it replaces. This is the only
  path tier-1/CPU ever executes.
- ``ops.pallas.decode_attn.decode_attention``: the fused kernel —
  scale -> mask -> softmax -> PV with K/V VMEM-resident, no
  ``[S, H, 1, L]`` score temporaries in HBM (arXiv 2502.17728's decode
  fusion applied to the slot arena).

Dispatch mirrors the flash crossover: ``impl='auto'`` routes to the
kernel only on TPU (``ops.dispatch``), only for supported shapes
(lanes-aligned head_dim), and only past a minimum arena length —
resolution ``APEX_DECODE_MIN_L`` env > :data:`DEFAULT_DECODE_MIN_L`.
The default is conservative and was never swept on a chip (decode is
memory-bound; the kernel's win is avoiding score-temporary traffic,
which only matters once L is large).
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.contrib.multihead_attn.flash_attention import NEG_INF
from apex_tpu.ops import dispatch

__all__ = ["slot_decode_attention", "reference_slot_decode_attention",
           "gather_pages", "decode_min_l", "DEFAULT_DECODE_MIN_L"]

_IMPLS = ("auto", "reference", "pallas")

# Smallest arena max_len 'auto' sends to the Pallas kernel: past the
# CPU-smoke shapes and below the long-context pools where
# score-temporary HBM traffic dominates the step. Not yet swept on a
# chip; the sweep that would justify another number is a later PR.
DEFAULT_DECODE_MIN_L = 1024


def decode_min_l() -> int:
    """APEX_DECODE_MIN_L env > DEFAULT_DECODE_MIN_L (read at trace
    time, same as flash_min_s)."""
    env = os.environ.get("APEX_DECODE_MIN_L")
    return int(env) if env else DEFAULT_DECODE_MIN_L


def gather_pages(pool: jax.Array, page_table: jax.Array) -> jax.Array:
    """Reconstruct per-slot logical K or V views from a page pool:
    pool [P_phys, H, page, hd] + page_table i32 [S, P] -> [S, H,
    P*page, hd]. Logical page i of slot s is pool[page_table[s, i]];
    unmapped entries point at the null page (0), whose garbage sits
    past every slot's length and is masked exactly like the dense
    arena's unwritten tail. This ONE gather is the entire layout
    difference between paged and dense attention — everything after
    it is byte-identical math, which is what makes paged greedy
    streams bit-equal to the dense baseline."""
    s, p = page_table.shape
    _, h, page, hd = pool.shape
    lanes = pool[page_table]                      # [S, P, H, page, hd]
    return jnp.moveaxis(lanes, 2, 1).reshape(s, h, p * page, hd)


def reference_slot_decode_attention(q, k, v, lengths, *,
                                    scale: Optional[float] = None,
                                    page_table=None):
    """Unfused lax twin: q [S, H, hd], k/v [S, H, L, hd], lengths i32
    [S]. Bit-identical math to ``reference_attention(causal=True,
    q_start=pos)`` vmapped over slots with one query row (the mask
    ``k_pos < length`` IS ``q_pos >= k_pos`` at q_pos = length - 1) —
    the parity basis the serve tests pin.

    ``page_table`` (r20, i32 [S, P]): k/v are PAGE POOLS
    ``[P_phys, H, page, hd]`` and each slot's logical view is gathered
    by page indices first (:func:`gather_pages`); the math after the
    gather is the same ops in the same order, so paged output is
    bit-equal to dense output whenever the mapped pages carry the same
    bytes.

    ``q`` may instead be ``[S, Q, H, hd]`` with ``lengths`` i32
    ``[S, Q]`` (r21 speculative scoring): Q query rows per slot, row j
    masked to its OWN length — the same op sequence run once with a
    real query axis, so each row's output matches the 1-query call at
    that row's position. Returns ``[S, Q, H, hd]``."""
    multi = q.ndim == 4
    if page_table is not None:
        k = gather_pages(k, page_table)
        v = gather_pages(v, page_table)
    hd = q.shape[-1]
    l_dim = k.shape[-2]
    if scale is None:
        scale = 1.0 / float(hd) ** 0.5
    if multi:
        qf = q.transpose(0, 2, 1, 3).astype(jnp.float32)  # [S, H, Q, hd]
        lmask = lengths[:, None, :, None]
    else:
        qf = q[:, :, None, :].astype(jnp.float32)         # [S, H, 1, hd]
        lmask = lengths[:, None, None, None]
    s = jnp.einsum("...qd,...kd->...qk", qf,
                   k.astype(jnp.float32)) * scale         # [S, H, Q, L]
    k_pos = jnp.arange(l_dim)[None, None, None, :]
    s = jnp.where(k_pos < lmask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.maximum(m, NEG_INF)
    p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - m), 0.0)
    l_sum = jnp.sum(p, axis=-1, keepdims=True)
    probs = p / jnp.where(l_sum > 0.0, l_sum, 1.0)
    o = jnp.einsum("...qk,...kd->...qd", probs,
                   v.astype(jnp.float32)).astype(q.dtype)
    if multi:
        return o.transpose(0, 2, 1, 3)                    # [S, Q, H, hd]
    return o[:, :, 0, :]                                  # [S, H, hd]


def _pallas_impl(q, k, v, lengths, *, scale=None):
    from apex_tpu.ops.pallas.decode_attn import decode_attention
    return decode_attention(q, k, v, lengths, scale=scale)


def slot_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          lengths: jax.Array, *,
                          scale: Optional[float] = None,
                          impl: str = "auto",
                          page_table=None) -> jax.Array:
    """Single-query attention over the slot arena, crossover-dispatched.

    q: [S, H, hd] (this decode step's query per slot); k/v: [S, H, L,
    hd] (the pool arena — positions past each slot's length may hold
    garbage and are masked); lengths: i32 [S] valid prefix per slot.
    Returns [S, H, hd] in q's dtype.

    ``page_table`` (r20, i32 [S, P]): the PAGED arena — k/v are page
    pools ``[P_phys, H, page, hd]`` and each slot's K/V is gathered by
    its page indices. The reference twin gathers then runs identical
    math (bit-comparable with the dense layout); the Pallas kernel
    never materializes the gather — the page map rides scalar prefetch
    and drives the K/V block selection directly (one page per grid
    step, flash-style accumulation).

    ``impl``: 'auto' (kernel on TPU for supported shapes past
    :func:`decode_min_l`, reference otherwise), or force 'reference' /
    'pallas' (the bitwise cross-check axis — 'pallas' off-TPU runs the
    interpreter).

    ``q`` may be ``[S, Q, H, hd]`` with ``lengths`` ``[S, Q]`` (r21
    speculative scoring — Q query rows per slot, per-row masking;
    returns ``[S, Q, H, hd]``). The reference twin handles the query
    axis natively; the Pallas kernels see the rows FLATTENED into the
    slot axis (their grid is one (slot, head) row per step, so Q rows
    are just S*Q slots — the paged kernel's page map is row-repeated,
    the dense kernel's K/V broadcast per row), no new kernel needed."""
    if impl not in _IMPLS:
        raise ValueError(f"impl must be one of {_IMPLS}, got {impl!r}")
    multi = q.ndim == 4
    if page_table is not None:
        from apex_tpu.ops.pallas.decode_attn import (
            paged_decode_attention, paged_supported)
        page = k.shape[-2]
        l_dim = page_table.shape[1] * page
        ok = paged_supported(page, q.shape[-1])
        if impl == "pallas":
            if not ok:
                raise ValueError(
                    f"impl='pallas' forced on unsupported paged shapes "
                    f"(page_size={page}, head_dim={q.shape[-1]})")
            fn = paged_decode_attention
        elif impl == "reference" or not ok:
            fn = reference_slot_decode_attention
        else:
            fn = dispatch.resolve_crossover(
                reference_slot_decode_attention, paged_decode_attention,
                l_dim, decode_min_l())
        if multi and fn is not reference_slot_decode_attention:
            sd, qd = q.shape[0], q.shape[1]
            o = fn(q.reshape(sd * qd, *q.shape[2:]), k, v,
                   lengths.reshape(sd * qd), scale=scale,
                   page_table=jnp.repeat(page_table, qd, axis=0))
            return o.reshape(sd, qd, *o.shape[1:])
        return fn(q, k, v, lengths, scale=scale,
                  page_table=page_table)
    from apex_tpu.ops.pallas.decode_attn import supported
    l_dim = k.shape[-2]
    ok = supported(l_dim, q.shape[-1])
    if impl == "pallas":
        if not ok:
            raise ValueError(
                f"impl='pallas' forced on unsupported shapes "
                f"(max_len={l_dim}, head_dim={q.shape[-1]})")
        fn = _pallas_impl
    elif impl == "reference" or not ok:
        fn = reference_slot_decode_attention
    else:
        fn = dispatch.resolve_crossover(
            reference_slot_decode_attention, _pallas_impl,
            l_dim, decode_min_l())
    if multi and fn is not reference_slot_decode_attention:
        sd, qd = q.shape[0], q.shape[1]
        rep = (sd * qd,) + k.shape[1:]
        kr = jnp.broadcast_to(k[:, None], (sd, qd) + k.shape[1:]) \
            .reshape(rep)
        vr = jnp.broadcast_to(v[:, None], (sd, qd) + v.shape[1:]) \
            .reshape(rep)
        o = fn(q.reshape(sd * qd, *q.shape[2:]), kr, vr,
               lengths.reshape(sd * qd), scale=scale)
        return o.reshape(sd, qd, *o.shape[1:])
    return fn(q, k, v, lengths, scale=scale)
