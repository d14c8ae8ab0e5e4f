"""Pallas single-query slot-attention kernel (the serve decode step).

The serving engine's decode step asks one question per slot: attend ONE
query (this step's token) against the slot's lanes of the preallocated
``[slots, H, max_len, hd]`` K/V arena, masked to the slot's current
length. Unfused, that is a scale -> mask -> softmax -> PV chain whose
``[S, H, 1, L]`` score/prob temporaries round-trip HBM between ops —
pure memory traffic on a step that is already memory-bound (arXiv
2502.17728's fusion argument, applied to the decode hot path the same
way the flash kernel fuses the training-side attention).

This kernel runs the whole chain for one (slot, head) pair per grid
step with the K/V block resident in VMEM: scores as a lane-reduction of
``q * k``, the masked softmax along sublanes (the L axis), and the PV
contraction as a sublane reduction — VPU-only by design; with a single
query row there is no MXU-shaped matmul worth forcing, the win is not
re-streaming K/V and never materializing scores off-chip. Per-slot
lengths arrive via scalar prefetch; positions past a slot's length are
masked exactly like ``reference_attention``'s causal ``q_start`` rule
(score = NEG_INF before the max/exp), so the not-yet-written arena tail
is unreachable. All score math fp32 regardless of arena dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.ops.pallas._common import LANES, interpret_mode as _interpret

# the flash kernel's finite -inf stand-in (exp() of it is exactly 0.0
# in fp32); shared so masked-lane math is bit-identical across kernels
NEG_INF = -1.0e30


def supported(max_len: int, head_dim: int) -> bool:
    """Shapes the kernel handles: lanes-aligned head_dim and a
    sublane-aligned arena length (the pool preallocates max_len, so in
    practice this is a constructor-time property, not per-call)."""
    return head_dim % LANES == 0 and max_len % 8 == 0 and max_len > 0


def paged_supported(page_size: int, head_dim: int) -> bool:
    """Shapes the PAGED kernel handles: lanes-aligned head_dim and a
    sublane-aligned page (also a constructor-time property — the pool
    fixes page_size)."""
    return head_dim % LANES == 0 and page_size % 8 == 0 and page_size > 0


def _decode_kernel(scale: float, len_ref, q_ref, k_ref, v_ref, o_ref):
    """One (slot, head) pair per grid step. q: [1, hd]; k/v: [L, hd]
    VMEM-resident; len_ref: prefetched i32 [S] slot lengths."""
    slot = pl.program_id(0)
    n = len_ref[slot]
    qf = q_ref[0].astype(jnp.float32)                     # [1, hd]
    kf = k_ref[0].astype(jnp.float32)                     # [L, hd]
    l_dim = kf.shape[0]
    # scores: lane-reduce q*k -> [L, 1]; mask the unwritten tail with
    # the same finite NEG_INF + where() sequence as reference_attention
    s = jnp.sum(kf * qf, axis=1, keepdims=True) * scale   # [L, 1]
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (l_dim, 1), 0)
    s = jnp.where(k_pos < n, s, NEG_INF)
    m = jnp.maximum(jnp.max(s, axis=0, keepdims=True), NEG_INF)
    p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - m), 0.0)  # [L, 1]
    l_sum = jnp.sum(p, axis=0, keepdims=True)
    probs = p / jnp.where(l_sum > 0.0, l_sum, 1.0)
    vf = v_ref[0].astype(jnp.float32)                     # [L, hd]
    o = jnp.sum(probs * vf, axis=0, keepdims=True)        # [1, hd]
    o_ref[0] = o.astype(o_ref.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array, *,
                     scale: float | None = None) -> jax.Array:
    """Fused single-query attention over the slot arena.

    q: [S, H, hd] (one query per slot); k/v: [S, H, L, hd] (the pool
    arena, possibly garbage past each slot's length); lengths: i32 [S]
    valid K/V prefix per slot. Returns [S, H, hd] in q's dtype. Shapes
    must pass :func:`supported` — the dispatch layer
    (``contrib.multihead_attn.decode_attention``) guards that and falls
    back to the lax reference, so callers never see a shape error."""
    from jax.experimental.pallas import tpu as pltpu

    s_dim, h, hd = q.shape
    l_dim = k.shape[2]
    if not supported(l_dim, hd):
        raise ValueError(
            f"decode_attention kernel needs head_dim % {LANES} == 0 and "
            f"max_len % 8 == 0, got head_dim={hd}, max_len={l_dim} — "
            f"route through contrib.multihead_attn.slot_decode_attention")
    if scale is None:
        scale = 1.0 / float(hd) ** 0.5
    q2 = q.reshape(s_dim * h, 1, hd)
    k2 = k.reshape(s_dim * h, l_dim, hd)
    v2 = v.reshape(s_dim * h, l_dim, hd)
    # one length per (slot, head) pair so the kernel indexes by its own
    # grid step (scalar prefetch: available before the body runs)
    lens = jnp.repeat(lengths.astype(jnp.int32), h)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s_dim * h,),
        in_specs=[
            pl.BlockSpec((1, 1, hd), lambda i, lens: (i, 0, 0)),
            pl.BlockSpec((1, l_dim, hd), lambda i, lens: (i, 0, 0)),
            pl.BlockSpec((1, l_dim, hd), lambda i, lens: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, hd), lambda i, lens: (i, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, float(scale)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_dim * h, 1, hd), q.dtype),
        interpret=_interpret(),
        name="apex_decode_dense",
    )(lens, q2, k2, v2)
    return out.reshape(s_dim, h, hd)


def _paged_decode_kernel(scale: float, h: int, n_pages: int,
                         page: int, len_ref, pt_ref, q_ref, k_ref,
                         v_ref, o_ref, m_ref, l_ref, acc_ref):
    """One (slot*head, logical page) pair per grid step. The K/V
    blocks arriving here were ALREADY gathered by the prefetched page
    map (the BlockSpec index maps read ``pt_ref`` — the DMA engine
    follows the page table, the kernel never sees a physical page id
    beyond its own block). Accumulation across the page grid dim is
    the flash-attention online softmax (running max / rescaled sum in
    scratch); masking uses the same finite NEG_INF + where() sequence
    as the dense kernel, so a null/garbage page past a slot's length
    contributes exactly 0.0."""
    i = pl.program_id(0)                       # slot * h + head
    j = pl.program_id(1)                       # logical page index
    n = len_ref[i]

    @pl.when(j == 0)
    def _init():
        m_ref[0, 0] = NEG_INF
        l_ref[0, 0] = 0.0
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qf = q_ref[0].astype(jnp.float32)                     # [1, hd]
    kf = k_ref[0, 0].astype(jnp.float32)                  # [page, hd]
    s = jnp.sum(kf * qf, axis=1, keepdims=True) * scale   # [page, 1]
    k_pos = j * page + jax.lax.broadcasted_iota(
        jnp.int32, (page, 1), 0)
    s = jnp.where(k_pos < n, s, NEG_INF)
    m_prev = m_ref[0, 0]
    m_new = jnp.maximum(m_prev,
                        jnp.maximum(jnp.max(s), NEG_INF))
    p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - m_new), 0.0)
    # all-masked-so-far: m_prev == m_new == NEG_INF -> alpha = 1 with
    # l = 0, so the rescale is a no-op, exactly like the dense path
    alpha = jnp.exp(m_prev - m_new)
    vf = v_ref[0, 0].astype(jnp.float32)                  # [page, hd]
    acc_ref[...] = acc_ref[...] * alpha \
        + jnp.sum(p * vf, axis=0, keepdims=True)          # [1, hd]
    l_ref[0, 0] = l_ref[0, 0] * alpha + jnp.sum(p)
    m_ref[0, 0] = m_new

    @pl.when(j == n_pages - 1)
    def _flush():
        l_sum = l_ref[0, 0]
        o_ref[0] = (acc_ref[...]
                    / jnp.where(l_sum > 0.0, l_sum, 1.0)
                    ).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           lengths: jax.Array, *,
                           scale: float | None = None,
                           page_table: jax.Array = None) -> jax.Array:
    """Fused single-query attention over the PAGED arena (r20).

    q: [S, H, hd]; k/v: page POOLS [P_phys, H, page, hd]; lengths: i32
    [S]; page_table: i32 [S, P_logical] mapping each slot's logical
    pages to physical pages (0 = the null page, always past a slot's
    length). The page map rides scalar prefetch NEXT TO the per-slot
    lengths — available before the grid body runs, so the BlockSpec
    index maps gather K/V blocks pool[page_table[slot, j]] directly:
    no [S, H, L, hd] logical view is ever materialized in HBM, which
    is the whole point of paging the arena. Accumulation across a
    slot's pages is the standard online softmax; agreement with the
    gathered reference is fp32-tolerance (same contract as the dense
    kernel vs its reference)."""
    from jax.experimental.pallas import tpu as pltpu

    s_dim, h, hd = q.shape
    n_phys, h2, page, hd2 = k.shape
    if page_table is None:
        raise ValueError("paged_decode_attention needs a page_table")
    n_pages = page_table.shape[1]
    if not paged_supported(page, hd):
        raise ValueError(
            f"paged decode_attention kernel needs head_dim % {LANES} "
            f"== 0 and page_size % 8 == 0, got head_dim={hd}, "
            f"page_size={page} — route through "
            f"contrib.multihead_attn.slot_decode_attention")
    if scale is None:
        scale = 1.0 / float(hd) ** 0.5
    q2 = q.reshape(s_dim * h, 1, hd)
    lens = jnp.repeat(lengths.astype(jnp.int32), h)
    pt = page_table.astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_dim * h, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, hd),
                         lambda i, j, lens, pt: (i, 0, 0)),
            pl.BlockSpec((1, 1, page, hd),
                         lambda i, j, lens, pt:
                         (pt[i // h, j], i % h, 0, 0)),
            pl.BlockSpec((1, 1, page, hd),
                         lambda i, j, lens, pt:
                         (pt[i // h, j], i % h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, hd),
                               lambda i, j, lens, pt: (i, 0, 0)),
        scratch_shapes=[
            pltpu.SMEM((1, 1), jnp.float32),     # running max
            pltpu.SMEM((1, 1), jnp.float32),     # running sum
            pltpu.VMEM((1, hd), jnp.float32),    # PV accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, float(scale), h,
                          int(n_pages), int(page)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_dim * h, 1, hd), q.dtype),
        interpret=_interpret(),
        name="apex_decode_paged",
    )(lens, pt, q2, k, v)
    return out.reshape(s_dim, h, hd)
