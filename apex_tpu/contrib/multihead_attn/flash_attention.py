"""Flash-style attention Pallas kernel (TPU-native fused MHA core).

The reference ships eight hand-fused CUDA attention extensions
(apex/contrib/csrc/multihead_attn/ — CUTLASS strided-batched GEMMs + fused
softmax/dropout, ~3.4k LoC) that fuse per-GPU attention but still
materialize the full [Sq, Sk] score matrix. The TPU-idiomatic equivalent is
a single flash/blockwise kernel: stream K/V blocks through VMEM, keep an
online-softmax accumulator, never materialize scores in HBM — O(S) memory
instead of O(S^2), which is also what makes long-context sequence/ring
parallelism possible (apex_tpu.parallel.ring_attention builds on this
kernel's (out, lse) contract).

Design notes:
- grid (batch*heads, steps): a q row's sweep over its k blocks, row after
  row, with the block of each step in a table the kernels and the
  ``BlockSpec`` index maps read from SMEM (scalar prefetch; where no block
  is dead the index maps compute the block from the step's number). TPU grids
  iterate the LAST axis innermost and sequentially, so the (acc, m, l)
  state lives in VMEM scratch that persists across a row's sweep
  (initialized on its first step, finalized on its last).
- a block's kind is known from the offsets alone (``_block_kind``) before
  any body runs: a dead block (wholly above the causal diagonal, past the
  k length, wholly farther back than a sliding ``window`` reaches, or
  holding no pair the ``block_diffusion`` mask shows) gets
  no step at all, so it is neither fetched nor computed; ``block_census``
  counts the dead, the interior (no element masked) and the edge blocks
  (the diagonal, the window's trailing edge, the k length's last block).
  With a window the grid is the band: a row's sweep is as long as the
  window is wide, whatever the sequence's length. Under ``block_diffusion``
  (``BlockDiffusion``: a sequence's noised copy beside its clean one) the
  grid is two block-causal triangles and the noised copy's own diagonal.
- softmax statistics are carried as (block_q, 128) lane-replicated tiles
  (the VPU-friendly layout); ``lse`` is emitted lane-replicated and sliced
  by the wrapper.
- causal masking uses global positions ``q_start + i`` vs ``k_start + j``
  where the offsets are SMEM scalars — a sequence-parallel caller passes
  shard offsets (ring attention) without recompiling per shard.
- optional additive bias block [bq, bk] (padding masks, ALiBi — the
  reference's additive-mask/time-mask softmax variants) and an O(S)
  per-key bias (key-padding masks; rides the ring with its K/V shard).
- in-kernel dropout on the softmax probabilities (the reference's fused
  softmax-dropout, dropout.h + softmax.h) from a stateless coordinate
  hash — no O(S^2) mask tensor, bit-identical fwd/bwd recompute.
- fp32 accumulation throughout (scores, stats, output accumulator)
  regardless of input dtype; output cast back to the input dtype.

Backward is a pair of Pallas kernels with flash-style recompute (no saved
probabilities, matching the reference backward exts' recompute-from-saved-
softmax-stats shape, self_multihead_attn_cuda.cu bwd half):
- dq kernel: the forward's grid (rows' sweeps), dq accumulates in VMEM
  scratch across a sweep; emits per-block ds as the bias gradient when a
  bias is present (then every block keeps its step: a dead one's are zeros).
- dk/dv kernel: a k column's sweep over its q blocks, column after column;
  dk/dv accumulate across a sweep.
Both recompute p = exp(s - lse) from the forward's saved lse; the dO·O row
term (delta) and the lse cotangent are folded into one per-row tensor
host-side. A jnp chunked-scan twin (``_bwd_chunked``) remains as the
numerics oracle and the ``APEX_TPU_FLASH_BWD=chunked`` fallback.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.key_set import SELECT_SPAN, unpack_select

LANES = 128
MAX_BLOCK = 512  # upper bound for _pick_block's divisor-aware sizing
# with a window the grid is the band, a few blocks a line, and a grid step
# is the unit of cost: the widest blocks that fit VMEM win. On the v5e at
# [64, 8192, 128], window 1024 (PERF.md, PR 39), forward 512 x 512 7.04 ms,
# 512 x 1024 5.25, 1024 x 1024 4.65 (1024 x 2048 does not fit); backward
# dq + dkv 256 x 512 13.98, 1024 x 1024 11.48, 512 x 512 10.84; the same
# order at windows of 256 and 4096 to within a tenth. At heads wider than
# a lane tile 1024 x 1024 passes the kernel's 16 MB of VMEM (17.2 at 256),
# so those keep the causal defaults
WINDOW_BLOCK = 1024
WINDOW_BWD_BLOCK = 512
# jax.ad_checkpoint.checkpoint_name of the forward kernel's two outputs under
# differentiation: a jax.checkpoint around the call whose policy is
# save_only_these_names(*SAVED_NAMES) keeps them and runs apex_flash_fwd
# once; under any other policy the names do nothing
SAVED_NAMES = ("apex_flash_out", "apex_flash_lse")


def _pick_block(s: int, most: int = MAX_BLOCK) -> int:
    """Largest block in {1024, 512, 384, 256, 128} up to ``most`` that
    divides the 128-rounded sequence length (no pad blowup); sub-128
    sequences use their own 16-rounded length."""
    from apex_tpu.ops.pallas._common import round_up
    if s <= 128:
        return max(16, round_up(s, 16))
    sp = round_up(s, 128)
    for b in (1024, 512, 384, 256, 128):
        if b <= most and sp % b == 0:
            return b
    return 128
NEG_INF = -1.0e30


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _vma(*arrays):
    """Union of the varying-manual-axes of the inputs — required on
    pallas_call out_shapes under shard_map(check_vma=True)."""
    vma = frozenset()
    typeof = getattr(jax, "typeof", None)
    if typeof is None:   # older jax: no vma tracking at all
        return vma
    for a in arrays:
        v = getattr(typeof(a), "vma", None)
        if v:
            vma = vma | v
    return vma


def _sds(shape, dtype, vma=frozenset()):
    """ShapeDtypeStruct carrying vma where this jax supports it (older
    jaxlibs have no vma kwarg — and nothing to declare)."""
    try:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    except TypeError:
        return jax.ShapeDtypeStruct(shape, dtype)


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def dropout_bits(seed, bh, q_pos, k_pos):
    """Counter-based RNG for attention dropout: uint32 hash of the global
    element coordinates (murmur3-finalizer quality). The reference fuses
    curand Philox into its softmax kernels
    (apex/contrib/csrc/multihead_attn/dropout.h, softmax.h); a stateless
    coordinate hash is the TPU-kernel equivalent — the same mask is
    recomputed bit-exactly in the forward kernel, both backward kernels,
    the chunked jnp backward, and the jnp oracle, with no RNG state to
    thread and no recompute drift between compiled and interpret modes."""
    u = jnp.uint32
    x = (q_pos.astype(jnp.uint32) * u(0x9E3779B1)
         + k_pos.astype(jnp.uint32) * u(0x85EBCA77)
         + jnp.asarray(bh, jnp.uint32) * u(0xC2B2AE3D)
         + jnp.asarray(seed, jnp.uint32) * u(0x27D4EB2F))
    x = x ^ (x >> u(16))
    x = x * u(0x7FEB352D)
    x = x ^ (x >> u(15))
    x = x * u(0x846CA68B)
    x = x ^ (x >> u(16))
    return x


def _drop_threshold(rate: float) -> int:
    return min(int(rate * 4294967296.0), 4294967295)


def _keep_mask(off_ref, bh, qb, kb, shape, rate):
    """[bq, bk] keep-mask for this block from global positions (so ring
    shards draw consistent masks)."""
    bq, bk = shape
    q_pos = off_ref[0] + qb * bq + \
        jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    k_pos = off_ref[1] + kb * bk + \
        jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    bits = dropout_bits(off_ref[3], bh, q_pos, k_pos)
    return bits >= jnp.uint32(_drop_threshold(rate))


def _selected(s, sel, kb):
    """A [bq, bk] score block with the keys outside the packed set ``sel``
    ([bq, 128] words of the block's span) masked."""
    bk = s.shape[1]
    first = (kb % (SELECT_SPAN // bk)) * (bk // LANES)
    keep = [jax.lax.shift_right_logical(
        sel, jnp.full(sel.shape, first + c, sel.dtype)) & 1
        for c in range(bk // LANES)]
    keep = keep[0] if len(keep) == 1 else jnp.concatenate(keep, axis=1)
    return jnp.where(keep != 0, s, NEG_INF)


class BlockDiffusion(NamedTuple):
    """The block-diffusion mask over ``2 * length`` rows, queries and keys
    alike: rows ``[0, length)`` are a sequence's noised copy, rows
    ``[length, 2 * length)`` its clean one, both cut into blocks of
    ``block`` positions. A noised row sees its own noised block (both
    ways) and the clean blocks before it; a clean row sees the clean blocks
    up to its own, its own whole; no clean row sees a noised one. Plain
    integers: the mask is structure, and the grids are built from it. It
    rides where a ``window`` does (the kernels' static ``window``), under
    ``causal=False``."""
    block: int
    length: int

    def visible(self, a, b):
        """Whether query row ``a`` sees key row ``b`` (integer arrays
        that broadcast; numpy or jax.numpy)."""
        block, length = self
        noised, k_noised = a < length, b < length
        # a key's position among the noised keys and among the clean ones,
        # out of every query's reach where it is of the other kind (so is
        # a key past the 2 * length rows); what varies with the query
        # alone or the key alone stays a column or a row
        own = b + 2 * length * ~k_noised
        past = b - length + 2 * length * k_noised
        lo = (a - length * ~noised) // block * block    # the query's block
        return ((own >= lo) & (own < (lo + block) * noised)) \
            | (past < lo + block * ~noised)

    def tile_kind(self, k_len, q0, k0, bq: int, bk: int):
        """``(live, interior)`` of the tiles of ``bq`` rows from ``q0`` and
        ``bk`` keys from ``k0`` (numpy, broadcasting): some pair visible,
        every pair visible. A tile is split at ``length`` into its noised
        and its clean rows and keys; each of the three quadrants that show
        anything is a comparison of first and last blocks."""
        block, length = self
        q1 = np.minimum(q0 + bq, 2 * length) - 1    # the last real row
        k1 = np.minimum(k0 + bk, k_len) - 1

        def blk(i):
            return i // block
        qn, qc, kn, kc = q0 < length, q1 >= length, k0 < length, k1 >= length
        qn1, kn1 = np.minimum(q1, length - 1), np.minimum(k1, length - 1)
        qc0, kc0 = (np.maximum(x, length) - length for x in (q0, k0))
        qc1, kc1 = q1 - length, k1 - length
        live = (k0 < k_len) & (
            (qn & kn & (blk(q0) <= blk(kn1)) & (blk(k0) <= blk(qn1)))
            | (qn & kc & (blk(kc0) < blk(qn1)))
            | (qc & kc & (blk(kc0) <= blk(qc1))))
        interior = live & ~(qc & kn) & (k0 + bk <= k_len) \
            & (~(qn & kn) | ((blk(q0) == blk(qn1)) & (blk(k0) == blk(kn1))
                             & (blk(q0) == blk(k0)))) \
            & (~(qn & kc) | (blk(kc1) < blk(q0))) \
            & (~(qc & kc) | (blk(kc1) <= blk(qc0)))
        return live, interior


def _masked_scores(s, off_ref, qb, kb, causal, window=None):
    """Apply causal (global positions from SMEM offsets), sliding-window
    (``window`` keys back from the query, itself included; a
    ``BlockDiffusion`` in its place: that mask, rows from 0) and k-length
    (local padding, offs[2]) masks to a [bq, bk] score block."""
    bq, bk = s.shape
    if isinstance(window, BlockDiffusion):      # it shows no padded key
        rows = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        keys = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        return jnp.where(window.visible(rows, keys), s, NEG_INF)
    k_local = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    s = jnp.where(k_local < off_ref[2], s, NEG_INF)
    if causal:
        q_pos = off_ref[0] + qb * bq + \
            jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = off_ref[1] + kb * bk + \
            jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if window is not None:
            s = jnp.where(q_pos - k_pos < window, s, NEG_INF)
    return s


def _block_kind(offs, qb, kb, bq, bk, causal, window=None):
    """``(live, interior)`` of score block (qb, kb), from the offsets
    alone. Not live (dead): wholly above the causal diagonal, past the
    k length, farther back than ``window - 1`` keys or with no pair a
    ``BlockDiffusion`` shows, nothing to compute, and no grid step.
    Interior: no element masked. Live and not interior (edge): the
    diagonal, the window's trailing edge and the k length's last block.
    The kernels act on live alone: a body without masks for
    the interior blocks measured nothing on the chip (PERF.md, PR 35);
    ``block_census`` counts all three. ``offs`` indexes as (q_start, k_start,
    k_len, ...): the kernels' SMEM ref, or plain integers in
    ``block_census``. ``window``: the band's width, or a ``BlockDiffusion``
    (plain offsets alone: ``BlockDiffusion.tile_kind``)."""
    k_lo = kb * bk
    if isinstance(window, BlockDiffusion):
        return window.tile_kind(offs[2], qb * bq, k_lo, bq, bk)
    live = k_lo < offs[2]
    interior = k_lo + bk <= offs[2]
    if causal:
        q_lo = offs[0] + qb * bq
        k_pos = offs[1] + k_lo
        live = live & (q_lo + bq - 1 >= k_pos)
        interior = interior & (q_lo >= k_pos + bk - 1)
        if window is not None:      # visible: 0 <= q_pos - k_pos < window
            live = live & (q_lo - (k_pos + bk - 1) < window)
            interior = interior & (q_lo + bq - 1 - k_pos < window)
    return live, interior


def _grid_kinds(xp, offs, nq, nk, bq, bk, causal, window=None):
    """``_block_kind`` of every block of an ``nq`` x ``nk`` grid, in numpy
    or jax.numpy: the blocks' indices [nq, 1] and [1, nk], and live and
    interior [nq, nk]."""
    qb = xp.arange(nq, dtype=xp.int32)[:, None]
    kb = xp.arange(nk, dtype=xp.int32)[None, :]
    live, interior = (xp.broadcast_to(a, (nq, nk)) for a in _block_kind(
        offs, qb, kb, bq, bk, causal, window))
    return qb, kb, live, interior


# a step's code: qb | kb << 12 | live << 24 | first << 25 | last << 26
_IDX, _KB, _LIVE, _FIRST, _LAST = 0xFFF, 12, 24, 25, 26


def _steps(known, offs, nq, nk, bq, bk, causal, by_col=False, every=False,
           window=None):
    """The grid's steps in order, int32 [T], one code each: the block
    (qb, kb) a step holds, whether it is live, and whether it is the first
    and the last step of its line (a q row's sweep over k blocks;
    ``by_col``: a k column's sweep over q blocks), where the accumulators
    start and the results are written. Dead blocks get no step, so they cost nothing
    (``every``: they keep theirs, for a result that has a block each); a
    line with no live block keeps one dead step, which writes its zeros.
    ``known`` offsets (plain integers, not traced) give exactly the steps
    there are, as constants; traced ones (``known`` None: a ring step's
    shard positions) as many as there are blocks, and the steps past the
    last one repeat its block as dead steps that are neither first nor
    last: nothing to fetch, nothing to do."""
    xp, offs = (jnp, offs) if known is None else (np, known)
    assert max(nq, nk) <= _IDX + 1
    qb, kb, live, _ = _grid_kinds(xp, offs, nq, nk, bq, bk, causal, window)
    code = qb | kb << _KB | live.astype(xp.int32) << _LIVE
    if by_col:
        live, code = live.T, code.T
    lines, sweep = live.shape
    line = xp.broadcast_to(xp.arange(lines)[:, None], live.shape).reshape(-1)
    # a line with no live block keeps its first step
    keep = xp.ones_like(live) if every else live | (
        (xp.arange(sweep)[None, :] == 0) & ~live.any(axis=1, keepdims=True))
    keep, code = keep.reshape(-1), code.reshape(-1)
    order = xp.argsort(~keep, stable=True)      # the kept steps, in order
    n = keep.sum()
    t = xp.arange(nq * nk if known is None else int(n))
    at = order[xp.minimum(t, n - 1)]
    line = line[at]
    edge = xp.full((1,), -1, line.dtype)
    first = line != xp.concatenate([edge, line[:-1]])
    last = (line != xp.concatenate([line[1:], edge])) | (t == n - 1)
    code = xp.where(t < n, code[at] | first << _FIRST | last << _LAST,
                    code[at] & (_IDX | _IDX << _KB))
    return xp.asarray(code, xp.int32)


def _block_of(code):
    """The (qb, kb) of a step's code."""
    return code & _IDX, (code >> _KB) & _IDX


def _step_block(b, t, steps, offs=None):
    """Index maps: the (b, qb, kb) of step ``t``."""
    return (b, *_block_of(steps[t]))


def _at(steps, known, nq, nk, by_col=False):
    """What the index maps read a step's block from: the table, or, where
    the table is the identity (offsets known, every block kept: a grid
    with nothing dead), the step's number alone. Reading the table in
    each of a call's 8-10 index maps costs a live step 0.08-0.15 us on the
    v5e, 8% of a long grid's time, which buys nothing where no step is
    gone (PERF.md, PR 35)."""
    if known is None or len(steps) != nq * nk:
        return _step_block
    if by_col:
        return lambda b, t, *_: (b, t % nq, t // nq)
    return lambda b, t, *_: (b, t // nk, t % nk)


def _run_step(code, init, body, finalize):
    """One grid step: start the line's accumulators on its first step, run
    ``body`` on a live block, write the line's results on its last."""
    pl.when((code >> _FIRST) & 1 == 1)(init)
    pl.when((code >> _LIVE) & 1 == 1)(body)
    pl.when((code >> _LAST) & 1 == 1)(finalize)


def block_census(sq: int, sk: int, block_q: int, block_k: int, causal: bool,
                 q_start: int = 0, k_start: int = 0,
                 k_len: Optional[int] = None,
                 window: Optional[int] = None,
                 block_diffusion: Optional[tuple] = None) -> dict:
    """Grid steps by kind for one batch-head, ``{"dead", "interior",
    "edge"}``, from the kernels' own predicate: of a kernel over ``sq`` x
    ``sk`` scores in ``block_q`` x ``block_k`` blocks, the blocks that get
    no step (with traced offsets an empty one), and of those that get one
    the blocks no mask touches and the blocks one does. ``sq``, ``sk``: the lengths the grid tiles
    (the backward's are the forward's padded ones); ``k_len``: the
    unpadded key length, ``sk`` by default; ``window``,
    ``block_diffusion``: as ``flash_attention``'s (the band's blocks, the
    blocks that hold a visible pair, are live, the rest dead)."""
    _, _, live, interior = _grid_kinds(
        np, (q_start, k_start, sk if k_len is None else k_len),
        -(-sq // block_q), -(-sk // block_k), block_q, block_k, causal,
        _structure(window, block_diffusion))
    return {"dead": int((~live).sum()), "interior": int(interior.sum()),
            "edge": int((live & ~interior).sum())}


def _spec(shape, block_index, at=_step_block):
    """A BlockSpec whose block is ``block_index(b, qb, kb)``, with ``at``
    mapping a grid step's indices (and the scalar prefetch) to the (b, qb,
    kb) it holds: by default through the step table."""
    return pl.BlockSpec(shape, lambda *g: block_index(*at(*g)))


def _in_specs(block_q, block_k, d, bias, kvb, backward=False, heads=0,
              **at):
    """BlockSpecs of q, k, v (``backward``: then dO, lse, delta), then
    the bias and the per-key bias [1|BH, 1, Sk] where present (either is
    shared across batch-heads when its leading dim is 1), then, with
    ``heads`` (the batch-heads that share a row of a packed ``select``),
    the [block_q, 128] words of the key block's span."""
    rows = _spec((1, block_q, d), lambda b, i, j: (b, i, 0), **at)
    cols = _spec((1, block_k, d), lambda b, i, j: (b, j, 0), **at)
    specs = [rows, cols, cols]
    if backward:
        stat = _spec((1, block_q, LANES), lambda b, i, j: (b, i, 0), **at)
        specs += [rows, stat, stat]
    if bias is not None:
        specs.append(_spec(
            (1, block_q, block_k),
            (lambda b, i, j: (0, i, j)) if bias.shape[0] == 1 else
            (lambda b, i, j: (b, i, j)), **at))
    if kvb is not None:
        specs.append(_spec(
            (1, 1, block_k),
            (lambda b, i, j: (0, 0, j)) if kvb.shape[0] == 1 else
            (lambda b, i, j: (b, 0, j)), **at))
    if heads:
        specs.append(_spec(
            (1, block_q, LANES),
            lambda b, i, j: (b // heads, i, j * block_k // SELECT_SPAN),
            **at))
    return specs


def _fwd_kernel(causal: bool, window, has_bias: bool, has_kvb: bool,
                scale: float, dropout: float, has_sel: bool, *refs):
    refs = list(refs)
    steps_ref, off_ref, q_ref, k_ref, v_ref = refs[:5]
    del refs[:5]
    bias_ref = refs.pop(0) if has_bias else None
    kvb_ref = refs.pop(0) if has_kvb else None
    sel_ref = refs.pop(0) if has_sel else None
    o_ref, lse_ref, acc_ref, m_ref, l_ref = refs

    # program_id must be read OUTSIDE pl.when bodies: interpret mode only
    # substitutes grid indices for top-level reads
    code = steps_ref[pl.program_id(1)]
    bh_i, (qb, kb) = pl.program_id(0), _block_of(code)

    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _body():
        q = q_ref[0].astype(jnp.float32)           # [bq, d]
        k = k_ref[0].astype(jnp.float32)           # [bk, d]
        v = v_ref[0].astype(jnp.float32)           # [bk, d]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [bq, bk]
        if has_bias:
            s = s + bias_ref[0].astype(jnp.float32)
        if has_kvb:
            s = s + kvb_ref[0].astype(jnp.float32)  # (1, bk) row-broadcast
        s = _masked_scores(s, off_ref, qb, kb, causal, window)
        if has_sel:
            s = _selected(s, sel_ref[0], kb)

        m_prev = m_ref[:, :1]                      # [bq, 1]
        row_max = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, row_max)
        # Rows with nothing unmasked yet must keep p == 0 (exp(NEG - NEG)
        # would otherwise contribute 1).
        p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - m_new), 0.0)  # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)            # [bq, 1]

        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        # dropout on the (to-be-normalized) probabilities: the softmax
        # denominator keeps ALL probs (reference dropout.h semantics —
        # dropout is applied to softmax results), so l accumulates the
        # undropped p while acc accumulates the masked, rescaled p.
        pa = p
        if dropout > 0.0:
            keep = _keep_mask(off_ref, bh_i, qb, kb, p.shape, dropout)
            pa = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout))
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            pa, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    def _finalize():
        l = l_ref[:, :1]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)
        lse = jnp.where(l > 0.0, m_ref[:, :1] + jnp.log(safe_l), NEG_INF)
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)

    _run_step(code, _init, _body, _finalize)


def _heads(sel, bh: int) -> int:
    """The batch-heads that share a row of the packed set ``sel`` [B, Sq,
    W]; 0 without a set."""
    return 0 if sel is None else bh // sel.shape[0]


def _family(window, sel) -> str:
    """What ``flash_`` reads in a call's name: a windowed call, one over
    a selected key set and one under the block-diffusion mask have names
    of their own (``apex_flash_win_fwd``, ``apex_flash_sel_fwd``,
    ``apex_flash_bd_fwd``), so a trace tells a layer kind's kernels
    apart."""
    if isinstance(window, BlockDiffusion):
        return "flash_bd_"
    return "flash_sel_" if sel is not None else \
        "flash_" if window is None else "flash_win_"


def _flash_fwd(q, k, v, bias, kvb, offs, *, causal, scale, block_q, block_k,
               dropout=0.0, known=None, window=None, sel=None):
    """q,k,v: [BH, S, D], pre-padded so block sizes divide S and D == lane
    multiple. offs: int32[4] = (q_start, k_start, k_len, seed) — k_len is
    the UNPADDED key length, masked in-kernel (no O(S^2) pad-bias tensor);
    seed drives the in-kernel dropout mask when ``dropout`` > 0.
    kvb: optional per-KEY additive bias [1|BH, 1, Sk] (key-padding masks)
    — O(S) instead of the O(S^2) bias tensor.
    sel: optional packed key set [B, Sq, W] (``pack_select``), shared by a
    row's BH / B heads.
    Returns (o, lse[BH,S])."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq = sq // block_q
    nk = sk // block_k

    has_bias = bias is not None
    has_kvb = kvb is not None
    args = [q, k, v] + [a for a in (bias, kvb, sel) if a is not None]

    steps = _steps(known, offs, nq, nk, block_q, block_k, causal,
                   window=window)
    at = _at(steps, known, nq, nk)
    kernel = functools.partial(_fwd_kernel, causal, window, has_bias, has_kvb,
                               float(scale), float(dropout), sel is not None)
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                          # steps, offs
            grid=(bh, len(steps)),
            in_specs=_in_specs(block_q, block_k, d, bias, kvb, at=at,
                               heads=_heads(sel, bh)),
            out_specs=[
                _spec((1, block_q, d), lambda b, i, j: (b, i, 0), at),
                _spec((1, block_q, LANES), lambda b, i, j: (b, i, 0), at),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32),
            ]),
        out_shape=[
            _sds((bh, sq, d), q.dtype, vma=_vma(q, k, v)),
            _sds((bh, sq, LANES), jnp.float32, vma=_vma(q, k, v)),
        ],
        interpret=_interpret(),
        name="apex_flash_fwd".replace("flash_", _family(window, sel)),
    )(steps, offs, *args)
    return o, lse[:, :, 0]


# ---------------------------------------------------------------------------
# Pallas backward kernels (dq / dbias and dk / dv)
# ---------------------------------------------------------------------------

def _recompute_p_ds(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
                    bias_ref, kvb_ref, bh_i, qb, kb, causal, scale, dropout,
                    window=None, sel_ref=None):
    """Shared bwd block math: recompute p from saved lse, return (pd, ds, q,
    k, do) as fp32 — ``pd`` is the (dropout-masked, rescaled) probability
    used for dv. ds = p * (mask*dp/keep - delta); delta = rowsum(dO·O)
    already equals sum_k pd*dp so no extra correction is needed, and the
    lse cotangent is pre-folded into delta host-side (lse is dropout-free,
    and d(lse)/ds = p undropped, which is exactly the factor outside)."""
    q = q_ref[0].astype(jnp.float32)               # [bq, d]
    k = k_ref[0].astype(jnp.float32)               # [bk, d]
    v = v_ref[0].astype(jnp.float32)               # [bk, d]
    do = do_ref[0].astype(jnp.float32)             # [bq, d]
    lse = lse_ref[0][:, :1]                        # [bq, 1]
    delta = dlt_ref[0][:, :1]                      # [bq, 1]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if bias_ref is not None:
        s = s + bias_ref[0].astype(jnp.float32)
    if kvb_ref is not None:
        s = s + kvb_ref[0].astype(jnp.float32)
    s = _masked_scores(s, off_ref, qb, kb, causal, window)
    if sel_ref is not None:
        s = _selected(s, sel_ref[0], kb)

    # exp(NEG - NEG) guard: fully-masked rows have lse == NEG_INF
    p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - lse), 0.0)   # [bq, bk]
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                   # [bq, bk]
    if dropout > 0.0:
        keep = _keep_mask(off_ref, bh_i, qb, kb, p.shape, dropout)
        inv = 1.0 / (1.0 - dropout)
        pd = jnp.where(keep, p, 0.0) * inv
        dp = jnp.where(keep, dp, 0.0) * inv
    else:
        pd = p
    ds = p * (dp - delta)
    return pd, ds, q, k, do


def _bwd_refs(refs, has_bias, has_kvb, has_sel):
    """A backward kernel's leading refs taken off ``refs``: (steps, offs,
    the six of ``_recompute_p_ds``, bias, kvb, sel | None)."""
    steps_ref, off_ref = refs[:2]
    six = refs[2:8]
    del refs[:8]
    bias_ref = refs.pop(0) if has_bias else None
    kvb_ref = refs.pop(0) if has_kvb else None
    sel_ref = refs.pop(0) if has_sel else None
    return steps_ref, off_ref, six, bias_ref, kvb_ref, sel_ref


def _bwd_dq_kernel(causal: bool, window, has_bias: bool, has_kvb: bool,
                   emit_dbias: bool, scale: float, dropout: float,
                   has_sel: bool, *refs):
    refs = list(refs)
    steps_ref, off_ref, six, bias_ref, kvb_ref, sel_ref = _bwd_refs(
        refs, has_bias, has_kvb, has_sel)
    dq_ref = refs.pop(0)
    dbias_ref = refs.pop(0) if emit_dbias else None
    dq_acc = refs.pop(0)

    code = steps_ref[pl.program_id(1)]
    bh_i, (qb, kb) = pl.program_id(0), _block_of(code)

    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _body():
        _, ds, _, k, _ = _recompute_p_ds(
            off_ref, *six, bias_ref, kvb_ref, bh_i, qb, kb, causal, scale,
            dropout, window, sel_ref)
        if dbias_ref is not None:
            dbias_ref[0] = ds
        dq_acc[...] += jax.lax.dot_general(
            ds * scale, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)

    _run_step(code, _init, _body, _finalize)

    if dbias_ref is not None:   # every block has its step: zeros on a dead one
        @pl.when((code >> _LIVE) & 1 == 0)
        def _zero_dbias():
            dbias_ref[0] = jnp.zeros_like(dbias_ref[0])


def _bwd_dkv_kernel(causal: bool, window, has_bias: bool, has_kvb: bool,
                    scale: float, dropout: float, has_sel: bool, *refs):
    refs = list(refs)
    steps_ref, off_ref, six, bias_ref, kvb_ref, sel_ref = _bwd_refs(
        refs, has_bias, has_kvb, has_sel)
    dk_ref, dv_ref, dk_acc, dv_acc = refs

    code = steps_ref[pl.program_id(1)]
    bh_i, (qb, kb) = pl.program_id(0), _block_of(code)

    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _body():
        pd, ds, q, _, do = _recompute_p_ds(
            off_ref, *six, bias_ref, kvb_ref, bh_i, qb, kb, causal, scale,
            dropout, window, sel_ref)
        dv_acc[...] += jax.lax.dot_general(
            pd, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bk, d]
        dk_acc[...] += jax.lax.dot_general(
            ds * scale, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bk, d]

    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    _run_step(code, _init, _body, _finalize)


def _bwd_dbias_kernel(nbh: int, causal: bool, window, has_kvb: bool,
                      scale: float, dropout: float, *refs):
    """Broadcast-bias gradient: grid (nq, nk, bh) with bh INNERMOST so the
    single (1, bq, bk) output block is revisited on consecutive iterations
    while ds accumulates over batch*heads in VMEM — never materializing a
    per-bh [bh, sq, sk] tensor in HBM."""
    refs = list(refs)
    (off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
     bias_ref) = refs[:8]
    del refs[:8]
    kvb_ref = refs.pop(0) if has_kvb else None
    dbias_ref, ds_acc = refs
    qb, kb, b = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @pl.when(b == 0)
    def _init():
        ds_acc[...] = jnp.zeros_like(ds_acc)

    @pl.when(_block_kind(off_ref, qb, kb, bq, bk, causal, window)[0])
    def _body():
        _, ds, *_ = _recompute_p_ds(
            off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
            bias_ref, kvb_ref, b, qb, kb, causal, scale, dropout, window)
        ds_acc[...] += ds

    @pl.when(b == nbh - 1)
    def _finalize():
        dbias_ref[0] = ds_acc[...]


def _bwd_pallas(res, do, dlse, *, causal, scale, block_q, block_k,
                bias_grad, dropout=0.0, known=None, window=None, sel=None):
    """Pallas flash backward over the padded residuals. Returns
    (dq, dk, dv, dbias) with dbias None when no bias was supplied and
    zeros when ``bias_grad`` is False (mask-only biases)."""
    q, k, v, bias, kvb, offs, lse, o = res
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq = sq // block_q
    nk = sk // block_k
    has_bias = bias is not None
    has_kvb = kvb is not None
    emit_dbias = has_bias and bias_grad
    # broadcast bias grads accumulate over bh in a dedicated kernel
    dbias_in_dq = emit_dbias and bias.shape[0] != 1

    do = do.astype(jnp.float32)
    # delta = rowsum(dO * O); the lse cotangent folds into the same
    # per-row subtraction: ds = p * (dp - (delta - dlse)).
    delta = jnp.sum(do * o.astype(jnp.float32), axis=-1)       # [bh, sq]
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    # lane-replicate row stats (the TPU-friendly [.., sq, 128] layout)
    lse_r = jnp.broadcast_to(lse[..., None], (*lse.shape, LANES))
    dlt_r = jnp.broadcast_to(delta[..., None], (*delta.shape, LANES))

    args = [q, k, v, do, lse_r, dlt_r] + [
        a for a in (bias, kvb, sel) if a is not None]
    vma = _vma(q, k, v, do)

    in_specs = functools.partial(_in_specs, block_q, block_k, d, bias, kvb,
                                 backward=True,
                                 heads=_heads(sel, bh))

    # --- dq (+ per-bh dbias): each q row's sweep over its k blocks ---------
    steps = _steps(known, offs, nq, nk, block_q, block_k, causal,
                   every=dbias_in_dq, window=window)
    at = _at(steps, known, nq, nk)
    dq_out_specs = [_spec((1, block_q, d), lambda b, i, j: (b, i, 0), at)]
    dq_out_shape = [_sds((bh, sq, d), q.dtype, vma=vma)]
    if dbias_in_dq:
        dq_out_specs.append(_spec(
            (1, block_q, block_k), lambda b, i, j: (b, i, j), at))
        dq_out_shape.append(
            _sds((bh, sq, sk), jnp.float32, vma=vma))
    dq_res = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal, window, has_bias, has_kvb,
                          dbias_in_dq, float(scale), float(dropout),
                          sel is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                          # steps, offs
            grid=(bh, len(steps)),
            in_specs=in_specs(at=at),
            out_specs=dq_out_specs,
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)]),
        out_shape=dq_out_shape,
        interpret=_interpret(),
        name="apex_flash_bwd_dq".replace("flash_", _family(window, sel)),
    )(steps, offs, *args)
    if dbias_in_dq:
        dq, dbias = dq_res
        dbias = dbias.astype(bias.dtype)
    else:
        (dq,) = dq_res if isinstance(dq_res, (list, tuple)) else (dq_res,)
        dbias = None
    if emit_dbias and not dbias_in_dq:
        dbias = pl.pallas_call(
            functools.partial(_bwd_dbias_kernel, bh, causal, window, has_kvb,
                              float(scale), float(dropout)),
            grid=(nq, nk, bh),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]        # offs
            + in_specs(at=lambda i, j, b: (b, i, j)),
            out_specs=pl.BlockSpec((1, block_q, block_k),
                                   lambda i, j, b: (0, i, j)),
            out_shape=_sds((1, sq, sk), jnp.float32, vma=vma),
            scratch_shapes=[pltpu.VMEM((block_q, block_k), jnp.float32)],
            interpret=_interpret(),
            name="apex_flash_bwd_dbias",
        )(offs, *args).astype(bias.dtype)
    if has_bias and not emit_dbias:
        dbias = jnp.zeros_like(bias)

    # --- dk / dv: each k column's sweep over its q blocks ------------------
    steps = _steps(known, offs, nq, nk, block_q, block_k, causal,
                   by_col=True, window=window)
    at = _at(steps, known, nq, nk, by_col=True)
    col = _spec((1, block_k, d), lambda b, i, j: (b, j, 0), at)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal, window, has_bias, has_kvb,
                          float(scale), float(dropout), sel is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                          # steps, offs
            grid=(bh, len(steps)),
            in_specs=in_specs(at=at),
            out_specs=[col, col],
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32)]),
        out_shape=[
            _sds((bh, sk, d), k.dtype, vma=vma),
            _sds((bh, sk, d), v.dtype, vma=vma),
        ],
        interpret=_interpret(),
        name="apex_flash_bwd_dkv".replace("flash_", _family(window, sel)),
    )(steps, offs, *args)
    return dq, dk, dv, dbias


# ---------------------------------------------------------------------------
# Unfused reference path + chunked flash backward
# ---------------------------------------------------------------------------

def reference_attention(q, k, v, bias=None, *, kv_bias=None,
                        causal=False, scale=None,
                        q_start=0, k_start=0, return_lse=False,
                        dropout_rate=0.0, dropout_seed=0, window=None,
                        select=None, block_diffusion=None):
    """Unfused jnp attention with the same (out, lse) contract — the
    impl='default' path (reference: the torch-composed SelfAttnFunc,
    apex/contrib/multihead_attn/self_multihead_attn_func.py:4) and the
    numerics oracle for the kernel tests. ``dropout_rate`` applies
    dropout to the softmax probabilities with the SAME coordinate-hash
    mask as the flash kernel, so the two impls agree bit-for-bit on which
    weights are dropped. ``window``, ``select``, ``block_diffusion``: as
    ``flash_attention``'s."""
    import math
    _check_window(window, causal)
    sq, d = q.shape[-2], q.shape[-1]
    sk = k.shape[-2]
    _check_block_diffusion(block_diffusion, sq, sk, causal, window, select,
                           bias, q_start, k_start)
    if scale is None:
        scale = 1.0 / float(d) ** 0.5
    s = jnp.einsum("...qd,...kd->...qk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if kv_bias is not None:
        s = s + kv_bias.astype(jnp.float32)[..., None, :]
    if causal:
        q_pos = jnp.asarray(q_start, jnp.int32) + jnp.arange(sq)[:, None]
        k_pos = jnp.asarray(k_start, jnp.int32) + jnp.arange(sk)[None, :]
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if window is not None:
            s = jnp.where(q_pos - k_pos < window, s, NEG_INF)
    if select is not None:
        keep = unpack_select(select, sk)            # [B, Sq, Sk]
        keep = keep[:, None] if q.ndim == 4 else jnp.repeat(
            keep, q.shape[0] // keep.shape[0], axis=0)
        s = jnp.where(keep, s, NEG_INF)
    if block_diffusion is not None:
        s = jnp.where(_structure(None, block_diffusion).visible(
            jnp.arange(sq)[:, None], jnp.arange(sk)[None, :]), s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.maximum(m, NEG_INF)
    p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    safe_l = jnp.where(l > 0.0, l, 1.0)
    probs = p / safe_l
    if dropout_rate > 0.0:
        lead = s.shape[:-2]
        bh_idx = jnp.arange(math.prod(lead)).reshape(*lead, 1, 1)
        qp = jnp.asarray(q_start, jnp.int32) + jnp.arange(sq)[:, None]
        kp = jnp.asarray(k_start, jnp.int32) + jnp.arange(sk)[None, :]
        bits = dropout_bits(dropout_seed, bh_idx, qp, kp)
        keep = bits >= jnp.uint32(_drop_threshold(dropout_rate))
        probs = jnp.where(keep, probs, 0.0) * (1.0 / (1.0 - dropout_rate))
    o = jnp.einsum("...qk,...kd->...qd", probs,
                   v.astype(jnp.float32)).astype(q.dtype)
    if return_lse:
        lse = jnp.where(l > 0.0, m + jnp.log(safe_l), NEG_INF)[..., 0]
        return o, lse
    return o


def _bwd_chunked(res, do, dlse, *, causal, scale, block_k, bias_grad=True,
                 dropout=0.0, window=None):
    """Flash backward: recompute p per K/V block from (q, k, v, lse), scan
    over blocks accumulating dq and emitting (dk, dv) — O(S·block) memory
    (the flash backward recurrence; replaces saving the S×S softmax the way
    the reference kernels recompute from saved softmax results).
    ``window``: the band's width or a ``BlockDiffusion``, as the kernels'."""
    q, k, v, bias, kvb, offs, lse, o = res
    bh, sq, d = q.shape
    sk = k.shape[1]
    q_start, k_start, k_len = offs[0], offs[1], offs[2]
    do = do.astype(jnp.float32)
    qf = q.astype(jnp.float32)
    delta = jnp.sum(do * o.astype(jnp.float32), axis=-1,
                    keepdims=True)                         # [bh, sq, 1]
    # lse cotangent: lse = logsumexp(s) => dL/ds += softmax(s) * dlse.
    # Folds into the same ds term as (dp - delta).
    if dlse is None:
        dlse = jnp.zeros(lse.shape, jnp.float32)
    else:
        dlse = dlse.astype(jnp.float32)

    if sk % block_k != 0:
        block_k = sk
    nk = sk // block_k

    kb = k.reshape(bh, nk, block_k, d).swapaxes(0, 1)      # [nk, bh, bk, d]
    vb = v.reshape(bh, nk, block_k, d).swapaxes(0, 1)
    has_bias = bias is not None
    if has_bias:
        nb = bias.shape[0]
        biasb = bias.reshape(nb, sq, nk, block_k).transpose(2, 0, 1, 3)
    else:
        biasb = jnp.zeros((nk, 1, 1, 1), jnp.float32)
    has_kvb = kvb is not None
    if has_kvb:
        kvbb = kvb.reshape(kvb.shape[0], nk, block_k).transpose(1, 0, 2)
    else:
        kvbb = jnp.zeros((nk, 1, 1), jnp.float32)

    q_pos = jnp.asarray(q_start, jnp.int32) + jnp.arange(sq)

    def one_block(dq_acc, blk):
        kj, vj, bj, kvbj, j = blk
        kjf, vjf = kj.astype(jnp.float32), vj.astype(jnp.float32)
        s = jnp.einsum("bqd,bkd->bqk", qf, kjf) * scale
        if has_bias:
            s = s + bj.astype(jnp.float32)
        if has_kvb:
            s = s + kvbj[:, None, :].astype(jnp.float32)
        k_local = j * block_k + jnp.arange(block_k)
        s = jnp.where(k_local[None, None, :] < k_len, s, NEG_INF)
        if causal:
            k_pos = jnp.asarray(k_start, jnp.int32) + k_local
            ahead = q_pos[None, :, None] - k_pos[None, None, :]
            s = jnp.where(ahead >= 0, s, NEG_INF)
            if window is not None:
                s = jnp.where(ahead < window, s, NEG_INF)
        elif isinstance(window, BlockDiffusion):
            s = jnp.where(window.visible(jnp.arange(sq)[:, None],
                                         k_local[None, :]), s, NEG_INF)
        p = jnp.where(s > NEG_INF * 0.5,
                      jnp.exp(s - lse[:, :, None]), 0.0)   # [bh, sq, bk]
        dp = jnp.einsum("bqd,bkd->bqk", do, vjf)
        if dropout > 0.0:
            # bit-exact twin of the kernels' _keep_mask
            kp = jnp.asarray(k_start, jnp.int32) + k_local
            bits = dropout_bits(
                offs[3], jnp.arange(bh)[:, None, None],
                q_pos[None, :, None], kp[None, None, :])
            keep = bits >= jnp.uint32(_drop_threshold(dropout))
            inv = 1.0 / (1.0 - dropout)
            pd = jnp.where(keep, p, 0.0) * inv
            dp = jnp.where(keep, dp, 0.0) * inv
        else:
            pd = p
        dv = jnp.einsum("bqk,bqd->bkd", pd, do)
        ds = p * (dp - delta + dlse[:, :, None])  # dL/ds: the bias grad
        ds_scaled = ds * scale         # dL/d(q·k): q/k grads
        dq_acc = dq_acc + jnp.einsum("bqk,bkd->bqd", ds_scaled, kjf)
        dk = jnp.einsum("bqk,bqd->bkd", ds_scaled, qf)
        return dq_acc, (dk, dv, ds if (has_bias and bias_grad)
                        else jnp.zeros((), jnp.float32))

    dq0 = jnp.zeros((bh, sq, d), jnp.float32)
    blks = (kb, vb, biasb, kvbb, jnp.arange(nk))
    dq, (dks, dvs, dss) = jax.lax.scan(one_block, dq0, blks)
    dk = dks.swapaxes(0, 1).reshape(bh, sk, d)
    dv = dvs.swapaxes(0, 1).reshape(bh, sk, d)
    if has_bias and bias_grad:
        # dss: [nk, bh, sq, bk] -> [bh, sq, sk]
        dbias = dss.transpose(1, 2, 0, 3).reshape(bh, sq, sk)
        if bias.shape[0] == 1:
            dbias = jnp.sum(dbias, axis=0, keepdims=True)
        dbias = dbias.astype(bias.dtype)
    elif has_bias:
        dbias = jnp.zeros_like(bias)
    else:
        dbias = None
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dbias)


# ---------------------------------------------------------------------------
# custom_vjp wiring
# ---------------------------------------------------------------------------

def _with(sel) -> dict:
    """The kernels' ``sel`` keyword, given only where there is a set."""
    return {} if sel is None else {"sel": sel}


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(6, 7, 8, 9, 10, 11, 12, 13, 14, 15))
def _flash_core(q, k, v, bias, kvb, sel, causal, window, scale, block_q,
                block_k, bwd_block_q, bwd_block_k, bias_grad, dropout, known,
                offs):
    """Returns (o, lse). lse is a true primal output with a correct
    cotangent path (its gradient folds into ds — needed by ring attention,
    which differentiates through the (o, lse) shard merge).
    ``bias_grad=False`` declares the bias non-differentiable (a constructed
    mask) and returns a zero cotangent without computing/materializing the
    O(S^2) dbias. ``kvb`` (per-key additive bias, always mask-semantics)
    likewise gets a zero cotangent. ``dropout`` is the static rate; the
    mask is recomputed from offs[3] (seed) in fwd and bwd. ``sel`` (a
    packed key set, ``pack_select``) is data, not a bias: zero cotangent.
    ``bwd_block_q``/``bwd_block_k`` size the backward kernels
    independently (their VMEM working set is ~3x the forward's); must
    divide the padded sequence lengths."""
    return _flash_fwd(q, k, v, bias, kvb, offs, causal=causal, scale=scale,
                      block_q=block_q, block_k=block_k, dropout=dropout,
                      known=known, window=window, **_with(sel))


def _flash_core_fwd(q, k, v, bias, kvb, sel, causal, window, scale, block_q,
                    block_k, bwd_block_q, bwd_block_k, bias_grad, dropout,
                    known, offs):
    o, lse = _flash_fwd(q, k, v, bias, kvb, offs, causal=causal, scale=scale,
                        block_q=block_q, block_k=block_k, dropout=dropout,
                        known=known, window=window, **_with(sel))
    o, lse = map(checkpoint_name, (o, lse), SAVED_NAMES)
    return (o, lse), ((q, k, v, bias, kvb, offs, lse, o), sel)


def _bwd_impl() -> str:
    """'pallas' (default) or 'chunked' (the jnp lax.scan twin) — the
    backward analog of the interpreter/compiled axis; tests pin both."""
    import os
    return os.environ.get("APEX_TPU_FLASH_BWD", "pallas")


# Crossover dispatch (VERDICT r4 #2). The reference ships eight fused
# MHA extensions precisely because composed attention wins at modest S
# (apex/contrib/examples/multihead_attn/perf_test_multihead_attn.py is
# its own crossover evidence); on TPU the shoe is on the other foot:
# XLA's composed attention beat this kernel 12x at S=1024 while the
# kernel wins 1.84x at S=4096 and is the ONLY path at S=16384 (r04,
# docs/PERF.md "Attention crossover"). impl='auto' in the modules routes
# below-crossover sequence lengths to reference_attention. 4096 is the
# conservative default — the smallest S where the kernel's win was
# measured; tools/kernel_bench.py --only flash_crossover prints the
# sweep that would justify another number.
DEFAULT_FLASH_MIN_S = 4096


def flash_min_s() -> int:
    """Smallest max(Sq, Sk) the 'auto' dispatch sends to the Pallas
    kernel: APEX_FLASH_MIN_S env > DEFAULT_FLASH_MIN_S — a function of
    the environment and of committed code, never of a file git does
    not track. Read at trace time (cheap: once per compile)."""
    import os
    env = os.environ.get("APEX_FLASH_MIN_S")
    return int(env) if env else DEFAULT_FLASH_MIN_S


def _flash_core_bwd(causal, window, scale, block_q, block_k, bwd_block_q,
                    bwd_block_k, bias_grad, dropout, known, res, cts):
    do, dlse = cts
    res, sel = res
    if sel is not None and _bwd_impl() == "chunked":
        raise NotImplementedError("select= has the Pallas backward alone "
                                  "(APEX_TPU_FLASH_BWD=chunked has none)")
    if _bwd_impl() == "chunked":
        # the chunked path exists for O(S*block) MEMORY: keep its k-chunk
        # at 128 regardless of the kernel block size (a 512 chunk would
        # quadruple its peak score/p/dp footprint)
        dq, dk, dv, dbias = _bwd_chunked(res, do, dlse, causal=causal,
                                         scale=scale,
                                         block_k=min(bwd_block_k, 128),
                                         bias_grad=bias_grad,
                                         dropout=dropout, window=window)
    else:
        dq, dk, dv, dbias = _bwd_pallas(res, do, dlse, causal=causal,
                                        scale=scale, block_q=bwd_block_q,
                                        block_k=bwd_block_k,
                                        bias_grad=bias_grad,
                                        dropout=dropout, known=known,
                                        window=window, **_with(sel))
    kvb, offs = res[4], res[5]
    d_kvb = None if kvb is None else jnp.zeros_like(kvb)
    d_offs = jnp.zeros_like(offs)  # int32 cotangent placeholder
    d_sel = None if sel is None else jnp.zeros_like(sel)
    return dq, dk, dv, dbias, d_kvb, d_sel, d_offs


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _check_window(window, causal) -> None:
    if window is None:
        return
    if not isinstance(window, (int, np.integer)) or window < 1:
        raise ValueError(f"window must be a plain positive integer (it "
                         f"shapes the grids), got {window!r}")
    if not causal:
        raise ValueError("a window needs causal=True: it reaches back "
                         "from the query's own position")


def _check_block_diffusion(bd, sq, sk, causal, window, select, bias,
                           q_start, k_start) -> None:
    if bd is None:
        return
    if causal or window is not None or select is not None \
            or bias is not None:
        raise ValueError("block_diffusion is a mask of its own: it goes "
                         "with causal=False and with neither a window, a "
                         "select nor a bias")
    if len(bd) != 2 or not all(isinstance(x, (int, np.integer)) and x >= 1
                               for x in bd) or bd[1] % bd[0]:
        raise ValueError(f"block_diffusion must be (block, length), plain "
                         f"positive integers (they shape the grids) with "
                         f"block dividing length, got {bd!r}")
    if sq != 2 * bd[1] or sk != sq or not all(
            isinstance(x, (int, np.integer)) and x == 0
            for x in (q_start, k_start)):
        raise ValueError(f"block_diffusion=(.., {bd[1]}) masks {2 * bd[1]} "
                         f"rows against themselves from position 0 (the "
                         f"noised copy, then the clean one), got {sq} x "
                         f"{sk} at ({q_start!r}, {k_start!r})")


def _structure(window, block_diffusion):
    """The kernels' static ``window``: the band's width, or the
    ``BlockDiffusion`` that rides in its place."""
    return window if block_diffusion is None \
        else BlockDiffusion(*block_diffusion)


def _capped(block: int, padded: int, most: int) -> int:
    """``block``, or where it is wider than ``most`` the largest of {512,
    384, 256, 192, 128} up to ``most`` that divides the padded length (a
    block of 384, 512 or 1024 guarantees a hit)."""
    if block > most:
        for cand in (512, 384, 256, 192, 128):
            if cand <= most and padded % cand == 0:
                return cand
    return block


def block_sizes(sq: int, sk: int, block_q: Optional[int] = None,
                block_k: Optional[int] = None,
                bwd_block_q: Optional[int] = None,
                bwd_block_k: Optional[int] = None,
                window: Optional[int] = None, d: Optional[int] = None):
    """``(block_q, block_k, bwd_block_q, bwd_block_k)`` as
    ``flash_attention`` tiles ``sq`` x ``sk`` scores: what was given, and
    its defaults for the rest; with a ``window``, at heads of up to ``d``
    = 128 lanes, the band's defaults (``WINDOW_BLOCK`` forward,
    ``WINDOW_BWD_BLOCK`` backward). A ``window`` needs the head width
    ``d``: the band's blocks do not fit VMEM at wider heads."""
    if window is not None and d is None:
        raise ValueError("block_sizes: a window needs the head width d")
    band = window is not None and d <= LANES
    # Adaptive default: wide blocks keep the MXU matmuls fat and cut the
    # grid-step count up to 16x vs a fixed 128 — at S=16k the fixed size
    # meant 262k sequential grid steps and the kernel ran
    # grid-overhead-bound (~1.5% MFU, docs/PERF.md r03). The pick is
    # divisor-aware (largest of 512/384/256/128 dividing the 128-rounded
    # length) so mid-length sequences don't pay pad blowup; note a wider
    # block changes the online-softmax accumulation ORDER for
    # 128 < S <= 512 vs the old fixed-128 blocking (allclose, not
    # bitwise, vs previous builds).
    most = WINDOW_BLOCK if band else MAX_BLOCK
    if block_q is None:
        block_q = _pick_block(sq, most)
    if block_k is None:
        block_k = _pick_block(sk, most)
    block_q = min(block_q, _round_up(sq, 16))
    block_k = min(block_k, _round_up(sk, 16))
    qpad = (-sq) % block_q
    kpad = (-sk) % block_k
    # Backward blocks default to the forward's CAPPED at q<=256 (k can
    # stay wide): the bwd kernels hold ~3x the forward's VMEM working
    # set, and the r4 on-chip sweep (docs/PERF.md r04 block sweep) measured
    # bwd 512x512 at 162.8 ms vs 18.4 ms for 256x512 at S=4096 — a VMEM
    # spill cliff. 256x512 was the sweep's best; the cap costs <7% vs
    # any other measured combo and avoids the 9x cliff. Overrides must
    # tile the padded lengths (the backward runs over the same padded
    # residuals).
    # With a window (the v5e of PR 39, WINDOW_BLOCK's readings) 512 x 512
    # was the best backward and no cliff: both sides cap at
    # WINDOW_BWD_BLOCK.
    if bwd_block_q is None:
        # largest of {256, 192, 128} dividing the padded length; sequences
        # whose own block is an odd size <= 256 keep it — one big tile
        # beats a sliver tile
        bwd_block_q = _capped(block_q, sq + qpad,
                              WINDOW_BWD_BLOCK if band else 256)
    if bwd_block_k is None:
        bwd_block_k = _capped(block_k, sk + kpad, WINDOW_BWD_BLOCK) \
            if band else block_k
    for name, blk, sz in (("bwd_block_q", bwd_block_q, sq + qpad),
                          ("bwd_block_k", bwd_block_k, sk + kpad)):
        if sz % blk:
            raise ValueError(f"{name}={blk} must divide the padded "
                             f"sequence length {sz}")
    return block_q, block_k, bwd_block_q, bwd_block_k


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    bias: Optional[jax.Array] = None, *,
                    kv_bias: Optional[jax.Array] = None,
                    causal: bool = False, scale: Optional[float] = None,
                    q_start=0, k_start=0,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    bwd_block_q: Optional[int] = None,
                    bwd_block_k: Optional[int] = None,
                    return_lse: bool = False,
                    bias_grad: bool = True,
                    dropout_rate: float = 0.0,
                    dropout_seed=0,
                    window: Optional[int] = None,
                    select: Optional[jax.Array] = None,
                    block_diffusion: Optional[tuple] = None):
    """Fused attention over [B, H, S, D] (or [BH, S, D]) inputs.

    bias: optional additive [1|BH, Sq, Sk] (or [B, H, Sq, Sk]) score bias —
    covers the reference's additive-mask and time-mask softmax variants
    (apex/contrib/multihead_attn/*_additive_mask_*).
    ``q_start``/``k_start``: global position offsets for causal masking of
    sequence shards (traced scalars — no recompile across ring steps).
    ``block_q``/``block_k`` tile the forward kernel (divisor-aware
    defaults up to MAX_BLOCK); ``bwd_block_q``/``bwd_block_k`` tile the
    backward kernels independently (their VMEM working set is ~3x the
    forward's — bwd 512x512 measured a 9x VMEM-spill cliff on v5e,
    docs/PERF.md r04 block sweep; sweep with ``tools/kernel_bench.py --only
    flash_blocks``). ``bwd_block_k`` defaults to ``block_k``;
    ``bwd_block_q`` defaults to ``block_q`` capped at the largest of
    {256, 192, 128} that divides the padded length (for block_q > 256).
    Explicit values must divide the padded sequence lengths.
    ``bias_grad=False`` marks the bias as a constructed mask whose
    cotangent is zero — skips materializing the O(Sq*Sk) bias gradient.
    ``kv_bias``: optional per-KEY additive bias [1|BH, Sk] (key-padding
    masks) — O(S) memory instead of the O(Sq*Sk) ``bias`` tensor, always
    mask-semantics (zero cotangent). Under ring attention it travels with
    its K/V shard.
    ``dropout_rate``/``dropout_seed``: in-kernel dropout applied to the
    softmax PROBABILITIES (the reference's fused softmax-dropout,
    apex/contrib/csrc/multihead_attn/dropout.h + softmax.h; module arg
    self_multihead_attn.py:24) — the [Sq, Sk] mask is never materialized;
    it is recomputed from a coordinate hash (``dropout_bits``) in the fwd
    and bwd kernels. ``dropout_seed`` may be a traced int32 scalar.
    ``window``: a sliding window under ``causal``, a plain integer: query
    ``i`` sees key ``j`` iff ``0 <= i - j < window`` in global positions
    (itself and the ``window - 1`` before it). The kernels' grids are cut
    to the band (a block wholly outside it gets no step: ``block_census``),
    the default blocks are the band's (``block_sizes``: at heads of up to
    128, up to 1024 x 1024 forward and 512 x 512 backward), and the three
    calls are named ``apex_flash_win_fwd`` / ``_win_bwd_dq``
    / ``_win_bwd_dkv``. ``None``: no window, the causal call as it was.
    ``select``: a per-query set of keys, data and not structure: int32
    [B, Sq, 128 * ceil(Sk / 4096)] as ``pack_select`` packs a bool [B, Sq,
    Sk] (a bit a key), shared by a row's BH / B heads; a key outside the
    set is masked like one above the diagonal (``causal`` and the key
    length still apply). It has a zero cotangent. The grids stay the
    causal ones, since which tiles hold a selected key is known only on
    the device, and a tile that holds none is masked like any other and
    not skipped. Key blocks are 512, 256 or 128
    wide (whole bits of a packed word), and the three calls are named
    ``apex_flash_sel_fwd`` / ``_sel_bwd_dq`` / ``_sel_bwd_dkv``. Neither
    a ``bias`` nor a ``window`` goes with it. ``None``: today's call.
    ``block_diffusion``: ``(block, length)``, plain integers, under
    ``causal=False``: the ``2 * length`` rows are a sequence's noised copy
    followed by its clean one, in blocks of ``block`` positions, and row
    ``a`` sees row ``b`` iff both are noised and of one block, or ``b`` is
    clean and its block lies before ``a``'s (``a`` noised) or up to
    ``a``'s (``a`` clean): ``BlockDiffusion``. The three grids hold exactly
    the tiles with a visible pair (``block_census(block_diffusion=)``),
    the blocks are the causal defaults, and the calls are named
    ``apex_flash_bd_fwd`` / ``_bd_bwd_dq`` / ``_bd_bwd_dkv``. Neither a
    ``window``, a ``select`` nor a ``bias`` goes with it, and the rows
    start at position 0. ``None``: today's call.
    """
    _check_window(window, causal)
    _check_block_diffusion(block_diffusion, q.shape[-2], k.shape[-2], causal,
                           window, select, bias, q_start, k_start)
    squeeze = q.ndim == 4
    if squeeze:
        b, h, _, _ = q.shape
        q = q.reshape(b * h, *q.shape[2:])
        k = k.reshape(b * h, *k.shape[2:])
        v = v.reshape(b * h, *v.shape[2:])
        if bias is not None and bias.ndim == 4:
            bias = bias.reshape(-1, bias.shape[-2], bias.shape[-1])
    bh, sq, d = q.shape
    sk = k.shape[1]
    if scale is None:
        scale = 1.0 / float(d) ** 0.5

    dpad = (-d) % LANES
    if select is not None:
        if bias is not None or window is not None:
            raise ValueError("select= goes with neither a bias nor a window")
        if select.ndim != 3 or bh % select.shape[0] or select.shape[1:] != (
                sq, LANES * -(-sk // SELECT_SPAN)):
            raise ValueError(
                f"select must be [B, {sq}, {LANES * -(-sk // SELECT_SPAN)}] "
                f"with B dividing {bh} (pack_select), got {select.shape}")
        # key blocks are whole bits of a packed word: 128 keys at the least
        if block_k is None:
            block_k = next(b for b in (512, 256, 128)
                           if _round_up(sk, LANES) % b == 0)
        for blk in (block_k, bwd_block_k or block_k):
            if blk % LANES or SELECT_SPAN % blk:
                raise ValueError(f"with select= a key block is 128 to 4096 "
                                 f"keys and divides 4096, got {blk}")
    block_q, block_k, bwd_block_q, bwd_block_k = block_sizes(
        sq, sk if select is None else _round_up(sk, LANES), block_q, block_k,
        bwd_block_q, bwd_block_k, window, d + dpad)
    qpad = (-sq) % block_q
    kpad = (-sk) % block_k

    qq, kk, vv, bb = q, k, v, bias
    if dpad:
        qq = jnp.pad(qq, ((0, 0), (0, 0), (0, dpad)))
        kk = jnp.pad(kk, ((0, 0), (0, 0), (0, dpad)))
        vv = jnp.pad(vv, ((0, 0), (0, 0), (0, dpad)))
    if qpad:
        qq = jnp.pad(qq, ((0, 0), (0, qpad), (0, 0)))
    if kpad:
        kk = jnp.pad(kk, ((0, 0), (0, kpad), (0, 0)))
        vv = jnp.pad(vv, ((0, 0), (0, kpad), (0, 0)))
    if bb is not None and (qpad or kpad):
        # padded-k masking happens in-kernel via k_len (offs[2]); bias
        # padding only needs to be finite to keep ds well-defined
        bb = jnp.pad(bb, ((0, 0), (0, qpad), (0, kpad)))
    if bb is not None:
        bb = bb.astype(jnp.float32)
    kvb = kv_bias
    if kvb is not None:
        if kvb.ndim != 2:
            raise ValueError(f"kv_bias must be [1|BH, Sk], got {kvb.shape}")
        if kpad:
            kvb = jnp.pad(kvb, ((0, 0), (0, kpad)))
        kvb = kvb.astype(jnp.float32)[:, None, :]   # [nb, 1, Sk]

    offs = jnp.stack([jnp.asarray(q_start, jnp.int32),
                      jnp.asarray(k_start, jnp.int32),
                      jnp.asarray(sk, jnp.int32),
                      jnp.asarray(dropout_seed, jnp.int32)])
    # offsets that are plain integers are known when the grids are made
    known = (int(q_start), int(k_start), sk) if all(
        isinstance(x, (int, np.integer)) for x in (q_start, k_start)) else None
    if select is not None and qpad:
        select = jnp.pad(select, ((0, 0), (0, qpad), (0, 0)))
    out, lse = _flash_core(qq, kk, vv, bb, kvb, select, causal,
                           _structure(window, block_diffusion),
                           float(scale), block_q, block_k, bwd_block_q,
                           bwd_block_k, bool(bias_grad), float(dropout_rate),
                           known, offs)
    lse = lse[:, :sq]
    out = out[:, :sq, :d]

    if squeeze:
        out = out.reshape(b, h, sq, d)
        if return_lse:
            lse = lse.reshape(b, h, sq)
    if return_lse:
        return out, lse
    return out
