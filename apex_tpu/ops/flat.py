"""Flat parameter store — the TPU-native data model replacing tensor lists.

Apex batches elementwise/reduction work over Python lists of scattered CUDA
allocations through ``multi_tensor_apply`` (reference:
csrc/multi_tensor_apply.cuh:15-130 packs <=110 tensor pointers plus a
block->(tensor, chunk) map into kernel arguments; apex/multi_tensor_apply/
multi_tensor_apply.py:24 is the Python chokepoint). The TPU-idiomatic design
is the inverse: keep ONE flat HBM-resident buffer per (role, dtype) — params,
master params, grads, exp_avg, exp_avg_sq — plus a static, hashable
``SegmentTable`` mapping each parameter to an aligned slice. Every
``multi_tensor_*`` op then becomes a single fused XLA/Pallas op over the flat
buffer; per-tensor semantics (LAMB trust ratios, NovoGrad per-tensor norms)
use the table's segment-id vector.

Segments are padded to ``align`` elements (default 128 = one TPU lane group)
so Pallas block boundaries never straddle two parameters. Padding is kept
zero by every op in this library, so sums/norms over segments stay exact.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# One TPU vreg lane row. 128 keeps every segment lane-aligned; callers that
# feed fp32 Pallas kernels with (8, 128) tiling may prefer align=1024.
DEFAULT_ALIGN = 128


def _round_up(n: int, align: int) -> int:
    return ((n + align - 1) // align) * align


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class SegmentTable:
    """Static metadata for a flat buffer: where each leaf lives.

    Hashable and registered static so it can be closed over or passed through
    ``jax.jit`` without retracing on value changes (there are none — it is
    all Python ints/tuples).
    """

    treedef: Any
    shapes: tuple[tuple[int, ...], ...]
    sizes: tuple[int, ...]          # exact element counts
    offsets: tuple[int, ...]        # aligned start offsets into the flat buffer
    padded_sizes: tuple[int, ...]   # size rounded up to align
    total: int                      # flat buffer length (sum of padded sizes)
    align: int

    @property
    def num_segments(self) -> int:
        return len(self.sizes)

    def segment_ids(self) -> jax.Array:
        """int32[total] mapping every flat element to its segment (pad elements
        included), for ``jax.ops.segment_sum``-style per-tensor reductions."""
        ids = np.zeros((self.total,), dtype=np.int32)
        for i, (off, psz) in enumerate(zip(self.offsets, self.padded_sizes)):
            ids[off : off + psz] = i
        return jnp.asarray(ids)

    def valid_mask(self) -> jax.Array:
        """bool[total]: True on real elements, False on alignment padding."""
        mask = np.zeros((self.total,), dtype=bool)
        for off, sz in zip(self.offsets, self.sizes):
            mask[off : off + sz] = True
        return jnp.asarray(mask)


def make_table(tree: Any, align: int = DEFAULT_ALIGN) -> SegmentTable:
    """Build the segment table for a pytree of arrays (values unused)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes, sizes, offsets, padded = [], [], [], []
    cursor = 0
    for leaf in leaves:
        shape = tuple(np.shape(leaf))
        size = int(np.prod(shape)) if shape else 1
        psz = _round_up(max(size, 1), align)
        shapes.append(shape)
        sizes.append(size)
        offsets.append(cursor)
        padded.append(psz)
        cursor += psz
    return SegmentTable(
        treedef=treedef,
        shapes=tuple(shapes),
        sizes=tuple(sizes),
        offsets=tuple(offsets),
        padded_sizes=tuple(padded),
        total=cursor,
        align=align,
    )


def flatten(tree: Any, table: SegmentTable | None = None,
            dtype: jnp.dtype | None = None,
            align: int = DEFAULT_ALIGN) -> tuple[jax.Array, SegmentTable]:
    """Pack a pytree into one flat (padded, zero-filled) buffer.

    Functional equivalent of ``apex_C.flatten`` (reference:
    csrc/flatten_unflatten.cpp:5-9) plus the alignment/padding that
    ``multi_tensor_apply`` achieves with its chunk map.
    """
    if table is None:
        table = make_table(tree, align=align)
    leaves = jax.tree_util.tree_leaves(tree)
    if len(leaves) != len(table.sizes):
        raise ValueError(
            f"tree has {len(leaves)} leaves but table describes "
            f"{len(table.sizes)} segments — was the table built for this tree?")
    for i, leaf in enumerate(leaves):
        size = int(np.prod(np.shape(leaf))) if np.shape(leaf) else 1
        if size != table.sizes[i]:
            raise ValueError(
                f"leaf {i} has {size} elements but table segment {i} expects "
                f"{table.sizes[i]}")
    if dtype is None:
        dtype = jnp.result_type(leaves[0]) if leaves else jnp.float32
    parts = []
    for leaf, size, psz in zip(leaves, table.sizes, table.padded_sizes):
        flat = jnp.ravel(jnp.asarray(leaf)).astype(dtype)
        if psz != size:
            flat = jnp.pad(flat, (0, psz - size))
        parts.append(flat)
    if not parts:
        return jnp.zeros((0,), dtype=dtype), table
    return jnp.concatenate(parts), table


def _unflatten_impl(flat: jax.Array, table: SegmentTable,
                    dtype) -> Any:
    if dtype is not None and flat.dtype != jnp.dtype(dtype):
        flat = flat.astype(dtype)
    leaves = []
    for shape, size, off in zip(table.shapes, table.sizes, table.offsets):
        leaves.append(jax.lax.slice(flat, (off,), (off + size,))
                      .reshape(shape))
    return jax.tree_util.tree_unflatten(table.treedef, leaves)


_LINEAR_CALL_DIFFABLE: bool | None = None


def _linear_call_diffable() -> bool:
    """Whether this jax exposes differentiation through ``linear_call``
    (older jaxlibs implement only its transpose, so ``jax.grad`` of a
    step containing unflatten dies with NotImplementedError). Probed once
    on a scalar — the result decides which custom-derivative mechanism
    ``unflatten`` pins its transpose with."""
    global _LINEAR_CALL_DIFFABLE
    if _LINEAR_CALL_DIFFABLE is None:
        try:
            jax.grad(lambda x: jax.custom_derivatives.linear_call(
                lambda _, f: f, lambda _, ct: ct, None, x))(0.0)
            _LINEAR_CALL_DIFFABLE = True
        except NotImplementedError:
            _LINEAR_CALL_DIFFABLE = False
    return _LINEAR_CALL_DIFFABLE


def unflatten(flat: jax.Array, table: SegmentTable,
              dtype: jnp.dtype | None = None) -> Any:
    """Recover the pytree from a flat buffer (``apex_C.unflatten``,
    reference: csrc/flatten_unflatten.cpp:11-13). Static offsets — free under
    jit (XLA slices, no gather).

    ``dtype`` converts on the FLAT buffer before slicing: one fused convert
    instead of one per leaf — per-leaf converts each pay XLA per-op
    overhead (~9 ms total for RN50's 161 params on a v5e, docs/PERF.md r03).

    Differentiating through ``unflatten(master, table, half)`` is the fast
    way to get flat master grads, so the transpose is pinned via
    ``linear_call`` to ``flatten`` (ONE concat + ONE convert) — autodiff's
    native transpose of N slices is N pad-then-adds, which measured
    ~30 ms/step at RN50 scale. ``linear_call`` (not custom_vjp) keeps
    forward-mode autodiff working: unflatten is linear, so a jvp just
    applies it to the tangents. On jaxlibs whose ``linear_call`` cannot be
    differentiated at all, a ``custom_vjp`` carries the same pinned
    transpose (reverse-mode only)."""
    in_dtype = flat.dtype

    def _fwd(_, f):
        return _unflatten_impl(f, table, dtype)

    def _transpose(_, ct):
        leaves = jax.tree_util.tree_leaves(ct)
        common = jnp.result_type(*leaves) if leaves else in_dtype
        buf = flatten(ct, table=table, dtype=common)[0]
        return buf.astype(in_dtype)

    # with a dtype this is the O2 cast of the master and, transposed, the
    # flat fp32 gradient: prof.SCOPES' "amp_cast" (metadata only)
    with jax.named_scope("amp_cast") if dtype is not None \
            else contextlib.nullcontext():
        if _linear_call_diffable():
            return jax.custom_derivatives.linear_call(_fwd, _transpose,
                                                      None, flat)

        @jax.custom_vjp
        def _unflat(f):
            return _fwd(None, f)

        _unflat.defvjp(lambda f: (_fwd(None, f), None),
                       lambda _res, ct: (_transpose(None, ct),))
        return _unflat(flat)


def split_table(table: SegmentTable,
                counts: Sequence[int]) -> tuple[SegmentTable, ...]:
    """Cut ``table`` at leaf boundaries into contiguous sub-tables of
    ``counts`` leaves each, in the flat's order (``sum(counts)`` is the
    table's leaf count). A sub-table's offsets are relative to its own
    slice of the flat buffer (see :func:`split`), and it unflattens to the
    list of its leaves. One count gives back ``table`` itself."""
    if sum(counts) != table.num_segments or min(counts, default=0) < 1:
        raise ValueError(
            f"counts {tuple(counts)} do not partition a table of "
            f"{table.num_segments} leaves")
    if len(counts) == 1:
        return (table,)
    subs, lo = [], 0
    for n in counts:
        hi, start = lo + n, table.offsets[lo]
        subs.append(SegmentTable(
            treedef=jax.tree_util.tree_structure([0] * n),
            shapes=table.shapes[lo:hi], sizes=table.sizes[lo:hi],
            offsets=tuple(o - start for o in table.offsets[lo:hi]),
            padded_sizes=table.padded_sizes[lo:hi],
            total=sum(table.padded_sizes[lo:hi]), align=table.align))
        lo = hi
    return tuple(subs)


def split(flat: jax.Array,
          tables: Sequence[SegmentTable]) -> tuple[jax.Array, ...]:
    """The slices of ``flat`` that :func:`split_table`'s sub-tables
    describe, in order; one table is the buffer itself."""
    if len(tables) == 1:
        return (flat,)
    bounds = np.cumsum([0] + [t.total for t in tables])
    return tuple(jax.lax.slice(flat, (int(lo),), (int(hi),))
                 for lo, hi in zip(bounds, bounds[1:]))


# The TPU compiler joins large buffers by updating the result in place,
# one fusion an operand, and walks each operand in windows of whole
# 1024-element tiles whose count must divide the operand's: 20,506 tiles
# (a layer's biases, norms and mlp.w1) go 2 at a time, at 135 GB/s on a
# v5e, where 16,384 go 512 at a time at 650 GB/s. Cut at a multiple of
# this many elements, the head of every operand finds wide windows and
# only a tail of a few tiles goes narrowly (PERF.md section 6, PR 28:
# 19 buckets of 1.6 GB joined in 5.0 ms, not 11.7).
_JOIN_CUT = 512 * 1024


def join(bufs: Sequence[jax.Array], divisor=None) -> jax.Array:
    """The buffers of :func:`split` (or their gradients) as one flat buffer
    again, each divided by ``divisor`` on the way (a sum's average rides
    the pass that joins, and costs none of its own); one buffer is itself,
    divided."""
    def scaled(x):
        return x if divisor is None else x / divisor
    if len(bufs) == 1:
        return scaled(bufs[0])
    parts = []
    for buf in bufs:
        head = buf.shape[0] // _JOIN_CUT * _JOIN_CUT
        # sliced first, divided after: two slices of one array side by
        # side the compiler would put back together
        parts += [scaled(buf[:head]), scaled(buf[head:])] \
            if 0 < head < buf.shape[0] else [scaled(buf)]
    return jnp.concatenate(parts)


def unflatten_split(bufs: Sequence[jax.Array],
                    tables: Sequence[SegmentTable], treedef: Any,
                    dtype: jnp.dtype | None = None) -> Any:
    """The whole tree from the slices of :func:`split`, each through its
    own :func:`unflatten`: differentiated with respect to ``bufs`` it
    gives one flat gradient a slice (one concat + one convert each),
    ready when that slice's leaves are and not when the last leaf is."""
    return jax.tree_util.tree_unflatten(treedef, [
        leaf for buf, table in zip(bufs, tables)
        for leaf in jax.tree_util.tree_leaves(
            unflatten(buf, table, dtype=dtype))])


def zeros_like_flat(table: SegmentTable, dtype=jnp.float32) -> jax.Array:
    return jnp.zeros((table.total,), dtype=dtype)
