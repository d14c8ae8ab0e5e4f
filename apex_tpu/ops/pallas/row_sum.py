"""A token's rows of a buffer sorted by expert, added up by a kernel that
walks the buffer in the tokens' order: ``out[n] = sum_e buf[at[n, e]]``
over the ``e`` with ``at[n, e] >= 0``.

``contrib/moe/expert_layer.py`` holds the (token, expert) pairs on the
experts a chip has in one buffer of rows sorted by expert and, inside an
expert's group, by token. The two sums that lead from rows back to tokens
(the combine, and the transpose of the rows' gather) are scatter-adds by
token as JAX writes them, which XLA runs on the TPU as a pass over the
buffer and a serial pass over the tokens; as gathers of ``top_k`` rows a
token they scatter nothing, but XLA's gather takes 50-64 ns a row from a
table in HBM and fetches the slots that are on absent experts too, three
in four on a chip that holds a quarter of the experts. Neither uses what
the sort gives: **the tokens of one block that chose one expert sit in
consecutive rows of that expert's group**, so a block of tokens needs, of
each held expert, one short run of the buffer.

:func:`sum_rows` (``apex_moe_rowsum``) takes ``at [N, held]``, the row of
token ``n``'s pair on held expert ``e`` or -1 (:func:`columns` makes it of
the layer's ``pos [N, top_k]`` and the pairs' experts), and walks the tokens a
block of ``BLOCK`` at a time. For each expert the block's rows lie
between the least and the largest ``at`` of its column, a range the
wrapper reads a block and hands over as scalar prefetch in chunks of
``CHUNK`` rows (what a row-slice of an HBM operand has to be aligned to,
for float32 and bfloat16 alike: Mosaic takes no single row of a tiled
``[rows, d]`` operand). A round fetches one chunk of every expert that
still has one (``held`` slices of ``[CHUNK, d]``, in flight together),
marks for each token the one row of each chunk that is its own (a 0/1
matrix ``[BLOCK, held * CHUNK]`` from ``at`` and the chunks' first rows)
and adds ``marks @ chunks`` to the block's float32 sum: a live row is
fetched once or twice whatever ``top_k`` is, a row that is nobody's (a dead
row, another block's) meets zeros, nothing but the result is written, and
the sum is rounded to the result's type once. The product is exact: a
mark is 0 or 1, and a float32 chunk enters as three bfloat16 parts that
add up to it. A round costs its fetches' latency and little else; a block
of 256 tokens whose experts each see an eighth of the tokens needs two or
three.

**Readings** (the v5e, the call alone, 16,384 tokens, ms; builder's, PR
43), against XLA's scatter-add and its gather-sum of ``top_k`` rows a
token, float32 rows (the combine) | bfloat16 rows (``dx``):

====================================  =====================  ===========  ===========
pairs for rows (``d``; experts held)  scatter-add, 2 calls   gather-sum   this kernel
====================================  =====================  ===========  ===========
131,072 for 65,536 (2304; 16 of 64)   6.86-6.87 | 6.51-7.88  8.40 | 6.59  2.24 | 1.04
131,072 for 65,536 (2048; 16 of 128)  5.30-7.86 | 5.21-6.48  7.87 | 6.14  1.48 | 0.70
98,304 for 24,576 (2048; 8 of 64)     2.51-2.52 | 2.38       5.78 | 1.32  1.02 | 0.54
65,536 for 16,384 (2048; 8 of 64)     1.95-2.00 | 1.84       3.72 | 0.92  0.85 | 0.46
163,840 for 9,216 (2048; 16 of 512)   1.45 | 1.36            4.10 | 2.11  1.13 | 0.54
====================================  =====================  ===========  ===========

It wins at every shape the five expert cells have: a share of 8 or 16
experts. Its stage, its marks and its products grow with the experts held
where the rows a token needs grow with ``top_k`` alone, so where a chip
holds many experts it fetches and multiplies mostly other tokens' rows
(same v5e, 16,384 tokens; second session, PR 43):

====================================  ===================  =============  =============
pairs for rows (``d``; experts held)  scatter-add          gather-sum     this kernel
====================================  ===================  =============  =============
131,072 for 81,920 (2304; 32 of 64)   8.44 | 8.00          8.05 | 6.51    4.11 | 1.80
131,072 for 139,264 (2304; 64 of 64)  13.77 | 13.10        7.59 | 5.87    7.79 | 3.29
131,072 for 147,328 (2048; 128, all)  10.95 | 10.87        6.87 | 5.32    9.86 | 4.18
163,840 for 228,864 (2048; 512, all)  16.49 | 16.39        8.53 | 6.63    no fit | 12.14
====================================  ===================  =============  =============

(16,000 tokens, the last block 128 short, at the first table's first
shape: 2.65 | 1.25.) **The rule** (:func:`takes`, a static function of the
shapes, nothing else): the kernel while ``held`` times the bfloat16 parts
of a chunk (1, or float32's 3) is at most ``EXPERT_PARTS`` = 128, which
keeps float32 rows to 42 experts (it wins at 32 by 2 x and ties at 64)
and bfloat16 rows to 128 (it wins there by 1.3 x and loses at 512), and
while a round's chunks fit the kernel's VMEM (:func:`_vmem`: rows of 8192
in float32 at 10 experts, of 12,288 at 2; ``tests/test_chip_compile.py``
compiles a whole layer of 64). Past either the layer's gather-sum in
``jax.numpy`` runs, which at whole layers, where every slot names a live
row, is itself ahead of the scatter-add.

Blocks of 64 | 128 | 256 tokens read 2.67 | 2.19 | 2.24 and 1.33 | 1.09 |
1.04 at the first table's first shape and 1.58 | 1.33 | 1.13 and 0.82 |
0.68 | 0.54 at its last (fewer rounds, each a latency); chunks of 32 rows
were slower everywhere (3.04 and 1.35 at the first: twice the product for
rounds that were already few). Those bodies had their loops over the experts
unrolled, which cost every program that traces the kernel seconds of
set-up; as it stands (``lax.fori_loop``) the first shape reads 2.30 and
1.05 a layer inside its training step.

What a dead row may hold: anything finite (zero times it is zero; a row
that is not finite reaches the tokens whose chunks it lies in).
Shapes: ``d`` in whole lanes and ``rows`` in whole chunks (:func:`takes`);
any ``N`` (the last block's missing tokens have no row).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.pallas._common import LANES, interpret_mode, vma

__all__ = ["columns", "sum_rows", "takes"]

BLOCK = 256         # tokens a grid step
CHUNK = 16          # rows a fetch: bfloat16's tile of rows, twice float32's
EXPERT_PARTS = 128  # the most of (experts held) x (bfloat16 parts a chunk)
_VMEM_LIMIT = 64 << 20
_PAST = 1 << 30      # a first row past any buffer's
_F32, _BF16 = jnp.float32, jnp.bfloat16


def takes(d: int, rows: int, held: int, itemsize: int) -> bool:
    """Whether the kernel takes these shapes and, by the readings above,
    wins at them: rows of ``d`` in whole lanes, a buffer in whole chunks,
    no more than ``EXPERT_PARTS`` bfloat16 parts of a round's chunks (an
    expert's chunk is one, three in float32: ``itemsize`` 4), and a
    round's chunks within the kernel's VMEM. Any number of tokens."""
    return (d % LANES == 0 and rows % CHUNK == 0
            and held * (3 if itemsize == 4 else 1) <= EXPERT_PARTS
            and _vmem(d, held, itemsize) <= _VMEM_LIMIT)


def _vmem(d: int, held: int, itemsize: int) -> int:
    """Bytes of VMEM a grid step needs, by what the body holds at once: a
    round's chunks, a float32 stage's three bfloat16 parts and the float32
    remainder they are cut from, the marks in the types they pass
    through, the block's sum, a product and the result's two buffers."""
    k = held * CHUNK
    parts = k * d * (3 * 2 + 2 * 4) if itemsize == 4 else 0
    marks = BLOCK * k * (4 + 4 + 2)
    return (k * d * itemsize + parts + marks
            + BLOCK * d * (4 + 4 + 2 * itemsize))


def columns(pos, col, cols: int):
    """``at [N, cols]`` from ``pos [N, K]``, a row or -1, and ``col [N,
    K]``, the column each sits in (a token's are distinct; one of ``cols``
    or past them has none): ``at[n, col[n, j]] = pos[n, j]``, -1 where a
    token has no row in a column."""
    hot = (col[:, :, None] == jnp.arange(cols)) & (pos >= 0)[:, :, None]
    return jnp.sum(jnp.where(hot, pos[:, :, None] + 1, 0), axis=1) - 1


def _parts(x):
    """``x`` as bfloat16 terms that add up to it: itself, or a float32's
    three (8 bits of mantissa each)."""
    if x.dtype == _BF16:
        return [x]
    out = []
    for _ in range(3):
        out.append(x.astype(_BF16))
        x = x - out[-1].astype(_F32)
    return out


def _kernel(first_ref, chunks_ref, rounds_ref, at_ref, buf_ref, out_ref,
            stage, acc, sem, *, held: int):
    """``first_ref`` / ``chunks_ref [blocks * held]``: an expert's first
    chunk for this block and how many it has; ``rounds_ref [blocks]``: the
    most of them. ``at_ref [BLOCK, held]``; ``buf_ref`` the buffer in HBM;
    ``stage [held * CHUNK, d]``: a round's chunks, an expert after the
    other."""
    b = pl.program_id(0)
    k = held * CHUNK

    @pl.when(b == 0)
    def _():        # finite, whatever the memory held: zero times it is zero
        stage[...] = jnp.zeros_like(stage)

    acc[...] = jnp.zeros_like(acc)
    at = at_ref[...]
    expert = lax.broadcasted_iota(jnp.int32, (1, held), 1)
    # column c of the marks is row c % CHUNK of expert c // CHUNK's chunk
    spread = (lax.broadcasted_iota(jnp.int32, (held, k), 1) // CHUNK
              == lax.broadcasted_iota(jnp.int32, (held, k), 0)).astype(_BF16)
    row = (lax.broadcasted_iota(jnp.int32, (BLOCK, k), 1) % CHUNK).astype(
        _F32)

    def a_round(q, carry):
        def fetch(e):
            start = pl.multiple_of((first_ref[b * held + e] + q) * CHUNK,
                                   CHUNK)
            return pltpu.make_async_copy(
                buf_ref.at[pl.ds(start, CHUNK)],
                stage.at[pl.ds(pl.multiple_of(e * CHUNK, CHUNK), CHUNK)],
                sem)

        def due(e):
            return q < chunks_ref[b * held + e]

        # loops over the experts, not unrolled: a body is traced once, and
        # the kernel is traced for every pass of every run of layers
        def begin(e, start):
            pl.when(due(e))(lambda: fetch(e).start())
            # the first row of e's chunk along the lanes; an expert with
            # no chunk left starts past all
            return jnp.where(expert == e, jnp.where(
                due(e), (first_ref[b * held + e] + q) * CHUNK, _PAST), start)
        start = lax.fori_loop(0, held, begin,
                              jnp.zeros((1, held), jnp.int32))

        def end(e, carry):
            pl.when(due(e))(lambda: fetch(e).wait())
            return carry
        lax.fori_loop(0, held, end, 0)
        # a token's row within its expert's chunk of this round, 0 ..
        # CHUNK - 1, or -1
        within = at - start
        within = jnp.where((at >= 0) & (within >= 0) & (within < CHUNK),
                           within, -1)
        marks = (jnp.dot(within.astype(_BF16), spread,
                         preferred_element_type=_F32) == row).astype(_BF16)
        acc[...] += functools.reduce(jnp.add, (
            jnp.dot(marks, part, preferred_element_type=_F32)
            for part in _parts(stage[...])))
        return carry
    lax.fori_loop(0, rounds_ref[b], a_round, 0)
    out_ref[...] = acc[...].astype(out_ref.dtype)


def sum_rows(buf, at, dtype=None):
    """``out[n] = sum over e with at[n, e] >= 0 of buf[at[n, e]]``, added
    in float32 and rounded to ``dtype`` (``buf``'s) once. ``buf [rows,
    d]`` float32 or bfloat16; ``at [N, held]`` int32, a row of ``buf`` or
    -1. Right for any ``at``; fast where the rows of a column within
    ``BLOCK`` consecutive tokens lie close together."""
    rows, d = buf.shape
    n, held = at.shape
    dtype = dtype or buf.dtype
    blocks = -(-n // BLOCK)     # the last block's missing tokens have no row
    at = jnp.pad(at, ((0, blocks * BLOCK - n), (0, 0)), constant_values=-1)
    mine = at.reshape(blocks, BLOCK, held)
    first = jnp.min(jnp.where(mine >= 0, mine, rows), axis=1) // CHUNK
    chunks = jnp.maximum(jnp.max(mine, axis=1) // CHUNK + 1 - first, 0)
    out = pl.pallas_call(
        functools.partial(_kernel, held=held),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(blocks,),
            in_specs=[pl.BlockSpec((BLOCK, held), lambda b, *_: (b, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((BLOCK, d), lambda b, *_: (b, 0)),
            scratch_shapes=[pltpu.VMEM((held * CHUNK, d), buf.dtype),
                            pltpu.VMEM((BLOCK, d), _F32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((blocks * BLOCK, d), dtype,
                                       vma=vma(buf, at)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret_mode(), name="apex_moe_rowsum",
    )(first.reshape(-1).astype(jnp.int32),
      chunks.reshape(-1).astype(jnp.int32),
      jnp.max(chunks, axis=1).astype(jnp.int32), at, buf)
    return out[:n]
