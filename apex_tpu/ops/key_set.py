"""A per-query set of keys as ``flash_attention(select=)`` reads it: a bit
a key, packed so that a kernel's key block is whole bits of one lane tile.

``ops/sparse_index.py`` makes the sets and ``contrib/multihead_attn/
flash_attention.py`` reads them; both import the layout from here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.ops.pallas._common import LANES

__all__ = ["SELECT_SPAN", "pack_select", "select_live", "unpack_select"]

# keys one lane tile of a packed set covers: bit ``b`` of the word in lane
# ``j`` of tile ``u`` is key ``SELECT_SPAN * u + LANES * b + j``, so a key
# block of a multiple of 128 keys is whole bits of one [block_q, 128] tile
# and unpacks with shifts alone, no move across lanes
SELECT_SPAN = 32 * LANES


def pack_select(mask):
    """A per-query key set ``mask`` bool [B, Sq, Sk] as ``flash_attention
    (select=)`` reads it: int32 [B, Sq, 128 * ceil(Sk / 4096)], a bit a key
    (``SELECT_SPAN``'s layout), 1/32 of a byte mask."""
    b, sq, sk = mask.shape
    m = jnp.pad(mask, ((0, 0), (0, 0), (0, (-sk) % SELECT_SPAN)))
    m = m.reshape(b, sq, -1, 32, LANES).astype(jnp.uint32)
    words = jnp.sum(m << jnp.arange(32, dtype=jnp.uint32)[:, None], axis=3,
                    dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.int32).reshape(b, sq, -1)


def unpack_select(select, sk: int):
    """``pack_select``'s inverse: bool [B, Sq, sk]."""
    b, sq, w = select.shape
    words = jax.lax.bitcast_convert_type(select, jnp.uint32).reshape(
        b, sq, w // LANES, 1, LANES)
    bits = (words >> jnp.arange(32, dtype=jnp.uint32)[:, None]) & 1
    return bits.reshape(b, sq, -1)[:, :, :sk] != 0


def select_live(select, block_q: int, block_k: int):
    """int32 [B, Sq / block_q, spans * SELECT_SPAN / block_k]: whether a
    ``block_q`` x ``block_k`` tile of scores holds a selected key. No
    kernel reads it (a tile with no selected key is masked like any other
    and not skipped: at 2,048 keys of 16,384 no 512 x 512 tile is empty);
    it is what ``sparse_index.live_tile_pct`` counts."""
    b, sq, w = select.shape
    words = jax.lax.bitcast_convert_type(select, jnp.uint32).reshape(
        b, sq // block_q, block_q, w // LANES, LANES)
    any_row = jax.lax.reduce(words, np.uint32(0), jax.lax.bitwise_or, (2, 4))
    bits = block_k // LANES                 # bits of a word a tile covers
    per = SELECT_SPAN // block_k            # tiles a span
    of_tile = jnp.asarray([((1 << bits) - 1) << (i * bits)
                           for i in range(per)], jnp.uint32)
    live = (any_row[..., None] & of_tile) != 0
    return live.reshape(b, sq // block_q, -1).astype(jnp.int32)
