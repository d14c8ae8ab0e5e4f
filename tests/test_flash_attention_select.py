"""``flash_attention(select=)``: a per-query set of keys, data and not
structure, shared by a row's heads. The kernels in interpret mode against
``reference_attention`` with the same set (forward and the three
gradients, a tile with no selected key included), the packed layout, which
tiles hold a key, what it refuses, and ``select=None`` as today's call."""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import key_set as KS

fa = importlib.import_module(
    "apex_tpu.contrib.multihead_attn.flash_attention")


def _top_mask(key, b, s, k):
    """bool [b, s, s]: each query's ``min(t + 1, k)`` best causal keys by a
    random score."""
    sc = jax.random.normal(key, (b, s, s))
    sc = jnp.where(jnp.arange(s)[:, None] >= jnp.arange(s)[None, :], sc,
                   -jnp.inf)
    n = jnp.minimum(jnp.arange(s) + 1, k)
    kth = jnp.take_along_axis(-jnp.sort(-sc, -1), jnp.broadcast_to(
        (n - 1)[None, :, None], (b, s, 1)), -1)
    return sc >= kth


def _qkv(b, h, s, d, dtype=jnp.float32):
    return [jax.random.normal(k, (b, h, s, d), jnp.float32).astype(dtype)
            for k in jax.random.split(jax.random.key(s + d), 3)]


@pytest.mark.parametrize("s", [1, 100, 4096, 4097, 9000])
def test_pack_and_unpack_are_inverses(s):
    mask = jax.random.bernoulli(jax.random.key(s), 0.3, (2, 3, s))
    words = KS.pack_select(mask)
    assert words.dtype == jnp.int32
    assert words.shape == (2, 3, 128 * -(-s // 4096))
    np.testing.assert_array_equal(KS.unpack_select(words, s), mask)
    # bit b of lane j of tile u is key 4096 u + 128 b + j
    key = min(s - 1, 4096 * ((s - 1) // 4096) + 128 * 3 + 5)
    one = KS.pack_select(jnp.zeros((1, 1, s), bool).at[0, 0, key].set(True))
    u, rest = divmod(key, 4096)
    assert int(one[0, 0, 128 * u + rest % 128]) == 1 << (rest // 128)
    assert int(jnp.sum(jax.lax.population_count(one))) == 1


CASES = {
    # b, h, s, d, keys a query, flash_attention's blocks
    "one_block": dict(b=2, h=2, s=48, d=16, k=5),
    "lane_pad": dict(b=1, h=3, s=300, d=64, k=17),
    "tiles_128": dict(b=1, h=2, s=640, d=128, k=40, block_q=128,
                      block_k=128),
    "tiles_256x512": dict(b=1, h=2, s=1024, d=128, k=100, block_q=256,
                          block_k=512, bwd_block_q=128, bwd_block_k=256),
    "two_spans": dict(b=1, h=1, s=4608, d=128, k=300),
    "bf16": dict(b=2, h=4, s=256, d=128, k=30, dtype=jnp.bfloat16),
}


@pytest.mark.parametrize("case", CASES)
def test_the_kernels_against_the_reference_with_the_same_set(case):
    """Forward, log-sum-exp and the three gradients; keys 128..255 are
    selected by no query from 256 on (where the sequence reaches them), so
    some live tile holds no selected key: every score of it is masked."""
    kw = dict(CASES[case])
    b, h, s, d, k = (kw.pop(x) for x in "bhsdk")
    dtype = kw.pop("dtype", jnp.float32)
    q, kk, v = _qkv(b, h, s, d, dtype)
    mask = _top_mask(jax.random.key(7), b, s, k)
    if s > 256:
        mask = mask.at[:, 256:, 128:256].set(False)
    sel = KS.pack_select(mask)
    if case == "tiles_128":     # queries 256.. against keys 128..255
        live = KS.select_live(sel, 128, 128)[0]
        assert int(live[2:, 1].sum()) == 0 and int(live[2:, 0].min()) == 1
    w = jnp.cos(jnp.arange(d, dtype=jnp.float32))

    def loss(attend, **more):
        def f(q, kk, v):
            o, lse = attend(q, kk, v, causal=True, select=sel,
                            return_lse=True, **more)
            return jnp.sum(o.astype(jnp.float32) * w) + 0.1 * jnp.sum(
                jnp.sin(lse))
        return f
    got = fa.flash_attention(q, kk, v, causal=True, select=sel,
                             return_lse=True, **kw)
    want = fa.reference_attention(q, kk, v, causal=True, select=sel,
                                  return_lse=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    for a, c in zip(got, want):
        np.testing.assert_allclose(a.astype(jnp.float32),
                                   c.astype(jnp.float32), atol=tol)
    g = jax.grad(loss(fa.flash_attention, **kw), (0, 1, 2))(q, kk, v)
    g_want = jax.grad(loss(fa.reference_attention), (0, 1, 2))(q, kk, v)
    for a, c in zip(g, g_want):
        np.testing.assert_allclose(a.astype(jnp.float32),
                                   c.astype(jnp.float32),
                                   atol=40 * tol if dtype == jnp.bfloat16
                                   else 10 * tol)
    # the set matters: dense causal attention is another function
    dense = fa.reference_attention(q, kk, v, causal=True)
    assert float(jnp.abs(dense.astype(jnp.float32)
                         - want[0].astype(jnp.float32)).max()) > 1e-2


def test_a_set_of_every_causal_key_is_causal_attention():
    q, k, v = _qkv(1, 2, 384, 128)
    sel = KS.pack_select(jnp.ones((1, 384, 384), bool))
    np.testing.assert_allclose(
        fa.flash_attention(q, k, v, causal=True, select=sel),
        fa.flash_attention(q, k, v, causal=True), atol=2e-6)


def test_a_row_of_the_batch_has_its_own_set_and_its_heads_share_it():
    q, k, v = _qkv(2, 3, 256, 128)
    mask = _top_mask(jax.random.key(3), 2, 256, 20)
    got = fa.flash_attention(q, k, v, causal=True,
                             select=KS.pack_select(mask))
    for row in range(2):
        for head in range(3):
            want = fa.reference_attention(
                q[row, head][None], k[row, head][None], v[row, head][None],
                causal=True, select=KS.pack_select(mask[row][None]))
            np.testing.assert_allclose(got[row, head], want[0], atol=2e-5)
    # [BH, S, D] inputs: a row's heads are consecutive
    flat = fa.flash_attention(*(a.reshape(6, 256, 128) for a in (q, k, v)),
                              causal=True, select=KS.pack_select(mask))
    np.testing.assert_allclose(flat.reshape(got.shape), got, atol=2e-6)


def test_select_live_reads_a_tile_from_its_bits():
    mask = jnp.zeros((1, 512, 8192), bool)
    mask = mask.at[0, 300, 5000].set(True).at[0, 10, 127].set(True)
    live = KS.select_live(KS.pack_select(mask), 256, 512)
    assert live.shape == (1, 2, 16)
    want = np.zeros((2, 16), np.int32)
    want[1, 5000 // 512] = want[0, 0] = 1
    np.testing.assert_array_equal(live[0], want)
    fine = KS.select_live(KS.pack_select(mask), 128, 128)
    assert int(fine.sum()) == 2 and int(fine[0, 2, 5000 // 128]) == 1


def test_what_select_refuses():
    q, k, v = _qkv(1, 2, 256, 128)
    sel = KS.pack_select(jnp.ones((1, 256, 256), bool))
    with pytest.raises(ValueError, match="neither a bias nor a window"):
        fa.flash_attention(q, k, v, causal=True, select=sel, window=64)
    with pytest.raises(ValueError, match="neither a bias nor a window"):
        fa.flash_attention(q, k, v, jnp.zeros((1, 256, 256)), causal=True,
                           select=sel)
    with pytest.raises(ValueError, match="pack_select"):
        fa.flash_attention(q, k, v, causal=True, select=sel[:, :, :64])
    with pytest.raises(ValueError, match="divides 4096"):
        fa.flash_attention(q, k, v, causal=True, select=sel, block_k=64)


def test_the_chunked_backward_has_no_select(monkeypatch):
    monkeypatch.setenv("APEX_TPU_FLASH_BWD", "chunked")
    q, k, v = _qkv(1, 1, 128, 128)
    sel = KS.pack_select(jnp.ones((1, 128, 128), bool))
    with pytest.raises(NotImplementedError):
        jax.grad(lambda q: jnp.sum(fa.flash_attention(
            q, k, v, causal=True, select=sel)))(q)


def test_the_calls_are_named_and_select_none_is_todays_call():
    """With a set the three calls carry names of their own; without one
    the program is the one it was, letter for letter: the names, the
    grids, the operands."""
    q, k, v = _qkv(1, 2, 1024, 128, jnp.bfloat16)
    sel = KS.pack_select(_top_mask(jax.random.key(1), 1, 1024, 64))

    def grad_text(**kw):
        return str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
            fa.flash_attention(q, k, v, causal=True, **kw).astype(
                jnp.float32)), (0, 1, 2)))(q, k, v))
    with_set = grad_text(select=sel)
    for kernel in ("fwd", "bwd_dq", "bwd_dkv"):
        assert len(re.findall(rf"name=apex_flash_sel_{kernel}\b",
                              with_set)) == 1
        assert not re.findall(rf"name=apex_flash_{kernel}\b", with_set)
    without = grad_text()
    assert without == grad_text(select=None)
    assert "apex_flash_sel" not in without
    for kernel in ("fwd", "bwd_dq", "bwd_dkv"):
        assert len(re.findall(rf"name=apex_flash_{kernel}\b", without)) == 1
    assert fa.SAVED_NAMES == ("apex_flash_out", "apex_flash_lse")


# the six LM cells' flash shapes (sq = sk, head width as the kernels see
# it, window) and their default blocks, forward and backward, as PR 41's
# tree had them: select= moves none of them
PARENT_BLOCKS = {
    "cgpt_train_s2048": ((2048, 128, None), (512, 512, 256, 512)),
    "qnext_train_s8192": ((8192, 256, None), (512, 512, 256, 512)),
    "kvl_train_s8192": ((8192, 256, None), (512, 512, 256, 512)),
    "lfm2_train_s8192": ((8192, 128, None), (512, 512, 256, 512)),
    "mellum2_train_s8192.full": ((8192, 128, None), (512, 512, 256, 512)),
    "mellum2_train_s8192.window": ((8192, 128, 1024),
                                   (1024, 1024, 512, 512)),
    "keye_train_s16384": ((16384, 128, None), (512, 512, 256, 512)),
}


@pytest.mark.parametrize("cell", PARENT_BLOCKS)
def test_the_cells_default_blocks_are_the_parents(cell):
    (s, d, window), want = PARENT_BLOCKS[cell]
    assert fa.block_sizes(s, s, window=window, d=d) == want
