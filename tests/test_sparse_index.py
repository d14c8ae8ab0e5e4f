"""``ops.sparse_index``: the exact top-k without a sort against
``jax.lax.top_k`` (lengths around ``topk``, planted ties, queries with
fewer keys than ``topk``), the search's kernel against both to the bit of
a packed word, the indexer's loss and its gradient against autodiff of the
plain formula, the kernels against their ``jax.numpy`` oracle, and the two
``stop_gradient``s that part the gradients."""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import key_set as KS, sparse_index as SI
from apex_tpu.ops.pallas import sparse_index as K

fa = importlib.import_module(
    "apex_tpu.contrib.multihead_attn.flash_attention")


def _by_top_k(scores, n_most):
    """``jax.lax.top_k``'s choice of each row's ``n_most`` largest, of the
    entries above ``-inf``."""
    _, idx = jax.lax.top_k(scores, min(n_most, scores.shape[-1]))
    lead = jnp.indices(idx.shape)[:-1]
    return jnp.zeros(scores.shape, bool).at[(*lead, idx)].set(True) \
        & (scores > -jnp.inf)


def _indexer(b=2, t=96, h=4, d=16, key=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(key), 3)
    return (jax.random.normal(ks[0], (b, t, h, d)).astype(dtype),
            jax.random.normal(ks[1], (b, t, d)).astype(dtype),
            0.3 * jax.random.normal(ks[2], (b, t, h)))


@pytest.mark.parametrize("t, topk", [(96, 20), (64, 64), (64, 63), (40, 100),
                                     (130, 1), (256, 129)])
@pytest.mark.parametrize("impl", ["fast", "reference"])
def test_the_selection_is_top_ks_at_lengths_around_topk(t, topk, impl):
    """Exactly ``min(t + 1, topk)`` keys a query, the ones
    ``jax.lax.top_k`` picks from the same scores."""
    qi, ki, w = _indexer(t=t, key=t + topk)
    sel = SI.select_keys(qi, ki, w, topk, chunk=t // 2 if t % 2 == 0 else None,
                         impl=impl)
    assert sel.shape == (2, t, 128) and sel.dtype == jnp.int32
    mask = KS.unpack_select(sel, t)
    scores = SI.index_scores(qi, ki, w, impl=impl)
    np.testing.assert_array_equal(mask, _by_top_k(scores, topk))
    np.testing.assert_array_equal(
        mask.sum(-1), np.broadcast_to(np.minimum(np.arange(t) + 1, topk),
                                      (2, t)))
    assert not bool(jnp.any(mask & (scores == -jnp.inf)))


@pytest.mark.parametrize("impl", ["fast", "reference"])
@pytest.mark.parametrize("levels", [2, 0.5, 0.0])
def test_planted_ties_go_to_the_lower_key(levels, impl):
    """Scores rounded to a few levels (at 0.0: every score equal, signed
    zeros among them) tie by the hundred: the choice is still exact and
    ``top_k``'s."""
    t, topk = 192, 50
    raw = SI.index_scores(*_indexer(t=t, key=5), impl="reference")
    scores = jnp.where(raw > -jnp.inf, jnp.round(raw * levels) / max(
        levels, 1.0), raw)
    if levels == 0.0:
        scores = jnp.where(raw > -jnp.inf, jnp.where(raw > 0, 0.0, -0.0),
                           raw)
        scores = jnp.where(scores == 0.0, 0.0, scores)  # as the ops do
    n = jnp.broadcast_to(jnp.minimum(jnp.arange(t) + 1, topk), (2, t))
    got = KS.unpack_select(SI._search(scores, 0, topk, impl), t)
    want = _by_top_k(scores, topk)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got.sum(-1), n)
    if levels == 0.0:       # all equal: the first n keys
        np.testing.assert_array_equal(
            got[0, 100], np.arange(t) < 50)


def _chunk_scores(b, c, t, start, key, above=-jnp.inf):
    """A chunk's ``[b, c, t]`` scores, query ``r`` at position ``start +
    r``; ``above`` in the key slabs wholly above a block's last query (the
    rest of what a query cannot see is ``-inf``, as the ops leave it)."""
    x = jax.random.normal(jax.random.key(key), (b, c, t))
    rows, keys = start + jnp.arange(c)[:, None], jnp.arange(t)[None, :]
    bq, bk = K.search_blocks(c, t + (-t) % 128)
    last = start + (jnp.arange(c)[:, None] // bq + 1) * bq - 1
    dead = keys // bk > last // bk
    return jnp.where(keys <= rows, x, jnp.where(dead, above, -jnp.inf))


# (queries, keys, the chunk's first position, topk)
SEARCHES = {
    "three_blocks_one_slab_of_three": (48, 1536, 0, 20),
    "a_chunk_that_starts_at_1000": (48, 1536, 1000, 300),
    "a_second_span_of_one_tile": (16, 4224, 4100, 2048),
    "an_odd_chunk_keys_padded_fewer_than_topk": (65, 200, 100, 1000),
    "one_key_a_query": (8, 256, 0, 1),
}


@pytest.mark.parametrize("c, t, start, topk", list(SEARCHES.values()),
                         ids=list(SEARCHES))
def test_the_search_kernel_packs_the_reference_choice(c, t, start, topk):
    """``apex_idx_search`` gives ``pack_select(topk_mask(...))`` to the
    bit, and ``jax.lax.top_k``'s keys; a key tile wholly above a block's
    last query is not visited (whatever stands there, its words are
    zeros)."""
    scores = _chunk_scores(2, c, t, start, key=c + t)
    want = SI._search(scores, start, topk, "reference")
    got = K.search(_chunk_scores(2, c, t, start, key=c + t, above=1e9),
                   start, topk)
    assert got.shape == want.shape and got.dtype == jnp.int32
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(KS.unpack_select(got, t),
                                  _by_top_k(scores, topk))


@pytest.mark.parametrize("tied", [False, True])
def test_a_block_with_no_tie_and_one_with_a_tie_in_one_row(tied):
    """No two of a row's float32 scores at its threshold are equal: the
    block takes every key at the threshold and searches no further. One row
    with a second key at its threshold's score, at a lower position, takes
    that one and leaves the other."""
    c, t, start, topk = 16, 512, 300, 64
    scores = _chunk_scores(1, c, t, start, key=11)
    row = scores[0, 5]
    at = int(jnp.argsort(-row)[topk - 1])           # the 64th largest
    assert at > 0 and int(jnp.sum(row == row[at])) == 1
    if tied:
        lower = int(jnp.argmin(row[:at]))           # unchosen, visible
        scores = scores.at[0, 5, lower].set(row[at])
    words = K.search(scores, start, topk)
    np.testing.assert_array_equal(
        words, SI._search(scores, start, topk, "reference"))
    got = KS.unpack_select(words, t)
    np.testing.assert_array_equal(got, _by_top_k(scores, topk))
    if tied:
        assert bool(got[0, 5, lower]) and not bool(got[0, 5, at])


def test_the_searchs_blocks_follow_the_shapes():
    """128 queries at 16,384 keys (8 MB of float32 scores), fewer at a
    longer row, a divisor of the chunk, an odd chunk whole."""
    assert K.search_blocks(1024, 16384) == (128, 512)
    assert K.search_blocks(1024, 32768) == (64, 512)
    assert K.search_blocks(48, 1536) == (16, 512)
    assert K.search_blocks(65, 256) == (65, 256)
    assert K.search_blocks(16, 4224) == (16, 128)


def test_the_kth_largest_by_bisection_on_the_bits():
    x = jnp.asarray([[3.5, -1.0, 3.5, 0.0, -2.0, 7.25, -jnp.inf, 1e-30]])
    u = SI._ordered(x)
    assert bool(jnp.all(jnp.argsort(u[0]) == jnp.argsort(x[0], stable=True)))
    # the bits tell -0.0 from 0.0, a comparison does not: the ops that
    # make scores leave one zero
    assert int(SI._ordered(jnp.float32(-0.0))) + 1 \
        == int(SI._ordered(jnp.float32(0.0)))
    for n, want in ((1, 7.25), (2, 3.5), (3, 3.5), (4, 1e-30), (6, -1.0)):
        got = SI._kth_largest(u, jnp.asarray([n]), 32)
        assert int(got[0]) == int(SI._ordered(jnp.float32(want)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_kernels_against_their_oracle(dtype):
    qi, ki, w = _indexer(t=128, h=3, d=24, key=2, dtype=dtype)
    fast = SI.index_scores(qi, ki, w, impl="fast")
    plain = SI.index_scores(qi, ki, w, impl="reference")
    seen = plain > -jnp.inf
    np.testing.assert_array_equal(fast > -jnp.inf, seen)
    np.testing.assert_array_equal(seen[0], np.tril(np.ones((128, 128), bool)))
    np.testing.assert_allclose(jnp.where(seen, fast, 0.0),
                               jnp.where(seen, plain, 0.0), atol=2e-5)
    by_hand = jnp.einsum("bth,bths->bts", w, jax.nn.relu(jnp.einsum(
        "bthd,bsd->bths", qi.astype(jnp.float32), ki.astype(jnp.float32))))
    np.testing.assert_allclose(jnp.where(seen, plain, 0.0),
                               jnp.where(seen, by_hand, 0.0), atol=2e-5)


def _main(b=2, t=96, hq=8, g=2, d=32, key=9):
    ks = jax.random.split(jax.random.key(key), 3)
    return [jax.random.normal(k, (b, n, t, d))
            for k, n in zip(ks, (hq, g, g))]


def _plain_loss(qi, ki, w, q, kk, keep, scale):
    """``L_I`` as written, whole ``[B, H, T, T]`` arrays and autodiff."""
    i = jnp.einsum("bth,bths->bts", w, jax.nn.relu(
        jnp.einsum("bthd,bsd->bths", qi, ki)))
    s = jnp.where(keep[:, None], jnp.einsum("bhtd,bhsd->bhts", q, kk)
                  * scale, -jnp.inf)
    p = jax.lax.stop_gradient(jnp.mean(jax.nn.softmax(s, -1), 1))
    log_qi = jax.nn.log_softmax(jnp.where(keep, i, -jnp.inf), -1)
    return jnp.mean(jnp.sum(jnp.where(
        keep, jax.scipy.special.xlogy(p, p) - p * jnp.where(keep, log_qi, 0),
        0.0), -1))


@pytest.mark.parametrize("impl", ["fast", "reference"])
def test_the_loss_and_its_gradient_are_autodiffs_of_the_plain_formula(impl):
    t, topk = 96, 20
    qi, ki, w = _indexer(t=t, key=4)
    q, k, v = _main(t=t)
    sel = SI.select_keys(qi, ki, w, topk, impl="reference")
    keep = KS.unpack_select(sel, t)
    kk, vv = (jnp.repeat(a, 4, 1) for a in (k, v))
    scale = 32 ** -0.5
    _, lse = fa.reference_attention(q, kk, vv, causal=True, select=sel,
                                    scale=scale, return_lse=True)
    want, g_want = jax.value_and_grad(_plain_loss, (0, 1, 2))(
        qi, ki, w, q, kk, keep, scale)

    def loss(qi, ki, w, q=q, k=k, lse=lse):
        return SI.index_loss(qi, ki, w, q, k, lse, sel, scale=scale,
                             chunk=32, impl=impl)
    got, g = jax.value_and_grad(loss, (0, 1, 2))(qi, ki, w)
    assert float(want) > 0.1
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    assert float(loss(qi, ki, w)) == pytest.approx(float(want), rel=2e-6)
    for a, c in zip(g, g_want):
        np.testing.assert_allclose(a, c, atol=2e-6 * float(jnp.abs(c).max())
                                   + 1e-9)
    # a cotangent scales it; p is a target: nothing reaches q, k or lse
    twice = jax.grad(lambda *a: 2.0 * loss(*a), (0, 1, 2))(qi, ki, w)
    np.testing.assert_allclose(twice[0], 2.0 * g[0], rtol=1e-6)
    to_main = jax.grad(lambda q, k, lse: loss(qi, ki, w, q, k, lse),
                       (0, 1, 2))(q, k, lse)
    assert all(float(jnp.abs(a).max()) == 0.0 for a in to_main)


def test_a_checkpoint_that_saves_the_names_runs_neither_again():
    """Under ``save_only_these_names(*SAVED_NAMES)`` the recomputed pass
    holds no second search and no second loss: the scores' kernel appears
    twice (the choice, the loss), the probabilities' once."""
    t = 64
    qi, ki, w = _indexer(t=t, key=6)
    q, k, _ = _main(t=t)
    lse = jnp.zeros((2, 8, t))

    def f(qi, ki, w):
        sel = SI.select_keys(qi, ki, w, 16)
        return SI.index_loss(qi, ki, w, q, k, lse, sel, scale=0.2)
    kept = jax.checkpoint(f, policy=jax.checkpoint_policies
                          .save_only_these_names(*SI.SAVED_NAMES))
    text = str(jax.make_jaxpr(jax.grad(kept, (0, 1, 2)))(qi, ki, w))
    calls = lambda kernel, text: len(re.findall(
        rf"name=apex_idx_{kernel}\b", text))
    assert [calls(k, text) for k in ("scores", "probs", "grad")] == [2, 1, 1]
    again = str(jax.make_jaxpr(jax.grad(jax.checkpoint(f), (0, 1, 2)))(
        qi, ki, w))
    assert calls("scores", again) > 2
    assert SI.SAVED_NAMES == ("apex_idx_select", "apex_idx_grads")


def test_the_live_tile_share():
    """Of the causal 512 x 512 tiles, those that hold a selected key."""
    t = 2048
    mask = jnp.zeros((1, t, t), bool).at[0, :, 0].set(True)    # key 0 alone
    mask = mask.at[0, jnp.arange(t), jnp.arange(t)].set(True)  # and itself
    assert float(SI.live_tile_pct(KS.pack_select(mask))) \
        == pytest.approx(100.0 * 7 / 10)        # 4 diagonal + column 0 of 10
    full = jnp.tril(jnp.ones((t, t), bool))[None]
    assert float(SI.live_tile_pct(KS.pack_select(full))) == 100.0
    assert float(SI.live_tile_pct(KS.pack_select(mask[:, :96, :96]))) == 100.0
