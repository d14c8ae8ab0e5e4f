"""The flash kernels under a sliding window: the band in the grid (the
step table, ``block_census``), the mask in the kernels against a naive
masked softmax with offsets known and traced, the Pallas backward against
the chunked oracle, and a window that covers everything as the causal call
itself. A file of its own beside ``test_multihead_attn.py`` (interpret mode
at S <= 512 in blocks of 128), so that the test run's workers can take it
apart from that long file."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.contrib.multihead_attn import (
    flash_attention, reference_attention)

# the module: the package's ``flash_attention`` is the function
fa = sys.modules["apex_tpu.contrib.multihead_attn.flash_attention"]

BLOCKS = dict(block_q=128, block_k=128)


def _qkv(bh, sq, sk, d=128, key=0):
    ks = jax.random.split(jax.random.key(key), 4)
    return (jax.random.normal(ks[0], (bh, sq, d), jnp.float32),
            jax.random.normal(ks[1], (bh, sk, d), jnp.float32),
            jax.random.normal(ks[2], (bh, sk, d), jnp.float32),
            jax.random.normal(ks[3], (bh, sq, d), jnp.float32))


def _naive(q, k, v, window, q_start=0, k_start=0):
    """A masked softmax written out: query ``i`` sees key ``j`` iff ``0 <=
    i - j < window`` in global positions; a row that sees nothing is 0."""
    s = jnp.einsum("bqd,bkd->bqk", q, k) * q.shape[-1] ** -0.5
    ahead = (q_start + jnp.arange(q.shape[1]))[:, None] \
        - (k_start + jnp.arange(k.shape[1]))[None, :]
    seen = (ahead >= 0) & (ahead < window)
    m = jnp.max(jnp.where(seen, s, -jnp.inf), -1, keepdims=True)
    m = jax.lax.stop_gradient(jnp.where(jnp.isfinite(m), m, 0.0))
    p = jnp.where(seen, jnp.exp(jnp.where(seen, s - m, 0.0)), 0.0)
    return jnp.einsum("bqk,bkd->bqd", p / jnp.maximum(
        jnp.sum(p, -1, keepdims=True), 1e-30), v)


# windows that are and are not multiples of a block; a query shard set off
# from its keys, offsets plain (the band is constants in the step table)
# and traced (a ring step: ``_block_kind`` decides in jax.numpy)
WINDOW_CASES = {
    "w1": dict(sq=384, sk=384, window=1),
    "w100": dict(sq=384, sk=384, window=100),
    "w128": dict(sq=384, sk=384, window=128),
    "w200": dict(sq=384, sk=384, window=200),
    "ragged_w100": dict(sq=300, sk=300, window=100),
    "offset_w128": dict(sq=256, sk=512, window=128, q_start=256),
    "offset_w200_traced": dict(sq=256, sk=512, window=200, q_start=384,
                               k_start=128, traced=True),
    "past_w100_traced": dict(sq=128, sk=256, window=100, q_start=512,
                             traced=True),
}


@pytest.mark.parametrize("case", sorted(WINDOW_CASES))
def test_window_forward_and_gradients_against_a_naive_masked_softmax(case):
    c = dict(WINDOW_CASES[case])
    traced, window = c.pop("traced", False), c["window"]
    q_start, k_start = c.get("q_start", 0), c.get("k_start", 0)
    q, k, v, w = _qkv(2, c["sq"], c["sk"], key=len(case))

    def flash(q, k, v, qs, ks):
        return flash_attention(q, k, v, causal=True, window=window,
                               q_start=qs, k_start=ks, **BLOCKS)
    if traced:
        run = jax.jit(lambda *a: flash(*a, jnp.int32(q_start),
                                       jnp.int32(k_start)))
    else:
        run = lambda *a: flash(*a, q_start, k_start)
    want = _naive(q, k, v, window, q_start, k_start)
    np.testing.assert_allclose(run(q, k, v), want, atol=2e-5)
    np.testing.assert_allclose(reference_attention(
        q, k, v, causal=True, window=window, q_start=q_start,
        k_start=k_start), want, atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(run(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    ref = jax.grad(lambda *a: jnp.sum(_naive(*a, window, q_start, k_start)
                                      * w), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, ref):
        np.testing.assert_allclose(a, b, atol=1e-4, err_msg=name)
    if case == "past_w100_traced":      # every key out of sight: zeros
        assert not np.asarray(want).any() and not np.asarray(got[1]).any()


def test_a_window_that_covers_everything_is_the_causal_call_bitwise():
    """``window >= S``: the causal call's steps exactly (rows and columns,
    the identity where the causal table is), and outputs and gradients
    bit for bit."""
    q, k, v, w = _qkv(2, 384, 384, key=3)
    for window in (384, 5000):
        for by_col in (False, True):
            for every in (False, True):
                args = ((0, 0, 384), None, 3, 3, 128, 128, True, by_col,
                        every)
                np.testing.assert_array_equal(
                    fa._steps(*args, window=window), fa._steps(*args))
        assert fa.block_census(384, 384, 128, 128, True, window=window) \
            == fa.block_census(384, 384, 128, 128, True)

        def loss(q, k, v, window=window):
            o, lse = flash_attention(q, k, v, causal=True, window=window,
                                     return_lse=True, **BLOCKS)
            return jnp.sum(o * w) + jnp.sum(lse), (o, lse)
        (_, got), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
        (_, want), g_want = jax.value_and_grad(
            lambda *a: loss(*a, window=None), argnums=(0, 1, 2),
            has_aux=True)(q, k, v)
        for a, b in zip(got + g, want + g_want):
            np.testing.assert_array_equal(a, b)
    # one key short of everything is another program, and another result
    assert len(fa._steps((0, 0, 384), None, 3, 3, 128, 128, True,
                         window=129)) == 5
    assert float(jnp.abs(flash_attention(
        q, k, v, causal=True, window=383, **BLOCKS) - want[0]).max()) > 1e-6


@pytest.mark.parametrize("window", [100, 256])
def test_window_pallas_backward_against_the_chunked_oracle(window,
                                                           monkeypatch):
    """``_bwd_chunked`` under the same window, with an additive bias whose
    gradient both give (a shared bias row: the dbias kernel's live test)."""
    q, k, v, w = _qkv(2, 256, 256, key=window)
    bias = jax.random.normal(jax.random.key(5), (1, 256, 256)) * 0.3

    def grads():
        return jax.grad(lambda q, k, v, b: jnp.sum(flash_attention(
            q, k, v, b, causal=True, window=window, **BLOCKS) * w),
            argnums=(0, 1, 2, 3))(q, k, v, bias)
    monkeypatch.setenv("APEX_TPU_FLASH_BWD", "pallas")
    got = grads()
    monkeypatch.setenv("APEX_TPU_FLASH_BWD", "chunked")
    want = grads()
    for name, a, b in zip(("dq", "dk", "dv", "dbias"), got, want):
        np.testing.assert_allclose(a, b, atol=1e-4, err_msg=name)
    ahead = np.arange(256)[:, None] - np.arange(256)[None, :]
    out = (ahead < 0) | (ahead >= window)
    assert not np.asarray(got[3])[0][out].any()     # nothing out of sight
    assert np.asarray(got[3])[0][~out].any()


@pytest.mark.parametrize("by_col", [False, True], ids=["rows", "columns"])
@pytest.mark.parametrize("sizes", [
    (8192, 512, 512, 1024), (8192, 256, 512, 1024), (8192, 1024, 1024, 1024),
    (1024, 128, 128, 200), (1024, 128, 256, 1)],
    ids=lambda s: "s{}_{}x{}_w{}".format(*s))
def test_the_band_is_the_grid(sizes, by_col):
    """``block_census`` with a window: dead + interior + edge are all the
    blocks, the live ones are those a naive mask leaves something of, and
    the step table sweeps exactly them, line after line."""
    s, bq, bk, window = sizes
    nq, nk = s // bq, s // bk
    census = fa.block_census(s, s, bq, bk, True, window=window)
    assert sum(census.values()) == nq * nk
    ahead = np.arange(s)[:, None] - np.arange(s)[None, :]
    seen = ((ahead >= 0) & (ahead < window)).reshape(nq, bq, nk, bk)
    live, whole = seen.any((1, 3)), seen.all((1, 3))
    assert census == {"dead": int((~live).sum()), "interior": int(whole.sum()),
                      "edge": int((live & ~whole).sum())}
    steps = fa._steps((0, 0, s), None, nq, nk, bq, bk, True, by_col,
                      window=window)
    qb, kb = steps & fa._IDX, (steps >> fa._KB) & fa._IDX
    assert len(steps) == live.sum() and live[qb, kb].all()
    assert ((steps >> fa._LIVE) & 1).all()
    line = kb if by_col else qb
    starts = np.r_[True, np.diff(line) != 0]
    np.testing.assert_array_equal((steps >> fa._FIRST) & 1, starts)
    np.testing.assert_array_equal((steps >> fa._LAST) & 1,
                                  np.r_[starts[1:], True])
    # a band never is the identity: the index maps read the table
    assert fa._at(steps, (0, 0, s), nq, nk, by_col) is fa._step_block


def test_block_census_of_the_window_cell():
    """What the benchmark's driver states of a window layer at S 8192,
    window 1024: 45 live blocks of 256 a head at 512 x 512 (the causal
    sweep keeps 136), 3 a row from the third row on."""
    assert fa.block_census(8192, 8192, 512, 512, True, window=1024) == {
        "dead": 211, "interior": 15, "edge": 30}
    assert fa.block_census(8192, 8192, 256, 512, True, window=1024) == {
        "dead": 422, "interior": 30, "edge": 60}
    assert fa.block_census(8192, 8192, 1024, 1024, True, window=1024) == {
        "dead": 49, "interior": 0, "edge": 15}


def test_a_window_is_a_plain_integer_under_causal():
    q, k, v, _ = _qkv(1, 64, 64, d=32)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, window=16)
    with pytest.raises(ValueError, match="plain positive integer"):
        flash_attention(q, k, v, causal=True, window=0)
    with pytest.raises(ValueError, match="plain positive integer"):
        jax.jit(lambda w: flash_attention(q, k, v, causal=True, window=w))(
            jnp.int32(16))
    with pytest.raises(ValueError, match="causal"):
        reference_attention(q, k, v, window=16)


def test_the_windowed_calls_have_names_of_their_own():
    """``apex_flash_win_fwd`` / ``_win_bwd_dq`` / ``_win_bwd_dkv``: still
    ``^%(\\w+_)?apex_flash_`` to the readers that exist, told from a full
    layer's by ``apex_flash_win_``; no window, the names as they were."""
    import re
    q, k, v, _ = _qkv(1, 256, 256)

    def names(window):
        text = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(flash_attention(
            *a, causal=True, window=window, **BLOCKS)), argnums=(0, 1, 2)))(
                q, k, v))
        return sorted(set(re.findall(r"name=(apex_flash_\w+)", text))
                      - set(fa.SAVED_NAMES))
    assert names(128) == ["apex_flash_win_bwd_dkv", "apex_flash_win_bwd_dq",
                          "apex_flash_win_fwd"]
    assert names(None) == ["apex_flash_bwd_dkv", "apex_flash_bwd_dq",
                           "apex_flash_fwd"]


def test_the_bands_default_blocks():
    """With a window, at heads of up to 128, forward blocks up to 1024
    and backward blocks up to 512 on both sides (the v5e's readings:
    ``WINDOW_BLOCK``); wider heads and no window keep the causal
    defaults; what is given is kept."""
    assert fa.block_sizes(8192, 8192) == (512, 512, 256, 512)
    assert fa.block_sizes(8192, 8192, window=1024, d=128) \
        == (1024, 1024, 512, 512)
    assert fa.block_sizes(8192, 8192, window=1024, d=256) \
        == (512, 512, 256, 512)
    assert fa.block_sizes(1536, 1536, window=64, d=128) \
        == (512, 512, 512, 512)
    assert fa.block_sizes(768, 768, window=64, d=128) == (384, 384, 384, 384)
    assert fa.block_sizes(96, 96, window=32, d=128) \
        == fa.block_sizes(96, 96) == (96, 96, 96, 96)
    assert fa.block_sizes(8192, 8192, 256, 256, window=1024, d=128) \
        == (256, 256, 256, 256)
    with pytest.raises(ValueError):     # the band's blocks go by head width
        fa.block_sizes(8192, 8192, window=1024)
