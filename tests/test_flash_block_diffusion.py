"""The flash kernels under the block-diffusion mask (a sequence's noised
copy beside its clean one, ``flash_attention(block_diffusion=(B, L))``):
the mask in the kernels and in the two jnp arms against a naive masked
softmax over the dense boolean mask, written here from the four cases; the
grid is the mask (no tile without a visible pair has a step, forward or
backward); the calls' names; what goes with the argument and what does
not; and that the calls that existed name no ``apex_flash_bd_*`` call. A
file of its own (interpret mode at 2L <= 400), so that the test run's
workers can take it apart from ``test_multihead_attn.py``."""

import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.contrib.multihead_attn import (
    flash_attention, reference_attention)

# the module: the package's ``flash_attention`` is the function
fa = sys.modules["apex_tpu.contrib.multihead_attn.flash_attention"]


def dense_mask(block: int, length: int) -> np.ndarray:
    """bool [2L, 2L], row ``a`` sees row ``b``: the four cases."""
    a = np.arange(2 * length)[:, None]
    b = np.arange(2 * length)[None, :]
    blk_a, blk_b = a % length // block, b % length // block
    return np.where(a < length,
                    np.where(b < length, blk_a == blk_b, blk_b < blk_a),
                    (b >= length) & (blk_b <= blk_a))


def _naive(q, k, v, mask):
    s = jnp.einsum("bqd,bkd->bqk", q, k) * q.shape[-1] ** -0.5
    return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(
        jnp.where(mask, s, -jnp.inf), -1), v)


def _qkvw(bh, rows, d=32, key=0):
    return tuple(jax.random.normal(k, (bh, rows, d), jnp.float32)
                 for k in jax.random.split(jax.random.key(key), 4))


def _all(fn, w):
    """A jitted ``(out, dq, dk, dv)`` of ``fn(q, k, v)`` under the
    cotangent ``w``."""
    def run(q, k, v):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out,) + vjp(w)
    return jax.jit(run)


# sizes at which dead, interior and edge tiles all occur; L a multiple of
# the tile and not; blocks of 4 and 8
CASES = {
    "B4_L100_32x32": (4, 100, 32, 32),
    "B8_L72_16x16": (8, 72, 16, 16),
    "B8_L104_16x48": (8, 104, 16, 48),
    "B4_L128_64x32": (4, 128, 64, 32),
    "B8_L200_128x128": (8, 200, 128, 128),
}


def test_the_dense_mask_is_the_four_cases():
    """The test's own oracle, read off by hand at B 2, L 4."""
    want = np.array([
        # noised keys   clean keys
        [1, 1, 0, 0,    0, 0, 0, 0],    # noised block 0 sees itself
        [1, 1, 0, 0,    0, 0, 0, 0],
        [0, 0, 1, 1,    1, 1, 0, 0],    # noised block 1: itself, clean 0
        [0, 0, 1, 1,    1, 1, 0, 0],
        [0, 0, 0, 0,    1, 1, 0, 0],    # clean block 0: its own, whole
        [0, 0, 0, 0,    1, 1, 0, 0],
        [0, 0, 0, 0,    1, 1, 1, 1],    # clean block 1: clean 0 and 1
        [0, 0, 0, 0,    1, 1, 1, 1]], bool)
    assert (dense_mask(2, 4) == want).all()
    assert dense_mask(4, 100).sum() == 100 * 100 + 4 * 100     # L^2 + B L
    for b, n in ((2, 4), (4, 100), (8, 72), (3, 9)):
        rows = np.arange(2 * n)
        assert (fa.BlockDiffusion(b, n).visible(
            rows[:, None], rows[None, :]) == dense_mask(b, n)).all()


@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_and_gradients_against_a_naive_masked_softmax(case,
                                                              monkeypatch):
    """Through the Pallas bodies (interpret mode), through ``_bwd_chunked``
    and through ``reference_attention`` (the ``attn_impl="default"`` arm)."""
    block, length, bq, bk = CASES[case]
    q, k, v, w = _qkvw(2, 2 * length, key=length)
    mask = jnp.asarray(dense_mask(block, length))
    want = _all(lambda *a: _naive(*a, mask), w)(q, k, v)

    def flash(q, k, v):
        return flash_attention(q, k, v, block_diffusion=(block, length),
                               block_q=bq, block_k=bk)
    census = fa.block_census(2 * length, 2 * length, bq, bk, False,
                             block_diffusion=(block, length))
    assert min(census.values()) > 0, census
    got = {"pallas": _all(flash, w)(q, k, v),
           "reference": _all(lambda *a: reference_attention(
               *a, block_diffusion=(block, length)), w)(q, k, v)}
    monkeypatch.setenv("APEX_TPU_FLASH_BWD", "chunked")
    got["chunked"] = _all(lambda *a: flash(*a), w)(q, k, v)
    for arm, outs in got.items():
        for name, a, b in zip(("out", "dq", "dk", "dv"), outs, want):
            np.testing.assert_allclose(a, b, atol=2e-5,
                                       err_msg=f"{arm} {name}")


def _live_tiles(block, length, bq, bk):
    """bool [nq, nk]: the tiles of the padded grid that hold a visible
    pair of the dense mask."""
    rows = 2 * length
    nq, nk = -(-rows // bq), -(-rows // bk)
    padded = np.zeros((nq * bq, nk * bk), bool)
    padded[:rows, :rows] = dense_mask(block, length)
    return padded.reshape(nq, bq, nk, bk).any((1, 3))


@pytest.mark.parametrize("length,block", [
    (24, 4), (40, 8), (100, 4), (64, 8), (128, 4), (80, 16), (9, 3), (96, 1)])
def test_the_grid_is_the_mask(length, block):
    """For a sweep of ``(L, B, block_q, block_k)`` the steps of the
    forward's / dq's table (rows' sweeps) and of dk / dv's (columns'
    sweeps) are exactly the tiles that hold a visible pair, each once and
    live, and ``block_census`` counts them."""
    bd, rows = fa.BlockDiffusion(block, length), 2 * length
    for bq in (16, 32, 48, 128):
        for bk in (16, 32, 128):
            live = _live_tiles(block, length, bq, bk)
            nq, nk = live.shape
            for by_col in (False, True):
                steps = fa._steps((0, 0, rows), None, nq, nk, bq, bk, False,
                                  by_col=by_col, window=bd)
                qb, kb = fa._block_of(steps)
                got = np.zeros_like(live)
                got[qb, kb] = True
                assert (got == live).all(), (bq, bk, by_col)
                assert len(steps) == live.sum()
                assert ((steps >> fa._LIVE) & 1).all()
            census = fa.block_census(rows, rows, bq, bk, False,
                                     block_diffusion=(block, length))
            assert census["dead"] == (~live).sum()
            assert census["interior"] + census["edge"] == live.sum()


def test_block_census_of_the_benchmarks_cell():
    """``sdar_train_s8192``: 8,192 positions twice in blocks of 4, at the
    blocks ``block_sizes`` picks: the live tiles are the two block-causal
    triangles and the noised copy's own diagonal, 288 of 1,024 tiles of
    512 x 512 forward and the same share backward."""
    length, block = 8192, 4
    fq, fk, bq, bk = fa.block_sizes(2 * length, 2 * length)
    for q, k in ((fq, fk), (bq, bk)):
        n, m = length // q, length // k     # a quadrant's tiles
        census = fa.block_census(2 * length, 2 * length, q, k, False,
                                 block_diffusion=(block, length))
        live = census["interior"] + census["edge"]
        diagonal = max(n, m)    # tiles a quadrant's diagonal crosses
        triangle = (n * m + diagonal) // 2
        assert live == 2 * triangle + diagonal
        assert census["dead"] == 4 * n * m - live
    forward = fa.block_census(2 * length, 2 * length, fq, fk, False,
                              block_diffusion=(block, length))
    assert forward["interior"] + forward["edge"] == 288     # 136 + 136 + 16
    assert sum(forward.values()) == 1024


def _names(fn, *args):
    return set(re.findall(r"name=(apex_flash_\w*(?:fwd|bwd)\w*)",
                          str(jax.make_jaxpr(fn)(*args))))


def test_the_three_calls_carry_their_own_names():
    q, k, v, _ = _qkvw(1, 64)
    names = _names(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, block_diffusion=(4, 32))), (0, 1, 2)), q, k, v)
    assert names == {"apex_flash_bd_fwd", "apex_flash_bd_bwd_dq",
                     "apex_flash_bd_bwd_dkv"}


@pytest.mark.parametrize("kw", [dict(causal=True), dict(causal=False),
                                dict(causal=True, window=24)],
                         ids=["causal", "full", "window"])
def test_without_the_argument_no_call_is_a_block_diffusion_call(kw):
    """The calls that existed: their jaxprs name no ``apex_flash_bd_*``
    call, and the argument's default is the call without it, bitwise."""
    q, k, v, _ = _qkvw(1, 64)

    def grads(**more):
        return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, **kw, **more) ** 2), (0, 1, 2))
    assert not {n for n in _names(grads(), q, k, v) if "_bd_" in n}
    for a, b in zip(grads()(q, k, v), grads(block_diffusion=None)(q, k, v)):
        assert (a == b).all()


@pytest.mark.parametrize("attend", [flash_attention, reference_attention],
                         ids=["flash", "reference"])
def test_what_does_not_go_with_the_argument(attend):
    q, k, v, _ = _qkvw(1, 64)
    ok = dict(block_diffusion=(4, 32))
    attend(q, k, v, **ok)
    bias = jnp.zeros((1, 64, 64))
    select = jnp.zeros((1, 64, 128), jnp.int32)
    for more in (dict(causal=True), dict(causal=True, window=8),
                 dict(select=select), dict(bias=bias), dict(q_start=4),
                 dict(k_start=jnp.int32(0))):
        with pytest.raises(ValueError, match="block_diffusion"):
            attend(q, k, v, **ok, **more)
    for bad in ((4, 30), (0, 32), (4.0, 32), (4, 16), (4, 32, 1), (5, 32)):
        with pytest.raises(ValueError, match="block_diffusion"):
            attend(q, k, v, block_diffusion=bad)
