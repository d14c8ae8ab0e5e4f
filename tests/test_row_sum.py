"""``ops/pallas/row_sum.py`` (``apex_moe_rowsum``; interpreter mode here):
a token's rows of a buffer added up, against a gather-sum in numpy, for
the expert layer's own maps and for maps it never makes (rows far apart,
a block with no row, a column with none), float32 and bfloat16, junk in
the rows nobody names; and ``ExpertLayer`` on the kernels' side against
its ``jax.numpy`` side."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.contrib.moe import ExpertLayer
from apex_tpu.ops import dispatch
from apex_tpu.ops.pallas import row_sum

D = 128


def _want(buf, at):
    buf = np.asarray(buf.astype(jnp.float32))
    out = np.zeros((at.shape[0], buf.shape[1]), np.float32)
    for e in range(at.shape[1]):
        has = at[:, e] >= 0
        out[has] += buf[at[has, e]]
    return out


def _sorted_groups(rng, n, held, rows, share):
    """``at`` as the layer makes it: an expert's tokens in consecutive rows
    of its group, groups on whole tiles of 128."""
    at = -np.ones((n, held), np.int32)
    start = 0
    for e in range(held):
        mine = np.flatnonzero(rng.random(n) < share)
        mine = mine[:max(0, rows - start)]
        at[mine, e] = start + np.arange(len(mine))
        start += -(-len(mine) // 128) * 128
    return at


def _maps(case, rng, n, held, rows):
    if case == "sorted":
        return _sorted_groups(rng, n, held, rows, 0.2)
    if case == "overfull":          # groups that run into the bound
        return _sorted_groups(rng, n, held, rows, 0.9)
    if case == "far-apart":         # any rows at all: many rounds a block
        at = rng.integers(0, rows, (n, held)).astype(np.int32)
        return np.where(rng.random((n, held)) < 0.3, at, -1)
    assert case == "holes"          # a block with no row, a column with none
    at = _sorted_groups(rng, n, held, rows, 0.3)
    at[row_sum.BLOCK:2 * row_sum.BLOCK] = -1
    at[:, 1] = -1
    return at


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", ["sorted", "overfull", "far-apart", "holes"])
def test_a_tokens_rows_add_up(case, dtype):
    """Float32 rows add up to float32's own rounding (three bfloat16 parts
    carry 24 bits); bfloat16 rows add exactly in float32 and round once;
    a row no token names holds a large finite value and reaches nothing."""
    n, held, rows = 4 * row_sum.BLOCK, 4, 1024
    rng = np.random.default_rng(3)
    at = _maps(case, rng, n, held, rows)
    named = np.zeros(rows, bool)
    named[at[at >= 0]] = True
    buf = jnp.asarray(np.where(named[:, None], rng.standard_normal(
        (rows, D), np.float32), 3e37), dtype)
    got = jax.jit(row_sum.sum_rows)(buf, jnp.asarray(at))
    assert got.dtype == dtype and got.shape == (n, D)
    want = _want(buf, at)
    if dtype == jnp.bfloat16:
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(jnp.asarray(want).astype(dtype)))
    else:
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-6)
    # and to float32 where the result is asked for in it
    wide = jax.jit(lambda b, a: row_sum.sum_rows(b, a, jnp.float32))(
        buf, jnp.asarray(at))
    np.testing.assert_allclose(np.asarray(wide), want, atol=1e-6)


def test_the_shapes_it_takes():
    """Whole lanes, whole chunks, a round's chunks within VMEM, and no
    more experts held than it wins at (the module's readings): a share of
    16 or 32 in both types, a whole layer of 64 or 128 in bfloat16 alone,
    of 512 in neither."""
    assert row_sum.takes(2304, 65536, 16, 4) and row_sum.takes(2304, 81920,
                                                               32, 4)
    assert not row_sum.takes(2304 + 64, 65536, 16, 4)   # half a lane tile
    assert not row_sum.takes(128, 1024 + 8, 16, 4)      # half a chunk
    assert row_sum.takes(2304, 139264, 64, 2)
    assert not row_sum.takes(2304, 139264, 64, 4)
    assert row_sum.takes(2048, 147328, 128, 2)
    assert not row_sum.takes(2048, 228864, 512, 2)
    assert row_sum.takes(8192, 65536, 10, 4)            # VMEM: rows this wide
    assert not row_sum.takes(8192, 65536, 16, 4)


def test_tokens_short_of_a_block_and_the_rows_by_column():
    """Any number of tokens (the last block's missing ones have no row);
    ``columns`` turns a row a pair and the pair's column into a row a
    column, -1 for no row and for a column past the last."""
    rng = np.random.default_rng(5)
    n, held, rows = row_sum.BLOCK + 40, 4, 512
    at = _maps("sorted", rng, n, held, rows)
    buf = jnp.asarray(rng.standard_normal((rows, D), np.float32))
    got = jax.jit(row_sum.sum_rows)(buf, jnp.asarray(at))
    assert got.shape == (n, D)
    np.testing.assert_allclose(np.asarray(got), _want(buf, at), atol=1e-6)
    # two pairs a token on distinct columns of 0 .. 4, column 4 absent
    col = np.argsort(rng.random((n, held + 1)), axis=1)[:, :2]
    pos = np.where(col < held, np.take_along_axis(
        np.concatenate([at, -np.ones((n, 1), np.int32)], 1), col, 1), -1)
    want = -np.ones((n, held + 1), np.int32)
    np.put_along_axis(want, col, pos, axis=1)
    np.testing.assert_array_equal(row_sum.columns(
        jnp.asarray(pos), jnp.asarray(col), held), want[:, :held])


@pytest.mark.parametrize("router, held", [("softmax", (8, 16)),
                                          ("sigmoid", (0, 16))])
def test_the_layers_two_sums_on_the_kernel_are_its_gather_sums(
        router, held, monkeypatch):
    """``dispatch.backend("pallas")`` sends the two sums to the kernel
    (the experts' products go to theirs on both sides here): the result
    and the gradients are what the gather-sums in ``jax.numpy`` give
    under the same products, ``x`` in bfloat16 so that ``dx`` is the
    kernel's once-rounded sum (an ulp of bfloat16 apart where the order
    of the float32 additions, by expert or by slot, moves a rounding)."""
    layer = ExpertLayer(hidden=D, ffn=128, num_experts=16, top_k=4,
                        experts_held=held, router=router)
    params = layer.init(jax.random.key(0), 0.3)
    lo, hi = layer.held
    params = {**params, **{k: params[k][:hi - lo]
                           for k in ("w_gate", "w_up", "w_down")}}
    x = jax.random.normal(jax.random.key(1), (2 * row_sum.BLOCK, D)).astype(
        jnp.bfloat16)

    def run(p, x):      # a fresh function a side: a trace is cached by it
        return jax.value_and_grad(lambda p, x: jnp.sum(jnp.sin(
            layer.routed(p, x)[0])), (0, 1))(p, x)
    with dispatch.backend("pallas"):
        text = str(jax.make_jaxpr(lambda p, x: run(p, x))(params, x))
        got = jax.jit(lambda p, x: run(p, x))(params, x)
        monkeypatch.setattr(row_sum, "takes", lambda *a: False)
        plain = str(jax.make_jaxpr(lambda p, x: run(p, x))(params, x))
        want = jax.jit(lambda p, x: run(p, x))(params, x)
    assert text.count("name=apex_moe_rowsum") == 2      # the combine, dx
    assert plain.count("name=apex_moe_rowsum") == 0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=0.02, atol=0.1)
