"""Flat parameter store round-trip and segment-table invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import flat


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w1": jnp.asarray(rng.normal(size=(37, 5)), jnp.float32),
        "b1": jnp.asarray(rng.normal(size=(5,)), jnp.float32),
        "nested": {"w2": jnp.asarray(rng.normal(size=(129,)), jnp.float32),
                   "scalar": jnp.asarray(3.5, jnp.float32)},
    }


def test_roundtrip():
    tree = _tree()
    buf, table = flat.flatten(tree)
    out = flat.unflatten(buf, table)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        tree, out)


def test_alignment_and_padding_zero():
    tree = _tree()
    buf, table = flat.flatten(tree, align=128)
    assert all(o % 128 == 0 for o in table.offsets)
    assert table.total % 128 == 0
    mask = np.asarray(table.valid_mask())
    np.testing.assert_array_equal(np.asarray(buf)[~mask], 0.0)
    # valid element count matches the tree
    assert mask.sum() == sum(int(np.prod(np.shape(l)) or 1)
                             for l in jax.tree_util.tree_leaves(tree))


def test_segment_ids_cover_buffer():
    tree = _tree()
    buf, table = flat.flatten(tree)
    ids = np.asarray(table.segment_ids())
    assert ids.shape == (table.total,)
    assert ids.min() == 0 and ids.max() == table.num_segments - 1
    # each segment's span is contiguous and matches padded size
    for i, (off, psz) in enumerate(zip(table.offsets, table.padded_sizes)):
        assert (ids[off:off + psz] == i).all()


def test_unflatten_under_jit():
    tree = _tree()
    buf, table = flat.flatten(tree)

    @jax.jit
    def f(b):
        t = flat.unflatten(b, table)
        return jax.tree_util.tree_map(lambda x: x * 2.0, t)

    out = f(buf)
    np.testing.assert_allclose(np.asarray(out["w1"]),
                               2.0 * np.asarray(tree["w1"]), rtol=0)


def test_dtype_conversion():
    tree = _tree()
    buf, table = flat.flatten(tree, dtype=jnp.bfloat16)
    assert buf.dtype == jnp.bfloat16
    out = flat.unflatten(buf, table, dtype=jnp.float32)
    assert out["w1"].dtype == jnp.float32


def test_empty_tree():
    buf, table = flat.flatten({})
    assert buf.shape == (0,)
    assert table.num_segments == 0
    assert flat.unflatten(buf, table) == {}


def test_table_is_static_hashable():
    _, t1 = flat.flatten(_tree(0))
    _, t2 = flat.flatten(_tree(1))
    assert hash(t1) == hash(t2)  # same structure -> same table
    assert t1 == t2


def test_grad_through_unflatten_matches_per_leaf():
    """The production gradient path (bench.py / examples / README):
    differentiate wrt the FLAT buffer through unflatten's pinned
    transpose (one concat + one convert) and compare against the
    per-leaf pattern. Covers leaf ordering, alignment-padding zero fill,
    and the bf16 -> fp32 dtype chain."""
    tree = _tree()
    buf, table = flat.flatten(tree)

    def loss_from_tree(t):
        return (jnp.sum(t["w1"].astype(jnp.float32) ** 2)
                + 3.0 * jnp.sum(t["b1"].astype(jnp.float32))
                + jnp.sum(jnp.sin(t["nested"]["w2"].astype(jnp.float32)))
                + t["nested"]["scalar"].astype(jnp.float32) ** 3)

    # flat-master pattern, with the fused half cast
    g_flat = jax.grad(lambda m: loss_from_tree(
        flat.unflatten(m, table, dtype=jnp.bfloat16)))(buf)
    assert g_flat.dtype == buf.dtype and g_flat.shape == buf.shape

    # per-leaf pattern (the old way), flattened for comparison
    g_tree = jax.grad(lambda t: loss_from_tree(
        jax.tree_util.tree_map(
            lambda l: l.astype(jnp.bfloat16), t)))(tree)
    g_ref = flat.flatten(g_tree, table=table, dtype=jnp.float32)[0]
    np.testing.assert_allclose(np.asarray(g_flat), np.asarray(g_ref),
                               rtol=1e-6, atol=1e-6)

    # alignment-padding positions carry exactly zero gradient
    ids = table.segment_ids()
    live = np.zeros((table.total,), bool)
    for off, size in zip(table.offsets, table.sizes):
        live[off:off + size] = True
    assert np.all(np.asarray(g_flat)[~live] == 0.0)
    del ids


def test_grad_through_unflatten_partial_use():
    """Only one leaf used: the other leaves' cotangents must come back
    as zeros through the pinned transpose (symbolic-zero handling)."""
    tree = _tree()
    buf, table = flat.flatten(tree)
    g = jax.grad(lambda m: jnp.sum(
        flat.unflatten(m, table)["b1"] ** 2))(buf)
    g_tree = jax.grad(lambda t: jnp.sum(t["b1"] ** 2))(tree)
    expect = np.asarray(flat.flatten(g_tree, table=table,
                                     dtype=jnp.float32)[0])
    np.testing.assert_array_equal(np.asarray(g), expect)


def test_jvp_through_unflatten():
    """unflatten is linear: forward-mode autodiff must keep working
    (custom_vjp would break jvp; linear_call preserves it)."""
    from apex_tpu.ops.flat import _linear_call_diffable
    if not _linear_call_diffable():
        pytest.skip("this jaxlib cannot differentiate linear_call at "
                    "all; unflatten runs the reverse-only custom_vjp "
                    "fallback (jvp is knowingly unsupported there)")
    tree = _tree()
    buf, table = flat.flatten(tree)
    tan = jnp.ones_like(buf)
    primal, tangent = jax.jvp(
        lambda m: flat.unflatten(m, table, dtype=jnp.bfloat16)["w1"],
        (buf,), (tan,))
    assert primal.shape == tangent.shape == (37, 5)
    np.testing.assert_allclose(np.asarray(tangent, np.float32),
                               np.ones((37, 5), np.float32))


@pytest.mark.parametrize("seed", range(8))
def test_fuzz_random_trees_roundtrip_and_grad(seed):
    """Randomized structural fuzz over the flat store — the data model
    every optimizer/AMP path rides. Random nesting, leaf count, shapes
    (incl. scalars, 0-d, rank-4, singleton dims), mixed storage dtypes,
    and alignments must round-trip exactly, pad with zeros, and carry
    gradients through the pinned unflatten transpose identically to
    per-leaf autodiff."""
    rng = np.random.default_rng(1000 + seed)

    def rand_leaf():
        rank = int(rng.integers(0, 5))
        shape = tuple(int(rng.integers(1, 6)) for _ in range(rank))
        dt = [jnp.float32, jnp.bfloat16][int(rng.integers(0, 2))]
        return jnp.asarray(rng.normal(size=shape), dt)

    def rand_tree(depth):
        if depth == 0 or rng.random() < 0.3:
            return rand_leaf()
        n = int(rng.integers(1, 4))
        return {f"k{i}": rand_tree(depth - 1) for i in range(n)}

    tree = {"root": rand_tree(3)}
    align = int(rng.choice([1, 8, 128]))
    buf, table = flat.flatten(tree, align=align, dtype=jnp.float32)
    # round-trip (through the fp32 buffer; bf16 leaves recast exactly:
    # bf16 -> fp32 -> bf16 is the identity)
    out = flat.unflatten(buf, table)
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(out)):
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32))
    # padding stays zero, offsets honor the alignment
    mask = np.asarray(table.valid_mask())
    np.testing.assert_array_equal(np.asarray(buf)[~mask], 0.0)
    assert all(o % align == 0 for o in table.offsets)
    # grads: reduce over EVERY leaf through unflatten == per-leaf grads
    def loss_flat(m):
        leaves = jax.tree_util.tree_leaves(flat.unflatten(m, table))
        return sum(jnp.sum(l.astype(jnp.float32) ** 2) for l in leaves)

    def loss_tree(t):
        return sum(jnp.sum(l.astype(jnp.float32) ** 2)
                   for l in jax.tree_util.tree_leaves(t))

    g_flat = jax.grad(loss_flat)(buf)
    g_tree = jax.grad(loss_tree)(tree)
    expect = np.asarray(flat.flatten(g_tree, table=table,
                                     dtype=jnp.float32)[0])
    np.testing.assert_allclose(np.asarray(g_flat), expect,
                               rtol=1e-6, atol=1e-6)


# -- a table cut into contiguous sub-tables (DDP's gradient buckets) -------

def _five_leaves():
    rng = np.random.default_rng(7)
    shapes = {"a": (3, 50), "b": (7,), "c": {"d": (129,), "e": ()},
              "f": (2, 3, 11)}
    return jax.tree.map(
        lambda s: jnp.asarray(rng.normal(size=s), jnp.float32), shapes,
        is_leaf=lambda s: isinstance(s, tuple))


def _every_split(n):
    """Every way to cut ``n`` leaves into contiguous runs."""
    for cuts in range(2 ** (n - 1)):
        counts, run = [], 1
        for i in range(n - 1):
            if cuts >> i & 1:
                counts.append(run)
                run = 0
            run += 1
        yield tuple(counts + [run])


@pytest.mark.parametrize("counts", list(_every_split(5)),
                         ids=lambda c: "-".join(map(str, c)))
@pytest.mark.parametrize("dtype", [None, jnp.bfloat16],
                         ids=["f32", "cast-bf16"])
def test_split_table_reproduces_the_whole(counts, dtype):
    """Offsets, sizes and alignment of every split: the slices unflatten to
    the whole's tree, and their transposes, joined, are the whole's
    flat gradient (the transpose of ``unflatten`` is ``flatten``)."""
    tree = _five_leaves()
    buf, table = flat.flatten(tree, align=128)
    subs = flat.split_table(table, counts)
    assert len(subs) == len(counts)
    assert sum(s.total for s in subs) == table.total
    lo = 0
    for sub, n in zip(subs, counts):
        assert sub.num_segments == n and sub.align == table.align
        assert sub.sizes == table.sizes[lo:lo + n]
        assert sub.shapes == table.shapes[lo:lo + n]
        assert sub.padded_sizes == table.padded_sizes[lo:lo + n]
        assert sub.total % 128 == 0
        if len(counts) > 1:
            assert sub.offsets[0] == 0
            assert sub.offsets == tuple(
                o - table.offsets[lo] for o in table.offsets[lo:lo + n])
        lo += n
    bufs = flat.split(buf, subs)
    assert [b.shape[0] for b in bufs] == [s.total for s in subs]
    np.testing.assert_array_equal(np.concatenate(bufs), np.asarray(buf))

    def loss(tree):
        return sum(jnp.sum(jnp.sin(leaf.astype(jnp.float32)) * (i + 1))
                   for i, leaf in enumerate(jax.tree.leaves(tree)))
    whole, g_whole = jax.value_and_grad(
        lambda b: loss(flat.unflatten(b, table, dtype=dtype)))(buf)
    parts, g_parts = jax.value_and_grad(
        lambda bs: loss(flat.unflatten_split(bs, subs, table.treedef,
                                             dtype=dtype)))(bufs)
    out = flat.unflatten_split(bufs, subs, table.treedef, dtype=dtype)
    assert jax.tree.structure(out) == jax.tree.structure(tree)
    jax.tree.map(np.testing.assert_array_equal, out,
                 flat.unflatten(buf, table, dtype=dtype))
    assert float(whole) == float(parts)
    assert all(g.dtype == jnp.float32 for g in g_parts)
    np.testing.assert_array_equal(np.asarray(flat.join(g_parts)),
                                  np.asarray(g_whole))
    np.testing.assert_array_equal(np.asarray(flat.join(bufs)),
                                  np.asarray(buf))


def test_one_bucket_is_the_table_and_the_buffer_themselves():
    buf, table = flat.flatten(_five_leaves())
    (sub,) = flat.split_table(table, (table.num_segments,))
    assert sub is table
    assert flat.split(buf, (sub,))[0] is buf


@pytest.mark.parametrize("counts", [(2, 2), (5, 1), (0, 5), (3, -1, 3), ()])
def test_split_table_refuses_counts_that_do_not_partition(counts):
    _, table = flat.flatten(_five_leaves())
    with pytest.raises(ValueError, match="partition"):
        flat.split_table(table, counts)


@pytest.mark.parametrize("sizes", [(5,), (3, 4), (1024, 2 * 1024 + 384, 128),
                                   (4096, 4096)], ids=str)
@pytest.mark.parametrize("divisor", [None, 4, 3.0])
def test_join_is_concatenate_whatever_the_cut(sizes, divisor, monkeypatch):
    """``join`` cuts each buffer at a multiple of ``_JOIN_CUT`` (for the
    TPU compiler's windows); cut or not, it is the buffers in order, each
    element divided once."""
    monkeypatch.setattr(flat, "_JOIN_CUT", 1024)
    rng = np.random.default_rng(3)
    bufs = [jnp.asarray(rng.normal(size=n), jnp.float32) for n in sizes]
    want = np.concatenate(bufs) if divisor is None \
        else np.concatenate([np.asarray(b / divisor) for b in bufs])
    got = jax.jit(lambda bs: flat.join(bs, divisor))(bufs)
    # a compiled x / 3 may be x * (1 / 3): an ulp; a power of two is exact
    np.testing.assert_allclose(np.asarray(got), want,
                               rtol=2e-7 if divisor == 3.0 else 0)
    text = str(jax.make_jaxpr(lambda bs: flat.join(bs, divisor))(bufs))
    pieces = sum(2 if n > 1024 and n % 1024 else 1 for n in sizes)
    if len(sizes) > 1:
        assert f"concatenate[dimension=0]" in text
        assert text.count(" slice[") == 2 * (pieces - len(sizes))
    else:
        assert "concatenate" not in text
