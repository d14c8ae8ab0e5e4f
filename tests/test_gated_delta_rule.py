"""The gated delta rule: the chunked form against the token-by-token
recurrence, forward and gradient, at lengths that are and are not a
multiple of the chunk; the inverse it rests on; the dispatch. What
follows the inverse both ways: ``jax.numpy`` with a ``lax.scan`` over the
chunks (the CPU's, and any shape's) and the Pallas pair ``apex_gdn_fwd``
/ ``apex_gdn_bwd`` in interpret mode (``dispatch.backend("pallas")`` at
head sizes of 128), against the recurrence and against each other."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import dispatch
from apex_tpu.ops import gated_delta_rule as G


def _inputs(length, seed=0, b=2, h=3, dk=16, dv=24, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (b, h, length, dk))
    k = jax.random.normal(ks[1], (b, h, length, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, h, length, dv))
    # slow decays, so that a chunk sees the chunks before it
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, h, length)) - 3.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, h, length)))
    return tuple(x.astype(dtype) for x in (q, k, v)) + (g, beta)


# after the inverse: the lax.scan at the small heads of the cases that were
# here, or the kernels, which take head sizes of 128 and chunks of 64 or
# 128, with (batch, heads) that do and do not fill their 8 heads a step
SCAN = dict(dk=16, dv=24)


def kernels(b, h):
    return dict(b=b, h=h, dk=128, dv=128)


@contextlib.contextmanager
def loop(sizes):
    """The dispatch side the sizes are meant for."""
    with dispatch.backend("pallas" if sizes["dk"] == 128 else "auto"):
        yield


def _runs_kernels(fn, *args) -> bool:
    # a new function each time: a trace is kept by function, and the
    # dispatch's side is no part of its key
    return "pallas_call" in str(jax.make_jaxpr(lambda *a: fn(*a))(*args))


@pytest.mark.parametrize("length, chunk, sizes", [
    (64, 64, SCAN), (128, 64, SCAN), (192, 32, SCAN), (100, 64, SCAN),
    (7, 16, SCAN), (200, 64, SCAN), (130, 128, SCAN),
    pytest.param(128, 64, kernels(1, 3), id="128-64-kernels-3heads"),
    pytest.param(200, 64, kernels(2, 8), id="200-64-kernels-16heads"),
    pytest.param(130, 128, kernels(1, 9), id="130-128-kernels-9heads")])
def test_chunked_is_the_recurrence(length, chunk, sizes):
    args = _inputs(length, **sizes)
    want = G.gated_delta_rule_recurrent(*args)
    with loop(sizes):
        got = G.gated_delta_rule_chunked(*args, chunk=chunk)
    assert got.shape == want.shape == args[2].shape
    np.testing.assert_allclose(got, want, atol=2e-6)


def _grads(fn, args):
    return jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a).astype(jnp.float32))),
                    argnums=(0, 1, 2, 3, 4))(*args)


@pytest.mark.parametrize("length, chunk, sizes", [
    (128, 64, SCAN), (100, 64, SCAN), (75, 16, SCAN),
    pytest.param(128, 64, kernels(2, 8), id="128-64-kernels-16heads"),
    pytest.param(100, 64, kernels(1, 3), id="100-64-kernels-3heads"),
    pytest.param(200, 128, kernels(1, 9), id="200-128-kernels-9heads")])
def test_chunked_gradient_is_the_recurrences(length, chunk, sizes):
    args = _inputs(length, seed=1, **sizes)
    want = _grads(G.gated_delta_rule_recurrent, args)
    with loop(sizes):
        got = _grads(lambda *a: G.gated_delta_rule_chunked(*a, chunk=chunk),
                     args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        np.testing.assert_allclose(a, b, atol=2e-6 * float(jnp.abs(b).max())
                                   + 1e-7, err_msg=name)


@pytest.mark.parametrize("dtype, tolerance", [(jnp.float32, 1e-6),
                                              (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("length, chunk, heads", [
    (192, 64, (2, 8)), (100, 64, (1, 3)), (256, 128, (1, 9))])
def test_the_kernels_are_the_scan(length, chunk, heads, dtype, tolerance):
    """The same inputs both ways, result and every gradient: in float32
    to rounding, in bfloat16 to a few of its ulps (a float32 cotangent
    that JAX's transposes round to bfloat16 between two products stays
    float32 inside the kernel)."""
    args = _inputs(length, seed=5, dtype=dtype, **kernels(*heads))
    fn = lambda *a: G.gated_delta_rule_chunked(*a, chunk=chunk)
    assert not _runs_kernels(fn, *args)
    want = (fn(*args),) + _grads(fn, args)
    with dispatch.backend("pallas"):
        assert _runs_kernels(fn, *args)
        got = (fn(*args),) + _grads(fn, args)
    for name, a, b in zip("o q k v g beta".split(), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert float(jnp.abs(a - b).max()) <= tolerance * float(
            jnp.abs(b).max()), name


@pytest.mark.parametrize("sizes", [SCAN, kernels(1, 3)],
                         ids=["scan", "kernels"])
def test_the_state_reaches_across_chunks(sizes):
    """A test that passed with the state dropped between chunks would
    prove nothing: with the first chunk's keys and values zeroed the
    later chunks' outputs change, and the first chunk's gradient is not
    zero for a loss that reads the last chunk alone."""
    q, k, v, g, beta = _inputs(192, seed=2, **sizes)
    with loop(sizes):
        whole = G.gated_delta_rule_chunked(q, k, v, g, beta, chunk=64)
        cut = G.gated_delta_rule_chunked(q, k, v.at[:, :, :64].set(0.0), g,
                                         beta, chunk=64)
        dv = jax.grad(lambda v: jnp.sum(G.gated_delta_rule_chunked(
            q, k, v, g, beta, chunk=64)[:, :, 128:] ** 2))(v)
    assert float(jnp.abs(whole[:, :, 64:] - cut[:, :, 64:]).max()) > 1e-2
    assert float(jnp.abs(dv[:, :, :64]).max()) > 1e-6


@pytest.mark.parametrize("sizes", [SCAN, kernels(1, 3)],
                         ids=["scan", "kernels"])
def test_bfloat16_inputs_keep_their_type_and_stay_close(sizes):
    args = _inputs(128, seed=3, **sizes)
    want = G.gated_delta_rule_recurrent(*args)
    low = tuple(x.astype(jnp.bfloat16) for x in args[:3]) + args[3:]
    with loop(sizes):
        got = G.gated_delta_rule_chunked(*low)
    assert got.dtype == jnp.bfloat16
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) < 0.05


@pytest.mark.parametrize("n", [16, 32, 64, 128])
def test_the_inverse_of_a_unit_lower_triangle(n):
    a = jnp.tril(jax.random.normal(jax.random.key(n), (3, n, n)) * 0.3, -1)
    want = np.linalg.inv(np.eye(n) + np.asarray(a, np.float64))
    np.testing.assert_allclose(G._inv_unit_lower(a), want, atol=1e-4)
    # its custom backward against differentiating the products, on the
    # strict lower triangle (the entries above it are never read)
    w = jax.random.normal(jax.random.key(1), (3, n, n))
    got = jax.grad(lambda a: jnp.sum(G._inv_unit_lower(a) * w))(a)
    ref = jax.grad(lambda a: jnp.sum(G._inv_blocks(a) * w))(a)
    np.testing.assert_allclose(jnp.tril(got, -1), jnp.tril(ref, -1),
                               atol=1e-4 * float(jnp.abs(ref).max()))


def test_dispatch_takes_the_recurrence_as_the_reference_twin(monkeypatch):
    args = _inputs(40, seed=4)
    called = []
    monkeypatch.setattr(G, "gated_delta_rule_recurrent",
                        lambda *a: called.append("recurrent") or a[2])
    monkeypatch.setattr(G, "gated_delta_rule_chunked",
                        lambda *a, chunk: called.append(("chunked", chunk))
                        or a[2])
    G.gated_delta_rule(*args, chunk=32)
    with dispatch.backend("reference"):
        G.gated_delta_rule(*args, chunk=32)
    assert called == [("chunked", 32), "recurrent"]


@pytest.mark.parametrize("dk, dv, chunk, backend, kernel", [
    (128, 128, 64, "pallas", True), (128, 256, 128, "pallas", True),
    (128, 128, 32, "pallas", False), (128, 128, 16, "pallas", False),
    (64, 128, 64, "pallas", False), (128, 96, 64, "pallas", False),
    (128, 128, 64, "auto", False)])
def test_shapes_the_kernels_do_not_take_fall_to_the_scan(dk, dv, chunk,
                                                          backend, kernel):
    """The choice reads the platform and the shapes, nothing else: whole
    lanes in both head sizes and a chunk of 64 or 128 on the Pallas side
    of the dispatch; the CPU, the rehearsal's chunk of 16 and odd head
    sizes keep the ``lax.scan``, with the same result."""
    args = _inputs(2 * chunk, seed=6, b=1, h=2, dk=dk, dv=dv)
    fn = lambda *a: G.gated_delta_rule_chunked(*a, chunk=chunk)
    with dispatch.backend(backend):
        assert _runs_kernels(fn, *args) == kernel
        assert _runs_kernels(jax.grad(lambda *a: jnp.sum(fn(*a))),
                             *args) == kernel
        got = fn(*args)
    np.testing.assert_allclose(got, G.gated_delta_rule_recurrent(*args),
                               atol=2e-6)


def test_a_chunk_size_that_is_no_power_of_two_is_refused():
    with pytest.raises(ValueError):
        G.gated_delta_rule_chunked(*_inputs(48), chunk=48)


# -- a decay a channel (Kimi Delta Attention): g [B, H, L, dk] ---------------

def _vector(length, seed=0, bias=-1.0, **sizes):
    """``_inputs`` with a gate a key channel, decays of every speed."""
    q, k, v, g, beta = _inputs(length, seed=seed, **sizes)
    g = -jax.nn.softplus(jax.random.normal(
        jax.random.key(seed + 100), g.shape + (q.shape[-1],)) + bias)
    return q, k, v, g, beta


def _both(fn):
    """``fn``'s result and its five gradients as one compiled program (a
    new one each call: the dispatch's side is no part of a trace's key)."""
    return jax.jit(lambda *a: (fn(*a),) + _grads(fn, a))


def _close(got, want, names, tolerance=2e-6):
    for name, a, b in zip(names.split(), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert bool(jnp.isfinite(a).all()), name
        np.testing.assert_allclose(a, b, atol=tolerance * float(
            jnp.abs(b).max()) + 1e-7, err_msg=name)


@pytest.mark.parametrize("length, chunk", [
    (64, 16), (96, 32), (128, 64), (100, 64), (130, 128), (7, 16)])
def test_vector_gate_chunked_is_the_recurrence(length, chunk):
    """Forward and all five gradients, ``g``'s in float32 ``[B, H, L,
    dk]``, at whole chunks and at a length that is none; a chunk of 16 is
    one level, 32 two, 64 three, 128 four (``_levels``)."""
    args = _vector(length, seed=length, b=1, h=2, **SCAN)
    fn = lambda *a: G.gated_delta_rule_chunked(*a, chunk=chunk)
    assert len(G._levels(chunk)) == {16: 1, 32: 2, 64: 3, 128: 4}[chunk]
    want = _both(G.gated_delta_rule_recurrent)(*args)
    got = _both(fn)(*args)
    assert got[4].shape == args[3].shape and got[4].dtype == jnp.float32
    _close(got, want, "o q k v g beta", 3e-6)


@pytest.mark.parametrize("chunk, sizes", [
    (32, SCAN), (64, SCAN), pytest.param(64, dict(dk=128, dv=128),
                                         id="64-kernels")])
def test_vector_gate_far_past_float32s_exp_range_over_a_chunk(chunk, sizes):
    """A gate of -5 a token a channel is 320 nats over a chunk of 64,
    where ``exp(-G)`` has long overflowed (88.7), beside channels that do
    not decay at all: finite and the recurrence's, result and gradients,
    in ``jax.numpy`` and through the kernels (the levels made in VMEM)."""
    q, k, v, g, beta = _vector(2 * chunk, seed=7, b=1, h=2, **sizes)
    g = jnp.full_like(g, -5.0).at[..., ::3].set(0.0)
    g = g.at[:, 0, :, 1].set(-0.3)
    assert float(G.chunk_decay_nats(g, chunk)) == pytest.approx(5.0 * chunk)
    assert 5.0 * chunk > 88.7
    args = (q, k, v, g, beta)
    fn = lambda *a: G.gated_delta_rule_chunked(*a, chunk=chunk)
    with loop(sizes):
        assert _runs_kernels(fn, *args) == (sizes is not SCAN)
        got = _both(fn)(*args)
    _close(got, _both(G.gated_delta_rule_recurrent)(*args),
           "o q k v g beta", 3e-6)


def test_vector_gate_equal_in_all_channels_is_the_scalar_path():
    """``g_t`` the same in all ``dk`` channels: the recurrence is the
    scalar one's to the bit, the chunked forms agree to rounding, and the
    vector gate's cotangent summed over the channels is the scalar's."""
    q, k, v, g, beta = _inputs(128, seed=8, b=1, h=2, **SCAN)
    wide = jnp.broadcast_to(g[..., None], g.shape + (q.shape[-1],))
    np.testing.assert_array_equal(
        G.gated_delta_rule_recurrent(q, k, v, wide, beta),
        G.gated_delta_rule_recurrent(q, k, v, g, beta))
    fn = lambda *a: G.gated_delta_rule_chunked(*a, chunk=64)
    scalar = _both(fn)(q, k, v, g, beta)
    vector = _both(fn)(q, k, v, wide, beta)
    vector = vector[:4] + (vector[4].sum(-1),) + vector[5:]
    _close(vector, scalar, "o q k v g beta", 3e-6)


def _parents_chunked(q, k, v, g, beta, *, chunk):
    """The parent commit's ``gated_delta_rule_chunked`` (scalar gate, the
    ``lax.scan`` side), frozen here: the path a vector gate must leave
    alone."""
    dt, f32, hi = v.dtype, jnp.float32, jax.lax.Precision.HIGHEST
    b, h, length, dk = q.shape
    pad = (-length) % chunk
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
                   for x in (q, k, v))
        g, beta = (jnp.pad(x, ((0, 0), (0, 0), (0, pad))) for x in (g, beta))
    n = (length + pad) // chunk

    def chunks(x):
        return x.reshape(b, h, n, chunk, *x.shape[3:])
    mm = lambda eq, x, y: jnp.einsum(eq, x.astype(dt), y.astype(dt),
                                     preferred_element_type=f32)
    q, k, v = chunks(q), chunks(k), chunks(v)
    beta = chunks(beta.astype(f32))[..., None]
    gsum = jnp.cumsum(chunks(g.astype(f32)), axis=-1)
    idx = jnp.arange(chunk)
    lower = idx[:, None] >= idx[None, :]
    decay = jnp.exp(jnp.where(lower, gsum[..., :, None] - gsum[..., None, :],
                              -jnp.inf))
    kk = mm("bhnck,bhnsk->bhncs", k, k)
    t_inv = G._inv_unit_lower(jnp.where(idx[:, None] > idx[None, :],
                                        beta * kk * decay, 0.0))
    u = jnp.matmul(t_inv, beta * v.astype(f32), precision=hi)
    w = jnp.matmul(t_inv, beta * jnp.exp(gsum)[..., None] * k.astype(f32),
                   precision=hi)
    qk = mm("bhnck,bhnsk->bhncs", q, k) * decay
    q_in = q.astype(f32) * jnp.exp(gsum)[..., None]
    last = gsum[..., -1:]
    k_out = k.astype(f32) * jnp.exp(last - gsum)[..., None]
    o = G._chunk_scan(*(x.astype(dt) for x in (w, u, q_in, k_out, qk)),
                      jnp.exp(last))
    return o.reshape(b, h, n * chunk, v.shape[-1])[:, :, :length]


@pytest.mark.parametrize("length, chunk", [(128, 64), (100, 32)])
def test_the_scalar_path_is_the_parents_to_the_bit(length, chunk):
    args = _inputs(length, seed=9, b=1, h=2, **SCAN)
    ours = lambda *a: G.gated_delta_rule_chunked(*a, chunk=chunk)
    theirs = lambda *a: _parents_chunked(*a, chunk=chunk)
    for a, b in zip(_both(ours)(*args), _both(theirs)(*args)):
        np.testing.assert_array_equal(a, b)
    # and the same equations, one by one
    eqns = lambda f: [(e.primitive.name, [v.aval for v in e.outvars])
                      for e in jax.make_jaxpr(f)(*args).eqns]
    assert eqns(ours) == eqns(theirs)


@pytest.mark.parametrize("length, chunk, heads, dtype, tolerance", [
    (128, 64, (2, 8), jnp.float32, 1e-6),
    (128, 64, (1, 8), jnp.bfloat16, 2e-2),
    (200, 128, (1, 3), jnp.float32, 1e-6),
    (192, 64, (1, 9), jnp.bfloat16, 2e-2),
    (100, 64, (1, 1), jnp.float32, 1e-6)])
def test_the_vector_gates_kernels_are_the_scan(length, chunk, heads, dtype,
                                               tolerance):
    """The op through its two Pallas pairs in interpret mode against the
    ``lax.scan`` form over ``jax.numpy``'s decayed operands (the parent's
    arithmetic) on the same inputs: **the result to the bit** (the local
    pair's products, and ``exp(G) q``, ``exp(G_last - G) k`` made in VMEM
    by the same float32 product and the one rounding), every gradient to
    rounding, with (batch, heads) that do and do not fill the kernels' 8
    heads a step."""
    args = _vector(length, seed=11, dtype=dtype, **kernels(*heads))
    fn = lambda *a: G.gated_delta_rule_chunked(*a, chunk=chunk)
    assert not _runs_kernels(fn, *args)
    want = _both(fn)(*args)
    with dispatch.backend("pallas"):
        text = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(
            fn(*a).astype(jnp.float32))))(*args))
        assert all(f"apex_kda_{name}" in text for name in (
            "fwd", "bwd", "local_fwd", "local_bwd")) \
            and "apex_gdn" not in text
        got = _both(fn)(*args)
    np.testing.assert_array_equal(got[0], want[0])
    for name, a, b in zip("o q k v g beta".split(), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert float(jnp.abs(a - b).max()) <= tolerance * float(
            jnp.abs(b).max()), name


def _scan_operands(chunk, dtype, heads, case="random", n=2, seed=16,
                   bias=-1.0):
    """What ``apex_kda_fwd`` takes, ``n`` chunks of ``heads``: ``q, k`` in
    ``dtype``, ``G`` and float32 ``w, u, m`` (``m`` masked), and a weight
    for the outputs. ``case``: ``steep`` decays 5 nats a token in two
    channels of three (320 a chunk of 64); ``late`` weighs the last chunk's
    outputs alone, so that the first chunk's gradients come through the
    state's cotangent."""
    q, k, v, g, _ = _vector(n * chunk, seed=seed, dtype=dtype, bias=bias,
                            **kernels(*heads))
    if case == "steep":
        g = jnp.full_like(g, -5.0).at[..., ::3].set(-0.05)
    split = lambda x: x.reshape(x.shape[:2] + (n, chunk) + x.shape[3:])
    ks = jax.random.split(jax.random.key(seed + 1), 4)
    w = 0.1 * jax.random.normal(ks[0], split(q).shape)
    u = jax.random.normal(ks[1], split(v).shape)
    m = 0.2 * jnp.tril(jax.random.normal(ks[2], w.shape[:-1] + (chunk,)))
    weight = jax.random.normal(ks[3], u.shape)
    if case == "late":
        weight = weight.at[:, :, :-1].set(0.0)
    return (split(q), split(k), jnp.cumsum(split(g), axis=-2), w, u, m), \
        weight


def _scan_oracle(q, k, g, w, u, m):
    """``_chunk_scan`` over ``jax.numpy``'s decayed operands, as
    ``_chunked_vector`` calls it off the TPU."""
    dt, f32 = q.dtype, jnp.float32
    last = g[..., -1:, :]
    q_in = q.astype(f32) * jnp.exp(g)
    k_out = k.astype(f32) * jnp.exp(last - g)
    return G._chunk_scan(*(x.astype(dt) for x in (w, u, q_in, k_out, m)),
                         jnp.exp(last[..., 0, :]))


def _scan_both(fn, weight):
    """``fn``'s outputs and the gradients of their weighted sum in all six
    operands, one compiled program."""
    loss = lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weight)
    return jax.jit(lambda *a: (fn(*a),) + jax.grad(
        loss, argnums=tuple(range(6)))(*a))


@pytest.mark.parametrize("chunk, heads, dtype, tolerance, case", [
    (64, (1, 1), jnp.float32, 5e-7, "random"),
    (64, (1, 3), jnp.float32, 5e-7, "random"),
    (128, (1, 9), jnp.float32, 5e-7, "random"),
    (64, (2, 8), jnp.bfloat16, 2e-2, "random"),
    (128, (1, 3), jnp.bfloat16, 2e-2, "random"),
    (64, (1, 3), jnp.float32, 5e-7, "steep"),
    (64, (1, 9), jnp.bfloat16, 2e-2, "steep"),
    (64, (1, 3), jnp.float32, 5e-7, "late"),
    (64, (1, 1), jnp.bfloat16, 2e-2, "late")])
def test_the_scan_pair_is_the_scan_over_jax_numpys_decayed_operands(
        chunk, heads, dtype, tolerance, case):
    """``apex_kda_fwd`` / ``apex_kda_bwd`` in interpret mode on ``q``,
    ``k``, ``G`` against ``_chunk_scan`` on the operands ``jax.numpy``
    decays: the outputs to the bit (the same float32 product, the one
    rounding to the products' type), ``dq``, ``dk``, ``dw``, ``du``, ``dm``
    and the one ``dG`` to rounding against JAX's differentiation of that
    form, with heads that do and do not fill the kernels' 8 a step; at 320
    nats a chunk every number finite; with the last chunk's outputs alone
    weighed, the first chunk's ``dG`` nonzero on its last token (the
    state's cotangent, through ``e`` and ``K``)."""
    from apex_tpu.ops.pallas import kda_delta_rule as K
    args, weight = _scan_operands(chunk, dtype, heads, case)
    text = str(jax.make_jaxpr(_scan_both(K.chunk_scan, weight))(*args))
    assert "apex_kda_fwd" in text and "apex_kda_bwd" in text
    got = _scan_both(K.chunk_scan, weight)(*args)
    want = _scan_both(_scan_oracle, weight)(*args)
    np.testing.assert_array_equal(got[0], want[0])
    assert got[0].dtype == dtype and got[3].dtype == jnp.float32
    if case == "late":
        assert float(jnp.abs(got[3][:, :, 0, -1]).max()) > 1e-3
    for name, a, b in zip("q k g w u m".split(), got[1:], want[1:]):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert bool(jnp.isfinite(a).all()), name
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert float(jnp.abs(a - b).max()) <= tolerance * float(
            jnp.abs(b).max()), name


@pytest.mark.parametrize("chunk_index, token", [(0, 63), (1, 63), (1, 20),
                                                (2, 3)])
def test_a_chunks_last_tokens_share_of_the_scan_pairs_gates_gradient(
        chunk_index, token):
    """The scan pair's ``dG`` on a chunk's last token holds, beside the
    token's own ``dQ Q - dK K``, the column sums of ``dK K`` and the
    state's decay ``e sum_v(S^T dS'^T)``: on token 63 of the first and of
    the second chunk of three, and on tokens that are not last (one in the
    last chunk, with no state after it), against central differences of
    the ``jax.numpy`` form in float32, along a direction over the
    channels (slow decays and the loss summed in float64, so that no
    path's share is lost in the sum's rounding)."""
    from apex_tpu.ops.pallas import kda_delta_rule as K
    (q, k, g, w, u, m), weight = _scan_operands(64, jnp.float32, (1, 3),
                                                n=3, seed=17, bias=-3.0)
    way = jnp.sign(jax.random.normal(jax.random.key(token), (128,)))
    step = jnp.zeros_like(g).at[0, 1, chunk_index, token].set(1e-2 * way)
    outs = jax.jit(lambda g: _scan_oracle(q, k, g, w, u, m))
    loss = lambda g: float(np.sum(np.asarray(outs(g), np.float64)
                                  * np.asarray(weight, np.float64)))
    want = (loss(g + step) - loss(g - step)) / 2e-2
    dg = _scan_both(K.chunk_scan, weight)(q, k, g, w, u, m)[3]
    got = float(jnp.sum(dg[0, 1, chunk_index, token] * way))
    assert abs(want) > 3e-3 and got == pytest.approx(want, rel=2e-2)


def _chunks_of(chunk, dtype, seed=14, heads=(1, 2)):
    """``q, k`` in ``dtype`` and ``G`` ``[B, H, 2, C, 128]`` (two chunks
    of ``[1, 2, 2 C, 128]``) and a weight for each of the two products."""
    q, k, _, g, _ = _vector(2 * chunk, seed=seed, dtype=dtype,
                            **kernels(*heads))
    split = lambda x: x.reshape(x.shape[:2] + (2, chunk) + x.shape[3:])
    w = jax.random.normal(jax.random.key(seed), (2,) + q.shape[:2]
                          + (2, chunk, chunk))
    return split(q), split(k), jnp.cumsum(split(g), axis=-2), w


def _local_both(fn, w):
    """``fn``'s two products and the gradients of their weighted sum in
    ``q``, ``k`` and ``G``, one compiled program."""
    def loss(*a):
        qk, kk = fn(*a)
        return jnp.sum(qk * w[0]) + jnp.sum(kk * w[1])
    return jax.jit(lambda *a: fn(*a) + jax.grad(loss, argnums=(0, 1, 2))(*a))


@pytest.mark.parametrize("dtype, tolerance", [(jnp.float32, 1e-6),
                                              (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("chunk, heads", [(64, (1, 2)), (128, (1, 2)),
                                          (64, (1, 5))])
def test_the_local_pair_is_jax_numpys_local_products(chunk, heads, dtype,
                                                     tolerance):
    """``apex_kda_local_fwd`` / ``apex_kda_local_bwd`` in interpret mode
    against ``_local_products``: the products to the bit (the same
    operands in the same type, one float32 accumulation), the three
    gradients to rounding (the kernel keeps in float32 what JAX's
    transposes round to the products' type), with chunks that do (4) and
    do not (10) fill the kernels' 8 a step."""
    from apex_tpu.ops.pallas import kda_delta_rule as K
    q, k, gsum, w = _chunks_of(chunk, dtype, heads=heads)
    pair = lambda *a: K.local_products(G._levels(chunk), *a)
    text = str(jax.make_jaxpr(_local_both(pair, w))(q, k, gsum))
    assert "apex_kda_local_fwd" in text and "apex_kda_local_bwd" in text
    got = _local_both(pair, w)(q, k, gsum)
    want = _local_both(G._local_products, w)(q, k, gsum)
    for a, b in zip(got[:2], want[:2]):
        np.testing.assert_array_equal(a, b)
    assert float(jnp.abs(want[1]).max()) > 0.1
    for name, a, b in zip("q k g".split(), got[2:], want[2:]):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert float(jnp.abs(a - b).max()) <= tolerance * float(
            jnp.abs(b).max()), name


@pytest.mark.parametrize("token", [0, 16, 32, 48, 37])
def test_a_reference_tokens_share_of_the_gates_gradient(token):
    """A level's reference token collects, minus, what every row and
    column decayed to it hands ``G``: the kernel's ``dG`` on tokens 0,
    16, 32, 48 of a chunk of 64 (and on one that is no reference) against
    central differences of the ``jax.numpy`` form, along a direction
    over the channels."""
    from apex_tpu.ops.pallas import kda_delta_rule as K
    q, k, gsum, w = _chunks_of(64, jnp.float32, seed=15)
    way = jnp.sign(jax.random.normal(jax.random.key(token), (128,)))
    step = jnp.zeros_like(gsum).at[0, 1, 1, token].set(1e-2 * way)
    loss = jax.jit(lambda g: sum(jnp.sum(p * x) for p, x in zip(
        G._local_products(q, k, g), w)))
    want = float(loss(gsum + step) - loss(gsum - step)) / 2e-2
    dg = _local_both(lambda *a: K.local_products(G._levels(64), *a), w)(
        q, k, gsum)[4]
    got = float(jnp.sum(dg[0, 1, 1, token] * way))
    assert abs(want) > 1e-2 and got == pytest.approx(want, rel=2e-2)


def test_the_vector_gates_kernels_reach_across_chunks_and_meet_the_recurrence():
    q, k, v, g, beta = _vector(192, seed=12, bias=-3.0, **kernels(1, 3))
    with dispatch.backend("pallas"):
        fn = jax.jit(lambda *a: G.gated_delta_rule_chunked(*a, chunk=64))
        whole = fn(q, k, v, g, beta)
        cut = fn(q, k, v.at[:, :, :64].set(0.0), g, beta)
    assert float(jnp.abs(whole[:, :, 64:] - cut[:, :, 64:]).max()) > 1e-2
    np.testing.assert_allclose(
        whole, jax.jit(G.gated_delta_rule_recurrent)(q, k, v, g, beta),
        atol=2e-6)


@pytest.mark.parametrize("dk, dv, chunk, backend, kernel", [
    (128, 128, 64, "pallas", True), (128, 128, 32, "pallas", False),
    (64, 128, 64, "pallas", False), (128, 128, 64, "auto", False)])
def test_vector_gate_shapes_the_kernels_do_not_take_fall_to_the_scan(
        dk, dv, chunk, backend, kernel):
    args = _vector(2 * chunk, seed=13, b=1, h=2, dk=dk, dv=dv)
    fn = lambda *a: G.gated_delta_rule_chunked(*a, chunk=chunk)
    with dispatch.backend(backend):
        assert _runs_kernels(fn, *args) == kernel
        # the local pair and the scan pair go together: both or neither
        text = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(fn(*a))))(
            *args))
        assert [name in text for name in (
            "apex_kda_local_fwd", "apex_kda_local_bwd", "apex_kda_fwd",
            "apex_kda_bwd")] == [kernel] * 4
        got = jax.jit(lambda *a: fn(*a))(*args)
    np.testing.assert_allclose(
        got, jax.jit(G.gated_delta_rule_recurrent)(*args), atol=2e-6)


def test_the_scalar_arm_never_imports_the_vector_gates_kernels():
    """The new bodies' module is the vector arm's alone: a program with a
    scalar gate (``qnext_train_s8192``'s) neither imports nor traces it."""
    import subprocess
    import sys
    code = ("import sys, jax, jax.numpy as jnp\n"
            "from apex_tpu.ops import dispatch\n"
            "from apex_tpu.ops.gated_delta_rule import gated_delta_rule\n"
            "import apex_tpu.models.hybrid_lm\n"
            "x = jnp.ones((1, 1, 64, 128))\n"
            "with dispatch.backend('pallas'):\n"
            "    jax.make_jaxpr(lambda *a: gated_delta_rule(*a))(\n"
            "        x, x, x, -x[..., 0], x[..., 0])\n"
            "assert 'apex_tpu.ops.pallas.gated_delta_rule' in sys.modules\n"
            "assert 'apex_tpu.ops.pallas.kda_delta_rule' not in sys.modules\n")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
