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
