"""The gated delta rule: the chunked form against the token-by-token
recurrence, forward and gradient, at lengths that are and are not a
multiple of the chunk; the inverse it rests on; the dispatch."""

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


@pytest.mark.parametrize("length, chunk", [
    (64, 64), (128, 64), (192, 32), (100, 64), (7, 16), (200, 64),
    (130, 128)])
def test_chunked_is_the_recurrence(length, chunk):
    args = _inputs(length)
    want = G.gated_delta_rule_recurrent(*args)
    got = G.gated_delta_rule_chunked(*args, chunk=chunk)
    assert got.shape == want.shape == args[2].shape
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("length, chunk", [(128, 64), (100, 64), (75, 16)])
def test_chunked_gradient_is_the_recurrences(length, chunk):
    args = _inputs(length, seed=1)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                        argnums=(0, 1, 2, 3, 4))(*args)
    want = grads(G.gated_delta_rule_recurrent)
    got = grads(lambda *a: G.gated_delta_rule_chunked(*a, chunk=chunk))
    for name, a, b in zip("q k v g beta".split(), got, want):
        np.testing.assert_allclose(a, b, atol=2e-6 * float(jnp.abs(b).max())
                                   + 1e-7, err_msg=name)


def test_the_state_reaches_across_chunks():
    """A test that passed with the state dropped between chunks would
    prove nothing: with the first chunk's keys and values zeroed the
    later chunks' outputs change."""
    q, k, v, g, beta = _inputs(128, seed=2)
    whole = G.gated_delta_rule_chunked(q, k, v, g, beta, chunk=64)
    cut = G.gated_delta_rule_chunked(q, k, v.at[:, :, :64].set(0.0), g, beta,
                                     chunk=64)
    assert float(jnp.abs(whole[:, :, 64:] - cut[:, :, 64:]).max()) > 1e-2


def test_bfloat16_inputs_keep_their_type_and_stay_close():
    args = _inputs(128, seed=3)
    want = G.gated_delta_rule_recurrent(*args)
    low = tuple(x.astype(jnp.bfloat16) for x in args[:3]) + args[3:]
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


def test_a_chunk_size_that_is_no_power_of_two_is_refused():
    with pytest.raises(ValueError):
        G.gated_delta_rule_chunked(*_inputs(48), chunk=48)
