"""``HybridLM``'s losses through the reduced fused head
(``contrib.xentropy.weighted_linear_cross_entropy``: blocks of rows, the
head's gradients made beside the loss) against the formulas it replaced,
written with the per-row op: ``jnp.mean`` of ``linear_cross_entropy``'s
rows for the next-token loss, ``sum(rows * masked / p) / (R L)`` for the
block-diffusion one. A file of its own beside ``test_hybrid_lm.py`` (the
suite's longest), so that the test run's workers can take it apart from
that file."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.contrib.xentropy import linear_cross_entropy
from test_hybrid_lm import _conv, _tiny, _tokens
from test_hybrid_lm_diffusion import VOCAB, _batch, _diffusion, _params


def _same_gradients(got, want, rel=0.0):
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(
            a, b, atol=2e-6 + rel * float(jnp.abs(b).max()),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("model", ["tiny", "tied"])
def test_the_next_token_loss_against_the_per_row_ops_mean(model):
    """With a head of its own and with the embedding as the head (its
    gradient the gather's scatter-add plus the head's float32 ``dW``)."""
    lm = {"tiny": lambda: _tiny(remat=True, head_chunk=24),
          "tied": lambda: _conv(remat=True, head_chunk=32)}[model]()
    params, toks = lm.init(jax.random.key(3), scale=0.1), _tokens(key=2)
    bias = lm.router_state() if lm.router == "sigmoid" else None

    def per_row(params):
        x, c = lm.hidden_states(params, toks[:, :-1], bias)
        return jnp.mean(linear_cross_entropy(
            x.reshape(-1, lm.hidden), lm._head(params),
            toks[:, 1:].reshape(-1), chunk=lm.head_chunk)) \
            + lm.aux_coef * c["load_balance_loss"]

    got, g_got = jax.value_and_grad(
        lambda p: lm.loss_with_counters(p, toks, bias)[0])(params)
    want, g_want = jax.value_and_grad(per_row)(params)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    _same_gradients(g_got, g_want)


def test_the_block_diffusion_loss_against_the_per_row_ops_weighted_sum():
    lm = _diffusion(head_chunk=32)
    params, (tokens, masked, p) = _params(lm), _batch(key=1)
    rows, length = tokens.shape

    def per_row(params):
        twice = jnp.concatenate([jnp.where(masked, VOCAB - 1, tokens),
                                 tokens], axis=1)
        x, c = lm.hidden_states(params, twice)
        losses = linear_cross_entropy(
            x[:, :length].reshape(-1, lm.hidden), lm._head(params),
            tokens.reshape(-1), chunk=lm.head_chunk)
        weight = (masked / p[:, None]).reshape(-1)
        return jnp.sum(losses * weight) / (rows * length) \
            + lm.aux_coef * c["load_balance_loss"]

    got, g_got = jax.value_and_grad(lambda q: lm.loss_with_counters(
        q, (tokens, masked, p))[0])(params)
    want, g_want = jax.value_and_grad(per_row)(params)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    _same_gradients(g_got, g_want, rel=1e-5)
