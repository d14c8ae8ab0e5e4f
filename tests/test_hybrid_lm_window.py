"""The window mixer and rotary tables by layer kind in ``HybridLM``: a
``window, window, window, full`` pattern as scans of three and one, the
mixer through the windowed flash kernels and through plain attention
against a naive masked softmax, its locality, and YaRN's table by hand.
A file of its own beside ``test_hybrid_lm.py`` (the suite's longest), so
that the test run's workers can take it apart from that file."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.hybrid_lm import (MIXERS, Yarn, _norm0, _rotary,
                                       _yarn)
from test_hybrid_lm import _tokens, _window

YARN = Yarn(16.0, 8192, 32.0, 1.0, 1.2772588722239782)     # the source's



def test_a_window_window_window_full_pattern_is_scans_of_three_and_one():
    """The fifth mixer kind as data: the full mixer's leaves, a run of
    three and a run of one, the result the layers' one after the other;
    under ``remat`` each run's body holds its forward kernel once, the
    window run's under its own name."""
    assert MIXERS == ("linear", "full", "latent", "conv", "window",
                      "sparse", "kda")
    lm = _window()
    p = lm.init(jax.random.key(0))
    assert all(set(p[f"layer_{i}"]) == {"norm1", "norm2", "attn", "moe"}
               for i in range(4))
    assert jax.tree.map(jnp.shape, p["layer_0"]["attn"]) \
        == jax.tree.map(jnp.shape, p["layer_3"]["attn"])
    assert "shared" not in p["layer_0"]["moe"]
    toks = _tokens(key=3)[:, :-1]
    jaxpr = jax.make_jaxpr(lm.apply)(p, toks)
    assert [e.params["length"] for e in jaxpr.eqns
            if e.primitive.name == "scan"] == [3, 1]
    x = p["embed"][toks]
    for i, kind in enumerate(lm.layer_types):
        x, _ = lm._block(kind, p[f"layer_{i}"], x)
    want = jnp.einsum("btd,vd->btv", _norm0(x, p["norm_f"], lm.rms_eps,
                                            False), p["head"])
    np.testing.assert_allclose(lm.apply(p, toks), want, atol=2e-5)
    text = str(jax.make_jaxpr(jax.grad(_window(remat=True).loss))(
        p, _tokens()))
    for kernel in ("win_fwd", "win_bwd_dq", "win_bwd_dkv", "fwd", "bwd_dq",
                   "bwd_dkv"):
        assert len(re.findall(rf"name=apex_flash_{kernel}\b", text)) == 1
    with pytest.raises(ValueError):
        _window(window=0)
    with pytest.raises(ValueError):
        _window(rope_yarn=(16.0, 8192))


@pytest.mark.parametrize("seq, window", [(128, 40), (80, 128)])
def test_the_window_mixer_against_a_naive_masked_softmax(seq, window):
    """8 query heads over 1 key/value head, the plain rotary table, query
    ``i`` seeing keys ``i - window + 1 .. i``: through the windowed flash
    kernels, through plain attention, and head by head; forward, and
    through the band the kernels' gradients. A window wider than the
    sequence is the causal mixer."""
    kw = dict(hidden=64, head_dim=16, rotary_dim=16, window=window,
              layer_types=("window",))
    fast, plain = _window(attn_impl="fast", **kw), _window(
        attn_impl="default", **kw)
    lp = fast.init(jax.random.key(seq), scale=0.2)["layer_0"]
    lp["norm1"] = lp["norm1"] + 0.1
    lp["attn"]["q_norm"] = lp["attn"]["q_norm"] - 0.2
    x = jax.random.normal(jax.random.key(1), (2, seq, 64))
    ahead = jnp.arange(seq)[:, None] - jnp.arange(seq)[None, :]

    def naive(lp, x):
        p = lp["attn"]
        h = _norm0(x, lp["norm1"], 1e-6, False)
        q = (h @ p["w_q"]).reshape(2, seq, 8, 16)
        k = (h @ p["w_k"]).reshape(2, seq, 1, 16)
        v = (h @ p["w_v"]).reshape(2, seq, 1, 16)
        q = _rotary(_norm0(q, p["q_norm"], 1e-6, False), 5e5, 16)
        k = _rotary(_norm0(k, p["k_norm"], 1e-6, False), 5e5, 16)
        out = []
        for head in range(8):
            s = jnp.einsum("btd,bsd->bts", q[:, :, head], k[:, :, 0]) * 0.25
            s = jnp.where((ahead >= 0) & (ahead < window), s, -jnp.inf)
            out.append(jax.nn.softmax(s, -1) @ v[:, :, 0])
        return x + jnp.concatenate(out, -1) @ p["w_o"]
    want = naive(lp, x)
    assert float(jnp.abs(want - x).max()) > 1e-2
    mixers = [functools.partial(lm._full_mixer, window=window)
              for lm in (fast, plain)]
    w = jax.random.normal(jax.random.key(9), x.shape)
    for mixer in mixers:
        np.testing.assert_allclose(mixer(lp, x), want, atol=2e-5)
    if window < seq:        # the gradients, through the band
        g_want = jax.grad(lambda lp, x: jnp.sum(naive(lp, x) * w),
                          argnums=(0, 1))(lp, x)
        got = jax.grad(lambda lp, x: jnp.sum(mixers[0](lp, x) * w),
                       argnums=(0, 1))(lp, x)
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                                jax.tree.leaves(g_want)):
            np.testing.assert_allclose(a, b, atol=1e-4, err_msg=str(path))
    # the full mixer on the same (plain) table: the window's limit
    causal = _window(attn_impl="default", rope_yarn=None, **kw)._full_mixer
    if window >= seq:
        np.testing.assert_array_equal(mixers[1](lp, x), causal(lp, x))
    else:
        assert float(jnp.abs(want - causal(lp, x)).max()) > 1e-3


@pytest.mark.parametrize("impl", ["fast", "default"])
def test_a_window_layer_is_local(impl):
    """One layer, window 16: a changed token moves the logits of its own
    position and of the 15 after it, and nothing farther on (a token
    ``window`` back is out of sight, one ``window - 1`` back is seen)."""
    lm = _window(layer_types=("window",), window=16, attn_impl=impl)
    p = lm.init(jax.random.key(4), scale=0.3)
    toks = _tokens(t=48, key=5)
    base = lm.apply(p, toks)
    for changed, moves in ((24, False), (25, True)):    # seen from t = 40
        other = toks.at[:, changed].set((toks[:, changed] + 1) % 96)
        got = lm.apply(p, other)
        assert bool(jnp.any(got[:, 40] != base[:, 40])) == moves
        np.testing.assert_array_equal(got[:, :changed], base[:, :changed])
        np.testing.assert_array_equal(got[:, changed + 16:],
                                      base[:, changed + 16:])
        assert all(float(jnp.abs(got[:, t] - base[:, t]).max()) > 1e-6
                   for t in (changed, changed + 15))


def test_yarns_table_by_hand_and_factor_one_is_the_plain_table():
    """The source's YaRN at heads of 128: pairs 0-18 keep the plain
    frequency (``low`` = 18), pairs 35-63 have it divided by 16 (``high``
    = 35), a linear ramp between; ``cos`` and ``sin`` carry the attention
    factor 0.1 ln 16 + 1; factor 1 is the plain table, and a window layer
    keeps the plain table whatever ``rope_yarn`` says."""
    plain = 5e5 ** (-np.arange(64) / 64.0)
    got = np.asarray(_yarn(jnp.asarray(plain, jnp.float32), 5e5, 128, YARN))
    c = lambda turns: 128 * np.log(8192 / (2 * np.pi * turns)) \
        / (2 * np.log(5e5))
    assert (int(np.floor(c(32))), int(np.ceil(c(1)))) == (18, 35)
    np.testing.assert_allclose(got[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(got[35:], plain[35:] / 16, rtol=1e-6)
    for m in (19, 26, 34):
        ramp = (m - 18) / 17
        np.testing.assert_allclose(
            got[m], plain[m] / 16 * ramp + plain[m] * (1 - ramp), rtol=1e-6)
    assert YARN.attention_factor == pytest.approx(0.1 * np.log(16) + 1,
                                                  rel=1e-12)
    x = jax.random.normal(jax.random.key(6), (1, 24, 2, 128))
    y = _rotary(x, 5e5, 128, YARN)
    np.testing.assert_allclose(
        jnp.linalg.norm(y, axis=-1),
        YARN.attention_factor * jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    # pair 0 keeps its frequency, pair 63 turns 16 times slower
    np.testing.assert_allclose(y[..., 0] / YARN.attention_factor,
                               _rotary(x, 5e5, 128)[..., 0], atol=1e-5)
    slow = np.asarray(x[0, :, 0, 63] * np.cos(np.arange(24) * plain[63] / 16)
                      - x[0, :, 0, 127] * np.sin(np.arange(24) * plain[63]
                                                 / 16))
    np.testing.assert_allclose(y[0, :, 0, 63] / YARN.attention_factor, slow,
                               atol=1e-5)
    np.testing.assert_allclose(_rotary(x, 5e5, 128,
                                       Yarn(1.0, 8192, 32, 1, 1.0)),
                               _rotary(x, 5e5, 128), atol=1e-6)
    # by layer kind: the full layer's output moves with rope_yarn, the
    # window layer's does not
    kw = dict(hidden=64, head_dim=16, rotary_dim=16)
    a, b = _window(**kw), _window(rope_yarn=None, **kw)
    lp = a.init(jax.random.key(2), scale=0.2)["layer_0"]
    h = jax.random.normal(jax.random.key(3), (1, 40, 64))
    np.testing.assert_array_equal(a._block("window", lp, h)[0],
                                  b._block("window", lp, h)[0])
    assert float(jnp.abs(a._block("full", lp, h)[0]
                         - b._block("full", lp, h)[0]).max()) > 1e-3
