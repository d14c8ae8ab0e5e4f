"""The "sparse" mixer in ``HybridLM``: grouped-query attention over a
learned per-query key set with a lightning indexer that carries a loss of
its own. The kernels' path against plain ``jax.numpy`` and against a naive
layer written out by hand, a run of sparse layers as one scanned body,
``remat`` on and off, the counters, and the two ``stop_gradient``s that
part the gradients. A file of its own beside ``test_hybrid_lm.py`` (the
suite's longest), so that the test run's workers can take it apart."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.hybrid_lm import MIXERS, HybridLM, _norm0, _rotary
from apex_tpu.ops import key_set as KS, sparse_index as SI
from test_hybrid_lm import _tokens



def _sparse(**kw):
    """Sparse layers, 8 query heads over 2 key/value heads, an indexer of 4
    heads of 8 that keeps 12 keys a query, a softmax router with no
    shared expert."""
    base = dict(
        vocab_size=96, hidden=32, layer_types=("sparse",) * 3, num_heads=8,
        num_kv_heads=2, head_dim=8, rotary_dim=8, rope_theta=1e7,
        attn_gate=False, index_heads=4, index_dim=8, index_topk=12,
        num_experts=8, top_k=3, expert_ffn=16, shared_ffn=0,
        experts_held=(2, 6), zero_centred_norm=False)
    return HybridLM(**{**base, **kw})


def _params(lm, key=0):
    """Seeded weights with the norms moved off 1 and the indexer's key
    norm's bias off 0: at their starts a wrong use would not show."""
    p = lm.init(jax.random.key(key), scale=0.3)
    return jax.tree.map(lambda x: x + 0.1 * jax.random.normal(
        jax.random.key(1), x.shape) if x.ndim == 1 else x, p)


def test_the_sparse_kind_is_data_and_a_run_is_one_scanned_body():
    assert MIXERS[5] == "sparse" and len(MIXERS) == 7
    lm = _sparse()
    p = lm.init(jax.random.key(0))
    assert all(set(p[f"layer_{i}"]) == {"norm1", "norm2", "attn", "index",
                                        "moe"} for i in range(3))
    assert jax.tree.map(jnp.shape, p["layer_0"]["index"]) == {
        "w_q": (32, 32), "w_k": (8, 32), "w_w": (4, 32),    # [out, in]
        "k_norm": {"w": (8,), "b": (8,)}}
    assert set(p["layer_0"]["attn"]) == set(
        _sparse(layer_types=("full",)).init(jax.random.key(0))[
            "layer_0"]["attn"])
    toks = _tokens(key=3)[:, :-1]
    jaxpr = jax.make_jaxpr(lm.apply)(p, toks)
    assert [e.params["length"] for e in jaxpr.eqns
            if e.primitive.name == "scan"] == [3]
    x = p["embed"][toks]
    block = jax.jit(lambda lp, x: lm._block("sparse", lp, x))
    for i in range(3):
        x, (_, index_aux) = block(p[f"layer_{i}"], x)
        assert set(index_aux) == {"index_loss", "select_pairs",
                                  "select_live_tile_pct"}
    want = jnp.einsum("btd,vd->btv", _norm0(x, p["norm_f"], lm.rms_eps,
                                            False), p["head"])
    np.testing.assert_allclose(lm.apply(p, toks), want, atol=2e-5)
    with pytest.raises(ValueError, match="no output gate"):
        _sparse(attn_gate=True)


def test_a_sparse_layer_beside_other_kinds():
    """Runs of other kinds before and after: their auxes join by kind."""
    lm = _sparse(layer_types=("full", "sparse", "sparse", "full"))
    p = _params(lm)
    (loss, c), g = jax.jit(jax.value_and_grad(
        lm.loss_with_counters, has_aux=True))(p, _tokens(t=41))
    assert np.isfinite(float(loss))
    assert int(c["select_pairs"]) == 2 * 2 * (12 * 40 - 12 * 11 // 2)
    assert "index" not in p["layer_0"] and "index" in p["layer_1"]
    assert float(jnp.abs(g["layer_2"]["index"]["w_q"]).max()) > 0


@pytest.mark.parametrize("seq, topk", [(96, 12), (40, 64)])
def test_the_sparse_mixer_against_a_naive_layer(seq, topk):
    """The layer written out head by head with ``jax.lax.top_k`` for the
    set: through the kernels and through plain ``jax.numpy``; forward, the
    indexer's loss, and the gradients of both. ``topk`` past the sequence
    is causal attention."""
    kw = dict(hidden=64, head_dim=16, rotary_dim=16, index_topk=topk,
              layer_types=("sparse",))
    fast, plain = _sparse(attn_impl="fast", **kw), _sparse(
        attn_impl="default", **kw)
    lp = _params(fast, seq)["layer_0"]
    x = jax.random.normal(jax.random.key(1), (2, seq, 64))
    seen = jnp.arange(seq)[:, None] >= jnp.arange(seq)[None, :]

    def naive(lp, x):
        p, ip = lp["attn"], lp["index"]
        h = _norm0(x, lp["norm1"], 1e-6, False)
        hbar = jax.lax.stop_gradient(h)
        qi = _rotary((hbar @ ip["w_q"]).reshape(2, seq, 4, 8), 1e7, 8)
        ki = hbar @ ip["w_k"].T
        ki = ki - ki.mean(-1, keepdims=True)
        ki = ki * jax.lax.rsqrt((ki * ki).mean(-1, keepdims=True) + 1e-6) \
            * ip["k_norm"]["w"] + ip["k_norm"]["b"]
        ki = _rotary(ki[:, :, None], 1e7, 8)[:, :, 0]
        w = hbar @ ip["w_w"].T * 32 ** -0.5
        i = jnp.einsum("bth,bths->bts", w, jax.nn.relu(
            jnp.einsum("bthd,bsd->bths", qi, ki)))
        i = jnp.where(seen, i, -jnp.inf)
        _, idx = jax.lax.top_k(i, min(topk, seq))
        keep = jnp.zeros(i.shape, bool).at[
            jnp.arange(2)[:, None, None], jnp.arange(seq)[None, :, None],
            idx].set(True) & seen
        q = (h @ p["w_q"]).reshape(2, seq, 8, 16)
        k = (h @ p["w_k"]).reshape(2, seq, 2, 16)
        v = (h @ p["w_v"]).reshape(2, seq, 2, 16)
        q = _rotary(_norm0(q, p["q_norm"], 1e-6, False), 1e7, 16)
        k = _rotary(_norm0(k, p["k_norm"], 1e-6, False), 1e7, 16)
        out, probs = [], 0.0
        for head in range(8):
            s = jnp.einsum("btd,bsd->bts", q[:, :, head],
                           k[:, :, head // 4]) * 0.25
            a = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
            probs = probs + jax.lax.stop_gradient(a) / 8
            out.append(a @ v[:, :, head // 4])
        log_qi = jax.nn.log_softmax(jnp.where(keep, i, -jnp.inf), -1)
        kl = jnp.where(keep, jax.scipy.special.xlogy(probs, probs)
                       - probs * jnp.where(keep, log_qi, 0.0), 0.0)
        return x + jnp.concatenate(out, -1) @ p["w_o"], \
            jnp.mean(jnp.sum(kl, -1)), keep
    want, want_loss, keep = jax.jit(naive)(lp, x)
    assert float(jnp.abs(want - x).max()) > 1e-2
    np.testing.assert_array_equal(keep.sum(-1)[0],
                                  np.minimum(np.arange(seq) + 1, topk))
    w = jax.random.normal(jax.random.key(9), x.shape)

    def both(mixer):
        def f(lp, x):
            y, aux = mixer(lp, x)
            return jnp.sum(y * w) + 3.0 * aux["index_loss"], (y, aux)
        return f
    g_want = jax.jit(jax.grad(
        lambda lp, x: (lambda y, l, _: jnp.sum(y * w) + 3.0 * l)(
            *naive(lp, x)), argnums=(0, 1)))(lp, x)
    for lm in (fast, plain):
        got, (y, aux) = jax.jit(jax.grad(
            both(lm._sparse_mixer), argnums=(0, 1), has_aux=True))(lp, x)
        np.testing.assert_allclose(y, want, atol=2e-5)
        assert float(aux["index_loss"]) == pytest.approx(float(want_loss),
                                                         rel=1e-5, abs=1e-7)
        assert int(aux["select_pairs"]) == int(keep.sum())
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                                jax.tree.leaves(g_want)):
            np.testing.assert_allclose(a, b, atol=1e-4, err_msg=str(path))
        sel = lm._index(lp["index"], _norm0(x, lp["norm1"], 1e-6, False))[-1]
        np.testing.assert_array_equal(KS.unpack_select(sel, seq), keep)
    causal = _sparse(attn_impl="default", **{**kw, "layer_types": ("full",)})
    full = causal._full_mixer({k: v for k, v in lp.items() if k != "index"},
                              x)
    if topk >= seq:
        np.testing.assert_allclose(want, full, atol=2e-5)
    else:
        assert float(jnp.abs(want - full).max()) > 1e-3


def test_fast_against_reference_and_remat_on_and_off_give_one_gradient():
    fast = _sparse()
    p, toks = _params(fast), _tokens(t=49)
    grads = {}
    for name, lm in (("fast", fast),
                     ("plain", dataclasses.replace(fast,
                                                   attn_impl="default")),
                     ("remat", dataclasses.replace(fast, remat=True))):
        (loss, c), g = jax.jit(jax.value_and_grad(
            lm.loss_with_counters, has_aux=True))(p, toks)
        grads[name] = (float(loss), c, g)
    loss, c, g = grads["fast"]
    assert float(c["index_loss"]) > 0.01
    assert int(c["select_pairs"]) == 3 * 2 * (12 * 48 - 12 * 11 // 2)
    assert 0.0 < float(c["select_live_tile_pct"]) <= 100.0
    assert int(c["moe_overflow_pairs"]) == 0
    for other in ("plain", "remat"):
        assert grads[other][0] == pytest.approx(loss, rel=2e-6)
        assert float(grads[other][1]["index_loss"]) == pytest.approx(
            float(c["index_loss"]), rel=2e-5)
        for (path, a), b in zip(
                jax.tree_util.tree_leaves_with_path(grads[other][2]),
                jax.tree.leaves(g)):
            np.testing.assert_allclose(
                a, b, atol=2e-5 * float(jnp.abs(b).max()) + 1e-8,
                err_msg=f"{other} {path}")


def test_remat_keeps_the_set_and_the_indexers_gradient_by_name():
    """The recomputed pass holds no second search, no second loss and no
    second forward kernel: the body of the scan holds each call once but
    the scores' (the choice and the loss)."""
    lm = _sparse(remat=True)
    text = str(jax.make_jaxpr(jax.grad(lm.loss))(
        lm.init(jax.random.key(0)), _tokens()))
    count = lambda name: len(re.findall(rf"name={name}\b", text))
    assert count("apex_idx_scores") == 2
    assert [count(n) for n in ("apex_idx_search", "apex_idx_probs",
                               "apex_idx_grad", "apex_flash_sel_fwd",
                               "apex_flash_sel_bwd_dq",
                               "apex_flash_sel_bwd_dkv")] == [1] * 6
    every = str(jax.make_jaxpr(jax.grad(dataclasses.replace(
        lm, index_topk=10 ** 6).loss))(lm.init(jax.random.key(0)),
                                       _tokens()))
    assert len(re.findall(r"name=apex_flash_sel_fwd\b", every)) == 1


def test_the_two_stop_gradients_part_the_gradients():
    """The indexer's leaves get exactly zero from the language-model loss
    and the balance term, every other leaf exactly zero from ``L_I``; the
    loss is the sum of the two."""
    lm = _sparse()
    p, toks = _params(lm), _tokens(t=49)
    rest = jax.jit(jax.grad(dataclasses.replace(lm, index_coef=0.0).loss))(
        p, toks)
    index = jax.jit(jax.grad(lambda p: lm.loss_with_counters(p, toks)[1][
        "index_loss"]))(p)
    whole = jax.jit(jax.grad(lm.loss))(p, toks)
    for i in range(3):
        layer = f"layer_{i}"
        for leaf in jax.tree.leaves(rest[layer]["index"]):
            assert float(jnp.abs(leaf).max()) == 0.0
        for leaf in jax.tree.leaves(index[layer]["index"]):
            assert float(jnp.abs(leaf).max()) > 0.0
        for name in ("norm1", "norm2", "attn", "moe"):
            for leaf in jax.tree.leaves(index[layer][name]):
                assert float(jnp.abs(leaf).max()) == 0.0
            for a, b in zip(jax.tree.leaves(whole[layer][name]),
                            jax.tree.leaves(rest[layer][name])):
                np.testing.assert_array_equal(a, b)
        for a, b in zip(jax.tree.leaves(whole[layer]["index"]),
                        jax.tree.leaves(index[layer]["index"])):
            np.testing.assert_allclose(a, b, rtol=1e-6)
    for name in ("embed", "head", "norm_f"):
        assert float(jnp.abs(index[name]).max()) == 0.0
    assert float(jnp.abs(rest["layer_0"]["attn"]["w_q"]).max()) > 0.0
    # index_coef scales the indexer's part and nothing else
    half = jax.jit(jax.grad(dataclasses.replace(lm, index_coef=0.5).loss))(
        p, toks)
    np.testing.assert_allclose(half["layer_1"]["index"]["w_k"],
                               0.5 * whole["layer_1"]["index"]["w_k"],
                               rtol=1e-6)


def test_first_selection_is_what_layer_0_reads():
    lm = _sparse()
    p, toks = _params(lm), _tokens(t=48)
    sel = lm.first_selection(p, toks)
    assert sel.shape == (2, 48, 128) and sel.dtype == jnp.int32
    lp = p["layer_0"]
    qi, ki, w, inside = lm._index(lp["index"], _norm0(
        p["embed"][toks], lp["norm1"], lm.rms_eps, False))
    np.testing.assert_array_equal(sel, inside)
    np.testing.assert_array_equal(sel, SI.select_keys(qi, ki, w, 12))
    assert int(jnp.sum(jax.lax.population_count(sel))) \
        == 2 * (12 * 48 - 12 * 11 // 2)
