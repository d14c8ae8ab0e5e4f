"""The Kimi Delta Attention mixer and latent attention without positions
in ``HybridLM``: a ``kda, kda, kda, latent, kda`` pattern led by a dense
layer as scans of one, two, one and one; the mixer through the chunked
delta rule and through the token-by-token recurrence against a naive
loop, forward and gradients; the latent mixer with its rotations left
out; recomputation; the scopes; the step's ``kda_chunk_decay_nats_max``
against ``numpy``. A file of its own beside ``test_hybrid_lm.py`` (the
suite's longest), so that the test run's workers can take it apart."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import prof
from apex_tpu.models.hybrid_lm import HybridLM, _causal_conv, _norm0
from apex_tpu.ops import dispatch
from test_hybrid_lm import _tokens

KINDS = ("kda", "kda", "kda", "latent", "kda")


def _kda(**kw):
    base = dict(
        vocab_size=96, hidden=32, layer_types=KINDS,
        ffn_types=("dense",) + ("experts",) * 4, kda_heads=4,
        kda_head_dim=8, conv_kernel=4, delta_chunk=16, num_heads=4,
        kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8,
        latent_rotary=False, num_experts=8, top_k=2, expert_ffn=16,
        shared_ffn=16, experts_held=(2, 6), router="sigmoid",
        routed_scale=2.446, dense_ffn=48, rms_eps=1e-5,
        zero_centred_norm=False)
    return HybridLM(**{**base, **kw})


def test_a_kda_kda_kda_latent_kda_pattern_led_by_a_dense_layer():
    """The seventh mixer kind as data: its sixteen leaves, runs of (kda,
    dense) x 1, (kda, experts) x 2, (latent, experts) x 1, (kda, experts)
    x 1, the result the layers' one after the other."""
    lm = _kda()
    p = lm.init(jax.random.key(0))
    assert set(p["layer_0"]) == {"norm1", "norm2", "kda", "mlp"}
    assert set(p["layer_3"]) == {"norm1", "norm2", "latent", "moe"}
    assert all(set(p[f"layer_{i}"]) == {"norm1", "norm2", "kda", "moe"}
               for i in (1, 2, 4))
    assert jax.tree.map(jnp.shape, p["layer_1"]["kda"]) == {
        "w_q": (32, 32), "w_k": (32, 32), "w_v": (32, 32),
        "conv_q": (4, 32), "conv_k": (4, 32), "conv_v": (4, 32),
        "w_f1": (32, 8), "w_f2": (8, 32), "A_log": (4,), "dt_bias": (32,),
        "w_b": (4, 32), "w_g1": (32, 8), "w_g2": (8, 32), "b_g": (32,),
        "norm": (8,), "w_out": (32, 32)}
    k = p["layer_1"]["kda"]
    assert float(jnp.abs(k["A_log"]).max()) == 0.0 == float(
        jnp.abs(k["b_g"]).max())
    assert float(k["dt_bias"].min()) == 1.0 == float(k["norm"].max())
    # the other kinds' draws are what they were: a latent layer's leaves
    # come from the layer's own keys, whatever kinds stand beside it
    toks = _tokens(key=3)[:, :-1]
    bias = 0.3 * jax.random.normal(jax.random.key(4), (4, 8))
    assert lm.router_state().shape == (4, 8)
    jaxpr = jax.make_jaxpr(lm.apply)(p, toks, bias)
    assert [e.params["length"] for e in jaxpr.eqns
            if e.primitive.name == "scan"] == [1, 2, 1, 1]

    @jax.jit
    def unrolled(p, toks, bias):
        x = p["embed"][toks]
        x, (aux, nats) = lm._block("kda", p["layer_0"], x, "dense")
        assert aux is None and set(nats) == {"kda_chunk_decay_nats"}
        for i in range(1, 5):
            x, aux = lm._block(KINDS[i], p[f"layer_{i}"], x, "experts",
                               bias[i - 1])
            assert isinstance(aux, tuple) == (KINDS[i] == "kda")
        return jnp.einsum("btd,vd->btv", lm._norm(x, p["norm_f"]),
                          p["head"])
    np.testing.assert_allclose(jax.jit(lm.apply)(p, toks, bias),
                               unrolled(p, toks, bias), atol=2e-5)


def _moved(lp, key=5):
    """A layer's leaves with the constants moved off 0 and 1, where a
    wrong use of them would not show."""
    ks = iter(jax.random.split(jax.random.key(key), 8))
    k = dict(lp["kda"])
    k["A_log"] = 0.5 * jax.random.normal(next(ks), k["A_log"].shape)
    k["dt_bias"] = 1.0 + 0.5 * jax.random.normal(next(ks),
                                                 k["dt_bias"].shape)
    k["b_g"] = 0.5 * jax.random.normal(next(ks), k["b_g"].shape)
    k["norm"] = 1.0 + 0.2 * jax.random.normal(next(ks), k["norm"].shape)
    return {**lp, "kda": k, "norm1": lp["norm1"] + 0.1}


def _naive_kda(lp, x, heads=4, d=8, eps=1e-5):
    """The mixer as the configuration's equations have it, a head and a
    token at a time."""
    p = lp["kda"]
    b, t, _ = x.shape
    h = _norm0(x, lp["norm1"], eps, False)

    def unit(a):
        return a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    q, k, v = (jax.nn.silu(_causal_conv(h @ p["w_" + n], p["conv_" + n]))
               .reshape(b, t, heads, d) for n in "qkv")
    q, k = unit(q) * d ** -0.5, unit(k)
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        (h @ p["w_f1"]) @ p["w_f2"] + p["dt_bias"]).reshape(b, t, heads, d)
    beta = jax.nn.sigmoid(h @ p["w_b"].T)
    s = jnp.zeros((b, heads, d, d))
    out = []
    for i in range(t):
        s = s * jnp.exp(g[:, i])[..., None]
        u = beta[:, i, :, None] * (v[:, i] - jnp.einsum(
            "bhkv,bhk->bhv", s, k[:, i]))
        s = s + k[:, i, :, :, None] * u[:, :, None, :]
        out.append(jnp.einsum("bhkv,bhk->bhv", s, q[:, i]))
    o = jnp.stack(out, 1)                               # [B, T, H, d]
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * p["norm"]
    gate = jax.nn.sigmoid((h @ p["w_g1"]) @ p["w_g2"] + p["b_g"])
    return x + (o.reshape(b, t, heads * d) * gate) @ p["w_out"], g


@pytest.mark.parametrize("seq, chunk", [(48, 16), (40, 32)])
def test_the_kda_mixer_against_a_naive_loop(seq, chunk):
    """Through the chunked op (the "fast" side) and through the
    recurrence (``dispatch.backend("reference")``): forward, the
    gradients in every leaf and in the input, and the mixer's counter
    against ``numpy``."""
    lm = _kda(delta_chunk=chunk, layer_types=("kda",), ffn_types=("dense",))
    lp = _moved(lm.init(jax.random.key(seq), scale=0.3)["layer_0"])
    x = jax.random.normal(jax.random.key(1), (2, seq, 32))
    want, g = jax.jit(_naive_kda)(lp, x)
    assert float(jnp.abs(want - x).max()) > 1e-2
    w = jax.random.normal(jax.random.key(9), x.shape)
    g_want = jax.jit(jax.grad(lambda lp, x: jnp.sum(_naive_kda(lp, x)[0] * w),
                              argnums=(0, 1)))(lp, x)
    # the counter: the largest summed decay of a chunk, padded with zeros
    gp = np.pad(np.asarray(g, np.float64), ((0, 0), (0, -seq % chunk),
                                            (0, 0), (0, 0)))
    nats = -gp.reshape(2, -1, chunk, 4, 8).sum(2).min()
    for side in ("auto", "reference"):
        with dispatch.backend(side):
            mixer = jax.jit(lambda lp, x: lm._kda_mixer(lp, x))
            got, aux = mixer(lp, x)
            grads = jax.jit(jax.grad(lambda lp, x: jnp.sum(
                lm._kda_mixer(lp, x)[0] * w), argnums=(0, 1)))(lp, x)
        np.testing.assert_allclose(got, want, atol=2e-5)
        assert float(aux["kda_chunk_decay_nats"]) == pytest.approx(
            nats, rel=1e-5)
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(g_want)):
            if "mlp" in str(path) or "norm2" in str(path):
                continue
            assert float(jnp.abs(b).max()) > 0, path
            # A_log's and dt_bias's are sums over every token that cancel
            np.testing.assert_allclose(a, b, atol=1e-3 * float(
                jnp.abs(b).max()) + 1e-6, err_msg=str(path))


@pytest.mark.parametrize("impl", ["fast", "default"])
def test_the_latent_mixer_without_positions_against_a_naive_softmax(impl):
    """``latent_rotary=False`` leaves both rotations out and keeps the
    shared 4-wide key head as it comes; the default still turns them."""
    kw = dict(layer_types=("latent",), ffn_types=("dense",), attn_impl=impl)
    lm, turned = _kda(**kw), _kda(latent_rotary=True, **kw)
    assert HybridLM(vocab_size=8, hidden=8,
                    layer_types=("latent",)).latent_rotary is True
    seq = 40
    lp = lm.init(jax.random.key(2), scale=0.3)["layer_0"]
    lp["latent"]["kv_norm"] = lp["latent"]["kv_norm"] - 0.2
    x = jax.random.normal(jax.random.key(1), (2, seq, 32))

    def naive(lp, x):
        p = lp["latent"]
        h = _norm0(x, lp["norm1"], 1e-5, False)
        q = (h @ p["w_q"]).reshape(2, seq, 4, 12)
        kva = h @ p["w_kva"]
        kv = (_norm0(kva[..., :16], p["kv_norm"], 1e-5, False)
              @ p["w_kvb"]).reshape(2, seq, 4, 16)
        out = []
        for head in range(4):
            k_h = jnp.concatenate([kv[:, :, head, :8], kva[..., 16:]], -1)
            s = jnp.einsum("btd,bsd->bts", q[:, :, head], k_h) * 12 ** -0.5
            s = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), s, -jnp.inf)
            out.append(jax.nn.softmax(s, -1) @ kv[:, :, head, 8:])
        return x + jnp.concatenate(out, -1) @ p["w_o"]
    want = naive(lp, x)
    np.testing.assert_allclose(lm._latent_mixer(lp, x), want, atol=2e-5)
    assert float(jnp.abs(turned._latent_mixer(lp, x) - want).max()) > 1e-3
    w = jax.random.normal(jax.random.key(9), x.shape)
    got = jax.grad(lambda lp, x: jnp.sum(lm._latent_mixer(lp, x) * w),
                   argnums=(0, 1))(lp, x)
    ref = jax.grad(lambda lp, x: jnp.sum(naive(lp, x) * w),
                   argnums=(0, 1))(lp, x)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(ref)):
        np.testing.assert_allclose(a, b, atol=1e-4, err_msg=str(path))


def test_recomputation_changes_nothing_and_the_counter_comes_out():
    """``remat`` on and off give one loss and one gradient; the step's
    counters gain ``kda_chunk_decay_nats_max``, the largest of the four
    Kimi Delta Attention layers' (the latent layer has none), and the
    sigmoid router's biases move as in any such model."""
    toks = _tokens(t=49, key=6)
    p = _kda().init(jax.random.key(0), scale=0.1)
    bias = _kda().router_state()
    outs = []
    for remat in (False, True):
        lm = _kda(remat=remat, head_chunk=32)
        outs.append(jax.jit(jax.value_and_grad(
            lm.loss_with_router_state, has_aux=True))(p, bias, toks))
    (loss, (moved, c)), grad = outs[0]
    (loss2, (_, c2)), grad2 = outs[1]
    assert float(loss) == pytest.approx(float(loss2), rel=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grad),
                            jax.tree.leaves(grad2)):
        np.testing.assert_allclose(a, b, atol=1e-6 + 1e-5 * float(
            jnp.abs(a).max()), err_msg=str(path))
    assert moved.shape == (4, 8) and float(jnp.abs(moved).max()) > 0
    # each layer's own reading, a layer at a time
    lm = _kda()

    @jax.jit
    def layer_by_layer(p, bias):
        x, each = p["embed"][toks[:, :-1]], []
        for i, kind in enumerate(KINDS):
            x, aux = lm._block(kind, p[f"layer_{i}"], x, lm.ffns[i],
                               None if i == 0 else bias[i - 1])
            if kind == "kda":
                each.append(aux[1]["kda_chunk_decay_nats"])
        return each
    each = [float(x) for x in layer_by_layer(p, bias)]
    assert len(each) == 4 and max(each) > min(each)
    assert float(c["kda_chunk_decay_nats_max"]) == pytest.approx(max(each))
    assert float(c2["kda_chunk_decay_nats_max"]) == pytest.approx(max(each))
    # about 1.31 nats a token at A = 1, dt_bias = 1 and small gates
    assert 16 * 1.2 < max(each) < 16 * 1.5
    # a model with no such layer has no such counter
    latent = _kda(layer_types=("latent",) * 5)
    assert "kda_chunk_decay_nats_max" not in jax.eval_shape(
        latent.loss_with_counters,
        jax.eval_shape(latent.init, jax.random.key(0)), toks)[1]


def test_the_mixers_scopes_are_siblings_in_the_vocabulary():
    """``kda_attention`` around ``delta_rule``, never nested, forward and
    backward, beside the latent layer's, the dense layer's and the expert
    layers' scopes."""
    lm = _kda(remat=True)
    p = lm.init(jax.random.key(0))
    text = jax.jit(jax.grad(lambda p, b, t: lm.loss_with_router_state(
        p, b, t)[0])).lower(p, lm.router_state(), _tokens(t=49)) \
        .compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))

    def under(scope, path):
        return re.search(rf"(^|[/(]){scope}([/)]|$)", path) is not None
    for scope in ("embed", "kda_attention", "delta_rule", "latent_attention",
                  "mlp", "moe_route", "moe_experts", "head_loss"):
        assert scope in prof.SCOPES
        mine = [q for q in paths if under(scope, q)]
        assert any("transpose(" not in q for q in mine), scope
        assert any("transpose(" in q for q in mine), scope
    assert not any(under("linear_attention", q) for q in paths)
    assert "delta_rule/kda_attention" not in text
    assert "kda_attention/delta_rule" not in text
