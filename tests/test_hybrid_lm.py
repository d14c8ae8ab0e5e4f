"""The hybrid linear-attention mixture-of-experts LM: the layer pattern as
data, grouped-query flash attention against plain attention at head size
256, the scopes its step opens, and its counters out of the step beside
the loss while the dense LM's step returns what it did."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import prof
from apex_tpu.models import HybridLM, TransformerLM
from apex_tpu.models.hybrid_lm import _norm0, _rotary

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))


def _tiny(**kw):
    base = dict(
        vocab_size=96, hidden=32, layer_types=("linear", "full"),
        num_heads=4, num_kv_heads=2, head_dim=16, rotary_dim=4,
        linear_k_heads=2, linear_v_heads=4, linear_k_dim=8, linear_v_dim=8,
        delta_chunk=16, num_experts=8, top_k=2, expert_ffn=16, shared_ffn=16,
        experts_held=(2, 6))
    return HybridLM(**{**base, **kw})


def _tokens(rows=2, t=33, vocab=96, key=0):
    return jax.random.randint(jax.random.key(key), (rows, t), 0, vocab)


def test_the_layer_pattern_is_data():
    lm = _tiny(layer_types=("linear", "linear", "linear", "full", "linear"))
    p = lm.init(jax.random.key(0))
    kinds = ["linear" if "linear" in p[f"layer_{i}"] else "full"
             for i in range(5)]
    assert kinds == ["linear"] * 3 + ["full", "linear"]
    assert "attn" in p["layer_3"]
    assert p["head"].shape == p["embed"].shape and p["head"] is not p["embed"]
    # the held experts' weights only; the router over all of them
    assert p["layer_0"]["moe"]["w_gate"].shape == (4, 32, 16)
    assert p["layer_0"]["moe"]["router"].shape == (32, 8)
    with pytest.raises(ValueError):
        _tiny(layer_types=("linear", "windowed"))
    with pytest.raises(ValueError):
        _tiny(layer_types=())


def test_a_run_of_layers_of_one_kind_is_one_scanned_body():
    """Three Gated DeltaNet layers compile once: the forward holds one
    scan a run, as long as the run, and the result is the layers' one
    after the other."""
    lm = _tiny(layer_types=("linear", "linear", "linear", "full", "linear"))
    params, toks = lm.init(jax.random.key(2)), _tokens(key=3)[:, :-1]
    jaxpr = jax.make_jaxpr(lm.apply)(params, toks)
    runs = [e.params["length"] for e in jaxpr.eqns
            if e.primitive.name == "scan"]
    assert runs == [3, 1, 1]
    x = params["embed"][toks]
    for i, kind in enumerate(lm.layer_types):
        x, _ = lm._block(kind, params[f"layer_{i}"], x)
    want = jnp.einsum("btd,vd->btv", _norm0(x, params["norm_f"], lm.rms_eps),
                      params["head"])
    np.testing.assert_allclose(lm.apply(params, toks), want, atol=2e-5)


def test_loss_goes_down_and_counters_come_out():
    lm = _tiny(head_chunk=32, remat=True)
    params = lm.init(jax.random.key(1))
    toks = _tokens()

    @jax.jit
    def step(p):
        (loss, c), g = jax.value_and_grad(lm.loss_with_counters,
                                          has_aux=True)(p, toks)
        return jax.tree.map(lambda a, b: a - 0.3 * b, p, g), loss, c
    first = None
    for _ in range(8):
        params, loss, c = step(params)
        first = first if first is not None else float(loss)
    assert float(loss) < first - 0.05
    assert set(c) == {"moe_overflow_pairs", "moe_held_pairs_max",
                      "expert_load_max_over_mean"}
    assert int(c["moe_overflow_pairs"]) == 0
    assert float(c["expert_load_max_over_mean"]) >= 1.0
    assert float(lm.loss(params, toks)) == pytest.approx(float(
        lm.loss_with_counters(params, toks)[0]))


def test_recomputation_and_the_chunked_head_change_nothing():
    toks = _tokens(key=2)
    plain = _tiny()
    params = plain.init(jax.random.key(3))
    want, g_want = jax.value_and_grad(plain.loss)(params, toks)
    got, g_got = jax.value_and_grad(_tiny(remat=True, head_chunk=24).loss)(
        params, toks)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_the_flash_kernels_and_plain_attention_agree_in_the_model():
    toks = _tokens(key=4)
    params = _tiny().init(jax.random.key(5))
    fast = _tiny(attn_impl="fast").apply(params, toks)
    plain = _tiny(attn_impl="default").apply(params, toks)
    np.testing.assert_allclose(fast, plain, atol=2e-5)


@pytest.mark.parametrize("seq", [128, 80])
def test_grouped_query_flash_against_plain_attention_at_head_size_256(seq):
    """16 query heads over 2 key/value heads of 256, K and V broadcast in
    front of the kernel: forward, and the group's summed dK and dV."""
    from apex_tpu.contrib.multihead_attn.flash_attention import (
        flash_attention)
    h, kv, hd = 16, 2, 256
    ks = jax.random.split(jax.random.key(seq), 3)
    q = jax.random.normal(ks[0], (1, h, seq, hd)) * 0.5
    k = jax.random.normal(ks[1], (1, kv, seq, hd)) * 0.5
    v = jax.random.normal(ks[2], (1, kv, seq, hd))

    def flash(q, k, v):
        kk, vv = (jnp.repeat(a, h // kv, axis=1) for a in (k, v))
        return flash_attention(q, kk, vv, causal=True, scale=hd ** -0.5)

    def plain(q, k, v):
        qg = q.reshape(1, kv, h // kv, seq, hd)
        s = jnp.einsum("bkgtd,bksd->bkgts", qg, k) * hd ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), s, -jnp.inf)
        return jnp.einsum("bkgts,bksd->bkgtd", jax.nn.softmax(s, -1),
                          v).reshape(1, h, seq, hd)
    np.testing.assert_allclose(flash(q, k, v), plain(q, k, v), atol=2e-5)
    w = jax.random.normal(jax.random.key(9), (1, h, seq, hd))
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-4, err_msg=name)


def test_partial_rotary_turns_the_first_dims_only_and_keeps_norms():
    x = jax.random.normal(jax.random.key(6), (1, 12, 2, 16))
    y = _rotary(x, 1e7, 4)
    np.testing.assert_array_equal(y[..., 4:], x[..., 4:])
    np.testing.assert_allclose(y[:, 0], x[:, 0], atol=1e-6)   # position 0
    np.testing.assert_allclose(jnp.linalg.norm(y[..., :4], axis=-1),
                               jnp.linalg.norm(x[..., :4], axis=-1),
                               rtol=1e-5)
    assert float(jnp.abs(y[:, 5, :, :4] - x[:, 5, :, :4]).max()) > 1e-2


def test_every_scope_of_the_step_is_in_the_vocabulary():
    """The model's scopes are siblings in ``prof.SCOPES``; each shows in
    the compiled step's op names, forward and backward."""
    lm = _tiny(head_chunk=32, remat=True)
    params = lm.init(jax.random.key(7))
    text = jax.jit(jax.grad(lm.loss)).lower(params, _tokens()).compile() \
        .as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))

    def under(scope, path):     # a whole component: bare, or in jvp( )
        return re.search(rf"(^|[/(]){scope}([/)]|$)", path) is not None
    for scope in ("embed", "linear_attention", "delta_rule", "attention",
                  "moe_route", "moe_experts", "head_loss"):
        assert scope in prof.SCOPES
        mine = [p for p in paths if under(scope, p)]
        assert any("transpose(" not in p for p in mine), scope
        assert any("transpose(" in p for p in mine), scope
    assert "delta_rule/linear_attention" not in text    # siblings
    assert "linear_attention/delta_rule" not in text


def test_the_step_builder_hands_the_counters_out_beside_the_loss():
    import lm_bench
    from apex_tpu.parallel import compile_step_with_plan, make_mesh
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    toks = _tokens(key=8)

    def one_step(lm, params):
        opt, state, step, plan = lm_bench.build_train_step(
            lm, params, mesh, half=jnp.bfloat16, lr=1e-3)
        return compile_step_with_plan(step, plan)(state, toks)
    lm = _tiny(head_chunk=32, remat=True)
    state, (loss, counters) = one_step(lm, lm.init(jax.random.key(9)))
    assert np.isfinite(float(loss))
    assert int(counters["moe_overflow_pairs"]) == 0
    assert float(counters["expert_load_max_over_mean"]) >= 1.0
    assert int(state[0].step) == 1
    # the dense LM's step returns what it always did: (state, loss)
    dense = TransformerLM(vocab_size=96, max_seq_len=64, embed_dim=32,
                          num_heads=2, num_layers=1)
    state, loss = one_step(dense, dense.init(jax.random.key(0)))
    assert loss.shape == () and np.isfinite(float(loss))


@pytest.mark.parametrize("arm", [dict(zero=True), dict(chips=2)])
def test_the_step_builder_refuses_counters_across_chips(arm):
    """Only the one-chip FusedAdam step carries a model's counters: the
    ZeRO and DDP arms say so before they build anything."""
    import lm_bench
    from apex_tpu.parallel import make_mesh
    chips = arm.pop("chips", 1)
    mesh = make_mesh({"data": chips}, devices=jax.devices()[:chips])
    lm = _tiny()
    with pytest.raises(NotImplementedError, match="counters"):
        lm_bench.build_train_step(lm, lm.init(jax.random.key(0)), mesh,
                                  half=jnp.bfloat16, **arm)
