"""The hybrid linear-attention mixture-of-experts LM: the layer pattern as
data, grouped-query flash attention against plain attention at head size
256, the scopes its step opens, and its counters out of the step beside
the loss while the dense LM's step returns what it did."""

import contextlib
import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import prof
from apex_tpu.contrib.moe import ExpertLayer
from apex_tpu.models import HybridLM, TransformerLM
from apex_tpu.models.hybrid_lm import MIXERS, Yarn, _norm0, _rotary

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
                      "moe_live_tiles_max", "expert_load_max_over_mean"}
    # every group's last tile may be part empty: pairs <= rows of live tiles
    assert int(c["moe_held_pairs_max"]) <= int(c["moe_live_tiles_max"]) \
        * ExpertLayer.tile
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


@pytest.mark.parametrize("model, scopes, absent", [
    ("hybrid", ("embed", "linear_attention", "delta_rule", "attention",
                "moe_route", "moe_experts", "head_loss"),
     ("latent_attention", "mlp")),
    ("latent", ("embed", "latent_attention", "mlp", "moe_route",
                "moe_experts", "head_loss"),
     ("attention", "linear_attention", "delta_rule", "short_conv")),
    ("conv", ("embed", "short_conv", "attention", "mlp", "moe_route",
              "moe_experts", "head_loss"),
     ("latent_attention", "linear_attention", "delta_rule")),
    ("window", ("embed", "window_attention", "attention", "moe_route",
                "moe_experts", "head_loss"),
     ("latent_attention", "linear_attention", "short_conv", "mlp"))])
def test_every_scope_of_the_step_is_in_the_vocabulary(model, scopes, absent):
    """The model's scopes are siblings in ``prof.SCOPES``; each shows in
    the compiled step's op names, forward and backward. Latent attention,
    the short convolution and the window mixer open one scope around all
    of theirs (the window model's full layers keep ``attention``), the
    dense FFN the dense LM's ``mlp``, the tied head ``head_loss`` and its
    gather's scatter-add ``embed``."""
    text = "\n".join(_grad_lines(model))
    paths = set(re.findall(r'op_name="([^"]*)"', text))

    def under(scope, path):     # a whole component: bare, or in jvp( )
        return re.search(rf"(^|[/(]){scope}([/)]|$)", path) is not None
    for scope in scopes:
        assert scope in prof.SCOPES
        mine = [p for p in paths if under(scope, p)]
        assert any("transpose(" not in p for p in mine), scope
        assert any("transpose(" in p for p in mine), scope
    assert not any(under(scope, p) for scope in absent for p in paths)
    assert "delta_rule/linear_attention" not in text    # siblings
    assert "linear_attention/delta_rule" not in text


def _model_and_loss(model: str, **kw):
    """The tiny model of a pattern (``hybrid`` with a run of two,
    ``latent``, ``conv``, ``window``) and its loss of ``(params,
    tokens)``."""
    lm = {"hybrid": functools.partial(
              _tiny, layer_types=("linear", "linear", "full")),
          "latent": _latent, "conv": _conv,
          "window": _window}[model](head_chunk=32, **kw)
    if lm.router == "softmax":
        return lm, lm.loss
    return lm, lambda p, t: lm.loss_with_router_state(
        p, lm.router_state(), t)[0]


@functools.lru_cache(maxsize=None)
def _grad_lines(model: str, remat: bool = True, regions: bool = True):
    """The instruction lines of the tiny model's compiled loss gradient
    (the hybrid pattern with a run of two, the latent or the conv
    pattern); ``regions`` false: with ``prof.REGIONS`` opening nothing,
    the program as it was before them."""
    lm, loss = _model_and_loss(model, remat=remat)
    params = lm.init(jax.random.key(7))
    real = jax.named_scope
    with pytest.MonkeyPatch.context() as mp:
        if not regions:
            mp.setattr(jax, "named_scope", lambda name: (
                contextlib.nullcontext() if name in prof.REGIONS
                else real(name)))
        text = jax.jit(jax.grad(loss)).lower(params, _tokens()).compile() \
            .as_text()
    return tuple(line for line in text.splitlines() if " = " in line)


def _paths(lines) -> list:
    return [m.group(1) for m in (re.search(r'op_name="([^"]*)"', line)
                                 for line in lines) if m]


@pytest.mark.parametrize("model", ["hybrid", "latent", "conv"])
def test_the_regions_enclose_the_scopes_and_move_none(model):
    """``prof.REGIONS`` around a run's stacked leaves and its scan:
    metadata only (the instructions are the ones without them), every
    instruction resolves to the scope it resolved to before, and what had
    no scope at the runs' level now has a region: the scans' ``while``s
    and loop-level slices ``layer_scan``, the stacked leaves and the
    counters' ``concatenate`` ``layer_stack``, forward and backward."""
    from benchmarks.readers import trace_region, trace_scope
    assert not set(prof.REGIONS) & set(prof.SCOPES)
    mine, before = _grad_lines(model), _grad_lines(model, regions=False)
    strip = re.compile(r", metadata=\{[^}]*\}")
    assert [strip.sub("", x) for x in mine] \
        == [strip.sub("", x) for x in before]
    a, b = _paths(mine), _paths(before)
    assert len(a) == len(b) > 1000
    assert not any(trace_region.region_of(p) for p in b)
    assert [trace_scope.scope_of(p) for p in a] \
        == [trace_scope.scope_of(p) for p in b]
    assert [re.sub(r"layer_(scan|stack)", "", p) for p in a] == b
    free = [p for p in a if trace_scope.scope_of(p) is None]
    whiles = [p for p in free if p.endswith("/while")]
    assert whiles and {trace_region.region_of(p) for p in whiles} \
        == {"layer_scan"}
    assert {"transpose(" in p for p in whiles} == {True, False}
    for op in ("dynamic_slice", "dynamic_update_slice"):
        level = [p for p in free if p.endswith("/while/body/" + op)]
        assert level and {trace_region.region_of(p) for p in level} \
            == {"layer_scan"}
    stack = [p for p in free if trace_region.region_of(p) == "layer_stack"]
    assert any(p.endswith("jvp(layer_stack)/concatenate") for p in stack)
    assert any(p.endswith("transpose(jvp(layer_stack))/split")
               for p in stack)
    # siblings: neither region inside the other
    assert not any("layer_scan" in p and "layer_stack" in p for p in a)


@pytest.mark.parametrize("model, mixers", [
    ("hybrid", ("linear_attention", "attention")),
    ("latent", ("latent_attention",)),
    ("conv", ("short_conv", "attention"))])
def test_what_the_backward_runs_again_carries_jax_checkpoints_own_name(
        model, mixers):
    """The recomputed forward has no scope of the program's: the span is
    the path component ``jax.checkpoint`` writes on what it runs again
    (``benchmarks/regions/hybrid_lm.json`` ``recomputed``, which
    ``trace_region`` reads). Pinned here, so that a JAX that renames it
    fails a test and not a metric: in the backward ``while`` every scoped
    op sits under ``checkpoint`` with the component right after it or
    not at all, each mixer has a recomputed matmul and a true backward
    one, and without ``remat`` only the delta rule's own checkpoint
    writes it."""
    from benchmarks.readers import trace_region, trace_scope
    name, = trace_region.patterns(key="recomputed")
    wrappers = {"closed_call", "checkpoint", name}
    again, back = {}, {}
    for p in _paths(_grad_lines(model)):
        scope = trace_scope.scope_of(p)
        _, _, rest = p.partition("transpose(jvp(layer_scan))/while/body/")
        if not rest or scope is None:
            continue
        parts = rest.split("/")
        lead = parts[:parts.index(scope)]
        assert set(lead) <= wrappers and "checkpoint" in lead, p
        if name in lead:
            assert lead[lead.index(name) - 1] == "checkpoint", p
        (again if name in lead else back).setdefault(scope, []).append(p)
    for mixer in mixers:
        assert any(p.endswith("dot_general") for p in again[mixer]), mixer
        assert any(p.endswith("dot_general") for p in back[mixer]), mixer
    assert set(again) == set(back)
    # forward, nothing is recomputed (a reduction's inner computation is
    # named by the tail of its path alone)
    assert not any(name in p for p in _paths(_grad_lines(model))
                   if p.startswith("jit(") and "transpose(" not in p)
    held = [p for p in _paths(_grad_lines(model, remat=False)) if name in p]
    assert all(trace_scope.scope_of(p) == "delta_rule" for p in held)
    assert bool(held) == (model == "hybrid")


@pytest.mark.parametrize("model, bodies", [
    ("hybrid", 1), ("latent", 2), ("conv", 1)])
def test_remat_keeps_what_the_flash_forward_made_and_nothing_else(
        model, bodies, monkeypatch):
    """Under ``remat`` a block is recomputed but for the two arrays the
    flash forward kernel made (``flash_attention.SAVED_NAMES``): the loss
    gradient's jaxpr holds ``apex_flash_fwd`` once a flash layer's scan
    body, where a plain ``jax.checkpoint(block)`` holds it twice, and the
    backward kernels as before; the gradient is bitwise the plain
    checkpoint's and within tolerance of no ``remat``; a model with no
    flash kernel has the jaxpr it has without the policy."""
    def eqns(jaxpr):    # every equation, those of inner jaxprs included
        for e in jaxpr.eqns:
            yield e
            for v in e.params.values():
                for x in v if isinstance(v, (list, tuple)) else (v,):
                    x = getattr(x, "jaxpr", x)
                    if hasattr(x, "eqns"):
                        yield from eqns(x)

    def traced(**kw):   # the gradient function of a trace of its own
        lm, loss = _model_and_loss(model, **kw)
        return (jax.grad(lambda *a: loss(*a)), lm.init(jax.random.key(7)),
                _tokens())

    def read(**kw):     # (the flash kernels in the jaxpr, its equations)
        grad, *args = traced(**kw)
        jaxpr = jax.make_jaxpr(grad)(*args)
        names = re.findall(r"name=(apex_flash_(?:fwd|bwd_\w+))", str(jaxpr))
        return ({k: names.count(k) for k in set(names)},
                [(e.primitive.name, [str(v.aval) for v in e.outvars])
                 for e in eqns(jaxpr.jaxpr)])

    def gradient(**kw):
        grad, *args = traced(**kw)
        return jax.tree.leaves(jax.jit(grad)(*args))
    kept, _ = read(remat=True)
    _, no_kernel = read(remat=True, attn_impl="default")
    g_kept, g_none = gradient(remat=True), gradient(remat=False)
    # the parent's wrapper: jax.checkpoint(block, policy=None)
    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                        lambda *names: None)
    plain, _ = read(remat=True)
    _, no_kernel_plain = read(remat=True, attn_impl="default")
    g_plain = gradient(remat=True)
    backward = {"apex_flash_bwd_dq": bodies, "apex_flash_bwd_dkv": bodies}
    assert kept == {"apex_flash_fwd": bodies, **backward}
    assert plain == {"apex_flash_fwd": 2 * bodies, **backward}
    for a, b, c in zip(g_kept, g_plain, g_none):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_allclose(a, c, atol=2e-5)
    # (printed, the two differ in how inner jaxprs are numbered and shared)
    assert len(no_kernel) > 1000 and no_kernel == no_kernel_plain
    assert not any(e[0] in ("pallas_call", "name") for e in no_kernel)


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


# -- latent attention, a leading dense layer, the sigmoid router's bias -------

def _latent(**kw):
    base = dict(
        vocab_size=96, hidden=32, layer_types=("latent",) * 3,
        ffn_types=("dense", "experts", "experts"), num_heads=4,
        kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8,
        rope_theta=8e5, num_experts=8, top_k=2, expert_ffn=16, shared_ffn=32,
        experts_held=(2, 6), router="sigmoid", routed_scale=2.446,
        dense_ffn=48, rms_eps=1e-5, zero_centred_norm=False)
    return HybridLM(**{**base, **kw})


def test_a_model_that_leads_with_a_dense_layer():
    """The FFN kind is data a layer as the mixer kind is: the dense layer
    has one SwiGLU and no router, a run of like layers is still one
    scanned body (1 + a scan of 2), plain norms start at one, and the
    result is the layers' one after the other."""
    lm = _latent()
    p = lm.init(jax.random.key(0))
    assert set(p["layer_0"]) == {"norm1", "norm2", "latent", "mlp"}
    assert set(p["layer_1"]) == {"norm1", "norm2", "latent", "moe"}
    assert p["layer_0"]["mlp"]["w_gate"].shape == (32, 48)
    assert "gate" not in p["layer_1"]["moe"]["shared"]      # ungated
    assert float(p["norm_f"].min()) == 1.0 == float(
        p["layer_2"]["latent"]["kv_norm"].max())
    assert p["layer_1"]["latent"]["w_kva"].shape == (32, 16 + 4)
    assert p["layer_1"]["latent"]["w_kvb"].shape == (16, 4 * (8 + 8))
    toks = _tokens(key=3)[:, :-1]
    bias = 0.3 * jax.random.normal(jax.random.key(4), (2, 8))
    assert lm.router_state().shape == (2, 8)
    assert _tiny().router_state() is None
    jaxpr = jax.make_jaxpr(lm.apply)(p, toks, bias)
    assert [e.params["length"] for e in jaxpr.eqns
            if e.primitive.name == "scan"] == [1, 2]
    x = p["embed"][toks]
    x, aux = lm._block("latent", p["layer_0"], x, "dense")
    assert aux is None
    for i in (1, 2):
        x, _ = lm._block("latent", p[f"layer_{i}"], x, "experts", bias[i - 1])
    want = jnp.einsum("btd,vd->btv", lm._norm(x, p["norm_f"]), p["head"])
    np.testing.assert_allclose(lm.apply(p, toks, bias), want, atol=2e-5)
    with pytest.raises(ValueError):
        _latent(ffn_types=("dense", "experts"))
    with pytest.raises(ValueError):
        _latent(ffn_types=("dense", "experts", "sparse"))


@pytest.mark.parametrize("seq", [128, 80])
def test_the_latent_mixer_against_a_naive_softmax_at_unpadded_widths(seq):
    """Queries and keys 192 wide (128 + 64 rotary, the rotary key one
    head shared by all) over values 128 wide: through the flash kernels
    (``v`` padded to 192, the result sliced), through plain attention, and
    head by head with nothing padded; forward and the gradients, the
    shared key's summed over the heads."""
    kw = dict(hidden=64, num_heads=2, kv_lora_rank=32, qk_nope_dim=128,
              qk_rope_dim=64, v_head_dim=128, layer_types=("latent",),
              ffn_types=("dense",))
    fast, plain = _latent(attn_impl="fast", **kw), _latent(
        attn_impl="default", **kw)
    lp = fast.init(jax.random.key(seq), scale=0.2)["layer_0"]
    lp["norm1"] = lp["norm1"] + 0.1
    lp["latent"]["kv_norm"] = lp["latent"]["kv_norm"] - 0.2
    x = jax.random.normal(jax.random.key(1), (2, seq, 64))

    def naive(lp, x):
        p = lp["latent"]
        h = _norm0(x, lp["norm1"], 1e-5, False)
        q = (h @ p["w_q"]).reshape(2, seq, 2, 192)
        kva = h @ p["w_kva"]
        kv = (_norm0(kva[..., :32], p["kv_norm"], 1e-5, False)
              @ p["w_kvb"]).reshape(2, seq, 2, 256)
        k_r = _rotary(kva[..., None, 32:], 8e5, 64)[:, :, 0]
        out = []
        for head in range(2):
            q_h = jnp.concatenate([q[:, :, head, :128], _rotary(
                q[:, :, head:head + 1, 128:], 8e5, 64)[:, :, 0]], -1)
            k_h = jnp.concatenate([kv[:, :, head, :128], k_r], -1)
            s = jnp.einsum("btd,bsd->bts", q_h, k_h) * 192 ** -0.5
            s = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), s, -jnp.inf)
            out.append(jax.nn.softmax(s, -1) @ kv[:, :, head, 128:])
        return x + jnp.concatenate(out, -1) @ p["w_o"]
    want = naive(lp, x)
    assert float(jnp.abs(want - x).max()) > 1e-2
    np.testing.assert_allclose(fast._latent_mixer(lp, x), want, atol=2e-5)
    np.testing.assert_allclose(plain._latent_mixer(lp, x), want, atol=2e-5)
    w = jax.random.normal(jax.random.key(9), x.shape)
    g_want = jax.grad(lambda lp, x: jnp.sum(naive(lp, x) * w),
                      argnums=(0, 1))(lp, x)
    for lm in (fast, plain):
        got = jax.grad(lambda lp, x: jnp.sum(lm._latent_mixer(lp, x) * w),
                       argnums=(0, 1))(lp, x)
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                                jax.tree.leaves(g_want)):
            np.testing.assert_allclose(a, b, atol=1e-4, err_msg=str(path))


def test_the_step_builder_carries_the_routers_biases_beside_the_state():
    """The biases are state that no gradient reaches: beside the
    optimizer's in the step's state, moved by each step's own pairs an
    expert, never a leaf of the flat master."""
    import lm_bench
    from apex_tpu.parallel import compile_step_with_plan, make_mesh
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    lm = _latent(head_chunk=32, remat=True, bias_rate=0.01)
    params = lm.init(jax.random.key(9))
    opt, state, step, plan = lm_bench.build_train_step(
        lm, params, mesh, half=jnp.bfloat16, lr=1e-3)
    assert opt.state == ()              # handed out, not copied
    assert state[0][0].master.size >= sum(
        x.size for x in jax.tree.leaves(params))
    np.testing.assert_array_equal(state[1], jnp.zeros((2, 8)))
    toks = _tokens(key=8)
    run = compile_step_with_plan(step, plan)
    state, (first, counters) = run(state, toks)
    pairs = np.asarray(counters["expert_pairs"])
    assert pairs.shape == (2, 8) and (pairs.sum(-1) == 2 * 32 * 2).all()
    np.testing.assert_allclose(
        state[1], 0.01 * np.sign(pairs.mean(-1, keepdims=True) - pairs),
        atol=1e-7)
    assert float(counters["router_bias_abs_max"]) == pytest.approx(0.01)
    for _ in range(5):
        state, (loss, counters) = run(state, toks)
    assert float(loss) < float(first)
    assert int(state[0][0].step) == 6
    assert 0.01 < float(counters["router_bias_abs_max"]) <= 0.06 + 1e-6
    assert int(counters["moe_overflow_pairs"]) == 0


# -- the short convolution, ungated attention at heads of 64, a tied head -----

def _conv(**kw):
    base = dict(
        vocab_size=96, hidden=32,
        layer_types=("conv", "conv", "conv", "full", "conv"),
        ffn_types=("dense",) + ("experts",) * 4, num_heads=4, num_kv_heads=2,
        head_dim=8, rotary_dim=8, rope_theta=1e6, attn_gate=False,
        conv_kernel=3, num_experts=8, top_k=2, expert_ffn=16, shared_ffn=0,
        experts_held=(2, 6), router="sigmoid", dense_ffn=48, aux_coef=0.0,
        rms_eps=1e-5, zero_centred_norm=False, tied_head=True)
    return HybridLM(**{**base, **kw})


def test_a_conv_conv_full_conv_pattern_led_by_a_dense_layer():
    """The fourth mixer kind as data: a leading dense conv layer, then the
    period conv, conv, full, conv with experts: scans of 1, 2, 1 and 1,
    the result the layers' one after the other, no head among the leaves,
    no shared expert, an ungated ``w_q``."""
    assert MIXERS[:4] == ("linear", "full", "latent", "conv")
    lm = _conv()
    p = lm.init(jax.random.key(0))
    assert "head" not in p
    assert set(p["layer_0"]) == {"norm1", "norm2", "conv", "mlp"}
    assert set(p["layer_1"]) == {"norm1", "norm2", "conv", "moe"}
    assert set(p["layer_3"]) == {"norm1", "norm2", "attn", "moe"}
    assert {k: v.shape for k, v in p["layer_1"]["conv"].items()} == {
        "w_in": (32, 96), "taps": (3, 32), "w_out": (32, 32)}
    assert p["layer_3"]["attn"]["w_q"].shape == (32, 4 * 8)     # no gate
    assert "shared" not in p["layer_1"]["moe"]
    toks = _tokens(key=3)[:, :-1]
    bias = 0.3 * jax.random.normal(jax.random.key(4), (4, 8))
    assert lm.router_state().shape == (4, 8)
    jaxpr = jax.make_jaxpr(lm.apply)(p, toks, bias)
    assert [e.params["length"] for e in jaxpr.eqns
            if e.primitive.name == "scan"] == [1, 2, 1, 1]
    x = p["embed"][toks]
    x, aux = lm._block("conv", p["layer_0"], x, "dense")
    assert aux is None
    for i, kind in enumerate(lm.layer_types[1:], 1):
        x, _ = lm._block(kind, p[f"layer_{i}"], x, "experts", bias[i - 1])
    want = jnp.einsum("btd,vd->btv", lm._norm(x, p["norm_f"]), p["embed"])
    np.testing.assert_allclose(lm.apply(p, toks, bias), want, atol=2e-5)
    # the gated mixer and the untied head are what they were
    gated = _tiny().init(jax.random.key(0))
    assert gated["layer_1"]["attn"]["w_q"].shape == (32, 4 * 2 * 16)
    assert "head" in gated


def test_the_conv_mixer_against_a_naive_loop_and_it_is_causal():
    """``x + (C * conv(B * u)) W_out`` with the convolution written token
    by token and tap by tap; a changed token moves nothing before it, and
    everything from it to two tokens on."""
    lm = _conv()
    lp = lm.init(jax.random.key(1), scale=0.3)["layer_1"]
    lp["norm1"] = lp["norm1"] + 0.1 * jax.random.normal(jax.random.key(2),
                                                        (32,))
    x = jax.random.normal(jax.random.key(3), (2, 12, 32))
    p = lp["conv"]
    bcu = np.asarray(_norm0(x, lp["norm1"], 1e-5, False) @ p["w_in"])
    b, c, u = bcu[..., :32], bcu[..., 32:64], bcu[..., 64:]
    taps = np.asarray(p["taps"])
    z = np.zeros_like(b)
    for t in range(12):
        for j in range(3):              # the last tap on the current token
            if t - 2 + j >= 0:
                z[:, t] += taps[j] * (b * u)[:, t - 2 + j]
    want = np.asarray(x) + (c * z) @ np.asarray(p["w_out"])
    got = lm._conv_mixer(lp, x)
    assert float(np.abs(want - np.asarray(x)).max()) > 1e-2
    np.testing.assert_allclose(got, want, atol=1e-5)
    moved = lm._conv_mixer(lp, x.at[:, 5].add(1.0))
    np.testing.assert_array_equal(moved[:, :5], got[:, :5])
    assert all(float(jnp.abs(moved[:, t] - got[:, t]).max()) > 1e-4
               for t in (5, 6, 7))
    np.testing.assert_allclose(moved[:, 8:], got[:, 8:], atol=1e-6)


@pytest.mark.parametrize("seq", [128, 80])
def test_the_ungated_mixer_at_heads_of_64_against_a_naive_softmax(seq):
    """32 wide, 4 query heads over 2 key/value heads of 64, the whole head
    rotated, no gate: through the flash kernels (which pad the head to 128
    lanes), through plain attention, and head by head with nothing padded;
    forward and the gradients."""
    kw = dict(hidden=64, num_heads=4, num_kv_heads=2, head_dim=64,
              rotary_dim=64, layer_types=("full",), ffn_types=("dense",))
    fast, plain = _conv(attn_impl="fast", **kw), _conv(attn_impl="default",
                                                       **kw)
    lp = fast.init(jax.random.key(seq), scale=0.2)["layer_0"]
    lp["norm1"] = lp["norm1"] + 0.1
    lp["attn"]["q_norm"] = lp["attn"]["q_norm"] - 0.2
    lp["attn"]["k_norm"] = lp["attn"]["k_norm"] + 0.3
    x = jax.random.normal(jax.random.key(1), (2, seq, 64))

    def naive(lp, x):
        p = lp["attn"]
        h = _norm0(x, lp["norm1"], 1e-5, False)
        q = (h @ p["w_q"]).reshape(2, seq, 4, 64)
        k = (h @ p["w_k"]).reshape(2, seq, 2, 64)
        v = (h @ p["w_v"]).reshape(2, seq, 2, 64)
        q = _rotary(_norm0(q, p["q_norm"], 1e-5, False), 1e6, 64)
        k = _rotary(_norm0(k, p["k_norm"], 1e-5, False), 1e6, 64)
        out = []
        for head in range(4):           # query head i reads kv head i // 2
            s = jnp.einsum("btd,bsd->bts", q[:, :, head],
                           k[:, :, head // 2]) * 64 ** -0.5
            s = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), s, -jnp.inf)
            out.append(jax.nn.softmax(s, -1) @ v[:, :, head // 2])
        return x + jnp.concatenate(out, -1) @ p["w_o"]
    want = naive(lp, x)
    assert float(jnp.abs(want - x).max()) > 1e-2
    np.testing.assert_allclose(fast._full_mixer(lp, x), want, atol=2e-5)
    np.testing.assert_allclose(plain._full_mixer(lp, x), want, atol=2e-5)
    w = jax.random.normal(jax.random.key(9), x.shape)
    g_want = jax.grad(lambda lp, x: jnp.sum(naive(lp, x) * w),
                      argnums=(0, 1))(lp, x)
    for lm in (fast, plain):
        got = jax.grad(lambda lp, x: jnp.sum(lm._full_mixer(lp, x) * w),
                       argnums=(0, 1))(lp, x)
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                                jax.tree.leaves(g_want)):
            np.testing.assert_allclose(a, b, atol=1e-4, err_msg=str(path))


def test_the_tied_head_against_an_untied_model_whose_head_is_a_copy():
    """The loss is the untied model's with ``head = embed``, and the
    embedding's gradient is that model's two added: the gather's
    scatter-add plus the chunked head's."""
    tied = _conv(head_chunk=32, remat=True)
    untied = _conv(head_chunk=32, remat=True, tied_head=False)
    params = tied.init(jax.random.key(11), scale=0.1)
    copy = {**params, "head": params["embed"]}
    assert jax.tree.structure(untied.init(jax.random.key(0))) \
        == jax.tree.structure(copy)
    toks, bias = _tokens(key=12), tied.router_state()
    loss, g = jax.value_and_grad(
        lambda p: tied.loss_with_router_state(p, bias, toks)[0])(params)
    want, g_want = jax.value_and_grad(
        lambda p: untied.loss_with_router_state(p, bias, toks)[0])(copy)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    assert float(jnp.linalg.norm(g_want["head"])) > 1e-3
    assert float(jnp.linalg.norm(g_want["embed"])) > 1e-3
    np.testing.assert_allclose(g["embed"], g_want["embed"] + g_want["head"],
                               atol=1e-6)
    np.testing.assert_allclose(
        tied.apply(params, toks[:, :-1], bias),
        untied.apply(copy, toks[:, :-1], bias), atol=1e-6)
    for i in range(1, 5):       # no balance term, a share: no gradient
        assert not np.asarray(g[f"layer_{i}"]["moe"]["router"]).any()


# -- the window model (its tests: test_hybrid_lm_window.py) -------------------

def _window(**kw):
    """Three sliding-window layers to one full layer under YaRN, 8 query
    heads over 1 key/value head, a softmax router with no shared expert."""
    base = dict(
        vocab_size=96, hidden=32,
        layer_types=("window", "window", "window", "full"), num_heads=8,
        num_kv_heads=1, head_dim=8, rotary_dim=8, rope_theta=5e5,
        attn_gate=False, window=12, rope_yarn=Yarn(4.0, 16, 4.0, 1.0, 1.2),
        num_experts=8, top_k=3, expert_ffn=16, shared_ffn=0,
        experts_held=(2, 6), zero_centred_norm=False)
    return HybridLM(**{**base, **kw})
