"""What only the LFM2 configuration has: the program against its plain
reference on seeded weights at a small size (logits, loss, per-leaf
gradients, the pairs an expert, the moved biases), the routers' matrices
with no gradient on either side and how the driver leaves them out, both
work functions by hand, the parameters re-counted from the specs, the cut
as the configuration file states it, and a reference that imports nothing
of the program."""

import ast
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import spec as S, weights as W, weights_lfm2 as WL
from benchmarks.drivers import train_lfm2
from benchmarks.reference import lfm2 as R
from benchmarks.work import flash_attn_layer_types_train, short_conv_train

NAME = "lfm2-24b-a2b-train"


def _cfg():
    with open(os.path.join(S.HERE, "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def small():
    """The rehearsal sizes, the program's model, seeded weights with the
    norms moved off 1 (at 1 a wrong use of them would not show) and
    biases that move the choice."""
    cfg = _cfg()
    cfg = {**cfg, **cfg["rehearsal"], "expert_chip": 1}
    ctx = types.SimpleNamespace(
        config=cfg, traffic={"steps_checked": 1, "kind": "train_fixed_batch",
                             "per_chip": 2, "distinct": 1},
        seed=3, devices=jax.devices()[:1], plugin=S.Spec().plugin)
    lm, _ = train_lfm2.Driver(ctx).model()
    params = W.build(WL.specs(cfg), W.seed_key(3), jnp.float32)
    params = jax.tree.map(
        lambda x: x + 0.1 * jax.random.normal(jax.random.key(1), x.shape)
        if x.ndim == 1 else 3.0 * x, params)
    toks = jax.random.randint(jax.random.key(5), (2, 49), 0,
                              cfg["vocab_size"])
    biases = 0.2 * jax.random.normal(jax.random.key(7),
                                     R.zero_biases(cfg).shape)
    return cfg, lm, params, toks, biases


def test_the_driver_builds_the_model_the_configuration_states():
    cfg = _cfg()
    ctx = types.SimpleNamespace(
        config=cfg, traffic={"steps_checked": 3, "kind": "train_fixed_batch",
                             "per_chip": 1, "distinct": 1},
        seed=0, devices=jax.devices()[:1], plugin=S.Spec().plugin)
    lm, shapes = train_lfm2.Driver(ctx).model()
    assert lm.layer_types == ("conv", "full", "conv", "conv", "conv")
    assert lm.ffns == ("dense",) + ("experts",) * 4
    assert (lm.num_heads, lm.num_kv_heads, lm.head_dim, lm.rotary_dim) \
        == (32, 8, 64, 64)
    assert not lm.attn_gate and lm.tied_head and lm.conv_kernel == 3
    assert (lm.num_experts, lm.top_k, lm.experts_held, lm.shared_ffn) \
        == (64, 4, (0, 8), 0)
    assert lm.router == "sigmoid" and lm.aux_coef == 0.0 \
        and lm.routed_scale == 1
    assert "head" not in shapes
    assert lm.router_state().shape == (4, 64)


def test_the_programs_logits_are_the_references(small):
    cfg, lm, params, toks, biases = small
    got = lm.apply(params, toks[:, :-1], biases)
    want = jnp.stack([R.logits(params, t[:-1], cfg, biases=biases)
                      for t in toks])
    assert float(jnp.abs(want).max()) > 0.5
    np.testing.assert_allclose(got, want, atol=5e-5)
    # and the biases moved some token's choice: without them it differs
    assert float(jnp.abs(lm.apply(params, toks[:, :-1],
                                  jnp.zeros_like(biases)) - want).max()) > 1e-3


def test_the_programs_loss_gradients_pairs_and_biases_are_the_references(
        small):
    """Leaf by leaf, the tied embedding's among them; the routers'
    matrices have no gradient at all, on both sides."""
    cfg, lm, params, toks, biases = small
    (loss, (moved, counters)), grad = jax.value_and_grad(
        lm.loss_with_router_state, has_aux=True)(params, biases, toks)
    want, want_grad, pairs = R.batch_loss_and_grad(params, toks, cfg,
                                                   biases=biases)
    assert float(loss) == pytest.approx(float(want), abs=2e-5)
    assert int(counters["moe_overflow_pairs"]) == 0
    np.testing.assert_array_equal(counters["expert_pairs"], pairs)
    assert int(pairs.sum()) == pairs.shape[0] * 2 * 48 \
        * cfg["num_experts_per_tok"]
    want_moved = R.moved_biases(biases, pairs, cfg["bias_update_speed"])
    np.testing.assert_allclose(moved, want_moved, atol=1e-7)
    norms = jax.tree.map(lambda a, b: (float(jnp.linalg.norm(a)),
                                       float(jnp.linalg.norm(b)),
                                       float(jnp.linalg.norm(a - b))),
                         grad, want_grad)
    routers = 0
    for path, (mine, theirs, apart) in jax.tree_util.tree_leaves_with_path(
            norms, is_leaf=lambda x: isinstance(x, tuple)):
        if path[-1].key == "router":
            assert mine == theirs == 0.0, path
            routers += 1
            continue
        assert theirs > 0, path
        assert mine == pytest.approx(theirs, rel=1e-4), path
        assert apart <= 1e-4 * theirs, path
    assert routers == 4
    assert "head" not in grad and float(jnp.linalg.norm(grad["embed"])) > 0


def test_the_reference_follows_three_steps_and_moves_the_biases(small):
    cfg, _, params, toks, _ = small
    got = R.train_steps(params, [toks, toks[::-1], toks], cfg, lr=1e-3)
    assert len(got["losses"]) == 3 and np.isfinite(got["losses"]).all()
    assert len(got["vectors"]) == 4
    assert all(v.shape == (R.width(cfg),) for v in got["vectors"])
    steps = np.abs(got["router_biases"]) / cfg["bias_update_speed"]
    assert steps.max() == pytest.approx(3.0)
    np.testing.assert_allclose(steps, np.round(steps), atol=1e-3)
    assert set(got["grad_norms"]) == set(got["delta_norms"]) == set(params)
    # the routers' matrices: no gradient, so no update either
    for i in range(1, 5):
        assert got["grad_norms"][f"layer_{i}"]["moe"]["router"] == 0.0
        assert got["delta_norms"][f"layer_{i}"]["moe"]["router"] == 0.0
    assert got["delta_norms"]["embed"] > 0


def test_a_leaf_with_no_gradient_is_held_at_zero_and_left_out_of_the_ratios(
        monkeypatch):
    """What ``train_lfm2.Driver`` does with the reference's readings: the
    leaves that read zero there leave both sides' trees, and what the
    program read on them is what ``zero_grad_leaf_norm`` holds at 0."""
    from benchmarks.drivers import train_kimi_vl
    from benchmarks.training import gaps

    def readings(router):
        tree = {"embed": 2.0, "layer_1": {"moe": {"router": router,
                                                  "w_up": 1.0}, "norm1": 0.5}}
        return {"losses": [1.0], "grad_norms": tree, "delta_norms": tree}
    monkeypatch.setattr(train_kimi_vl.Driver, "_reference_readings",
                        lambda self, precision: readings(0.0))
    driver = object.__new__(train_lfm2.Driver)      # no context, no feed
    driver.readings = readings(3e-9)
    ref = driver._reference_readings("float32")
    assert driver.zero == ["['layer_1']['moe']['router']"]
    assert driver.zero_read == 3e-9                         # > the limit 0
    left = {"['embed']": 2.0, "['layer_1']['moe']['w_up']": 1.0,
            "['layer_1']['norm1']": 0.5}
    for side in (ref, driver.readings):
        assert side["grad_norms"] == side["delta_norms"] == left
    assert gaps(driver.readings, ref)["grad_norm_gap"] == 0.0
    # the control's trees lose the same leaves
    low = driver._reference_readings("fp8")
    assert low["grad_norms"] == left and driver.zero_read == 3e-9
    with open(os.path.join(S.HERE, "limits", "lfm2_train_s8192.json")) as f:
        limits = json.load(f)
    assert limits["zero_grad_leaf_norm"] == 0 \
        == limits["rehearsal"]["zero_grad_leaf_norm"]
    assert "left out of the ratios" in train_lfm2.__doc__


def test_the_reference_holds_the_share_the_configuration_states(small):
    cfg, _, _, _, _ = small
    held = cfg["num_experts"]
    assert R.held(cfg) == (held, 2 * held)
    assert R.held(_cfg()) == (0, 8) and R.width(_cfg()) == 64
    assert R.layer_kinds(_cfg()) == [("conv", "dense"), ("full", "experts")] \
        + [("conv", "experts")] * 3
    assert R.zero_biases(_cfg()).shape == (4, 64)
    assert R.head_dim(_cfg()) == 64


def test_the_reference_imports_nothing_of_the_program():
    for name in ("reference/lfm2.py", "weights_lfm2.py"):
        with open(os.path.join(S.HERE, name)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""]
            else:
                continue
            assert not any(n.split(".")[0] == "apex_tpu" for n in names), name


def test_parameters_are_recounted_from_the_specs():
    cfg = _cfg()
    specs = WL.specs(cfg)
    dense, attn, conv = specs["layer_0"], specs["layer_1"], specs["layer_2"]
    assert {k: W.count(v) for k, v in conv["conv"].items()} == {
        "w_in": 12_582_912, "taps": 6_144, "w_out": 4_194_304}
    assert W.count(conv["conv"]) == W.count(dense["conv"]) == 16_783_360
    assert {k: W.count(v) for k, v in attn["attn"].items()} == {
        "w_q": 4_194_304, "w_k": 1_048_576, "w_v": 1_048_576,
        "q_norm": 64, "k_norm": 64, "w_o": 4_194_304}
    assert W.count(attn["attn"]) == 10_485_888
    assert W.count(dense["norm1"]) + W.count(dense["norm2"]) == 4_096
    assert W.count(dense["mlp"]) == 3 * 2048 * 11776 == 72_351_744
    moe = conv["moe"]
    assert "shared" not in moe
    assert W.count(moe["router"]) == 131_072
    assert W.count(moe["w_gate"]) * 3 == 8 * 9_437_184
    assert W.count(moe) == 75_628_544
    assert W.count(dense) == 89_139_200
    assert W.count(attn) == 86_118_528
    assert all(W.count(specs[f"layer_{i}"]) == 92_416_000 for i in (2, 3, 4))
    assert "head" not in specs          # tied
    assert W.count(specs["embed"]) + W.count(specs["norm_f"]) == 16_779_264
    assert W.count(specs) == 469_284_992 == cfg["parameters"]


def test_the_file_states_the_cut_and_the_programs_bounds():
    cfg = _cfg()
    pub = cfg["published"]
    assert cfg["source"].startswith("https://huggingface.co/LiquidAI/LFM2-24B")
    assert cfg["num_experts"] * cfg["expert_chips"] == pub["num_experts"] == 64
    assert cfg["vocab_size"] * cfg["vocab_chips"] == pub["vocab_size"]
    assert cfg["vocab_size"] * 8 >= pub["vocab_size"]           # the floors
    assert cfg["num_experts"] >= 8
    assert cfg["num_hidden_layers"] - cfg["num_dense_layers"] >= 4
    # published layers 1-5: the dense layers counted once, then a period
    first, last = cfg["published_layers"]
    assert cfg["layer_types"] == pub["layer_types"][first:last + 1]
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] == 5
    assert first == pub["num_dense_layers"] - cfg["num_dense_layers"] == 1
    period = cfg["layer_types"][cfg["num_dense_layers"]:]
    assert sorted(period) == ["conv"] * 3 + ["full_attention"]
    assert sorted(cfg["reduced"]) == sorted([
        "num_experts", "num_hidden_layers", "layer_types",
        "num_dense_layers", "vocab_size"])
    # no width differs from the source's
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "intermediate_size", "moe_intermediate_size", "conv_L_cache",
                "num_experts_per_tok", "routed_scaling_factor", "norm_eps",
                "rope_parameters", "conv_bias", "use_expert_bias"):
        assert cfg[key] == pub[key], key
    # tokens an expert sees a step, 1/8 of the deployment's
    assert cfg["input"]["seq"] * 2 * cfg["num_experts_per_tok"] \
        // pub["num_experts"] == 1024
    prog = cfg["program"]
    assert prog["remat"].startswith("block")
    assert prog["dispatch_bound"] % 128 == 0
    assert prog["dispatch_bound"] >= 2 * 16384 * 4 * 8 // 64
    assert cfg["vocab_size"] % prog["head_chunk"] == 0
    for key in ("deployment", "assumed", "published", "reduced"):
        assert cfg[key]
    for key in ("head_dim", "tie_embedding", "bias_update_speed",
                "bias_counts", "renormalisation", "intermediate_size",
                "aux_loss", "router_gradient", "rope", "initializer_range",
                "norms", "optimizer", "conv"):
        assert cfg["assumed"][key], key
    assert "stop_gradient" in cfg["assumed"]["router_gradient"]
    assert "exactly zero" in cfg["assumed"]["router_gradient"]
    for said in ("8 chips share each layer's 64 experts", "experts 0-7",
                 "8 chips the vocabulary", "Published layers 1-5"):
        assert said in cfg["deployment"], said
    spec = S.Spec()
    cell = spec.cell("lfm2_train_s8192")
    assert cell["traffic"] == "train-fixed-16k-s8192" and cell["chips"] == 1
    assert spec.traffic(cell)["lr"] == 1e-4


def _run(cfg, per_chip, steps):
    return types.SimpleNamespace(
        ctx=types.SimpleNamespace(config=cfg, traffic={"per_chip": per_chip}),
        rec={"steps": steps})


def test_short_conv_work_by_hand():
    cfg = _cfg()
    # 16,384 tokens, four conv layers: the two projections (2048 to 6144,
    # 2048 to 2048) once forward and twice backward
    layer = 3 * 2 * 16384 * 2048 * (6144 + 2048)
    assert layer == 1_649_267_441_664
    assert short_conv_train.conv_layers(cfg) == 4
    # the input read and the output written, and their cotangents: bf16
    assert short_conv_train.step_work(cfg, 2) == {
        "flops": 4 * layer, "bytes": 4 * 4 * 16384 * 2048 * 2}
    assert short_conv_train.total(_run(cfg, 2, 3)) == {
        "flops": 12.0 * layer, "bytes": 3.0 * 1_073_741_824}


def test_flash_work_counts_the_attention_layers_from_the_layer_types():
    cfg = _cfg()
    # 2 rows, 32 heads of 64, one layer: over the lower half of 8192^2
    # scores two products forward and four backward
    half = 8192 * 8192 // 2
    assert flash_attn_layer_types_train.full_layers(cfg) == 1
    assert flash_attn_layer_types_train.step_flops(cfg, 2) \
        == 2 * 32 * half * 2 * 64 * 6 == 1_649_267_441_664
    assert flash_attn_layer_types_train.total(_run(cfg, 2, 3)) \
        == {"flops": 3.0 * 1_649_267_441_664}
    whole = {**cfg, "layer_types": cfg["published"]["layer_types"]}
    assert flash_attn_layer_types_train.full_layers(whole) == 10
