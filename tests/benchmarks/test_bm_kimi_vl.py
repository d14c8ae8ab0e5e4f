"""What only the Kimi-VL configuration has: the program against its plain
reference on seeded weights at a small size (logits, loss, per-leaf
gradients, the pairs an expert, the moved biases), the work function by
hand, the parameters re-counted from the specs, the cut as the
configuration file states it, and a reference that imports nothing of
the program."""

import ast
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import spec as S, weights as W, weights_kimi_vl as WK
from benchmarks.reference import kimi_vl as R
from benchmarks.work import flash_attn_mla_train

NAME = "kimi-vl-a3b-train"


def _cfg():
    with open(os.path.join(S.HERE, "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def small():
    """The rehearsal sizes, the program's model, seeded weights with the
    norms moved off 1 (at 1 a wrong use of them would not show) and
    biases that move the choice."""
    from benchmarks.drivers import train_kimi_vl
    cfg = _cfg()
    cfg = {**cfg, **cfg["rehearsal"], "expert_chip": 1}
    ctx = types.SimpleNamespace(
        config=cfg, traffic={"steps_checked": 1, "kind": "train_fixed_batch",
                             "per_chip": 2, "distinct": 1},
        seed=3, devices=jax.devices()[:1], plugin=S.Spec().plugin)
    lm, _ = train_kimi_vl.Driver(ctx).model()
    params = W.build(WK.specs(cfg), W.seed_key(3), jnp.float32)
    params = jax.tree.map(
        lambda x: x + 0.1 * jax.random.normal(jax.random.key(1), x.shape)
        if x.ndim == 1 else 3.0 * x, params)
    toks = jax.random.randint(jax.random.key(5), (2, 49), 0,
                              cfg["vocab_size"])
    biases = 0.2 * jax.random.normal(jax.random.key(7),
                                     R.zero_biases(cfg).shape)
    return cfg, lm, params, toks, biases


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
    assert float(counters["router_bias_abs_max"]) == pytest.approx(
        float(jnp.abs(want_moved).max()))
    norms = jax.tree.map(lambda a, b: (float(jnp.linalg.norm(a)),
                                       float(jnp.linalg.norm(b)),
                                       float(jnp.linalg.norm(a - b))),
                         grad, want_grad)
    for path, (mine, theirs, apart) in jax.tree_util.tree_leaves_with_path(
            norms, is_leaf=lambda x: isinstance(x, tuple)):
        assert theirs > 0, path
        assert mine == pytest.approx(theirs, rel=1e-4), path
        assert apart <= 1e-4 * theirs, path


def test_the_reference_follows_three_steps_and_moves_the_biases(small):
    cfg, _, params, toks, _ = small
    got = R.train_steps(params, [toks, toks[::-1], toks], cfg, lr=1e-3)
    assert len(got["losses"]) == 3 and np.isfinite(got["losses"]).all()
    layers = R.ffn_kinds(cfg).count("experts")
    assert len(got["vectors"]) == layers
    assert all(v.shape == (R.width(cfg),) for v in got["vectors"])
    # three moves of u: every bias is an odd multiple of u up to 3 u, or
    # an even one where an expert sat at the mean load
    steps = np.abs(got["router_biases"]) / cfg["bias_update_speed"]
    assert steps.max() == pytest.approx(3.0)
    np.testing.assert_allclose(steps, np.round(steps), atol=1e-3)
    assert set(got["grad_norms"]) == set(got["delta_norms"]) == set(params)


def test_the_reference_holds_the_share_the_configuration_states(small):
    cfg, _, _, _, _ = small
    held = cfg["n_routed_experts"]
    assert R.held(cfg) == (held, 2 * held)
    assert R.held(_cfg()) == (0, 8) and R.width(_cfg()) == 64
    assert R.ffn_kinds(_cfg()) == ["dense"] + ["experts"] * 4
    assert R.zero_biases(_cfg()).shape == (4, 64)


def test_the_reference_imports_nothing_of_the_program():
    for name in ("reference/kimi_vl.py", "weights_kimi_vl.py"):
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
    specs = WK.specs(cfg)
    dense, expert = specs["layer_0"], specs["layer_1"]
    assert {k: W.count(v) for k, v in dense["latent"].items()} == {
        "w_q": 6_291_456, "w_kva": 1_179_648, "kv_norm": 512,
        "w_kvb": 2_097_152, "w_o": 4_194_304}
    assert W.count(dense["latent"]) == W.count(expert["latent"]) \
        == 13_763_072
    assert W.count(dense["norm1"]) + W.count(dense["norm2"]) == 4_096
    assert W.count(dense["mlp"]) == 3 * 2048 * 11264 == 69_206_016
    moe = expert["moe"]
    assert W.count(moe["router"]) == 131_072
    assert W.count(moe["shared"]) == 17_301_504
    assert W.count(moe["w_gate"]) * 3 == 8 * 8_650_752
    assert W.count(moe) == 86_638_592
    assert W.count(dense) == 82_973_184
    assert all(W.count(specs[f"layer_{i}"]) == 100_405_760
               for i in range(1, 5))
    assert W.count(specs["embed"]) + W.count(specs["head"]) \
        + W.count(specs["norm_f"]) == 83_888_128
    assert W.count(specs) == 568_484_352 == cfg["parameters"]


def test_the_file_states_the_cut_and_the_programs_bounds():
    cfg = _cfg()
    pub = cfg["published"]
    assert cfg["n_routed_experts"] * cfg["expert_chips"] \
        == pub["n_routed_experts"] == 64
    assert cfg["vocab_size"] * cfg["vocab_chips"] == pub["vocab_size"]
    assert cfg["vocab_size"] * 8 >= pub["vocab_size"]           # the floors
    assert cfg["n_routed_experts"] >= 8
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["reduced"] == ["n_routed_experts", "num_hidden_layers",
                              "vocab_size"]
    # no width differs from the source's
    for key in ("hidden_size", "num_attention_heads", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
                "intermediate_size", "moe_intermediate_size",
                "n_shared_experts", "num_experts_per_tok",
                "routed_scaling_factor", "rms_norm_eps", "rope_theta"):
        assert cfg[key] == pub[key], key
    # tokens an expert sees a step, 1/8 of the deployment's
    assert cfg["input"]["seq"] * 2 * cfg["num_experts_per_tok"] \
        // pub["n_routed_experts"] == 1536
    prog = cfg["program"]
    assert prog["remat"].startswith("block")
    assert prog["dispatch_bound"] == 192 * 128 == 2 * 12288
    assert cfg["vocab_size"] % prog["head_chunk"] == 0
    for key in ("deployment", "assumed", "published", "reduced"):
        assert cfg[key]
    for key in ("aux_loss_alpha", "bias_update_speed", "bias_counts",
                "seq_aux", "rope", "router_gradient", "initializer_range",
                "norms", "optimizer", "vision", "mtp"):
        assert cfg["assumed"][key], key
    assert "stop_gradient" in cfg["assumed"]["router_gradient"]
    spec = S.Spec()
    cell = spec.cell("kvl_train_s8192")
    assert cell["traffic"] == "train-fixed-16k-s8192" and cell["chips"] == 1
    assert spec.traffic(cell)["lr"] == 1e-4


def test_the_bias_gap_reads_one_for_biases_left_where_they_were():
    from benchmarks.drivers import train_kimi_vl
    ref = np.array([[1e-3, -3e-3, 1e-3], [-1e-3, 3e-3, -1e-3]])
    assert train_kimi_vl.bias_gap(np.zeros_like(ref), ref) \
        == pytest.approx(1.0)
    assert train_kimi_vl.bias_gap(ref, ref) == 0.0
    flipped = ref.copy()
    flipped[0, 0] = -1e-3               # one expert at the mean, one step
    assert train_kimi_vl.bias_gap(flipped, ref) == pytest.approx(
        2e-3 / np.linalg.norm(ref))


def _run(cfg, per_chip, steps):
    return types.SimpleNamespace(
        ctx=types.SimpleNamespace(config=cfg, traffic={"per_chip": per_chip}),
        rec={"steps": steps})


def test_latent_flash_work_by_hand():
    cfg = _cfg()
    # 2 rows, 16 heads, five layers: over the lower half of 8192^2 scores
    # a product against the 192-wide keys and one against the 128-wide
    # values, once forward and twice backward
    half = 8192 * 8192 // 2
    pair = 2 * 16 * half * 2 * (192 + 128)
    assert flash_attn_mla_train.step_flops(cfg, 2) == 3 * pair * 5 \
        == 10_307_921_510_400
    assert flash_attn_mla_train.total(_run(cfg, 2, 3)) \
        == {"flops": 3.0 * 10_307_921_510_400}
    # what the kernels compute at 256 | 256 is not what is counted
    assert (192 + 128) / (256 + 256) == 0.625
