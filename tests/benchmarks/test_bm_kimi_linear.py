"""What only the Kimi-Linear configuration has: the program against its
plain reference on seeded weights at the rehearsal size (logits, loss,
per-leaf gradients, the pairs an expert, the moved biases, the mixers'
counter), the ranks' shares of one expert layer adding up to the uncut
layer, the parameters re-counted from the specs, the cut as the
configuration file states it (``linear_attn_config`` whole), both work
functions by hand, the new entries under ``bm_tree``'s invariants, and a
reference that imports nothing of the program."""

import ast
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bm_tree
from benchmarks import spec as S, weights as W, weights_kimi_linear as WK
from benchmarks.drivers import train_kimi_linear
from benchmarks.reference import kimi_linear as R
from benchmarks.work import (flash_attn_mla_layers_train,
                             flash_attn_mla_train, kda_delta_rule)

NAME = "kimi-linear-48b-a3b-train"
CELL = "klin_train_s8192"


def _cfg():
    with open(os.path.join(S.HERE, "configs", NAME + ".json")) as f:
        return json.load(f)


def _driver(cfg):
    ctx = types.SimpleNamespace(
        config=cfg, traffic={"steps_checked": 1, "kind": "train_fixed_batch",
                             "per_chip": 2, "distinct": 1},
        seed=3, devices=jax.devices()[:1], plugin=S.Spec().plugin)
    return train_kimi_linear.Driver(ctx)


def _moved(params):
    """The norms and the gates' constants moved off their starts (at 0 and
    1 a wrong use of them would not show), the matrices larger."""
    return jax.tree.map(
        lambda x: x + 0.1 * jax.random.normal(jax.random.key(1), x.shape)
        if x.ndim == 1 else 3.0 * x, params)


@pytest.fixture(scope="module")
def small():
    cfg = _cfg()
    cfg = {**cfg, **cfg["rehearsal"], "expert_chip": 1}
    lm, _ = _driver(cfg).model()
    params = _moved(W.build(WK.specs(cfg), W.seed_key(3), jnp.float32))
    toks = jax.random.randint(jax.random.key(5), (2, 49), 0,
                              cfg["vocab_size"])
    biases = 0.2 * jax.random.normal(jax.random.key(7),
                                     R.zero_biases(cfg).shape)
    return cfg, lm, params, toks, biases


def test_the_driver_builds_the_model_the_configuration_states():
    lm, _ = _driver(_cfg()).model()
    assert lm.layer_types == ("kda", "kda", "kda", "latent", "kda")
    assert lm.ffns == ("dense",) + ("experts",) * 4
    assert (lm.kda_heads, lm.kda_head_dim, lm.conv_kernel,
            lm.delta_chunk) == (32, 128, 4, 64)
    assert (lm.num_heads, lm.qk_nope_dim, lm.qk_rope_dim, lm.v_head_dim,
            lm.kv_lora_rank, lm.latent_rotary) == (32, 128, 64, 128, 512,
                                                   False)
    assert (lm.num_experts, lm.top_k, lm.experts_held, lm.expert_ffn,
            lm.shared_ffn, lm.router, lm.routed_scale) == (
                256, 8, (0, 8), 1024, 1024, "sigmoid", 2.446)
    assert (lm.hidden, lm.dense_ffn, lm.vocab_size, lm.rms_eps) == (
        2304, 9216, 20480, 1e-5)
    assert lm.remat and lm.dispatch_bound == 8192 and not lm.tied_head


def test_the_programs_logits_are_the_references(small):
    cfg, lm, params, toks, biases = small
    got = jax.jit(lm.apply)(params, toks[:, :-1], biases)
    want = jnp.stack([R.logits(params, t[:-1], cfg, biases=biases)
                      for t in toks])
    assert float(jnp.abs(want).max()) > 0.5
    np.testing.assert_allclose(got, want, atol=5e-5)


def test_the_programs_loss_gradients_pairs_biases_and_counter_are_the_references(
        small):
    cfg, lm, params, toks, biases = small
    (loss, (moved, counters)), grad = jax.jit(jax.value_and_grad(
        lm.loss_with_router_state, has_aux=True))(params, biases, toks)
    want, want_grad, pairs = R.batch_loss_and_grad(params, toks, cfg,
                                                   biases=biases)
    assert float(loss) == pytest.approx(float(want), abs=2e-5)
    assert int(counters["moe_overflow_pairs"]) == 0
    np.testing.assert_array_equal(counters["expert_pairs"], pairs)
    assert int(pairs.sum()) == pairs.shape[0] * 2 * 48 \
        * cfg["num_experts_per_token"]
    np.testing.assert_allclose(
        moved, R.moved_biases(biases, pairs, cfg["bias_update_speed"]),
        atol=1e-7)
    # the first layer's reading is the reference's own of that layer; the
    # step's is the largest of the four
    chunk = cfg["program"]["delta_chunk"]
    first = max(R.decay_nats(params, t[:-1], cfg, chunk) for t in toks)
    assert float(counters["kda_chunk_decay_nats_max"]) >= first * (1 - 1e-5)
    x, each = params["embed"][toks[:, :-1]], []
    for i, kind in enumerate(lm.layer_types):
        x, aux = jax.jit(lambda lp, x, b, _k=kind, _f=lm.ffns[i]: lm._block(
            _k, lp, x, _f, b))(params[f"layer_{i}"], x,
                               biases[max(i - 1, 0)])
        if kind == "kda":
            each.append(float(aux[1]["kda_chunk_decay_nats"]))
    assert each[0] == pytest.approx(first, rel=1e-5)
    assert float(counters["kda_chunk_decay_nats_max"]) == pytest.approx(
        max(each), rel=1e-5)
    norms = jax.tree.map(lambda a, b: (float(jnp.linalg.norm(a)),
                                       float(jnp.linalg.norm(b)),
                                       float(jnp.linalg.norm(a - b))),
                         grad, want_grad)
    for path, (mine, theirs, apart) in jax.tree_util.tree_leaves_with_path(
            norms, is_leaf=lambda x: isinstance(x, tuple)):
        assert theirs > 0, path
        assert mine == pytest.approx(theirs, rel=2e-4), path
        assert apart <= 3e-4 * theirs, path


def test_a_scalar_gate_under_this_models_name_is_seen(small):
    """The kept fault the cell's limits are read against: the gate taken
    as its head's mean moves the logits by far more than rounding."""
    cfg, lm, params, toks, biases = small
    import apex_tpu.models.hybrid_lm as H
    want = jax.jit(lm.apply)(params, toks[:, :-1], biases)
    real = H.gated_delta_rule
    try:
        H.gated_delta_rule = lambda q, k, v, g, beta, chunk: real(
            q, k, v, jnp.mean(g, -1), beta, chunk=chunk)
        got = jax.jit(lambda *a: lm.apply(*a))(params, toks[:, :-1], biases)
    finally:
        H.gated_delta_rule = real
    assert float(jnp.abs(got - want).max()) > 100 * 5e-5


def test_the_ranks_shares_add_up_to_the_uncut_layer():
    """One Kimi Delta Attention expert layer at the rehearsal's widths
    with all 16 experts held is the uncut layer; the four ranks each hold
    four of them, see the same mixer, the same router and the same shared
    expert, and add their experts' part: mixer and shared expert counted
    once, the four parts sum to the uncut layer's."""
    cfg = _cfg()
    cfg = {**cfg, **cfg["rehearsal"]}
    chips, held = cfg["expert_chips"], cfg["num_experts"]
    whole_cfg = {**cfg, "num_experts": held * chips, "expert_chips": 1,
                 "expert_chip": 0}
    whole = _moved(W.build(WK.specs(whole_cfg), W.seed_key(7),
                           jnp.float32))["layer_1"]
    assert set(whole) == {"norm1", "norm2", "kda", "moe"}
    x = jax.random.normal(jax.random.key(2), (48, cfg["hidden_size"]))
    bias = 0.2 * jax.random.normal(jax.random.key(3), (held * chips,))
    kind = ("kda", "experts")
    want, pairs, _ = R.block(x, whole, bias, kind, whole_cfg, "float32")
    eps = cfg["rms_norm_eps"]
    mixed = x + R.kda_mixer(R.rms(x, whole["norm1"], eps), whole["kda"],
                            whole_cfg, "float32")
    shared = R.swiglu(R.rms(mixed, whole["norm2"], eps),
                      whole["moe"]["shared"], "float32")
    total = 0.0
    for chip in range(chips):
        share_cfg = {**cfg, "expert_chip": chip}
        lo, hi = R.held(share_cfg)
        assert (lo, hi) == (chip * held, (chip + 1) * held)
        share = {**whole, "moe": {
            **{k: whole["moe"][k] for k in ("router", "shared")},
            **{k: whole["moe"][k][lo:hi]
               for k in ("w_gate", "w_up", "w_down")}}}
        y, pairs_c, _ = R.block(x, share, bias, kind, share_cfg, "float32")
        np.testing.assert_array_equal(pairs_c, pairs)   # one router, 16 wide
        total = total + (y - mixed - shared)
        if chip in (0, 3):      # and the program's share is the reference's
            lm, _ = _driver(share_cfg).model()
            got, _ = jax.jit(lambda lp, x, b: lm._block(
                "kda", lp, x, "experts", b))(share, x[None], bias)
            np.testing.assert_allclose(got[0], y, atol=5e-5)
    np.testing.assert_allclose(mixed + shared + total, want, atol=2e-5)
    assert float(jnp.abs(total).max()) > 1e-2
    assert float(jnp.abs(shared).max()) > 1e-2


def test_the_reference_follows_three_steps_from_weights_on_the_host(small):
    cfg, _, params, toks, _ = small
    got = R.train_steps(jax.device_get(params), [toks, toks[::-1], toks],
                        cfg, lr=1e-3)
    assert len(got["losses"]) == 3 and np.isfinite(got["losses"]).all()
    assert len(got["vectors"]) == 4
    assert all(v.shape == (R.width(cfg),) for v in got["vectors"])
    steps = np.abs(got["router_biases"]) / cfg["bias_update_speed"]
    assert steps.max() == pytest.approx(3.0)
    assert set(got["grad_norms"]) == set(got["delta_norms"]) == set(params)


def test_the_reference_holds_the_share_and_the_kinds_the_file_states(small):
    cfg, _, _, _, _ = small
    held = cfg["num_experts"]
    assert R.held(cfg) == (held, 2 * held)
    full = _cfg()
    assert R.held(full) == (0, 8) and R.width(full) == 256
    assert R.kinds(full) == [("kda", "dense"), ("kda", "experts"),
                             ("kda", "experts"), ("latent", "experts"),
                             ("kda", "experts")]
    assert WK.layer_kinds(full).count("kda") == 4
    assert R.zero_biases(full).shape == (4, 256)
    # all 27 layers: 20 of the one kind, 7 of the other, 3:1 but the last
    kinds = WK.layer_kinds({**full, "num_hidden_layers": 27})
    assert kinds.count("kda") == 20 and kinds.count("latent") == 7
    assert [i + 1 for i, k in enumerate(kinds) if k == "latent"] \
        == full["linear_attn_config"]["full_attn_layers"]


def test_the_reference_imports_nothing_of_the_program():
    for name in ("reference/kimi_linear.py", "weights_kimi_linear.py"):
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
    dense, expert, latent = specs["layer_0"], specs["layer_1"], \
        specs["layer_3"]
    assert {k: W.count(v) for k, v in dense["kda"].items()} == {
        "w_q": 9_437_184, "w_k": 9_437_184, "w_v": 9_437_184,
        "conv_q": 16_384, "conv_k": 16_384, "conv_v": 16_384,
        "w_f1": 294_912, "w_f2": 524_288, "A_log": 32, "dt_bias": 4_096,
        "w_b": 73_728, "w_g1": 294_912, "w_g2": 524_288, "b_g": 4_096,
        "norm": 128, "w_out": 9_437_184}
    assert W.count(dense["kda"]) == W.count(expert["kda"]) == 39_518_368
    assert W.count(latent["latent"]) == 29_114_880
    assert W.count(dense["norm1"]) + W.count(dense["norm2"]) == 4_608
    assert W.count(dense["mlp"]) == 3 * 2304 * 9216 == 63_700_992
    moe = expert["moe"]
    assert W.count(moe["router"]) == 589_824
    assert W.count(moe["shared"]) == 7_077_888
    assert W.count(moe["w_gate"]) * 3 == 8 * 7_077_888 == 56_623_104
    assert W.count(dense) == 103_223_968
    assert all(W.count(specs[f"layer_{i}"]) == 103_813_792
               for i in (1, 2, 4))
    assert W.count(latent) == 93_410_304
    assert W.count(specs["embed"]) + W.count(specs["head"]) \
        + W.count(specs["norm_f"]) == 94_374_144
    assert W.count(specs) == 602_449_792 == cfg["parameters"]


def test_the_file_states_the_cut_and_the_programs_bounds():
    cfg = _cfg()
    pub = cfg["published"]
    assert cfg["linear_attn_config"] == pub["linear_attn_config"] == {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4}
    assert cfg["num_experts"] * cfg["expert_chips"] == pub["num_experts"] \
        == 256
    assert cfg["vocab_size"] * cfg["vocab_chips"] == pub["vocab_size"]
    assert cfg["vocab_size"] * 8 >= pub["vocab_size"]           # the floors
    assert cfg["num_experts"] >= 8
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert set(WK.layer_kinds(cfg)) == {"kda", "latent"}        # a period
    assert cfg["reduced"] == ["num_experts", "num_hidden_layers",
                              "vocab_size"]
    assert {k for k, v in pub.items() if cfg[k] != v} == set(cfg["reduced"])
    # no width differs from the source's
    for key in ("hidden_size", "num_attention_heads", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
                "intermediate_size", "moe_intermediate_size",
                "num_shared_experts", "num_experts_per_token",
                "routed_scaling_factor", "rms_norm_eps", "head_dim",
                "mla_use_nope"):
        assert cfg[key] == pub[key], key
    # tokens an expert sees a step, 1/32 of the deployment's
    assert cfg["input"]["seq"] * 2 * cfg["num_experts_per_token"] \
        // pub["num_experts"] == 512
    prog = cfg["program"]
    assert prog["remat"].startswith("block") and prog["delta_chunk"] == 64
    assert prog["dispatch_bound"] == 64 * 128 == 2 * 4096
    assert cfg["vocab_size"] % prog["head_chunk"] == 0
    for key in ("deployment", "assumed", "published", "reduced"):
        assert cfg[key]
    for key in ("kda_equations", "gate_rank", "output_gate_bias", "conv",
                "beta", "A_log", "dt_bias", "output_norm_eps",
                "aux_loss_alpha", "bias_update_speed", "bias_counts",
                "router_gradient", "nope", "initializer_range", "norms",
                "optimizer", "shared_experts", "mtp", "float32_leaves"):
        assert cfg["assumed"][key], key
    assert "stop_gradient" in cfg["assumed"]["router_gradient"]
    # the rehearsal meets several chunks and levels: 96 tokens, chunks of 32
    small = {**cfg, **cfg["rehearsal"]}
    assert small["input"]["seq"] // small["program"]["delta_chunk"] == 3
    assert small["linear_attn_config"]["kda_layers"] \
        == pub["linear_attn_config"]["kda_layers"]
    spec = S.Spec()
    cell = spec.cell(CELL)
    assert cell["traffic"] == "train-fixed-16k-s8192" and cell["chips"] == 1
    assert spec.traffic(cell)["lr"] == 1e-4


def test_the_cells_entries_and_their_readers():
    """Found by name, wherever a later PR's entries stand behind them."""
    spec = S.Spec()
    cell = spec.cell(CELL)
    reported = {m["name"]: m for m in spec.per_layer(cell)}
    shared = {"device_idle_pct.lm", "unscoped_pct.lm",
              "optimizer_ms_per_step.lm", "amp_ms_per_step.lm",
              "adam_kernel_roofline", "head_loss_ms_per_step",
              "delta_rule_ms_per_step", "latent_attention_ms_per_step",
              "mlp_ms_per_step.kvl", "moe_route_ms_per_step",
              "moe_experts_ms_per_step", "moe_overflow_pairs",
              "moe_held_pairs_max", "expert_load_max_over_mean",
              "router_bias_abs_max"}
    own = {"kda_attention_ms_per_step": "trace_scope",
           "delta_rule_roofline.klin": "trace_scope_roofline",
           "flash_attn_roofline.klin": "trace_kernel_roofline",
           "backward_ms_per_step.klin": "trace_scope",
           "kda_chunk_decay_nats_max": "counter"}
    assert set(reported) == shared | set(own) | set(
        bm_tree.region_metrics(spec))
    assert len(reported) == 24
    names = [m["name"] for m in spec.bm["per_layer"]]
    at = names.index("kda_attention_ms_per_step")
    assert names[at:at + 5] == list(own)        # together, in this order
    for name, reader in own.items():
        entry, = [m for m in spec.bm["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_tok_s"
        assert reported[name]["reader"] == reader
        assert callable(spec.plugin("readers", reader).read)
        if "work" in reported[name]["args"]:
            assert callable(spec.plugin(
                "work", reported[name]["args"]["work"]).total)
    assert [m["name"] for m in spec.end_to_end(cell)] \
        == ["train_tok_s", "setup_s"]
    from apex_tpu import prof
    with open(os.path.join(S.HERE, "scopes", "kda_lm.json")) as f:
        scopes = [s["pattern"] for s in json.load(f)["scopes"]]
    assert scopes == ["kda_attention"] and set(scopes) <= set(prof.SCOPES)
    assert "kda_chunk_decay_nats_max" in spec.limits(cell)
    bm_tree.everything_holds(spec)


def _run(cfg, per_chip, steps):
    return types.SimpleNamespace(
        ctx=types.SimpleNamespace(config=cfg, traffic={"per_chip": per_chip}),
        rec={"steps": steps})


def test_both_work_functions_by_hand():
    cfg = _cfg()
    # 2 rows of 8192, 32 heads, the four KDA layers of the cut: nine
    # products with the 128 x 128 state a token; q, k, v, o in bf16, 128
    # floats of g and one of beta, three times over
    tokens = 2 * 8192 * 32 * 4
    assert kda_delta_rule.kda_layers(cfg) == 4
    work = kda_delta_rule.step_work(cfg, 2)
    assert work == {"flops": tokens * 9 * 2 * 128 * 128,
                    "bytes": tokens * 3 * (4 * 128 * 2 + 128 * 4 + 4)}
    assert work["flops"] == 618_475_290_624
    assert work["bytes"] == 9_688_842_240
    assert kda_delta_rule.total(_run(cfg, 2, 3)) \
        == {k: 3.0 * v for k, v in work.items()}
    # the one latent layer of the cut, not num_hidden_layers
    assert flash_attn_mla_layers_train.latent_layers(cfg) == 1
    half = 8192 * 8192 // 2
    assert flash_attn_mla_layers_train.step_flops(cfg, 2) \
        == 3 * 2 * 32 * half * 2 * (192 + 128) == 4_123_168_604_160
    assert flash_attn_mla_layers_train.step_flops(cfg, 2) * 5 \
        == flash_attn_mla_train.step_flops(cfg, 2)
    assert flash_attn_mla_layers_train.total(_run(cfg, 2, 3)) \
        == {"flops": 3.0 * 4_123_168_604_160}
    # a small size, counted token by token: 8 layers hold 6 + 2
    small = {**cfg, **cfg["rehearsal"], "num_hidden_layers": 8}
    assert (kda_delta_rule.kda_layers(small),
            flash_attn_mla_layers_train.latent_layers(small)) == (6, 2)
    count = sum(9 * 2 * 16 * 16 for _ in range(96) for _ in range(4)) * 6
    assert kda_delta_rule.step_work(small, 1)["flops"] == count
    pairs = sum(t + 1 for t in range(96))
    assert abs(flash_attn_mla_layers_train.step_flops(small, 1)
               - 2 * 3 * 4 * pairs * 2 * (16 + 8 + 16)) \
        <= 2 * 3 * 4 * 96 * 2 * 40          # the diagonal's half
