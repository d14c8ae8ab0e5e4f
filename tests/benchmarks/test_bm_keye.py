"""What only the Keye-VL 2.0 configuration has: the program against its
plain reference on seeded weights at the rehearsal size (logits, loss,
per-leaf gradients, the indexer's loss, the chosen keys), that the
comparison sees a wrong selection, the eight ranks' shares of one layer
adding up to the uncut layer, the parameters re-counted from the specs,
the cut as the configuration file states it, both work functions against
brute-force counts, the new entries under ``bm_tree``'s invariants, and a
reference that imports nothing of the program."""

import ast
import dataclasses
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bm_tree
from apex_tpu.ops import key_set as KS
from benchmarks import spec as S, weights as W, weights_keye_vl as WK
from benchmarks.drivers import train_keye_vl
from benchmarks.reference import keye_vl as R
from benchmarks.work import flash_attn_select_train, sparse_index_train

NAME = "keye-vl-2.0-30b-a3b-train"
CELL = "keye_train_s16384"


def _cfg():
    with open(os.path.join(S.HERE, "configs", NAME + ".json")) as f:
        return json.load(f)


def _driver(cfg, **traffic):
    ctx = types.SimpleNamespace(
        config=cfg, traffic={"steps_checked": 1, "kind": "train_fixed_batch",
                             "per_chip": 2, "distinct": 1, **traffic},
        seed=3, devices=jax.devices()[:1], plugin=S.Spec().plugin)
    return train_keye_vl.Driver(ctx)


def _moved(params):
    """The norms moved off their starts (at 1 and 0 a wrong use of them
    would not show), the matrices larger."""
    return jax.tree.map(
        lambda x: x + 0.1 * jax.random.normal(jax.random.key(1), x.shape)
        if x.ndim == 1 else 3.0 * x, params)


@pytest.fixture(scope="module")
def small():
    """The rehearsal sizes (24 keys a query under 48 tokens), the
    program's model, seeded weights, another chip's share."""
    cfg = _cfg()
    cfg = {**cfg, **cfg["rehearsal"], "expert_chip": 1}
    lm, _ = _driver(cfg).model()
    params = _moved(W.build(WK.specs(cfg), W.seed_key(3), jnp.float32))
    toks = jax.random.randint(jax.random.key(5), (2, 49), 0,
                              cfg["vocab_size"])
    return cfg, lm, params, toks


def test_the_driver_builds_the_model_the_configuration_states():
    d = _driver(_cfg())
    lm, shapes = d.model()
    assert lm.layer_types == ("sparse",) * 5 and lm.ffns == ("experts",) * 5
    assert (lm.num_heads, lm.num_kv_heads, lm.head_dim, lm.rotary_dim) \
        == (32, 4, 128, 128)
    assert (lm.index_heads, lm.index_dim, lm.index_topk, lm.index_coef) \
        == (16, 64, 2048, 1.0)
    assert lm.rope_theta == 1e7 and lm.rope_yarn is None
    assert not lm.attn_gate and not lm.tied_head and not lm.window
    assert (lm.num_experts, lm.top_k, lm.experts_held, lm.shared_ffn,
            lm.expert_ffn) == (128, 8, (0, 16), 0, 768)
    assert lm.router == "softmax" and lm.aux_coef == 0.001
    assert lm.router_state() is None and "head" in shapes
    assert lm.remat and lm.dispatch_bound == 65536
    assert d.expected_pairs() == 5 * 2 * 31_458_304
    assert R.selected_pairs(16384, 2048) == 31_458_304 \
        == sum(min(t + 1, 2048) for t in range(16384))
    census = d.census()
    assert census["forward"]["blocks"] == [512, 512]
    assert census["backward"]["blocks"] == [256, 512]
    assert census["forward"]["dead"] == 32 * 31 // 2
    assert census["forward"]["edge"] == 32


def test_the_programs_logits_are_the_references(small):
    cfg, lm, params, toks = small
    got = lm.apply(params, toks[:, :-1])
    want = jnp.stack([R.logits(params, t[:-1], cfg) for t in toks])
    assert float(jnp.abs(want).max()) > 0.5
    np.testing.assert_allclose(got, want, atol=5e-5)


@pytest.mark.parametrize("wrong", ["topk", "dense", "no_relu"])
def test_the_comparison_sees_a_wrong_selection(small, wrong):
    """A reference that keeps half as many keys, keeps every key, or
    scores without the ReLU is another function: the logits part."""
    cfg, lm, params, toks = small
    sa = cfg["sa_config"]
    if wrong == "no_relu":
        import unittest.mock
        with unittest.mock.patch.object(jax.nn, "relu", lambda x: x):
            other = R.logits(params, toks[0, :-1], cfg)
    else:
        other = R.logits(params, toks[0, :-1], {**cfg, "sa_config": {
            **sa, "topk": sa["topk"] // 2 if wrong == "topk" else 10 ** 6}})
    got = lm.apply(params, toks[:1, :-1])[0]
    assert float(jnp.abs(got - other).max()) > 1e-2


def test_the_programs_loss_gradients_and_set_are_the_references(small):
    """Leaf by leaf, the indexer's among them (they learn from ``L_I``
    alone), the routers' (from the balance term alone); the indexer's loss
    and the pairs selected; layer 0's chosen keys bit for bit."""
    cfg, lm, params, toks = small
    (loss, counters), grad = jax.value_and_grad(
        lm.loss_with_counters, has_aux=True)(params, toks)
    want, want_grad, pairs, facts = R.batch_loss_and_grad(params, toks, cfg)
    assert float(loss) == pytest.approx(float(want), abs=2e-5)
    assert float(counters["index_loss"]) == pytest.approx(
        facts["index_loss"], rel=2e-5)
    assert facts["index_loss"] > 0.05
    assert int(counters["select_pairs"]) == facts["select_pairs"] \
        == 2 * 2 * R.selected_pairs(48, 24)
    assert int(counters["moe_overflow_pairs"]) == 0
    lo, hi = R.held(cfg)
    assert int(counters["moe_held_pairs_max"]) == int(
        pairs[:, lo:hi].sum(1).max())
    mine = KS.unpack_select(lm.first_selection(params, toks[:1, :-1]), 48)
    theirs = np.unpackbits(np.asarray(facts["select_bits"]), axis=-1,
                           count=48).astype(bool)
    np.testing.assert_array_equal(mine[0], theirs)
    norms = jax.tree.map(lambda a, b: (float(jnp.linalg.norm(a)),
                                       float(jnp.linalg.norm(b)),
                                       float(jnp.linalg.norm(a - b))),
                         grad, want_grad)
    for path, (got, theirs, apart) in jax.tree_util.tree_leaves_with_path(
            norms, is_leaf=lambda x: isinstance(x, tuple)):
        assert theirs > 0, path
        assert got == pytest.approx(theirs, rel=1e-4), path
        assert apart <= 2e-4 * theirs, path


def test_the_drivers_own_gaps_read_zero_on_itself_and_see_a_fault(small):
    """``own_gaps`` against readings made from the program's own numbers
    reads ~0; against a reference that keeps half the keys it reads a
    disagreement of about a half."""
    cfg, lm, params, toks = small
    d = _driver(cfg)
    d.n_checked = 1
    d.first_select = lm.first_selection(params, toks[:1, :-1])
    _, c = lm.loss_with_counters(params, toks)
    d.seen = [c]
    ref = R.train_steps(jax.device_get(params), [toks], cfg, lr=1e-3)
    got = d.own_gaps(ref)
    assert got["select_disagreement"] == 0.0
    assert got["index_loss_gap"] < 1e-4
    assert ref["select_pairs"] == [int(c["select_pairs"])]
    half = {**cfg, "sa_config": {**cfg["sa_config"], "topk": 12}}
    wrong = R.train_steps(jax.device_get(params), [toks], half, lr=1e-3)
    far = d.own_gaps({**ref, "select_bits": wrong["select_bits"]})
    assert far["select_disagreement"] == 0.0    # the larger set holds it
    d.first_select = dataclasses.replace(lm, index_topk=12).first_selection(
        params, toks[:1, :-1])
    assert 0.3 < d.own_gaps(ref)["select_disagreement"] < 0.6


def test_the_eight_ranks_shares_add_up_to_the_uncut_layer():
    """One layer at the rehearsal's widths with all 32 experts held is the
    uncut layer; the eight ranks each hold four of them, see the same
    mixer and the same router, and add their experts' part: the mixer
    counted once, the eight parts sum to the uncut layer's."""
    cfg = _cfg()
    cfg = {**cfg, **cfg["rehearsal"], "num_hidden_layers": 1}
    chips, held = cfg["expert_chips"], cfg["num_experts"]
    whole_cfg = {**cfg, "num_experts": held * chips, "expert_chips": 1,
                 "expert_chip": 0}
    whole = _moved(W.build(WK.specs(whole_cfg), W.seed_key(7),
                           jnp.float32))["layer_0"]
    x = jax.random.normal(jax.random.key(2), (48, cfg["hidden_size"]))
    want, idx, _, index_loss, keep = R.block(x, whole, "sparse", whole_cfg,
                                             "float32")
    mixer_out = x + R.sparse_mixer(
        R.rms(x, whole["norm1"], cfg["rms_norm_eps"]), whole["attn"],
        whole["index"], whole_cfg, "float32")[0]
    total = 0.0
    for chip in range(chips):
        share_cfg = {**cfg, "expert_chip": chip}
        lo, hi = R.held(share_cfg)
        assert (lo, hi) == (chip * held, (chip + 1) * held)
        share = {**whole, "moe": {
            "router": whole["moe"]["router"],
            **{k: whole["moe"][k][lo:hi]
               for k in ("w_gate", "w_up", "w_down")}}}
        y, idx_c, _, loss_c, keep_c = R.block(x, share, "sparse", share_cfg,
                                              "float32")
        np.testing.assert_array_equal(idx_c, idx)   # one router, 32 wide
        np.testing.assert_array_equal(keep_c, keep)     # one mixer
        assert float(loss_c) == pytest.approx(float(index_loss), rel=1e-6)
        total = total + (y - mixer_out)
        if chip in (0, 5):      # and the program's share is the reference's
            lm, _ = _driver({**share_cfg}).model()
            got, _ = jax.jit(lambda lp, x: lm._block("sparse", lp, x))(
                share, x[None])
            np.testing.assert_allclose(got[0], y, atol=5e-5)
    np.testing.assert_allclose(mixer_out + total, want, atol=2e-5)
    assert float(jnp.abs(total).max()) > 1e-2


def test_the_reference_follows_three_steps_from_weights_on_the_host(small):
    cfg, _, params, toks = small
    got = R.train_steps(jax.device_get(params), [toks, toks[::-1], toks],
                        cfg, lr=1e-3)
    assert len(got["losses"]) == 3 and np.isfinite(got["losses"]).all()
    assert len(got["index_losses"]) == 3 and min(got["index_losses"]) > 0
    assert got["select_pairs"] == [2 * 2 * R.selected_pairs(48, 24)] * 3
    assert got["select_bits"].shape == (48, 6)
    assert set(got["grad_norms"]) == set(got["delta_norms"]) == set(params)
    assert got["grad_norms"]["layer_0"]["index"]["w_q"] > 0
    assert got["grad_norms"]["layer_1"]["index"]["k_norm"]["b"] > 0
    assert got["delta_norms"]["layer_1"]["attn"]["k_norm"] > 0


def test_the_reference_imports_nothing_of_the_program():
    for name in ("reference/keye_vl.py", "weights_keye_vl.py",
                 "work/flash_attn_select_train.py",
                 "work/sparse_index_train.py"):
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
    layer = specs["layer_0"]
    assert {k: W.count(v) for k, v in layer["attn"].items()} == {
        "w_q": 8_388_608, "w_k": 1_048_576, "w_v": 1_048_576,
        "q_norm": 128, "k_norm": 128, "w_o": 8_388_608}
    assert W.count(layer["attn"]) + W.count(layer["norm1"]) \
        + W.count(layer["norm2"]) == 18_878_720
    assert {k: W.count(v) for k, v in layer["index"].items()} == {
        "w_q": 2_097_152, "w_k": 131_072, "w_w": 32_768, "k_norm": 128}
    assert W.count(layer["index"]) == 2_261_120
    moe = layer["moe"]
    assert "shared" not in moe
    assert W.count(moe["router"]) == 262_144
    assert W.count(moe["w_gate"]) * 3 == 16 * 4_718_592
    assert all(W.count(specs[f"layer_{i}"]) == 96_899_456 for i in range(5))
    assert W.count(specs["embed"]) + W.count(specs["head"]) \
        + W.count(specs["norm_f"]) == 77_793_280
    assert W.count(specs) == 562_290_560 == cfg["parameters"]
    # four layers (what fits if five do not) and six (which do not)
    assert W.count(specs) - 96_899_456 == 465_391_104
    assert W.count(specs) + 96_899_456 == 659_190_016


def test_the_file_states_the_cut_and_the_programs_bounds():
    cfg = _cfg()
    pub = cfg["published"]
    assert cfg["source"] == ("https://huggingface.co/Kwai-Keye/"
                             "Keye-VL-2.0-30B-A3B/blob/main/config.json")
    assert cfg["num_experts"] * cfg["expert_chips"] == pub["num_experts"] \
        == pub["num_local_experts"] == 128
    assert cfg["num_local_experts"] == cfg["num_experts"] == 16
    assert cfg["vocab_size"] * cfg["vocab_chips"] == pub["vocab_size"]
    assert cfg["vocab_size"] * 8 >= pub["vocab_size"]           # the floors
    assert cfg["num_experts"] >= 8 and cfg["num_hidden_layers"] >= 4
    assert cfg["published_layers"] == [0, 4] and pub["num_hidden_layers"] == 48
    assert sorted(cfg["reduced"]) == sorted([
        "num_experts", "num_local_experts", "num_hidden_layers",
        "vocab_size"])
    # every other key of the source is as published: no width differs
    assert {k for k in pub if cfg[k] != pub[k]} == set(cfg["reduced"])
    assert cfg["sa_config"] == pub["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["rope_theta"]) \
        == (2048, 128, 32, 4, 768, 8, 10_000_000)
    # tokens an expert sees a step, 1/8 of the deployment's
    assert cfg["input"]["seq"] * cfg["num_experts_per_tok"] \
        // pub["num_experts"] == 1024
    prog = cfg["program"]
    assert prog["remat"].startswith("block")
    for said in ("o and lse", "packed key sets", "indexer's gradient"):
        assert said in prog["remat"], said
    assert prog["dispatch_bound"] % 128 == 0
    assert prog["dispatch_bound"] >= 2 * 16384 * 8 * 16 // 128
    assert cfg["vocab_size"] % prog["head_chunk"] == 0
    for key in ("deployment", "assumed", "published", "reduced"):
        assert cfg[key]
    for key in ("qk_norm", "positions", "indexer", "indexer_input",
                "indexer_key_norm", "indexer_rope", "indexer_scales",
                "indexer_precision", "chunk_sizes", "selection",
                "indexer_loss", "router", "router_aux_loss_coef",
                "router_gradient", "unused_keys", "initializer_range",
                "embedding_initializer_range", "optimizer"):
        assert cfg["assumed"][key], key
    assert "stop_gradient" in cfg["assumed"]["router_gradient"]
    assert "stop_gradient" in cfg["assumed"]["indexer_loss"]
    for said in ("8 chips share each layer's 128 experts", "experts 0-15",
                 "8 chips the vocabulary", "Published layers 0-4"):
        assert said in cfg["deployment"], said
    # the rehearsal keeps fewer keys than its sequence: selection happens
    small = cfg["rehearsal"]
    assert small["sa_config"]["topk"] < small["input"]["seq"]
    spec = S.Spec()
    cell = spec.cell(CELL)
    assert cell["traffic"] == "train-fixed-16k-s16384" and cell["chips"] == 1
    traffic = spec.traffic(cell)
    # ISSUE 42's traffic, the other LM cells' count and rate
    assert (traffic["per_chip"], traffic["distinct"], traffic["lr"],
            traffic["steps_checked"], traffic["trace_seconds"]) \
        == (1, 32, 1e-4, 3, 5)
    assert traffic["routers"]   # what the routers do under it, on the chip
    # the four hybrid cells' tokens a step, as one row
    assert traffic["per_chip"] * cfg["input"]["seq"] == 2 * 8192


def test_the_cells_entries_and_their_readers():
    spec = S.Spec()
    cell = spec.cell(CELL)
    reported = {m["name"]: m for m in spec.per_layer(cell)}
    shared = {"device_idle_pct.lm", "unscoped_pct.lm",
              "optimizer_ms_per_step.lm", "amp_ms_per_step.lm",
              "adam_kernel_roofline", "head_loss_ms_per_step",
              "moe_route_ms_per_step", "moe_experts_ms_per_step",
              "moe_overflow_pairs", "moe_held_pairs_max",
              "expert_load_max_over_mean"}
    own = {"sparse_attention_ms_per_step": "trace_scope",
           "sparse_index_ms_per_step": "trace_scope",
           "backward_ms_per_step.keye": "trace_scope",
           "flash_attn_roofline.keye": "trace_kernel_roofline",
           "sparse_index_roofline": "trace_scope_roofline",
           "select_live_tile_pct": "counter"}
    assert set(reported) == shared | set(own) | set(
        bm_tree.region_metrics(spec))
    assert len(reported) == 21
    # the six are the list's last, in the issue's order, and name this cell
    assert [m["name"] for m in spec.bm["per_layer"][-6:]] == list(own)
    for name, reader in own.items():
        entry, = [m for m in spec.bm["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_tok_s"
        assert reported[name]["reader"] == reader
        assert callable(spec.plugin("readers", reader).read)
        if "work" in reported[name]["args"]:
            assert callable(spec.plugin(
                "work", reported[name]["args"]["work"]).total)
    assert reported["flash_attn_roofline.keye"]["args"]["pattern"] \
        == r"^%(\w+_)?apex_flash_"
    assert [m["name"] for m in spec.end_to_end(cell)] \
        == ["train_tok_s", "setup_s"]
    assert spec.bm["workloads"][-1]["name"] == CELL
    assert spec.bm["configs"][-1]["name"] == NAME
    # both scopes are in the vocabulary and in the program's list
    from apex_tpu import prof
    with open(os.path.join(S.HERE, "scopes", "sparse_lm.json")) as f:
        scopes = [s["pattern"] for s in json.load(f)["scopes"]]
    assert scopes == ["sparse_attention", "sparse_index"]
    assert set(scopes) <= set(prof.SCOPES)
    bm_tree.everything_holds(spec)


def _run(cfg, per_chip, steps):
    return types.SimpleNamespace(
        ctx=types.SimpleNamespace(config=cfg, traffic={"per_chip": per_chip}),
        rec={"steps": steps})


def test_both_work_functions_against_brute_force_counts():
    cfg = _cfg()
    # 1 row, 32 heads of 128: two products forward and four backward over
    # the selected pairs of five layers
    pairs = 16384 * 2048 - 2048 * 2047 // 2
    assert pairs == 31_458_304 == flash_attn_select_train.selected_pairs(cfg)
    causal = 16384 * 16385 // 2
    assert round(100 * pairs / causal, 1) == 23.4
    assert flash_attn_select_train.step_flops(cfg, 1) \
        == 32 * pairs * 2 * 128 * 6 * 5 == 7_731_192_791_040
    assert flash_attn_select_train.total(_run(cfg, 1, 3)) \
        == {"flops": 3.0 * 7_731_192_791_040}
    work = sparse_index_train.step_work(cfg, 1)
    assert work["flops"] == 5 * 3 * 2 * causal * 16 * 64 \
        == 4_123_420_262_400
    assert work["bytes"] == 5 * 2 * (16384 * (16 * 64 * 2 + 64 * 2 + 16 * 4)
                                     + 16384 * 16384 // 8)
    assert sparse_index_train.total(_run(cfg, 1, 2)) \
        == {k: 2.0 * v for k, v in work.items()}
    # a small size, the selected pairs counted from a program's own set
    small = {**cfg, **cfg["rehearsal"]}
    lm, _ = _driver(small).model()
    params = W.build(WK.specs(small), W.seed_key(2), jnp.float32)
    toks = jax.random.randint(jax.random.key(1), (1, 96), 0, 256)
    chosen = KS.unpack_select(lm.first_selection(params, toks), 96)
    assert int(chosen.sum()) == flash_attn_select_train.selected_pairs(small) \
        == sum(min(t + 1, 24) for t in range(96))
    assert flash_attn_select_train.step_flops(small, 2) \
        == 2 * 8 * int(chosen.sum()) * 2 * 16 * 6 * 2
    assert sparse_index_train.step_work(small, 2)["flops"] \
        == 2 * 2 * 3 * 2 * int(np.tril(np.ones((96, 96))).sum()) * 4 * 8
    # topk past the sequence: every causal pair
    assert flash_attn_select_train.selected_pairs(
        {**small, "sa_config": {**small["sa_config"], "topk": 4096}}) \
        == 96 * 97 // 2
