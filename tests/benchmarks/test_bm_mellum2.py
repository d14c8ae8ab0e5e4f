"""What only the Mellum 2 configuration has: the program against its plain
reference on seeded weights at a small size (logits, loss, per-leaf
gradients, the pairs an expert) with a window shorter than the sequence
and YaRN on the full layer, that the comparison sees a wrong window and a
wrong rotary table, both work functions by hand, the parameters re-counted
from the specs, the cut as the configuration file states it, what the
driver states of the flash grids, and a reference that imports nothing of
the program."""

import ast
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bm_tree
from benchmarks import spec as S, weights as W, weights_mellum2 as WM
from benchmarks.drivers import train_mellum2
from benchmarks.reference import mellum2 as R
from benchmarks.work import flash_attn_window_train, flash_window_band_train

NAME = "mellum2-12b-a2.5b-train"
CELL = "mellum2_train_s8192"


def _cfg():
    with open(os.path.join(S.HERE, "configs", NAME + ".json")) as f:
        return json.load(f)


def _driver(cfg, **traffic):
    ctx = types.SimpleNamespace(
        config=cfg, traffic={"steps_checked": 1, "kind": "train_fixed_batch",
                             "per_chip": 2, "distinct": 1, **traffic},
        seed=3, devices=jax.devices()[:1], plugin=S.Spec().plugin)
    return train_mellum2.Driver(ctx)


@pytest.fixture(scope="module")
def small():
    """The rehearsal sizes (window 32 under 48 tokens), the program's
    model, seeded weights with the norms moved off 1 (at 1 a wrong use of
    them would not show), another chip's share."""
    cfg = _cfg()
    cfg = {**cfg, **cfg["rehearsal"], "expert_chip": 1}
    lm, _ = _driver(cfg).model()
    params = W.build(WM.specs(cfg), W.seed_key(3), jnp.float32)
    params = jax.tree.map(
        lambda x: x + 0.1 * jax.random.normal(jax.random.key(1), x.shape)
        if x.ndim == 1 else 3.0 * x, params)
    toks = jax.random.randint(jax.random.key(5), (2, 49), 0,
                              cfg["vocab_size"])
    return cfg, lm, params, toks


def test_the_driver_builds_the_model_the_configuration_states():
    lm, shapes = _driver(_cfg()).model()
    assert lm.layer_types == ("window", "window", "window", "full")
    assert lm.ffns == ("experts",) * 4
    assert (lm.num_heads, lm.num_kv_heads, lm.head_dim, lm.rotary_dim) \
        == (32, 4, 128, 128)
    assert lm.window == 1024 and not lm.attn_gate and not lm.tied_head
    assert lm.rope_theta == 5e5
    assert lm.rope_yarn == (16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    assert (lm.num_experts, lm.top_k, lm.experts_held, lm.shared_ffn) \
        == (64, 8, (0, 16), 0)
    assert lm.router == "softmax" and lm.aux_coef == 0.001
    assert lm.router_state() is None and "head" in shapes
    assert lm.remat and lm.dispatch_bound % 128 == 0


def test_the_programs_logits_are_the_references(small):
    cfg, lm, params, toks = small
    got = lm.apply(params, toks[:, :-1])
    want = jnp.stack([R.logits(params, t[:-1], cfg) for t in toks])
    assert float(jnp.abs(want).max()) > 0.5
    np.testing.assert_allclose(got, want, atol=5e-5)


@pytest.mark.parametrize("wrong", [
    {"sliding_window": 48}, {"sliding_window": 16}, "plain_table"])
def test_the_comparison_sees_a_wrong_window_and_a_wrong_table(small, wrong):
    """A reference with another window, or with the plain rotary table on
    the full layer, is another function: the logits part."""
    cfg, lm, params, toks = small
    if wrong == "plain_table":
        rope = cfg["rope_parameters"]
        wrong = {"rope_parameters": {**rope, "full_attention":
                                     rope["sliding_attention"]}}
    other = R.logits(params, toks[0, :-1], {**cfg, **wrong})
    got = lm.apply(params, toks[:1, :-1])[0]
    assert float(jnp.abs(got - other).max()) > 1e-2


def test_the_programs_loss_gradients_and_pairs_are_the_references(small):
    """Leaf by leaf, the routers' among them (a share's router learns from
    the balance term alone)."""
    cfg, lm, params, toks = small
    (loss, counters), grad = jax.value_and_grad(
        lm.loss_with_counters, has_aux=True)(params, toks)
    want, want_grad, pairs = R.batch_loss_and_grad(params, toks, cfg)
    assert float(loss) == pytest.approx(float(want), abs=2e-5)
    assert int(counters["moe_overflow_pairs"]) == 0
    lo, hi = R.held(cfg)
    assert int(counters["moe_held_pairs_max"]) == int(
        pairs[:, lo:hi].sum(1).max())
    assert int(pairs.sum()) == 4 * 2 * 48 * cfg["num_experts_per_tok"]
    norms = jax.tree.map(lambda a, b: (float(jnp.linalg.norm(a)),
                                       float(jnp.linalg.norm(b)),
                                       float(jnp.linalg.norm(a - b))),
                         grad, want_grad)
    for path, (mine, theirs, apart) in jax.tree_util.tree_leaves_with_path(
            norms, is_leaf=lambda x: isinstance(x, tuple)):
        assert theirs > 0, path
        assert mine == pytest.approx(theirs, rel=1e-4), path
        assert apart <= 2e-4 * theirs, path


def test_the_reference_follows_three_steps_from_weights_on_the_host(small):
    cfg, _, params, toks = small
    got = R.train_steps(jax.device_get(params), [toks, toks[::-1], toks],
                        cfg, lr=1e-3)
    assert len(got["losses"]) == 3 and np.isfinite(got["losses"]).all()
    assert set(got["grad_norms"]) == set(got["delta_norms"]) == set(params)
    assert got["grad_norms"]["layer_0"]["moe"]["router"] > 0
    assert got["delta_norms"]["layer_3"]["attn"]["k_norm"] > 0
    assert "vectors" not in got         # a softmax router: no state to move


def test_the_reference_holds_the_share_and_writes_its_own_tables(small):
    cfg, _, _, _ = small
    held = cfg["num_experts"]
    assert R.held(cfg) == (held, 2 * held)
    full = _cfg()
    assert R.held(full) == (0, 16) and R.width(full) == 64
    assert R.layer_kinds(full) == ["window"] * 3 + ["full"]
    plain, one = R.inv_freq(full, "window")
    yarn, factor = R.inv_freq(full, "full")
    want = 5e5 ** (-np.arange(64) / 64.0)
    np.testing.assert_allclose(plain, want, rtol=1e-6)
    assert one == 1.0 and factor == 1.2772588722239782
    np.testing.assert_allclose(yarn[:19], want[:19], rtol=1e-6)
    np.testing.assert_allclose(yarn[35:], want[35:] / 16, rtol=1e-6)
    np.testing.assert_allclose(
        yarn[26], want[26] / 16 * (8 / 17) + want[26] * (9 / 17), rtol=1e-6)


def test_the_reference_imports_nothing_of_the_program():
    for name in ("reference/mellum2.py", "weights_mellum2.py",
                 "work/flash_attn_window_train.py",
                 "work/flash_window_band_train.py"):
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
    specs = WM.specs(cfg)
    layer = specs["layer_0"]
    assert {k: W.count(v) for k, v in layer["attn"].items()} == {
        "w_q": 9_437_184, "w_k": 1_179_648, "w_v": 1_179_648,
        "q_norm": 128, "k_norm": 128, "w_o": 9_437_184}
    assert W.count(layer["attn"]) == 21_233_920
    assert W.count(layer["norm1"]) + W.count(layer["norm2"]) == 4_608
    moe = layer["moe"]
    assert "shared" not in moe
    assert W.count(moe["router"]) == 147_456
    assert W.count(moe["w_gate"]) * 3 == 16 * 6_193_152
    assert W.count(moe) == 99_237_888
    assert all(W.count(specs[f"layer_{i}"]) == 120_476_416 for i in range(4))
    assert W.count(specs["embed"]) + W.count(specs["head"]) \
        + W.count(specs["norm_f"]) == 113_248_512
    assert W.count(specs) == 595_154_176 == cfg["parameters"]


def test_the_file_states_the_cut_and_the_programs_bounds():
    cfg = _cfg()
    pub = cfg["published"]
    assert cfg["source"] == ("https://huggingface.co/JetBrains/"
                             "Mellum2-12B-A2.5B-Instruct/blob/main/config.json")
    assert cfg["num_experts"] * cfg["expert_chips"] == pub["num_experts"] == 64
    assert cfg["vocab_size"] * cfg["vocab_chips"] == pub["vocab_size"]
    assert cfg["vocab_size"] * 8 >= pub["vocab_size"]           # the floors
    assert cfg["num_experts"] >= 8 and cfg["num_hidden_layers"] >= 4
    first, last = cfg["published_layers"]
    assert (first, last) == (0, 3)
    assert cfg["layer_types"] == pub["layer_types"][first:last + 1] \
        == ["sliding_attention"] * 3 + ["full_attention"]
    assert cfg["mlp_layer_types"] == pub["mlp_layer_types"][:4] \
        == ["sparse"] * 4
    assert sorted(cfg["reduced"]) == sorted([
        "num_experts", "num_hidden_layers", "layer_types",
        "mlp_layer_types", "vocab_size"])
    # every other key of the source is as published: no width differs
    assert {k for k in pub if cfg[k] != pub[k]} == set(cfg["reduced"])
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["sliding_window"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"]) \
        == (2304, 128, 1024, 896, 8)
    # tokens an expert sees a step, 1/4 of the deployment's
    assert cfg["input"]["seq"] * 2 * cfg["num_experts_per_tok"] \
        // pub["num_experts"] == 2048
    # the original positions are the cell's sequence
    assert cfg["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] == cfg["input"]["seq"]
    prog = cfg["program"]
    assert prog["remat"].startswith("block") and "o and lse" in prog["remat"]
    assert prog["dispatch_bound"] % 128 == 0
    assert prog["dispatch_bound"] >= 16384 * 8 * 16 // 64
    assert cfg["vocab_size"] % prog["head_chunk"] == 0
    for key in ("deployment", "assumed", "published", "reduced"):
        assert cfg[key]
    for key in ("qk_norm", "router", "router_aux_loss_coef",
                "router_gradient", "sliding_window", "yarn",
                "max_window_layers", "rope", "mtp", "initializer_range",
                "optimizer"):
        assert cfg["assumed"][key], key
    assert "stop_gradient" in cfg["assumed"]["router_gradient"]
    for said in ("4 chips share each layer's 64 experts", "experts 0-15",
                 "4 chips the vocabulary", "Published layers 0-3"):
        assert said in cfg["deployment"], said
    # the rehearsal's window is shorter than its sequence: a band
    small = cfg["rehearsal"]
    assert small["sliding_window"] < small["input"]["seq"]
    spec = S.Spec()
    cell = spec.cell(CELL)
    assert cell["traffic"] == "train-fixed-16k-s8192" and cell["chips"] == 1
    assert spec.traffic(cell)["lr"] == 1e-4
    reported = {m["name"]: m for m in spec.per_layer(cell)}
    assert {"adam_kernel_roofline", "moe_route_ms_per_step",
            "moe_experts_ms_per_step", "moe_held_pairs_max",
            "moe_overflow_pairs", "head_loss_ms_per_step",
            "device_idle_pct.lm"} <= set(reported)
    assert "router_bias_abs_max" not in reported        # no bias here
    # the cell's own four metrics are entries (PR 41) that name this
    # cell, each read by the reader its file names
    listed = {m["name"]: m for m in spec.bm["per_layer"]}
    for name, reader in (("window_attention_ms_per_step", "trace_scope"),
                         ("backward_ms_per_step.mellum2", "trace_scope"),
                         ("flash_attn_roofline.mellum2",
                          "trace_kernel_roofline"),
                         ("window_flash_roofline", "trace_kernel_roofline")):
        assert CELL in listed[name]["workloads"]
        with open(os.path.join(S.HERE, "layer_metrics", name + ".json")) as f:
            m = json.load(f)
        assert reported[name]["reader"] == m["reader"] == reader
        assert reported[name]["args"] == m["args"]
        assert callable(spec.plugin("readers", reader).read)
        if "work" in m["args"]:
            assert callable(spec.plugin("work", m["args"]["work"]).total)
    # its runs of layers are scanned under the regions, like its siblings'
    assert set(bm_tree.region_metrics(spec)) <= set(reported)


def _run(cfg, per_chip, steps):
    return types.SimpleNamespace(
        ctx=types.SimpleNamespace(config=cfg, traffic={"per_chip": per_chip}),
        rec={"steps": steps})


def test_both_flash_work_functions_by_hand():
    cfg = _cfg()
    # 2 rows, 32 heads of 128: two products forward and four backward
    # over the lower half of 8192^2 scores (the full layer), over 8192 x
    # 1024 less the first queries' missing half-square (a window layer)
    full = 2 * 32 * (8192 * 8192 // 2) * 2 * 128 * 6
    band = 2 * 32 * (8192 * 1024 - 1024 * 1024 // 2) * 2 * 128 * 6
    assert (full, band) == (3_298_534_883_328, 773_094_113_280)
    assert flash_attn_window_train.step_flops(cfg, 2) == full + 3 * band \
        == 5_617_817_223_168
    assert flash_window_band_train.step_flops(cfg, 2) == 3 * band \
        == 2_319_282_339_840
    assert flash_attn_window_train.total(_run(cfg, 2, 3)) \
        == {"flops": 3.0 * 5_617_817_223_168}
    assert flash_window_band_train.total(_run(cfg, 2, 3)) \
        == {"flops": 3.0 * 2_319_282_339_840}
    whole = {**cfg, "layer_types": cfg["published"]["layer_types"]}
    assert flash_attn_window_train.step_flops(whole, 2) \
        == 7 * (full + 3 * band)
    # a window as wide as the sequence is a full layer's work
    wide = {**cfg, "sliding_window": 8192}
    assert flash_attn_window_train.step_flops(wide, 2) == 4 * full


@pytest.mark.parametrize("kind", ["window_forward", "window_backward",
                                  "full_forward", "full_backward"])
def test_the_driver_states_the_flash_grids_blocks_by_kind(kind):
    """A window layer and the full layer at the cell's sizes, forward and
    backward: the blocks are what the program's ``block_sizes`` answers
    for those shapes (no size is written here: a ``perf_opt`` PR may move
    a default), and the census over them is the grid they give, counted
    here from the band's own inequality ``0 <= row - column < window``:
    dead outside it, interior where every pair of the block is visible,
    edge between; the live blocks hold the visible pairs the work
    function counts and the interior ones no more than those."""
    import importlib
    # the package exports a function under the module's name
    fa = importlib.import_module(
        "apex_tpu.contrib.multihead_attn.flash_attention")
    cfg = _cfg()
    s = cfg["input"]["seq"]
    window = cfg["sliding_window"] if kind.startswith("window") else None
    sizes = fa.block_sizes(s, s, window=window, d=cfg["head_dim"])
    q, k = sizes[:2] if kind.endswith("forward") else sizes[2:]
    got = _driver(cfg).census()[kind]
    assert got["blocks"] == [q, k]
    assert got["dead"] + got["interior"] + got["edge"] == (s // q) * (s // k)
    i, j = np.arange(s // q)[:, None], np.arange(s // k)[None, :]
    least = i * q - (j + 1) * k + 1             # row - column over a block
    most = (i + 1) * q - 1 - j * k
    live = (most >= 0) & (least < (window or s))
    interior = (least >= 0) & (most < (window or s))
    assert got == {"blocks": [q, k], "dead": int((~live).sum()),
                   "interior": int(interior.sum()),
                   "edge": int((live & ~interior).sum())}
    assert got["dead"] > 0 and got["edge"] > 0
    pairs = flash_attn_window_train.visible_pairs(
        cfg, "sliding_attention" if window else "full_attention")
    assert got["interior"] * q * k <= pairs \
        <= (got["interior"] + got["edge"]) * q * k
