"""What only the SDAR configuration has: the generator's arrays as a
function of the seed alone (and ``masked``'s mean following ``p``), the
driver's model and the triple it feeds, the reference's mask against a
mask written out row by row, the work function against the dense mask's
sum, the parameters re-counted from the specs, the cut as the
configuration file states it, the new entries found by name under
``bm_tree``'s invariants, and a reference that imports nothing of the
program. The cell's rehearsal, its control and the step that returns its
state unchanged are ``test_bm_rehearse.py``'s and ``test_bm_correct.py``'s
cases (the cells and drivers there come from the spec); the program
against this reference, leaf by leaf, is
``tests/test_hybrid_lm_diffusion.py``."""

import ast
import json
import os
import types

import jax
import numpy as np

import bm_tree
from benchmarks import spec as S, weights as W, weights_sdar
from benchmarks.drivers import train_sdar
from benchmarks.generators import train_block_diffusion as G
from benchmarks.reference import sdar as R
from benchmarks.work import flash_attn_block_diffusion_train as work

NAME = "sdar-30b-a3b-train"
CELL = "sdar_train_s8192"


def _cfg():
    with open(os.path.join(S.HERE, "configs", NAME + ".json")) as f:
        return json.load(f)


def _traffic():
    spec = S.Spec()
    return spec.traffic(spec.cell(CELL))


def test_the_generators_arrays_are_a_function_of_the_seed_alone():
    """Tokens, masked positions and probabilities from ``--seed``: the
    same seed the same arrays, data ids below the mask token's, the
    batches' noise levels one a stratum of [0, 1), ``p = 0.999 t + 0.001``,
    and ``masked``'s mean follows ``p`` batch by batch."""
    cfg, traffic = _cfg(), _traffic()
    seed = 2 ** 31 + 50             # the driver's seeds pass 32 signed bits
    a, b = (G.generate(traffic, cfg, seed, 1) for _ in range(2))
    other = G.generate(traffic, cfg, seed + 1, 1)
    for key in ("x", "masked", "p"):
        assert (a[key] == b[key]).all() and (a[key] != other[key]).any()
    assert a["x"].shape == a["masked"].shape == (32, 1, 8192)
    assert (a["x"].dtype, a["masked"].dtype, a["p"].dtype) \
        == (np.int32, np.bool_, np.float32) and a["p"].shape == (32, 1)
    assert a["units_per_step"] == 8192          # the data's tokens, once
    assert 0 <= a["x"].min() and a["x"].max() == cfg["vocab_size"] - 2
    t = (a["p"].astype(np.float64).ravel() - 0.001) / 0.999
    assert sorted(np.floor(t * 32 + 1e-4).astype(int)) == list(range(32))
    assert np.ptp((t * 32) % 1.0) < 1e-3        # one u for all of them
    share = a["masked"].mean(-1).ravel()
    assert np.abs(share - a["p"].ravel()).max() < 4 * 0.5 / 8192 ** 0.5
    # the mean a step of the positions that carry loss: about half
    assert 3000 < a["masked"].sum(-1).mean() < 5200
    small = G.generate({**traffic, "per_chip": 2, "distinct": 6},
                       {**cfg, **cfg["rehearsal"]}, 3, 1)
    assert small["p"].shape == (6, 2) and small["x"].max() <= 254


def test_the_driver_builds_the_model_and_feeds_triples():
    cfg = _cfg()
    small = {**cfg, **cfg["rehearsal"]}
    ctx = types.SimpleNamespace(
        config=small, traffic={**_traffic(), "per_chip": 2, "distinct": 3},
        seed=3, devices=jax.devices()[:1], plugin=S.Spec().plugin)
    driver = train_sdar.Driver(ctx)
    lm, mine = driver.model()
    assert lm.block_diffusion == small["block_length"] == 4
    assert set(lm.layer_types) == {"full"} and not lm.attn_gate
    assert (lm.num_experts, lm.experts_held, lm.top_k, lm.shared_ffn) \
        == (16, (0, 4), 3, 0)
    assert lm.router == "softmax" and lm.remat and not lm.zero_centred_norm
    assert len(driver.feed["x"]) == 3
    tokens, masked, p = driver.feed["x"][0]
    assert tokens.shape == masked.shape == (2, 96) and p.shape == (2,)
    # the cell's own grids: 288 of 1,024 forward tiles live
    driver.ctx.config = cfg
    census = driver.census()
    assert census["forward"]["interior"] + census["forward"]["edge"] == 288
    assert sum(v for k, v in census["forward"].items()
               if k != "blocks") == 1024
    back = census["backward"]
    assert (back["interior"] + back["edge"]) * 1024 \
        == 288 * (back["dead"] + back["interior"] + back["edge"])


def test_the_references_mask_row_by_row_and_the_work_functions_count():
    """The four cases written out pair by pair at a small size; the work
    file's count is that mask's sum, ``L^2 + B L``, whatever the grid."""
    block, length = 4, 24
    want = np.zeros((2 * length, 2 * length), bool)
    for a in range(2 * length):
        for b in range(2 * length):
            if a < length and b < length:
                want[a, b] = a // block == b // block
            elif a < length:
                want[a, b] = (b - length) // block < a // block
            elif b >= length:
                want[a, b] = (b - length) // block <= (a - length) // block
    rows = np.arange(2 * length)
    got = np.asarray(R.visible(rows[:, None], rows[None, :], block, length))
    assert (got == want).all() and want.any(-1).all()
    small = {"input": {"seq": length}, "block_length": block,
             "num_attention_heads": 8, "head_dim": 16,
             "num_hidden_layers": 2}
    assert work.visible_pairs(small) == want.sum() \
        == R.visible_pairs(block, length)
    assert work.step_flops(small, 3) \
        == (2 + 4) * 2 * int(want.sum()) * 16 * 8 * 3 * 2
    cfg = _cfg()
    assert work.visible_pairs(cfg) == 67_141_632
    assert work.step_flops(cfg, 1) == 12 * 67_141_632 * 128 * 32 * 5 \
        == 16_500_727_480_320
    run = types.SimpleNamespace(
        ctx=types.SimpleNamespace(config=cfg, traffic={"per_chip": 1}),
        rec={"steps": 3})
    assert work.total(run) == {"flops": 3.0 * 16_500_727_480_320}


def test_the_reference_imports_nothing_of_the_program():
    for name in ("reference/sdar.py", "weights_sdar.py",
                 "generators/train_block_diffusion.py",
                 "work/flash_attn_block_diffusion_train.py"):
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
    specs = weights_sdar.specs(cfg)
    layer = specs["layer_0"]
    assert {k: W.count(v) for k, v in layer["attn"].items()} == {
        "w_q": 8_388_608, "w_k": 1_048_576, "w_v": 1_048_576, "q_norm": 128,
        "k_norm": 128, "w_o": 8_388_608}
    assert W.count(layer["norm1"]) + W.count(layer["norm2"]) == 4_096
    assert W.count(layer["moe"]["router"]) == 262_144
    assert W.count(layer["moe"]["w_gate"]) * 3 == 16 * 4_718_592
    assert all(W.count(specs[f"layer_{i}"]) == 94_638_336 for i in range(5))
    assert W.count(specs["embed"]) + W.count(specs["head"]) \
        == 2 * 18_992 * 2048 == 77_791_232
    assert W.count(specs["norm_f"]) == 2_048
    assert W.count(specs) == 550_984_960 == cfg["parameters"]
    assert specs["embed"][1] == ("normal", 1.0)
    assert specs["head"][1] == ("normal", 0.02)


def test_the_file_states_the_cut_and_the_programs_bounds():
    cfg = _cfg()
    pub = cfg["published"]
    assert cfg["num_experts"] * cfg["expert_chips"] == pub["num_experts"] \
        == R.width(cfg) == 128
    assert R.held(cfg) == (0, 16)
    assert cfg["vocab_size"] * cfg["vocab_chips"] == pub["vocab_size"]
    assert cfg["vocab_size"] * 8 >= pub["vocab_size"]           # the floors
    assert cfg["num_experts"] >= 8 and cfg["num_hidden_layers"] >= 4
    assert cfg["reduced"] == ["num_experts", "num_hidden_layers",
                              "vocab_size"]
    assert {k for k, v in pub.items() if cfg[k] != v} == set(cfg["reduced"])
    assert cfg["source"].startswith("https://huggingface.co/JetLM/SDAR-30B")
    # no width differs from the source's
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "moe_intermediate_size", "intermediate_size",
                "num_experts_per_tok", "rms_norm_eps", "rope_theta",
                "norm_topk_prob"):
        assert cfg[key] == pub[key], key
    assert R.layer_kinds(cfg) == ["full"] * 5
    # rows an expert sees a step, 1/8 of the deployment's
    assert 2 * cfg["input"]["seq"] * cfg["num_experts_per_tok"] \
        // pub["num_experts"] == 1024
    prog = cfg["program"]
    assert prog["remat"].startswith("block")
    assert prog["dispatch_bound"] % 128 == 0 \
        and prog["dispatch_bound"] >= 4 * 16_384
    assert "my chip runs, PR 50" in prog["dispatch_bound_why"]
    assert cfg["vocab_size"] % prog["head_chunk"] == 0
    for key in ("deployment", "assumed", "published", "reduced"):
        assert cfg[key]
    for key in ("block_length", "mask", "positions", "noise", "no_shift",
                "mask_token", "loss", "qk_norm", "router",
                "router_aux_loss_coef", "router_gradient",
                "initializer_range", "embedding_initializer_range", "norms",
                "optimizer", "float32_leaves", "unused_keys", "serving"):
        assert cfg["assumed"][key], key
    assert "stop_gradient" in cfg["assumed"]["router_gradient"]
    assert cfg["block_length"] == 4 and cfg["input"]["seq"] % 4 == 0
    small = {**cfg, **cfg["rehearsal"]}
    assert small["block_length"] == 4 and small["input"]["seq"] == 96
    spec = S.Spec()
    cell = spec.cell(CELL)
    assert cell["traffic"] == "train-bdiff-8k-s8192" and cell["chips"] == 1
    traffic = spec.traffic(cell)
    assert {k: traffic[k] for k in (
        "kind", "per_chip", "distinct", "steps_checked", "lr", "noise_eps",
        "trace_seconds")} == {
        "kind": "train_block_diffusion", "per_chip": 1, "distinct": 32,
        "steps_checked": 3, "lr": 3e-5, "noise_eps": 0.001,
        "trace_seconds": 5}


def test_the_cells_entries_and_their_readers():
    """Found by name, wherever a later PR's entries stand behind them."""
    spec = S.Spec()
    cell = spec.cell(CELL)
    reported = {m["name"]: m for m in spec.per_layer(cell)}
    shared = {"device_idle_pct.lm", "unscoped_pct.lm",
              "optimizer_ms_per_step.lm", "amp_ms_per_step.lm",
              "adam_kernel_roofline", "head_loss_ms_per_step",
              "moe_route_ms_per_step", "moe_experts_ms_per_step",
              "moe_overflow_pairs", "moe_held_pairs_max",
              "expert_load_max_over_mean"}
    own = {"diffusion_attention_ms_per_step": "trace_scope",
           "flash_attn_roofline.sdar": "trace_kernel_roofline",
           "backward_ms_per_step.sdar": "trace_scope",
           "diffusion_masked_tokens": "counter"}
    assert set(reported) == shared | set(own) | set(
        bm_tree.region_metrics(spec))
    assert len(reported) == 19
    names = [m["name"] for m in spec.bm["per_layer"]]
    at = names.index("diffusion_attention_ms_per_step")
    assert names[at:at + 4] == list(own)        # together, in this order
    for name, reader in own.items():
        entry, = [m for m in spec.bm["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_tok_s"
        assert reported[name]["reader"] == reader
        assert callable(spec.plugin("readers", reader).read)
    roof = reported["flash_attn_roofline.sdar"]["args"]
    assert roof["work"] == "flash_attn_block_diffusion_train"
    assert roof["pattern"] == r"^%(\w+_)?apex_flash_bd_"
    assert callable(spec.plugin("work", roof["work"]).total)
    assert "diffusion_attention" in reported[
        "backward_ms_per_step.sdar"]["args"]["scope"]
    assert [m["name"] for m in spec.end_to_end(cell)] \
        == ["train_tok_s", "setup_s"]
    from apex_tpu import prof
    with open(os.path.join(S.HERE, "scopes", "diffusion_lm.json")) as f:
        scopes = [s["pattern"] for s in json.load(f)["scopes"]]
    assert scopes == ["diffusion_attention"] \
        and set(scopes) <= set(prof.SCOPES)
    limits = spec.limits(cell)
    for name in ("loss_gap", "grad_norm_gap", "grad_norm_mid_gap",
                 "update_norm_gap", "forward_stat_gap", "forward_stat_mid_gap"):
        assert 0 < limits[name] < 1 and 0 < limits["rehearsal"][name] < 1
    assert "my chip runs, PR 50" in limits["set_from"]
    bm_tree.everything_holds(spec)
