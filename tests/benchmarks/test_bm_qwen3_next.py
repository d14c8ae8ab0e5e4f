"""What only the Qwen3-Next configuration has: the program against its
plain reference on seeded weights at a small size (logits, loss, per-leaf
gradient norms), the work functions by hand, the parameters re-counted
from the specs, the cut as the configuration file states it, and a
reference that imports nothing of the program."""

import ast
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import spec as S, weights as W, weights_qwen3_next as WQ
from benchmarks.reference import qwen3_next as R
from benchmarks.work import flash_attn_gqa_train, gdn_delta_rule

NAME = "qwen3-next-80b-a3b-train"


def _cfg():
    with open(os.path.join(S.HERE, "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def small():
    """The rehearsal sizes, the program's model and seeded weights with
    the norms, ``A_log`` and ``dt_bias`` moved off their initial values
    (at 0 and 1 a wrong use of them would not show)."""
    from benchmarks.drivers import train_hybrid_lm
    cfg = _cfg()
    cfg = {**cfg, **cfg["rehearsal"], "expert_chip": 1}
    ctx = types.SimpleNamespace(
        config=cfg, traffic={"steps_checked": 1, "kind": "train_fixed_batch",
                             "per_chip": 2, "distinct": 1},
        seed=3, devices=jax.devices()[:1], plugin=S.Spec().plugin)
    lm, _ = train_hybrid_lm.Driver(ctx).model()
    params = W.build(WQ.specs(cfg), W.seed_key(3), jnp.float32)
    params = jax.tree.map(
        lambda x: x + 0.1 * jax.random.normal(jax.random.key(1), x.shape)
        if x.ndim == 1 else 3.0 * x, params)
    toks = jax.random.randint(jax.random.key(5), (2, 49), 0,
                              cfg["vocab_size"])
    return cfg, lm, params, toks


def test_the_programs_logits_are_the_references(small):
    cfg, lm, params, toks = small
    got = lm.apply(params, toks[:, :-1])
    want = jnp.stack([R.logits(params, t[:-1], cfg) for t in toks])
    assert float(jnp.abs(want).max()) > 0.5
    np.testing.assert_allclose(got, want, atol=5e-5)


def test_the_programs_loss_and_gradients_are_the_references(small):
    cfg, lm, params, toks = small
    (loss, counters), grad = jax.value_and_grad(
        lm.loss_with_counters, has_aux=True)(params, toks)
    want, want_grad = R.batch_loss_and_grad(params, toks, cfg)
    assert float(loss) == pytest.approx(float(want), abs=2e-5)
    assert int(counters["moe_overflow_pairs"]) == 0
    norms = jax.tree.map(lambda a, b: (float(jnp.linalg.norm(a)),
                                       float(jnp.linalg.norm(b)),
                                       float(jnp.linalg.norm(a - b))),
                         grad, want_grad)
    for path, (mine, theirs, apart) in jax.tree_util.tree_leaves_with_path(
            norms, is_leaf=lambda x: isinstance(x, tuple)):
        assert theirs > 0, path
        assert mine == pytest.approx(theirs, rel=1e-4), path
        assert apart <= 1e-4 * theirs, path


def test_the_reference_holds_the_share_the_configuration_states(small):
    cfg, _, _, _ = small
    assert R.held(cfg) == (cfg["num_experts"], 2 * cfg["num_experts"])
    assert R.held(_cfg()) == (0, 16)
    assert R.layer_kinds(_cfg()) == ["linear", "linear", "linear", "full"]


def test_the_reference_imports_nothing_of_the_program():
    for name in ("reference/qwen3_next.py", "weights_qwen3_next.py"):
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
    specs = WQ.specs(cfg)
    layer = {k: W.count(v) for k, v in specs["layer_0"].items()}
    assert layer["linear"] == 33_718_464
    assert W.count(specs["layer_3"]["attn"]) == 27_263_488
    assert W.count(specs["layer_0"]["moe"]["router"]) == 1_048_576
    assert W.count(specs["layer_0"]["moe"]["shared"]) == 3_147_776
    assert W.count(specs["layer_0"]["moe"]["w_gate"]) * 3 \
        == 16 * 3_145_728
    assert layer["norm1"] + layer["norm2"] == 4_096
    assert W.count(specs["layer_0"]) == 88_250_560
    assert W.count(specs["layer_3"]) == 81_795_584
    assert W.count(specs["embed"]) + W.count(specs["head"]) \
        + W.count(specs["norm_f"]) == 77_793_280
    assert W.count(specs) == 424_340_544 == cfg["parameters"]


def test_the_file_states_the_cut_and_the_programs_bounds():
    cfg = _cfg()
    assert cfg["num_experts"] * cfg["expert_chips"] \
        == cfg["published"]["num_experts"] == 512
    assert cfg["vocab_size"] * cfg["vocab_chips"] \
        == cfg["published"]["vocab_size"]
    assert cfg["num_hidden_layers"] == cfg["full_attention_interval"]
    assert cfg["input"]["seq"] * 2 * cfg["num_experts_per_tok"] \
        // cfg["published"]["num_experts"] == 320     # tokens an expert
    prog = cfg["program"]
    assert prog["remat"].startswith("block")
    assert prog["dispatch_bound"] % 128 == 0
    assert cfg["vocab_size"] % prog["head_chunk"] == 0
    for key in ("deployment", "assumed", "published", "reduced"):
        assert cfg[key]
    # the cut's one departure in the mathematics is said, and the cell
    # trains at the rate of the dense cells
    assert "stop_gradient" in cfg["assumed"]["router_gradient"]
    spec = S.Spec()
    assert spec.traffic(spec.cell("qnext_train_s8192"))["lr"] == 1e-4


def test_the_median_leafs_gap_by_hand():
    """``grad_norm_mid_gap``: the median over the leaves of the gap
    between two norms against the reference's norm of that leaf or of the
    median leaf; the worst leaf does not move it."""
    from benchmarks.drivers import train_hybrid_lm
    ref = {"grad_norms": {"a": 1.0, "b": {"c": 2.0, "d": 0.001}, "e": 4.0,
                          "f": 3.0}}
    got = {"grad_norms": {"a": 1.01, "b": {"c": 2.2, "d": 0.003}, "e": 4.0,
                          "f": 6.0}}
    # gaps: a .01/2, c .2/2, d .002/2 (floor: the median norm, 2), e 0, f 1
    assert train_hybrid_lm.mid_gap(got, ref) == pytest.approx(0.005)
    assert train_hybrid_lm.mid_gap(ref, ref) == 0.0


def _run(cfg, per_chip, steps):
    return types.SimpleNamespace(
        ctx=types.SimpleNamespace(config=cfg, traffic={"per_chip": per_chip}),
        rec={"steps": steps})


def test_delta_rule_work_by_hand():
    cfg = _cfg()
    # a token of a value head: 3 products of 2 x 128 x 128 forward, twice
    # that backward
    assert gdn_delta_rule.token_head_flops(cfg) == 9 * 2 * 128 * 128 \
        == 294_912
    # q, k, v in and o out in bf16, g and beta in float32, three times over
    assert gdn_delta_rule.token_head_bytes(cfg) \
        == 3 * (4 * 128 * 2 + 2 * 4) == 3_096
    assert gdn_delta_rule.linear_layers(cfg) == 3
    tokens_heads = 2 * 8192 * 32 * 3
    got = gdn_delta_rule.total(_run(cfg, 2, 7))
    assert got == {"flops": 7.0 * tokens_heads * 294_912,
                   "bytes": 7.0 * tokens_heads * 3_096}


def test_grouped_query_flash_work_by_hand():
    cfg = _cfg()
    # one full layer, 2 rows, 16 heads: a causal matmul is 8192^2 x 256
    matmul = 2 * 16 * 8192 * 8192 * 256
    assert flash_attn_gqa_train.full_layers(cfg) == 1
    assert flash_attn_gqa_train.step_flops(cfg, 2) == 6 * matmul \
        == 3_298_534_883_328
    assert flash_attn_gqa_train.total(_run(cfg, 2, 3)) \
        == {"flops": 3.0 * 6 * matmul}


def test_the_scope_roofline_reader_finds_nothing_without_its_scope(
        monkeypatch):
    """On a program without the scope (the parent commit) the reader
    returns nothing and does not raise."""
    from benchmarks.readers import trace_scope, trace_scope_roofline
    monkeypatch.setattr(trace_scope, "read", lambda run, what, scope: None)
    assert trace_scope_roofline.read(object(), "^delta_rule$",
                                     "gdn_delta_rule") is None
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    cfg = _cfg()
    run = _run(cfg, 2, 10)
    run.ctx.peaks, run.ctx.plugin = peaks, S.Spec().plugin
    monkeypatch.setattr(trace_scope, "read", lambda run, what, scope: 100.0)
    bytes_s = 10 * 2 * 8192 * 32 * 3 * 3_096 / 819e9
    assert trace_scope_roofline.read(run, "^delta_rule$", "gdn_delta_rule") \
        == pytest.approx(100.0 * bytes_s / (0.1 * 10))
