"""The scope reader on a hand-written trace (text proto beside this file:
a ``while`` in a ``while``, a named kernel, fusions under ``jvp(mlp)`` and
``transpose(jvp(mlp))``, an ``optimizer`` fusion and the layout copy in
front of it, an async copy pair that an ``amp_cast`` fusion reads, a copy
that nothing reads and a fusion whose path holds no scope), and the two
work functions against numbers worked by hand."""

import json
import os
import types

import pytest

from benchmarks import spec as S, xplane
from benchmarks.readers import trace_scope as T

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "cgpt_train_s2048"
with open(os.path.join(S.HERE, "layer_metrics",
                       "backward_ms_per_step.lm.json")) as _f:
    MODEL = json.load(_f)["args"]["scope"]      # the model scopes, as committed


@pytest.fixture
def run(tmp_path, monkeypatch):
    """A run whose trace is where ``run.py`` has the profiler write it."""
    from jax.profiler import ProfileData
    with open(os.path.join(HERE, "data", "bm_scope_xplane.txt")) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    d = tmp_path / ".bench_out" / CELL / "trace" / "plugins" / "profile" / "r"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(raw)
    monkeypatch.setattr(T, "ROOT", str(tmp_path))
    ctx = types.SimpleNamespace(cell={"name": CELL})
    run = types.SimpleNamespace(ctx=ctx, rec={"steps": 2})
    run.ops = xplane.load(T.trace_file(run))
    return run


def test_op_names_come_from_the_event_metadata_of_device_planes(run):
    names = T.op_names(T.trace_file(run))
    assert list(names) == [0]                   # the host plane is no device
    assert len(names[0]) == 9                   # four copies have no op_name
    assert names[0]["%fusion.6 = bf16[8192,8192] fusion(bf16[8192,2048] %h)"
                    ", kind=kOutput"] == "jit(step)/jvp(mlp)/dot_general"
    # a string kept by reference (XStat.ref_value) reads the same
    assert names[0]["%fusion.8 = f32[1024,128] fusion(f32[1024,128] %copy.9, "
                    "f32[] %lr), kind=kLoop"] == "jit(step)/optimizer/mul"


# own times, ms: while.1 10 - 4, while.2 4 - 2, fusion.3 2 (head_loss fwd
# 10); kernel 4 (attention bwd); mlp 2 fwd, 5 bwd; optimizer 3 + the copy
# it reads 2; amp_cast bwd 1 + the async copy pair it reads 0.5 + 0.5; no
# scope 1 (a path of its own without one) + 1 (a copy nothing reads); 30
# in all, over 2 steps
@pytest.mark.parametrize("args, want", [
    (dict(scope="^head_loss$"), 5.0),
    (dict(scope="^mlp$"), 3.5),
    (dict(scope="^mlp$", direction="fwd"), 1.0),
    (dict(scope="^mlp$", direction="bwd"), 2.5),
    (dict(scope="^optimizer$"), 2.5),
    (dict(scope="^amp_", direction="bwd"), 1.0),
    (dict(scope="^amp_", direction="fwd"), None),
    (dict(scope=MODEL, direction="bwd"), 4.5),
    (dict(scope=MODEL, direction="fwd"), 6.0),
    (dict(scope="^collective$"), None),
    (dict(scope="^attention$", direction="fwd"), None),
])
def test_ms_per_step_by_scope_and_direction(run, args, want):
    got = T.read(run, what="ms_per_step", **args)
    assert got == (want if want is None else pytest.approx(want))


def test_unscoped_pct_is_the_share_without_a_vocabulary_scope(run):
    assert T.read(run, what="unscoped_pct") == pytest.approx(100 * 2 / 30)


def test_an_event_without_op_name_takes_its_first_consumers_path(run):
    dev = run.ops[0]
    own = T.op_names(T.trace_file(run))[0]
    full = T.with_consumers(dev, own)
    by_instruction = {n.partition(" = ")[0]: p for n, p in full.items()}
    assert by_instruction["%copy.9"] == "jit(step)/optimizer/mul"
    # through a consumer that has none itself, direction included
    assert by_instruction["%copy-start.11"] == by_instruction[
        "%copy-done.11"] == "jit(step)/transpose(jvp(amp_cast))/" \
                            "convert_element_type"
    assert by_instruction["%copy.13"] == ""      # nothing reads it
    # a path of its own is kept, vocabulary scope or none
    assert by_instruction["%fusion.10"] == "jit(step)/jvp()/reduce_sum"
    assert {n: p for n, p in full.items() if n in own} == own


def test_a_trace_without_scopes_gives_nothing_and_does_not_raise(run):
    # the parent's LM step: op_names, but none of the vocabulary
    run.ops = {0: [(n, s, d) for n, s, d in run.ops[0]
                   if n.startswith(("%copy", "%fusion.10"))]}
    assert T.read(run, what="unscoped_pct") is None
    assert T.read(run, what="ms_per_step", scope="^optimizer$") is None


def test_reader_refuses_what_it_does_not_know(run):
    with pytest.raises(ValueError):
        T.read(run, what="ms_per_token")
    with pytest.raises(ValueError):
        T.read(run, what="ms_per_step", scope="mlp", direction="sideways")


def test_scope_of_steps_over_wrappers():
    assert T.scope_of("jit(step)/shard_map/checkpoint/jvp(attention)/"
                      "apex_flash_fwd/pallas_call") == "attention"
    assert T.scope_of("jit(step)/transpose(jvp(stage3_block2))/conv") == \
        "stage3_block2"
    assert T.scope_of("jit(step)/optimizer/while/body/add") == "optimizer"
    assert T.scope_of("jit(mlp)/add") is None   # a jit's name is no scope
    assert T.scope_of("jit(step)/mlp_like/add") is None
    assert T.scope_of("jit(step)/jvp()/reduce_sum") is None
    assert T.scope_of("") is None


def test_the_benchmarks_vocabulary_is_the_programs():
    from apex_tpu import prof
    assert T.VOCABULARY == "|".join(prof.SCOPES)


# -- work functions ----------------------------------------------------------

def _work_run(steps):
    with open(os.path.join(S.HERE, "configs",
                           "cerebras-gpt-1.3b-train.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(S.HERE, "traffic", "train-fixed-8k.json")) as f:
        traffic = json.load(f)
    return types.SimpleNamespace(
        ctx=types.SimpleNamespace(config=cfg, traffic=traffic),
        rec={"steps": steps})


def test_flash_flops_of_the_committed_configuration():
    from benchmarks.work import flash_attn_train as W
    # one causal matmul: 2 x 4 rows x 16 heads x 2048^2 x 128 / 2 = 2^35;
    # 2 forward + 4 backward, 6 layers: 36 x 2^35 = 1.237e12 a step
    assert W.total(_work_run(1))["flops"] == 36 * 2 ** 35 == 1_236_950_581_248
    assert W.total(_work_run(23))["flops"] == 23 * 36 * 2 ** 35
    # PR 23's hand arithmetic counted the backward's recomputed QK^T too
    assert 7 / 6 * W.total(_work_run(1))["flops"] == pytest.approx(1.443e12,
                                                                  rel=1e-3)


def test_adam_bytes_of_the_committed_configuration():
    from benchmarks.work import adam_bytes as W
    # 28 B x 409,274,368 parameters
    assert W.total(_work_run(1))["bytes"] == 11_459_682_304
    assert W.total(_work_run(23))["bytes"] == 23 * 11_459_682_304


# -- the committed benchmark with the new entries ---------------------------

NEW = {
    "cgpt_train_s2048": {
        "flash_attn_roofline", "adam_kernel_roofline",
        "optimizer_ms_per_step.lm", "amp_ms_per_step.lm",
        "backward_ms_per_step.lm", "head_loss_ms_per_step",
        "unscoped_pct.lm"},
    "rn50_train_b384": {
        "optimizer_ms_per_step.rn50", "amp_ms_per_step.rn50",
        "backward_ms_per_step.rn50", "unscoped_pct.rn50"},
}


@pytest.mark.parametrize("cell", sorted(NEW))
def test_committed_benchmark_validates_and_names_each_new_metric(cell):
    spec = S.Spec()                     # validates BENCHMARK.json
    metrics = {m["name"]: m for m in spec.per_layer(spec.cell(cell))}
    assert NEW[cell] <= set(metrics)
    for name in NEW[cell]:
        m = metrics[name]
        assert m["source"] == "device_trace" and m["workloads"] == [cell]
        reader = S.plugin("readers", m["reader"])
        assert callable(reader.read)
        if "work" in m.get("args", {}):
            assert callable(S.plugin("work", m["args"]["work"]).total)


@pytest.mark.parametrize("metric, pattern_matches", [
    ("flash_attn_roofline", ["%apex_flash_fwd.3", "%apex_flash_bwd_dkv.1",
                             "%transpose_jvp_apex_flash_bwd_dq__.1"]),
    ("adam_kernel_roofline", ["%apex_mt_adam.1"]),
])
def test_kernel_patterns_match_the_names_the_chip_compiler_gives(
        metric, pattern_matches):
    import re
    with open(os.path.join(S.HERE, "layer_metrics", metric + ".json")) as f:
        rx = re.compile(json.load(f)["args"]["pattern"])
    for name in pattern_matches:
        assert rx.search(f"{name} = bf16[8] custom-call(bf16[8] %x)")
    assert not rx.search("%fusion.1 = bf16[8] fusion(bf16[8] %apex_mt_adam.1)")
    assert not rx.search("%apex_mt_lamb_stage1.1 = f32[8] custom-call()")
