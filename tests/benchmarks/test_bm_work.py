"""FLOP and byte functions against hand-worked values."""

import json
import os
import types

import pytest

from benchmarks import spec as S, weights as W
from benchmarks.work import decode_attn_bytes


def _cfg(name):
    with open(os.path.join(S.HERE, "configs", name)) as f:
        return json.load(f)


def test_kv_token_bytes_of_the_served_model():
    # 2 (K, V) x 24 layers x 2048 wide x 2 B
    assert decode_attn_bytes.kv_token_bytes(_cfg("cerebras-gpt-1.3b.json")) \
        == 196_608


@pytest.mark.parametrize("name, layers, total", [
    ("cerebras-gpt-1.3b.json", 24, 1_315_723_264),
    ("cerebras-gpt-1.3b-train.json", 6, 409_274_368),
])
def test_parameter_counts(name, layers, total):
    cfg = _cfg(name)
    assert cfg["n_layer"] == layers
    assert W.count(W.gpt2_specs(cfg)) == total == cfg["parameters"]


def test_resnet50_parameter_count():
    cfg = _cfg("resnet50.json")
    assert W.count(W.resnet_specs(cfg)) == 25_557_032 == cfg["parameters"]


def _run(cfg, traffic, rec):
    return types.SimpleNamespace(
        ctx=types.SimpleNamespace(config=cfg, traffic=traffic), rec=rec)


def test_decode_bytes_count_live_tokens_in_the_span():
    cfg = _cfg("cerebras-gpt-1.3b.json")
    rec = {"span": (10.0, 20.0), "every_request": [
        # first token (the commit's) never counts; tokens 1, 2 in the span
        {"prompt_len": 100, "token_times": [9.0, 11.0, 12.0, 21.0]},
        {"prompt_len": 50, "token_times": [19.0, 19.5, 20.0]},
    ]}
    live = (100 + 1) + (100 + 2) + (50 + 1)
    assert decode_attn_bytes.total(_run(cfg, {}, rec))["bytes"] == \
        live * 196_608


# -- readers, on hand-made runs ---------------------------------------------

def _reader_run(**kw):
    base = dict(
        ctx=types.SimpleNamespace(
            config=_cfg("cerebras-gpt-1.3b-train.json"), traffic={},
            devices=[0], peaks={"bf16_flops_per_s": 197e12,
                                "hbm_bytes_per_s": 819e9}),
        rec={"window_s": 10e-3, "steps": 2, "stats": {}, "counters": {}},
        ops={0: [("%a.1 = f32[] fusion()", 0.0, 4e-3),
                 ('%k.1 = f32[] custom-call(), custom_call_target='
                  '"tpu_custom_call"', 4e-3, 2e-3),
                 ("%all-reduce.1 = f32[8] all-reduce(f32[8] %g)", 7e-3,
                  2e-3)]},
        async_ops={}, modules={0: [("jit_step(1)", 0.0, 10e-3)]}, e2e={})
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_reader_trace_idle():
    from benchmarks.readers import trace_idle
    assert trace_idle.read(_reader_run()) == pytest.approx(20.0)   # 8 of 10


def test_reader_kernel_roofline_is_bytes_or_flops_over_kernel_time():
    from benchmarks.readers import trace_kernel_roofline as r
    run = _reader_run()
    run.rec.update(span=(0.0, 1.0), every_request=[
        {"prompt_len": 999, "token_times": [0.1, 0.5]}])   # 1000 live tokens
    run.ctx.config = _cfg("cerebras-gpt-1.3b.json")
    want = 100 * (1000 * 196_608 / 819e9) / 2e-3
    assert r.read(run, pattern="tpu_custom_call", work="decode_attn_bytes",
                  module=r"^jit_step\(") == pytest.approx(want)
    assert r.read(run, pattern="no_such", work="decode_attn_bytes") is None
    assert r.read(run, pattern="tpu_custom_call", work="decode_attn_bytes",
                  module="no_such_program") is None


def test_reader_trace_collective():
    from benchmarks.readers import trace_collective as r
    args = dict(pattern="all-reduce|all-gather")
    run = _reader_run()
    assert r.read(run, what="ms_per_step", **args) == pytest.approx(1.0)
    assert r.read(run, what="exposed_pct", **args) == pytest.approx(100.0)
    run.ops[0].append(("%b.2 = f32[] fusion()", 7e-3, 1e-3))
    assert r.read(run, what="exposed_pct", **args) == pytest.approx(50.0)
    assert r.read(_reader_run(ops={0: [("%a.1 = f32[] fusion()", 0, 1e-3)]}),
                  what="ms_per_step", **args) is None


def test_readers_of_counts_and_requests():
    from benchmarks.readers import (counter, request_percentile,
                                    request_share, stats_percentile)
    rows = [{"ok": True, "arrival_s": 1.0, "first_token_s": 1.0 + 0.1 * i,
             "finish_s": 2.0 + 0.1 * i, "tokens": [0] * 11,
             "prompt_len": 100, "prefix_tokens": 64} for i in range(1, 11)]
    rows.append({"ok": False, "arrival_s": 1.0, "first_token_s": None,
                 "finish_s": None, "tokens": [], "prompt_len": 100,
                 "prefix_tokens": 0})
    run = _reader_run()
    run.rec.update(requests=rows, stats={"step_ms": [3.0, 1.0, 2.0]},
                   counters={"compiles_in_window": 0})
    assert request_percentile.read(run, what="ttft", q=95) == \
        pytest.approx(1000.0)
    assert request_percentile.read(run, what="tpot", q=50) == \
        pytest.approx(100.0)
    assert request_share.read(run, part="prefix_tokens",
                              whole="prompt_len") == \
        pytest.approx(100 * 640 / 1100)     # the failed request's prompt counts
    assert stats_percentile.read(run, key="step_ms", q=50) == 2.0
    assert stats_percentile.read(run, key="absent", q=50) is None
    assert counter.read(run, key="compiles_in_window") == 0
    assert counter.read(run, key="absent") is None


@pytest.mark.parametrize("metric, sound", [("tpot_p95_ms", 100.0),
                                           ("ttft_p95_ms", 100.0)])
def test_failed_requests_stand_in_the_tail(metric, sound):
    from benchmarks.drivers.serve_engine import Driver
    rows = [{"ok": True, "arrival_s": 0.0, "first_token_s": 0.1,
             "finish_s": 1.1, "tokens": [0] * 11} for _ in range(9)]
    rows.append({"ok": False, "arrival_s": 0.0, "first_token_s": None,
                 "finish_s": None, "tokens": []})
    rec = {"requests": rows, "failed": 1, "stats": {"duration_s": 60.0}}
    assert Driver.end_to_end(None, rec)[metric] == 60_000.0
    rec = {"requests": rows[:9], "failed": 0, "stats": {"duration_s": 60.0}}
    assert Driver.end_to_end(None, rec)[metric] == pytest.approx(sound)
