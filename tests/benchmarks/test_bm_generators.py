"""The generators: the same seed gives the same inputs, every seed the
same amount of work, and no request passes the engine's ``max_len``."""

import collections
import json
import os

import numpy as np
import pytest

from benchmarks import spec as S
from benchmarks.generators import open_loop, train_fixed_batch

BIG = 2 ** 31 + 12345          # the driver's seeds pass 32 signed bits


def _json(*parts):
    with open(os.path.join(S.HERE, *parts)) as f:
        return json.load(f)


CHAT = _json("traffic", "chat-sysprompt-r0.6.json")
GPT = _json("configs", "cerebras-gpt-1.3b.json")


def test_open_loop_is_deterministic_in_the_seed():
    a = open_loop.generate(CHAT, GPT, BIG, 20.0)
    b = open_loop.generate(CHAT, GPT, BIG, 20.0)
    c = open_loop.generate(CHAT, GPT, BIG + 1, 20.0)
    assert len(a["requests"]) == len(b["requests"]) == len(c["requests"])
    for x, y in zip(a["requests"], b["requests"]):
        assert x["arrival_s"] == y["arrival_s"] and x["max_new"] == y["max_new"]
        assert np.array_equal(x["prompt"], y["prompt"])
    assert any(not np.array_equal(x["prompt"][:8], y["prompt"][:8])
               for x, y in zip(a["requests"], c["requests"]))


def test_every_seed_gets_the_same_arrivals_and_the_same_sizes():
    runs = [open_loop.generate(CHAT, GPT, s, 30.0) for s in (1, 2, BIG)]
    ramp = runs[0]["ramp_s"]

    def sizes(run, part):
        return collections.Counter(
            (len(r["prompt"]), r["max_new"]) for r in run["requests"]
            if (r["arrival_s"] < ramp) == part)
    for run in runs[1:]:
        assert [r["arrival_s"] for r in run["requests"]] == \
            [r["arrival_s"] for r in runs[0]["requests"]]
        for part in (True, False):
            assert sizes(run, part) == sizes(runs[0], part)
    orders = {tuple(r["max_new"] for r in run["requests"]) for run in runs}
    assert len(orders) == len(runs)         # ... in another order


def test_length_laws_respect_max_len_and_their_clips():
    run = open_loop.generate(CHAT, GPT, 7, 200.0)
    sys_len, eng = CHAT["system_prompt"], CHAT["engine"]
    first = run["requests"][0]["prompt"][:sys_len]
    for r in run["requests"]:
        own = len(r["prompt"]) - sys_len
        assert CHAT["prompt"]["min"] <= own <= CHAT["prompt"]["max"]
        assert CHAT["output"]["min"] <= r["max_new"] <= CHAT["output"]["max"]
        assert len(r["prompt"]) + r["max_new"] <= eng["max_len"]
        assert np.array_equal(r["prompt"][:sys_len], first)     # shared
        assert r["prompt"].dtype == np.int32
        assert 0 <= r["prompt"].min() and r["prompt"].max() < GPT["vocab_size"]
    due = [r["arrival_s"] for r in run["requests"]]
    assert due == sorted(due) and due[-1] < run["end_s"]
    rate = len(due) / run["end_s"]
    assert abs(rate - CHAT["rate"]) < 0.15 * CHAT["rate"]


def test_laws_that_pass_max_len_are_refused():
    bad = {**CHAT, "prompt": {**CHAT["prompt"], "max": 4096}}
    with pytest.raises(ValueError):
        open_loop.generate(bad, GPT, 1, 500.0)


def test_poisson_arrivals():
    mix = {**CHAT, "rate": 50.0, "ramp_s": 0.0}
    due = np.array([r["arrival_s"] for r in
                    open_loop.generate(mix, GPT, 1, 400.0)["requests"]])
    gaps = np.diff(due)
    assert abs(len(due) / 400.0 - 50.0) < 5.0
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.25


@pytest.mark.parametrize("key, law", [
    ("arrivals", "gamma"), ("prompt", {"law": "uniform", "min": 1, "max": 9})])
def test_a_law_the_generator_does_not_have_is_refused(key, law):
    with pytest.raises(ValueError):
        open_loop.generate({**CHAT, key: law}, GPT, 1, 20.0)


@pytest.mark.parametrize("config, traffic, chips", [
    ("cerebras-gpt-1.3b-train.json", "train-fixed-8k.json", 1),
    ("cerebras-gpt-1.3b-train.json", "train-fixed-8k.json", 4),
    ("resnet50.json", "train-fixed-b384.json", 1),
])
def test_training_batches(config, traffic, chips):
    cfg, mix = _json("configs", config), _json("traffic", traffic)
    cfg, mix = {**cfg, **cfg["rehearsal"]}, {**mix, **mix["rehearsal"]}
    a = train_fixed_batch.generate(mix, cfg, BIG, chips)
    b = train_fixed_batch.generate(mix, cfg, BIG, chips)
    c = train_fixed_batch.generate(mix, cfg, BIG + 1, chips)
    assert np.array_equal(a["x"], b["x"]) and not np.array_equal(a["x"], c["x"])
    rows = mix["per_chip"] * chips
    assert a["x"].shape[:2] == (mix["distinct"], rows)
    if cfg["input"]["kind"] == "tokens":
        assert a["x"].shape[2] == cfg["input"]["seq"] + 1
        assert a["units_per_step"] == rows * cfg["input"]["seq"]
        assert a["x"].max() < cfg["vocab_size"]
        flat = a["x"].reshape(-1, a["x"].shape[-1])
        assert len({r.tobytes() for r in flat}) == len(flat)   # rows differ
    else:
        assert a["units_per_step"] == rows
        assert a["y"].shape == (mix["distinct"], rows)
