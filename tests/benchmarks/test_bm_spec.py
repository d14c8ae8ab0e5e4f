"""``BENCHMARK.json`` against the harness's own reading of the contract,
every file a cell names found by name, and a cell, a configuration, a
traffic mix and a per-layer metric added as new files plus one entry."""

import copy
import json
import os
import shutil

import pytest

from benchmarks import spec as S


@pytest.fixture(scope="module")
def bm():
    return S.Spec().bm


def test_committed_file_is_valid(bm):
    S.validate(bm)
    assert bm["paths"] == ["benchmarks", "tests/benchmarks"]
    assert bm["command"] == ["python3", "benchmarks/run.py"]
    assert len(json.dumps(bm)) < 64 * 1024


def test_the_cells_are_the_proved_ones_in_their_order(bm, every_cell_root):
    assert [w["name"] for w in bm["workloads"]] == [
        "cgpt_train_s2048", "rn50_train_b384"]
    assert [w["name"] for w in S.Spec(every_cell_root).bm["workloads"]][2:] \
        == ["cgpt_serve_chat", "cgpt_train_ddp4"]


@pytest.mark.parametrize("cell", ["cgpt_train_s2048", "rn50_train_b384",
                                  "cgpt_serve_chat", "cgpt_train_ddp4"])
def test_every_file_of_a_cell_is_found_by_name(every_cell_root, cell):
    spec = S.Spec(every_cell_root)
    c = spec.cell(cell)
    config, traffic = spec.config(c), spec.traffic(c)
    assert set(spec.limits(c)) >= {"set_from"}
    assert hasattr(S.plugin("drivers", config["driver"]), "Driver")
    assert hasattr(S.plugin("generators", traffic["kind"]), "generate")
    assert S.plugin("reference", config["reference"])
    assert "rehearsal" in config
    entry, = [x for x in spec.bm["configs"] if x["name"] == c["config"]]
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    names = [m["name"] for m in spec.end_to_end(c)]
    assert "setup_s" in names and len(names) >= 2
    layer = spec.per_layer(c)
    assert layer
    for m in layer:
        assert hasattr(S.plugin("readers", m["reader"]), "read")
        if "work" in m.get("args", {}):
            assert S.plugin("work", m["args"]["work"])


def test_widths_are_as_published(every_cell_root):
    spec = S.Spec(every_cell_root)
    for name in ("cerebras-gpt-1.3b", "cerebras-gpt-1.3b-train"):
        entry, = [x for x in spec.bm["configs"] if x["name"] == name]
        with open(os.path.join(S.ROOT, entry["file"])) as f:
            c = json.load(f)
        assert sorted(entry["reduced"]) == sorted(c["reduced"])
        assert (c["n_embd"], c["n_head"], c["n_inner"], c["n_positions"],
                c["vocab_size"]) == (2048, 16, 8192, 2048, 50257)
        assert c["n_layer"] == 24 or c["reduced"] == ["n_layer"]


def test_new_cell_config_traffic_and_metric_are_new_files_plus_entries(
        tmp_path, bm):
    """Copy the benchmark, add files and entries, edit nothing that was
    there: the harness picks the new cell up."""
    root = tmp_path / "copy"
    shutil.copytree(os.path.join(S.ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmarks").rglob("*")
              if p.is_file()}
    b = root / "benchmarks"
    old = json.loads((b / "configs" / "cerebras-gpt-1.3b-train.json")
                     .read_text())
    (b / "configs" / "cerebras-gpt-111m.json").write_text(json.dumps(
        {**old, "n_embd": 768, "n_head": 12, "n_layer": 10, "n_inner": 3072,
         "reduced": []}))
    mix = json.loads((b / "traffic" / "train-fixed-8k.json").read_text())
    (b / "traffic" / "train-fixed-16k.json").write_text(json.dumps(
        {**mix, "per_chip": 8}))
    (b / "limits" / "cgpt111m_train.json").write_text(json.dumps(
        {"loss_gap": 0.1, "grad_norm_gap": 0.1, "update_norm_gap": 0.7,
         "set_from": "a test"}))
    (b / "layer_metrics" / "steps_in_window.json").write_text(json.dumps(
        {"reader": "steps_counted"}))
    (b / "readers" / "steps_counted.py").write_text(
        "def read(run):\n    return run.rec['steps']\n")
    new = copy.deepcopy(bm)
    new["configs"].append({
        "name": "cerebras-gpt-111m", "source": "arXiv:2304.03208 Table 1",
        "file": "benchmarks/configs/cerebras-gpt-111m.json", "reduced": [],
        "why": "the family's smallest, whole"})
    new["workloads"].append({
        "name": "cgpt111m_train", "config": "cerebras-gpt-111m",
        "traffic": "train-fixed-16k", "chips": 1, "why": "a test"})
    for m in new["end_to_end"]:
        if m["name"] == "train_tok_s":
            m["workloads"] = m["workloads"] + ["cgpt111m_train"]
    new["per_layer"].append({
        "name": "steps_in_window", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "models (models/transformer.py)",
        "moves": "train_tok_s", "workloads": ["cgpt111m_train"]})
    (root / "BENCHMARK.json").write_text(json.dumps(new))

    spec = S.Spec(str(root))
    cell = spec.cell("cgpt111m_train")
    assert spec.config(cell)["n_embd"] == 768
    assert spec.traffic(cell)["per_chip"] == 8
    assert spec.limits(cell)["set_from"] == "a test"
    assert [m["name"] for m in spec.end_to_end(cell)] == [
        "train_tok_s", "setup_s"]
    assert [(m["name"], m["reader"]) for m in spec.per_layer(cell)] == [
        ("steps_in_window", "steps_counted")]
    for p, raw in before.items():
        assert p.read_bytes() == raw, f"{p} was edited"


def _broken(bm, how):
    bad = copy.deepcopy(bm)
    how(bad)
    return bad


@pytest.mark.parametrize("how", [
    lambda b: b.update(extra=1),
    lambda b: b.update(run_seconds=52),
    lambda b: b["workloads"][0].update(name="has space"),
    lambda b: b["workloads"][0].update(chips=2),
    lambda b: b["workloads"][0].update(chips=4) or
    b["workloads"][-1].update(chips=4),
    lambda b: b["workloads"].append(dict(b["workloads"][0], name="twin")),
    lambda b: b["end_to_end"][0].update(unit="tokens per second"),
    lambda b: b["end_to_end"][0].update(bound=0.2),
    lambda b: b["end_to_end"][0].update(why="no such key"),
    lambda b: b["end_to_end"][0].update(source="program_span"),
    lambda b: b["per_layer"][0].update(moves="no_such_metric"),
    lambda b: b["per_layer"][0].update(better="faster"),
    lambda b: b["per_layer"][0].update(layer="two\nlines"),
    lambda b: b["configs"][0].update(reduced=["head_dim"]),
    lambda b: b["configs"][0].update(file="apex_tpu/x.json"),
    lambda b: b["end_to_end"].pop(),
], ids=lambda f: None)
def test_what_the_contract_refuses(bm, how):
    with pytest.raises((S.SpecError, KeyError)):
        S.validate(_broken(bm, how))


def test_a_moved_metric_must_be_reported_by_its_cells(bm):
    bad = copy.deepcopy(bm)
    lm = [m for m in bad["per_layer"] if m["moves"] == "train_tok_s"][0]
    lm["workloads"] = [w["name"] for w in bad["workloads"]]
    if len(bad["workloads"]) > 1:
        with pytest.raises(S.SpecError):
            S.validate(bad)
