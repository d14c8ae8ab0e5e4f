"""``BENCHMARK.json`` against the harness's own reading of the contract,
every file a cell names found by name, and a ``model_config`` PR acted
out on a copy of the tree: a cell, a configuration, a traffic mix, limits,
a driver, a scopes file and a per-layer metric are new files plus entries
(the metric's at the end of ``per_layer``) and the cell's name appended to
the ``workloads`` of the metrics it joins, the region metrics among them,
and every invariant holds on the copy."""

import copy
import json
import os
import shutil

import pytest

import bm_tree as T
from benchmarks import run, spec as S


@pytest.fixture(scope="module")
def bm():
    return T.COMMITTED.bm


def test_committed_file_is_valid():
    T.file_is_valid(T.COMMITTED)


def test_no_unproved_cell_is_in_the_committed_file(bm):
    with open(os.path.join(S.HERE, "unproved.json")) as f:
        waiting = json.load(f)
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        assert not {e["name"] for e in waiting[section]} \
            & {e["name"] for e in bm[section]}, section
    every = {w["name"] for w in T.EVERY.bm["workloads"]}
    assert every == {w["name"] for w in bm["workloads"]} \
        | {w["name"] for w in waiting["workloads"]}


@pytest.mark.parametrize("cell", T.PROVED)
def test_a_committed_cells_limits_were_read_on_the_chip(cell):
    T.limits_were_read_on_the_chip(T.COMMITTED, cell)


@pytest.mark.parametrize("cell", T.CELLS)
def test_every_file_of_a_cell_is_found_by_name(cell):
    T.every_file_of_a_cell_is_found_by_name(T.EVERY, cell)


@pytest.mark.parametrize("config", T.CONFIGS)
def test_widths_are_as_published(config):
    T.widths_are_as_published(T.EVERY, config)


def test_the_scopes_are_data():
    T.scopes_are_data(T.COMMITTED)


NEW_DRIVER = '''"""A later model's driver: the dense LM's, with one more count."""
from benchmarks.drivers import train_lm


class Driver(train_lm.Driver):
    def counters(self):
        return {"new_driver_ran": 1}
'''


def test_new_cell_config_traffic_and_metric_are_new_files_plus_entries(
        tmp_path, bm, capsys):
    """What a ``model_config`` PR does, on a copy of the tree: files
    added, entries added (the per-layer one **at the list's end**), the
    cell's name appended to ``train_tok_s``'s ``workloads`` and to those
    of the region metrics (found by their reader, as the tests find
    them), no file that was there edited. Every invariant of these tests
    then holds on the copy, the whole of ``test_bm_trace_region.py``'s
    test of the file's shape among them: a test that pins the list's
    tail, a metric's ``workloads`` or the cells that may report a metric
    fails here first. The new cell then rehearses."""
    root = tmp_path / "copy"
    shutil.copytree(os.path.join(S.ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmarks").rglob("*")
              if p.is_file()}
    b = root / "benchmarks"
    old = json.loads((b / "configs" / "cerebras-gpt-1.3b-train.json")
                     .read_text())
    small = {"n_embd": 768, "n_head": 12, "n_layer": 10, "n_inner": 3072}
    (b / "configs" / "cerebras-gpt-111m.json").write_text(json.dumps(
        {**old, **small, "published": {**old["published"], **small},
         "reduced": [], "driver": "train_lm_counted"}))
    mix = json.loads((b / "traffic" / "train-fixed-8k.json").read_text())
    (b / "traffic" / "train-fixed-16k.json").write_text(json.dumps(
        {**mix, "per_chip": 8}))
    rehearsal = json.loads((b / "limits" / "cgpt_train_s2048.json")
                           .read_text())["rehearsal"]
    (b / "limits" / "cgpt111m_train.json").write_text(json.dumps(
        {"loss_gap": 0.1, "grad_norm_gap": 0.1, "update_norm_gap": 0.7,
         "set_from": "a test", "rehearsal": rehearsal}))
    (b / "drivers" / "train_lm_counted.py").write_text(NEW_DRIVER)
    (b / "scopes" / "newmodel.json").write_text(json.dumps(
        {"what": "a later model's own scopes", "scopes": [
            {"pattern": r"expert\d+", "opened": "a test"}]}))
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
    regions = list(T.region_metrics(T.COMMITTED))
    assert regions
    for m in new["per_layer"]:
        if m["name"] in regions:
            m["workloads"] = m["workloads"] + ["cgpt111m_train"]
    (root / "BENCHMARK.json").write_text(json.dumps(new))

    spec = S.Spec(str(root))
    cell = spec.cell("cgpt111m_train")
    assert spec.config(cell)["n_embd"] == 768
    assert spec.traffic(cell)["per_chip"] == 8
    assert spec.limits(cell)["set_from"] == "a test"
    assert [m["name"] for m in spec.end_to_end(cell)] == [
        "train_tok_s", "setup_s"]
    assert [(m["name"], m["reader"]) for m in spec.per_layer(cell)] == [
        *((name, "trace_region") for name in regions),
        ("steps_in_window", "steps_counted")]
    # ``test_bm_trace_region.py``'s test of the committed file's shape,
    # whole, on every cell of the copy: the cells that reported a region
    # metric still do, the new one does, and no other cell does
    assert list(T.region_metrics(spec)) == regions
    for w in spec.bm["workloads"]:
        T.region_metrics_name_their_cells(spec, w["name"])
    # the modules are the copy's own, the scopes too
    steps = spec.plugin("readers", "steps_counted")
    assert steps.read(type("Run", (), {"rec": {"steps": 7}})) == 7
    from benchmarks.readers import trace_scope
    assert trace_scope.scope_of("jit(step)/jvp(expert12)/dot_general",
                                trace_scope.vocabulary(str(root))) \
        == "expert12"
    assert trace_scope.scope_of("jit(step)/jvp(expert12)/dot_general") is None
    assert set(trace_scope.patterns(str(root))) \
        == set(trace_scope.patterns()) | {r"expert\d+"}
    # every invariant of these tests, on every cell of the copy
    T.everything_holds(spec)
    # and the new cell walks a whole run through the copy's own driver
    assert run.main(["--workload", "cgpt111m_train", "--seed", "11",
                     "--seconds", "1", "--trace", "0", "--rehearse"],
                    root=str(root)) == 0
    lines = [json.loads(x) for x in
             capsys.readouterr().out.strip().splitlines()]
    assert lines[-1]["correct"] is True and lines[-1]["attempted"] > 0
    assert any(x.get("new_driver_ran") == 1 for x in lines)
    for p, raw in before.items():
        assert p.read_bytes() == raw, f"{p} was edited"
    assert {str(p.relative_to(b)) for p in b.rglob("*") if p.is_file()
            and "__pycache__" not in p.parts} - {
                str(p.relative_to(b)) for p in before} == {
        "configs/cerebras-gpt-111m.json", "traffic/train-fixed-16k.json",
        "limits/cgpt111m_train.json", "drivers/train_lm_counted.py",
        "scopes/newmodel.json", "layer_metrics/steps_in_window.json",
        "readers/steps_counted.py"}


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
