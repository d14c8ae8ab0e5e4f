"""The region reader on a hand-written trace of a scanned, recomputed
run of layers (text proto beside this file, 36 ms over 2 steps: the
stacked leaves' ``concatenate`` under ``layer_stack``; a copy with no
``op_name`` that the forward ``while`` reads; that ``while`` under
``layer_scan`` with a loop-level ``dynamic-slice``, a matmul fusion and a
flash kernel under ``attention`` in its body; the backward ``while`` with
a recomputed matmul and a recomputed flash kernel under
``rematted_computation/attention``, a true backward fusion, a recomputed
cast under no scope and a loop-level ``dynamic-update-slice``; the
gradients' ``split`` under ``layer_stack``; a copy nothing reads, a
fusion with a path and no name in it, and the optimizer), the regions'
vocabulary as data, and the names in the program that the reader rests
on: one hybrid configuration's rehearsal-size step with and without the
regions."""

import contextlib
import json
import os
import re
import shutil
import types

import pytest

import bm_tree
from benchmarks import spec as S, xplane
from benchmarks.readers import trace_region as R, trace_scope as T

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "kvl_train_s8192"
REGION_METRICS = list(bm_tree.region_metrics(bm_tree.COMMITTED))


def _metric(name: str) -> dict:
    with open(os.path.join(S.HERE, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def _trace(edit=lambda text: text) -> bytes:
    from jax.profiler import ProfileData
    with open(os.path.join(HERE, "data", "bm_region_xplane.txt")) as f:
        return ProfileData.text_proto_to_serialized_xspace(edit(f.read()))


@pytest.fixture
def make_run(tmp_path, monkeypatch):
    """A run whose trace is where ``run.py`` has the profiler write it;
    ``edit`` rewrites the text proto first."""
    def make(edit=lambda text: text, steps=2):
        d = tmp_path / ".bench_out" / CELL / "trace" / "plugins" / \
            "profile" / "r"
        d.mkdir(parents=True, exist_ok=True)
        (d / "host.xplane.pb").write_bytes(_trace(edit))
        monkeypatch.setattr(T, "ROOT", str(tmp_path))
        T.op_names.cache_clear()
        ctx = types.SimpleNamespace(cell={"name": CELL}, root=S.ROOT)
        run = types.SimpleNamespace(ctx=ctx, rec={"steps": steps})
        run.ops = xplane.load(T.trace_file(run))
        return run
    return make


@pytest.fixture
def run(make_run):
    return make_run()


# own times, ms: layer_scan = the copy the while reads 1 + while.3 10 - 7
# + its dynamic-slice 1 + while.7 16 - 14 + the recomputed cast 1 + the
# dynamic-update-slice 2 = 10; layer_stack = concatenate 2 + split 1;
# neither = a copy nothing reads 1 + jvp()/reduce_sum 1; attention 6
# forward, 6 recomputed, 5 backward; optimizer 4; 36 in all, 2 steps
@pytest.mark.parametrize("args, want", [
    (_metric("layer_scan_ms_per_step")["args"], 5.0),
    (_metric("layer_stack_ms_per_step")["args"], 1.5),
    (_metric("unowned_pct")["args"], 100 * 2 / 36),
    (_metric("recompute_ms_per_step")["args"], 3.5),
    (dict(what="recompute_ms_per_step",
          instruction=r"^%(\w+_)?apex_flash_fwd"), 1.0),
    (dict(what="region_ms_per_step", region="^layer_"), 6.5),
    (dict(what="region_ms_per_step", region="^layer_norm$"), None),
    (dict(what="recompute_ms_per_step", instruction="^%fusion"), 2.5),
    (dict(what="recompute_ms_per_step",
          instruction=r"^%(\w+_)?apex_mt_adam"), None),
])
def test_each_what_on_the_hand_written_trace(run, args, want):
    got = R.read(run, **args)
    assert got == (want if want is None else pytest.approx(want))


@pytest.mark.parametrize("name", REGION_METRICS)
def test_a_listed_region_metrics_file_asks_what_this_reader_knows(
        name, run):
    """Each ``per_layer`` entry whose file names this reader (found so,
    not by a list here): the file's arguments are ones ``read`` takes,
    a ``what`` it does not know is refused, and on the hand-written
    trace it reads a time or share above 0 or nothing, never 0."""
    got = R.read(run, **_metric(name)["args"])
    assert got is None or got > 0


def test_scan_plus_stack_plus_unowned_is_what_trace_scope_leaves_unscoped(
        run):
    busy_ms = 36.0 / run.rec["steps"]
    scan = R.read(run, "region_ms_per_step", region="^layer_scan$")
    stack = R.read(run, "region_ms_per_step", region="^layer_stack$")
    unowned = R.read(run, "unowned_pct") / 100 * busy_ms
    unscoped = T.read(run, what="unscoped_pct") / 100 * busy_ms
    assert unscoped == pytest.approx(15 / 2)
    assert scan + stack + unowned == pytest.approx(unscoped)


def test_scope_first_else_the_innermost_region_else_nobody(run):
    rows = {r.name.partition(" = ")[0]: list(r[1:])
            for r in R.table(run.ops[0], T.op_names(T.trace_file(run))[0])}
    # the compiler's copy has no op_name; its reader is the while
    assert rows["%copy.2"][1:] == [None, "layer_scan", False]
    assert rows["%while.3"][0] == pytest.approx(3e-3)       # less its body
    assert rows["%while.7"][0] == pytest.approx(2e-3)
    assert rows["%while.7"][1:] == [None, "layer_scan", False]
    # a scoped op inside a region is its scope's, as before
    assert rows["%fusion.5"][1:] == ["attention", "layer_scan", False]
    assert rows["%apex_flash_fwd.6"][1] == "attention"
    # recomputed, under a scope and under none
    assert rows["%fusion.8"][1:] == ["attention", "layer_scan", True]
    assert rows["%fusion.11"][1:] == [None, "layer_scan", True]
    assert rows["%fusion.10"][1:] == ["attention", "layer_scan", False]
    assert rows["%fusion.13"][1:] == [None, "layer_stack", False]
    assert rows["%copy.14"][1:] == rows["%fusion.15"][1:] \
        == [None, None, False]
    assert rows["%fusion.16"][1:] == ["optimizer", None, False]


def test_a_recomputed_op_is_still_its_scopes_and_still_backward(run):
    """The third pass is read beside the two directions, not out of
    them: what ``trace_scope`` gave a scope it still gives."""
    bwd = T.read(run, what="ms_per_step", scope="^attention$",
                 direction="bwd")
    assert bwd == pytest.approx(5.5)            # 6 recomputed + 5 backward
    assert T.read(run, what="ms_per_step", scope="^attention$",
                  direction="fwd") == pytest.approx(3.0)
    assert R.read(run, "recompute_ms_per_step") <= bwd
    assert T.read(run, what="ms_per_step", scope="^optimizer$") \
        == pytest.approx(2.0)


def test_the_parents_program_has_no_regions_and_still_a_third_pass(
        make_run):
    """The parent of PR 37 opens no region (its paths read ``jvp()``
    there): nothing to read, nothing raised; ``jax.checkpoint`` wrote its
    component all along."""
    run = make_run(lambda text: text.replace("layer_scan", "")
                   .replace("layer_stack", ""))
    assert R.read(run, "region_ms_per_step", region="^layer_scan$") is None
    assert R.read(run, "region_ms_per_step", region="^layer_stack$") is None
    assert R.read(run, "unowned_pct") is None
    assert R.read(run, "recompute_ms_per_step") == pytest.approx(3.5)
    assert T.read(run, what="unscoped_pct") == pytest.approx(100 * 15 / 36)


def test_a_trace_without_scopes_or_without_recomputation_gives_nothing(
        make_run):
    run = make_run(lambda text: text.replace("/attention/", "/mixer/")
                   .replace("/optimizer/", "/update/"))
    for name in REGION_METRICS:
        assert R.read(run, **_metric(name)["args"]) is None
    run = make_run(lambda text: text.replace("rematted_computation/", ""))
    assert R.read(run, "recompute_ms_per_step") is None
    assert R.read(run, "region_ms_per_step", region="^layer_scan$") \
        == pytest.approx(5.0)


def test_a_run_is_split_once_and_its_file_parsed_once(run, monkeypatch):
    calls = []
    real = xplane.self_times
    monkeypatch.setattr(xplane, "self_times",
                        lambda ev: calls.append(1) or real(ev))
    for name in REGION_METRICS:
        R.read(run, **_metric(name)["args"])
    assert len(calls) == 1
    T.read(run, what="unscoped_pct")            # the same cached parse
    assert T.op_names.cache_info().misses == 1


def test_reader_refuses_what_it_does_not_know(run):
    with pytest.raises(ValueError):
        R.read(run, what="region_pct")


def test_region_of_takes_the_innermost_and_steps_over_wrappers():
    assert R.region_of("jit(step)/transpose(jvp(layer_scan))/while/body/"
                       "dynamic_update_slice") == "layer_scan"
    assert R.region_of("jit(step)/jvp(layer_scan)/while/body/closed_call/"
                       "layer_stack/concatenate") == "layer_stack"
    assert R.region_of("jit(step)/jvp(layer_scanner)/while") is None
    assert R.region_of("jit(layer_scan)/add") is None   # a jit's name
    assert R.region_of("") is None
    # a region is no scope: trace_scope steps over it
    assert T.scope_of("jit(step)/jvp(layer_scan)/while") is None
    assert T.scope_of("jit(step)/jvp(layer_scan)/while/body/closed_call/"
                      "checkpoint/rematted_computation/attention/tanh") \
        == "attention"


def test_the_benchmarks_regions_are_the_programs_and_no_scope():
    from apex_tpu import prof
    assert set(R.patterns()) == set(prof.REGIONS)
    assert len(R.patterns()) == len(prof.REGIONS)
    assert not set(prof.REGIONS) & set(prof.SCOPES)
    assert not set(R.patterns()) & set(T.patterns())
    # the third pass's name lives in the same data, in one place
    assert R.patterns(key="recomputed") == ["rematted_computation"]
    assert not set(R.patterns(key="recomputed")) & (
        set(prof.REGIONS) | set(prof.SCOPES))
    for name in os.listdir(os.path.join(S.HERE, "regions")):
        with open(os.path.join(S.HERE, "regions", name)) as f:
            family = json.load(f)
        assert family["what"]
        assert all(r["pattern"] and r["opened"] for r in family["regions"])
        assert all(r["pattern"] and r["written"]
                   for r in family.get("recomputed", ()))


def test_a_configuration_pr_adds_a_regions_file_beside_its_scopes_file(
        tmp_path):
    """Acted out on a copy: ``regions/<family>.json`` is a new file, read
    in name order after the files that are there, none of which is
    edited, and every invariant of the benchmark's tests holds."""
    root = tmp_path / "copy"
    shutil.copytree(os.path.join(S.ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(S.ROOT, "BENCHMARK.json"), root)
    before = {p: p.read_bytes() for p in (root / "benchmarks").rglob("*")
              if p.is_file()}
    b = root / "benchmarks"
    (b / "scopes" / "newmodel.json").write_text(json.dumps(
        {"what": "a later model's own scopes", "scopes": [
            {"pattern": "mixer", "opened": "a test"}]}))
    (b / "regions" / "newmodel.json").write_text(json.dumps(
        {"what": "a later model's own regions", "regions": [
            {"pattern": r"stage\d+_pipeline", "opened": "a test"}]}))
    assert R.patterns(str(root)) == R.patterns() + [r"stage\d+_pipeline"]
    assert R.patterns(str(root), "recomputed") == ["rematted_computation"]
    rx, scopes = R.vocabulary(str(root)), T.vocabulary(str(root))
    path = "jit(step)/jvp(stage3_pipeline)/while/body/mixer/dot_general"
    assert R.region_of(path, rx) == "stage3_pipeline"
    assert R.region_of(path) is None                    # not the tree's own
    assert T.scope_of(path, scopes) == "mixer"
    assert R.region_of("jit(step)/jvp(layer_scan)/while", rx) == "layer_scan"
    bm_tree.everything_holds(S.Spec(str(root)))
    for p, raw in before.items():
        assert p.read_bytes() == raw, f"{p} was edited"


@pytest.mark.parametrize("cell", bm_tree.PROVED)
def test_a_region_metric_is_reported_by_the_cells_it_names_and_no_other(
        cell):
    """The whole of this test is one function of a ``Spec``, with no name
    of a cell or a metric in it: ``test_bm_spec.py`` acts a configuration
    PR out on a copy (an entry appended after these, its cell joined to
    their ``workloads``) and runs the same function on every cell there,
    so what would refuse that PR here refuses it there first."""
    bm_tree.region_metrics_name_their_cells(bm_tree.COMMITTED, cell)


# -- the names in the program ----------------------------------------------

def _step_lines(cell_name: str, regions: bool) -> list:
    """The instruction lines of one configuration's rehearsal-size step,
    compiled on the CPU through its driver; ``regions`` false: with
    ``prof.REGIONS`` opening nothing, the program as PR 37's parent had
    it."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import prof
    from apex_tpu.parallel import compile_step_with_plan
    from benchmarks import weights as W
    spec = S.Spec()
    cell = spec.cell(cell_name)
    cfg, traffic = spec.config(cell), spec.traffic(cell)
    ctx = types.SimpleNamespace(
        config={**cfg, **cfg["rehearsal"]},
        traffic={**traffic, **traffic["rehearsal"]}, seed=3,
        devices=jax.devices()[:1], plugin=spec.plugin,
        mark=lambda name: None)
    driver = spec.plugin("drivers", cfg["driver"]).Driver(ctx)
    real = jax.named_scope
    with pytest.MonkeyPatch.context() as mp:
        if not regions:
            mp.setattr(jax, "named_scope", lambda name: (
                contextlib.nullcontext() if name in prof.REGIONS
                else real(name)))
        _, state, step, plan = driver.program(
            ctx.devices, W.build(driver.specs, W.seed_key(3), jnp.float32))
        text = compile_step_with_plan(step, plan).lower(
            state, jnp.asarray(driver.feed["x"][0])).compile().as_text()
    return [line for line in text.splitlines() if " = " in line]


_METADATA = re.compile(r", metadata=\{[^}]*\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def test_the_step_is_the_parents_but_for_names_and_every_scope_stays():
    """``qnext_train_s8192``'s rehearsal-size step, whole (AMP, the
    optimizer, the scans of 3 and 1): with the metadata taken off, the
    regions change no instruction; with it, every instruction resolves to
    the scope it resolved to without them, the layer scans' ``while``s and
    loop-level slices carry ``layer_scan``, the stacked leaves
    ``layer_stack`` forward and backward."""
    mine = _step_lines("qnext_train_s8192", regions=True)
    parents = _step_lines("qnext_train_s8192", regions=False)
    assert len(mine) == len(parents) > 5000
    assert [_METADATA.sub("", x) for x in mine] \
        == [_METADATA.sub("", x) for x in parents]
    paths = [(_OP_NAME.search(x), _OP_NAME.search(y))
             for x, y in zip(mine, parents)]
    assert all((a is None) == (b is None) for a, b in paths)
    paths = [(a.group(1), b.group(1)) for a, b in paths if a]
    assert not any(R.region_of(b) for _, b in paths)
    assert [T.scope_of(a) for a, _ in paths] \
        == [T.scope_of(b) for _, b in paths]
    # a path differs from the parent's in the regions' names and nothing
    # else
    assert all(re.sub(r"layer_(scan|stack)", "", a) == b for a, b in paths)

    def unscoped(opcode, within=""):
        return [a for line, (a, _) in zip(
                    (x for x in mine if _OP_NAME.search(x)), paths)
                if f" {opcode}(" in line and T.scope_of(a) is None
                and within in a]
    whiles = unscoped("while")
    # the run of three, both directions (XLA unrolls a scan of one)
    assert len(whiles) == 2
    assert {R.region_of(a) for a in whiles} == {"layer_scan"}
    assert {("transpose(" in a) for a in whiles} == {True, False}
    for opcode in ("dynamic-slice", "dynamic-update-slice"):
        level = unscoped(opcode, "/while/body/dynamic_")
        assert level and {R.region_of(a) for a in level} == {"layer_scan"}
    stacks = [a for a, _ in paths if R.region_of(a) == "layer_stack"]
    assert any(a.endswith("jvp(layer_stack)/concatenate") for a in stacks)
    assert any("transpose(jvp(layer_stack))" in a for a in stacks)
