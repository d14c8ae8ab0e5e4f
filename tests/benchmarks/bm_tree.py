"""What the tests of ``tests/benchmarks/`` share.

``BENCHMARK.json`` with the cells that are written but not proved on the
chip (``benchmarks/unproved.json``) merged in, read once at collection
time: the tests take their cells, configurations and drivers from it and
name none themselves, so a cell that a later PR adds is rehearsed, and
held to every invariant below, by the tests that are there.

The invariants are functions of a ``Spec``: the tests run them on the
tree, and ``test_bm_spec.py`` acts a ``model_config`` PR out on a copy of
the tree and runs them there too.
"""

import copy
import json
import os

from benchmarks import spec as S


def _unproved() -> dict:
    with open(os.path.join(S.HERE, "unproved.json")) as f:
        return json.load(f)


def merged(bm: dict, more: dict) -> dict:
    """``bm`` with ``more``'s entries after its own; ``also_reported_by``
    (where ``more`` has it) names for a cell the metrics of ``bm`` whose
    ``workloads`` it joins."""
    bm = copy.deepcopy(bm)
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        have = {e["name"] for e in bm[section]}
        bm[section] += [e for e in more[section] if e["name"] not in have]
    for cell, metrics in more.get("also_reported_by", {}).items():
        for m in bm["end_to_end"] + bm["per_layer"]:
            if m["name"] in metrics and cell not in m["workloads"]:
                m["workloads"] = m["workloads"] + [cell]
    # setup_s last, as the committed file has it
    bm["end_to_end"].sort(key=lambda m: m["name"] == "setup_s")
    return bm


COMMITTED = S.Spec()
EVERY = S.Spec(bm=merged(COMMITTED.bm, _unproved()))

CELLS = [w["name"] for w in EVERY.bm["workloads"]]
PROVED = [w["name"] for w in COMMITTED.bm["workloads"]]
CONFIGS = [c["name"] for c in EVERY.bm["configs"]]


def driver_of(spec, cell_name: str) -> str:
    return spec.config(spec.cell(cell_name))["driver"]


def _first_cell_of_each_driver() -> dict:
    out = {}
    for name in CELLS:
        out.setdefault(driver_of(EVERY, name), name)
    return out


DRIVERS = _first_cell_of_each_driver()          # driver -> a cell it drives
TRAIN_DRIVERS = {d: c for d, c in DRIVERS.items()
                 if "steps_checked" in EVERY.traffic(EVERY.cell(c))}


# -- the invariants ----------------------------------------------------------

def file_is_valid(spec) -> None:
    bm = spec.bm
    S.validate(bm)          # a (config, traffic) pair twice is refused there
    assert bm["paths"] == ["benchmarks", "tests/benchmarks"]
    assert bm["command"] == ["python3", "benchmarks/run.py"]
    assert len(json.dumps(bm)) < 64 * 1024
    assert bm["run_seconds"] == 51


def every_file_of_a_cell_is_found_by_name(spec, cell_name: str) -> None:
    c = spec.cell(cell_name)
    config, traffic = spec.config(c), spec.traffic(c)
    assert set(spec.limits(c)) >= {"set_from", "rehearsal"}
    assert hasattr(spec.plugin("drivers", config["driver"]), "Driver")
    assert hasattr(spec.plugin("generators", traffic["kind"]), "generate")
    assert spec.plugin("reference", config["reference"])
    assert "rehearsal" in config
    names = [m["name"] for m in spec.end_to_end(c)]
    assert "setup_s" in names and len(names) >= 2
    layer = spec.per_layer(c)
    assert layer
    for m in layer:
        assert callable(spec.plugin("readers", m["reader"]).read)
        if "work" in m.get("args", {}):
            assert callable(spec.plugin("work", m["args"]["work"]).total)


def limits_were_read_on_the_chip(spec, cell_name: str) -> None:
    """A committed cell's limits file holds limits of its own, not only
    the rehearsal's."""
    limits = spec.limits(spec.cell(cell_name))
    own = set(limits) - {"set_from", "rehearsal"}
    assert own and own >= set(limits["rehearsal"]), limits["set_from"]
    assert all(isinstance(limits[k], (int, float)) for k in own)


def widths_are_as_published(spec, config_name: str) -> None:
    """``published`` holds the source's sizes; the file differs from them
    in the keys ``reduced`` lists and in no other."""
    entry, = [x for x in spec.bm["configs"] if x["name"] == config_name]
    with open(os.path.join(spec.root, entry["file"])) as f:
        c = json.load(f)
    assert sorted(entry["reduced"]) == sorted(c["reduced"])
    published = c["published"]
    assert published
    assert {k for k, v in published.items() if c[k] != v} == set(c["reduced"])


def scopes_are_data(spec) -> None:
    """Every file of ``scopes/`` gives patterns with where they are
    opened, no pattern twice, and each compiles."""
    import re

    from benchmarks.readers import trace_scope as T
    pats = T.patterns(spec.root)
    assert pats and len(pats) == len(set(pats))
    for p in pats:
        re.compile(p)
    rx = T.vocabulary(spec.root)
    assert T.scope_of("jit(step)/jvp(nothing_of_the_kind)/add", rx) is None
    scopes = os.path.join(spec.root, "benchmarks", "scopes")
    for name in os.listdir(scopes):
        assert name.endswith(".json")
        with open(os.path.join(scopes, name)) as f:
            family = json.load(f)
        assert family["what"]
        assert all(s["pattern"] and s["opened"] for s in family["scopes"])


def region_metrics(spec) -> dict:
    """``{name: entry}`` of the ``per_layer`` entries that the reader
    ``trace_region`` reads, in the list's order: found by the reader
    their files name, wherever they stand and whatever a later PR
    appended, and by no list of names or cells written in a test."""
    out = {}
    for m in spec.bm["per_layer"]:
        with open(os.path.join(spec.root, "benchmarks", "layer_metrics",
                               m["name"] + ".json")) as f:
            if json.load(f)["reader"] == "trace_region":
                out[m["name"]] = m
    return out


def region_metrics_name_their_cells(spec, cell_name: str) -> None:
    """A region metric is read from the device's trace, is a time or a
    share of one (lower is better), and **names its cells**: a program
    opens ``prof.REGIONS`` or it does not, so without ``workloads`` the
    metric would be owed by every cell that reports what it moves. Its
    cells are its entry's own ``workloads`` and nothing else: a cell
    listed there reports it through ``trace_region`` and one that is not
    does not. A later configuration joins by appending its cell's name."""
    listed = region_metrics(spec)
    assert listed
    reported = {m["name"]: m for m in spec.per_layer(spec.cell(cell_name))}
    for name, entry in listed.items():
        assert (entry["source"], entry["better"]) == (
            "device_trace", "lower"), name
        assert entry["workloads"], name
        assert (name in reported) == (cell_name in entry["workloads"]), name
        if name in reported:
            assert reported[name]["reader"] == "trace_region"


def everything_holds(spec) -> None:
    """Every invariant above, on every cell and configuration of ``spec``."""
    file_is_valid(spec)
    scopes_are_data(spec)
    for w in spec.bm["workloads"]:
        every_file_of_a_cell_is_found_by_name(spec, w["name"])
        limits_were_read_on_the_chip(spec, w["name"])
        region_metrics_name_their_cells(spec, w["name"])
    for c in spec.bm["configs"]:
        widths_are_as_published(spec, c["name"])
