"""A copy of ``BENCHMARK.json`` with the cells that are written but not
proved on the chip (``benchmarks/unproved.json``) merged in, so that
their files are rehearsed on the CPU like a proved cell's."""

import copy
import json
import os

import pytest

from benchmarks import spec as S


@pytest.fixture(scope="session")
def every_cell_root(tmp_path_factory):
    """The root of a checkout whose ``BENCHMARK.json`` holds the proved
    cells and the unproved ones; ``benchmarks/`` is the tree's own."""
    with open(os.path.join(S.HERE, "unproved.json")) as f:
        more = json.load(f)
    bm = copy.deepcopy(S.Spec().bm)
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        have = {e["name"] for e in bm[section]}
        bm[section] += [e for e in more[section] if e["name"] not in have]
    for cell, metrics in more["also_reported_by"].items():
        for m in bm["end_to_end"] + bm["per_layer"]:
            if m["name"] in metrics and cell not in m["workloads"]:
                m["workloads"] = m["workloads"] + [cell]
    # setup_s last, as the committed file has it
    bm["end_to_end"].sort(key=lambda m: m["name"] == "setup_s")
    S.validate(bm)
    root = tmp_path_factory.mktemp("every_cell")
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    os.symlink(S.HERE, root / "benchmarks")
    return str(root)
