"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one cell sits in files found by name:

    configs/<config>.json        sizes, source, ``driver``, ``reference``
    traffic/<traffic>.json       ``kind`` (a generator) and its parameters
    limits/<cell>.json           the limits of the cell's ``correct``
    layer_metrics/<metric>.json  ``reader`` and its arguments
    drivers/<driver>.py  generators/<kind>.py  readers/<reader>.py
    work/<work>.py  reference/<reference>.py

so a later PR adds a cell, a configuration, a traffic mix or a per-layer
metric as new files plus one entry, and edits no file that is there.
``validate`` is the harness's own reading of the contract; the tests
run it on the committed file.
"""

from __future__ import annotations

import importlib
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
_SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
_TOP = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


class SpecError(ValueError):
    pass


def _line(s, what):
    if not (isinstance(s, str) and 1 <= len(s) <= 200
            and "\n" not in s and "\t" not in s):
        raise SpecError(f"{what}: 1 to 200 characters on one line")


def validate(bm: dict) -> None:
    """Raise ``SpecError`` where ``bm`` breaks the benchmark's contract."""
    if set(bm) != _TOP:
        raise SpecError(f"top-level keys must be exactly {sorted(_TOP)}")
    if not (isinstance(bm["run_seconds"], int)
            and 1 <= bm["run_seconds"] <= 51):
        raise SpecError("run_seconds: a whole number from 1 to 51")
    for section, keys in _KEYS.items():
        names = set()
        for entry in bm[section]:
            extra = set(entry) - keys - (
                {"workloads"} if section in ("end_to_end", "per_layer")
                else set())
            if extra or keys - set(entry):
                raise SpecError(f"{section} {entry.get('name')}: keys must "
                                f"be {sorted(keys)}")
            if not _NAME.match(entry["name"]):
                raise SpecError(f"bad name {entry['name']!r}")
            if entry["name"] in names:
                raise SpecError(f"{section}: {entry['name']} twice")
            names.add(entry["name"])
    configs = {c["name"]: c for c in bm["configs"]}
    for c in bm["configs"]:
        _line(c["source"], "source")
        _line(c["why"], "why")
        if not any(c["file"].startswith(p + "/") for p in bm["paths"]):
            raise SpecError(f"{c['file']} is not under paths")
        for k in c["reduced"]:
            if not _NAME.match(k) or k.endswith(("_dim", "_rank")):
                raise SpecError(f"reduced may not name {k!r}")
    cells = {}
    pairs = set()
    for w in bm["workloads"]:
        _line(w["why"], "why")
        if w["config"] not in configs:
            raise SpecError(f"{w['name']}: unknown config {w['config']}")
        if not _NAME.match(w["traffic"]) or w["chips"] not in (1, 4):
            raise SpecError(f"{w['name']}: bad traffic name or chips")
        if (w["config"], w["traffic"]) in pairs:
            raise SpecError(f"{w['name']}: config and traffic twice")
        pairs.add((w["config"], w["traffic"]))
        cells[w["name"]] = w
    four = sum(w["chips"] == 4 for w in bm["workloads"])
    if four > max(1, len(cells) // 4):
        raise SpecError("at most a quarter of the cells, or one, takes 4 chips")
    if {c["name"] for c in bm["configs"]} != {w["config"] for w in
                                              bm["workloads"]}:
        raise SpecError("every configuration is used by some cell")
    e2e = {}
    for m in bm["end_to_end"] + bm["per_layer"]:
        if not _UNIT.match(m["unit"]) or m["better"] not in ("lower", "higher") \
                or m["source"] not in _SOURCES:
            raise SpecError(f"{m['name']}: bad unit, better or source")
        for c in m.get("workloads", ()):
            if c not in cells:
                raise SpecError(f"{m['name']}: unknown cell {c}")
    for m in bm["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            raise SpecError(f"{m['name']}: end-to-end source")
        if not 0 < m["bound"] <= 0.1:
            raise SpecError(f"{m['name']}: bound in (0, 0.1]")
        e2e[m["name"]] = set(m.get("workloads", cells))
    if e2e.get("setup_s") != set(cells):
        raise SpecError("every cell reports setup_s")
    for name in cells:
        if not any(name in c for k, c in e2e.items() if k != "setup_s"):
            raise SpecError(f"{name}: no end-to-end metric besides setup_s")
    covered = set()
    for m in bm["per_layer"]:
        _line(m["layer"], "layer")
        if m["moves"] not in e2e:
            raise SpecError(f"{m['name']}: moves unknown {m['moves']}")
        where = set(m.get("workloads", e2e[m["moves"]]))
        if not where <= e2e[m["moves"]]:
            raise SpecError(f"{m['name']}: a cell does not report "
                            f"{m['moves']}")
        covered |= where
    if covered != set(cells):
        raise SpecError("every cell reports a per-layer metric")


def _read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def plugin(kind: str, name: str):
    """The module ``benchmarks/<kind>/<name>.py``."""
    if not _NAME.match(name):
        raise SpecError(f"bad {kind} name {name!r}")
    return importlib.import_module(f"benchmarks.{kind}.{name}")


class Spec:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.bm = _read(os.path.join(root, "BENCHMARK.json"))
        validate(self.bm)

    def _data(self, *parts) -> dict:
        return _read(os.path.join(self.root, "benchmarks", *parts))

    def cell(self, name: str) -> dict:
        for w in self.bm["workloads"]:
            if w["name"] == name:
                return w
        raise SpecError(f"no cell named {name!r}")

    def config(self, cell: dict) -> dict:
        entry, = [c for c in self.bm["configs"] if c["name"] == cell["config"]]
        return _read(os.path.join(self.root, entry["file"]))

    def traffic(self, cell: dict) -> dict:
        return self._data("traffic", cell["traffic"] + ".json")

    def limits(self, cell: dict) -> dict:
        return self._data("limits", cell["name"] + ".json")

    def _reported(self, section: str, cell: dict) -> list:
        e2e = {m["name"]: m for m in self.bm["end_to_end"]}

        def here(m):
            if "workloads" in m:
                return cell["name"] in m["workloads"]
            return section == "end_to_end" or here(e2e[m["moves"]])
        return [m for m in self.bm[section] if here(m)]

    def end_to_end(self, cell: dict) -> list:
        return self._reported("end_to_end", cell)

    def per_layer(self, cell: dict) -> list:
        """The cell's per-layer metrics, each with its reader's file."""
        return [{**m, **self._data("layer_metrics", m["name"] + ".json")}
                for m in self._reported("per_layer", cell)]
