"""The first device's time that no scope owns, split by the program's
regions, and the time of what ``jax.checkpoint`` runs again.

``trace_scope`` gives an event's own time to the first component of its
``op_name`` path that is in the scopes' vocabulary. What it leaves under
no scope is, in a model that scans a run of like layers, mostly the
run's own machinery: the ``while`` less its body, the slices of stacked
operands, the compiler's copies at the loop's boundary (whose reader is
the ``while``). The program names that structure with **regions**
(``apex_tpu.prof.REGIONS``): ``jax.named_scope``s that *enclose* scopes.
The benchmark's copy is data, ``benchmarks/regions/<family>.json``, every
file of the directory in name order, in the shape of ``scopes/*.json``
(``regions``: ``pattern``, ``opened``); a test holds the set equal to
the program's. The rule: scope first, else the innermost region of the
path (``jvp(`` / ``transpose(`` taken off as ``trace_scope`` does), else
unowned. A region is in no ``scopes/*.json``, so it moves no metric
``trace_scope`` reads.

The same files name, under ``recomputed``, the path component that
``jax.checkpoint`` itself writes on what it runs again in the backward
(``.../checkpoint/rematted_computation/attention/...`` beside the true
backward's ``.../checkpoint/attention/...``): a third pass beside
``trace_scope``'s two directions, which count it as backward.

This module parses nothing: paths are ``trace_scope.op_names`` (its
cached parse: a cell reads its trace file once) handed on to the
compiler's own instructions by ``trace_scope.with_consumers``, own times
are ``xplane.self_times``. The table of one run is kept on the run for
its next metric.

``what``: ``region_ms_per_step``, the own time a step of the events with
no scope whose innermost region matches ``region`` (a regex);
``unowned_pct``, the share of the device's busy own time with neither
scope nor region (``unscoped_pct`` less the regions');
``recompute_ms_per_step``, the own time a step of the events whose path
holds a recomputed component, under every scope and none, narrowed by
``instruction`` (a regex searched in the event's name, which is the
whole HLO instruction) where given. Nothing to read (a trace without
``tf_op``, a program without scopes, without that region or with
nothing recomputed): ``None``.
"""

from __future__ import annotations

import functools
import glob
import json
import os
import re
import typing

from benchmarks import xplane
from benchmarks.readers import trace_scope
from benchmarks.spec import ROOT


def patterns(root: str = ROOT, key: str = "regions") -> list:
    """Every pattern under ``key`` (``regions`` | ``recomputed``) of
    ``<root>/benchmarks/regions/*.json``, the files in name order."""
    out = []
    for path in sorted(glob.glob(os.path.join(root, "benchmarks", "regions",
                                              "*.json"))):
        with open(path) as f:
            out += [r["pattern"] for r in json.load(f).get(key, ())]
    return out


@functools.lru_cache(maxsize=None)
def vocabulary(root: str = ROOT, key: str = "regions"):
    """One compiled alternation of ``patterns(root, key)``, matched
    against a whole component of a path; ``None`` where there is none."""
    pats = patterns(root, key)
    return re.compile(f"(?:{'|'.join(pats)})$") if pats else None


def region_of(path: str, regions=None):
    """The innermost component of an ``op_name`` path that ``regions`` (a
    compiled vocabulary; the tree's own by default) holds, or ``None``."""
    regions = regions or vocabulary()
    if regions is None:
        return None
    return trace_scope.scope_of("/".join(reversed(path.split("/"))), regions)


class Row(typing.NamedTuple):
    """One distinct event of a device's line."""
    name: str                   # the whole HLO instruction
    seconds: float              # own time over the window
    scope: str | None
    region: str | None
    recomputed: bool


def table(events, names: dict, scopes=None, regions=None,
          recomputed=None) -> list:
    """A ``Row`` a distinct event of ``events``, each named through
    ``names`` (event name -> op_name) and its readers."""
    recomputed = recomputed or vocabulary(key="recomputed")
    names = trace_scope.with_consumers(events, names)
    out = []
    for name, seconds in xplane.self_times(events).items():
        path = names.get(name, "")
        out.append(Row(name, seconds, trace_scope.scope_of(path, scopes),
                       region_of(path, regions),
                       bool(recomputed
                            and trace_scope.scope_of(path, recomputed))))
    return out


def _table(run) -> list:
    rows = getattr(run, "trace_region_table", None)
    if rows is None:
        dev = min(run.ops)
        names = trace_scope.op_names(trace_scope.trace_file(run)).get(dev, {})
        root = run.ctx.root
        rows = run.trace_region_table = table(
            run.ops[dev], names, trace_scope.vocabulary(root),
            vocabulary(root), vocabulary(root, "recomputed"))
    return rows


def read(run, what: str, region: str = "", instruction: str = ""):
    rows = _table(run)
    if not any(r.scope for r in rows):
        return None             # a program, or a trace, without the scopes
    steps = run.rec["steps"]
    if what == "region_ms_per_step":
        rx = re.compile(region)
        mine = [r for r in rows if r.region and rx.search(r.region)]
        if not mine:
            return None         # a program that does not open it
        return 1e3 * sum(r.seconds for r in mine if not r.scope) / steps
    if what == "unowned_pct":
        if not any(r.region for r in rows):
            return None         # a program without regions
        return 100.0 * sum(r.seconds for r in rows
                           if not r.scope and not r.region) \
            / sum(r.seconds for r in rows)
    if what == "recompute_ms_per_step":
        rx = re.compile(instruction)
        mine = [r.seconds for r in rows
                if r.recomputed and rx.search(r.name)]
        if not mine:
            return None         # nothing recomputed, or not by that name
        return 1e3 * sum(mine) / steps
    raise ValueError(f"what must be region_ms_per_step, unowned_pct or "
                     f"recompute_ms_per_step, not {what!r}")
