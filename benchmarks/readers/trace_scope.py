"""The first device's time split by the program's own scopes.

The program opens one vocabulary of ``jax.named_scope``s where its work
is issued (``apex_tpu.prof.SCOPES``; ``VOCABULARY`` below is the
benchmark's copy, which a test holds equal to it), so every HLO
instruction's ``op_name`` is a path such as
``jit(step)/transpose(jvp(mlp))/dot_general``. An ``XLA Ops`` event's
*own* time (``xplane.self_times``: a ``while`` less its body) goes to the
first component of that path that, with any ``jvp(`` / ``transpose(``
around it taken off, is in the vocabulary (``jit(...)``, ``checkpoint``,
``shard_map``, ``while/body`` are stepped over), and to a direction:
``transpose(`` anywhere in the path is the backward pass, a
recomputation inside it included. A fusion has one ``op_name``, its
root's: the time of a fusion that spans two scopes goes to the root's
and is not split. An instruction the compiler makes itself (a layout
copy, an async copy pair) has no ``op_name``: it takes the path of the
instruction that reads its result (``with_consumers``), so the three
layout copies in front of the Adam kernel are the optimizer's.

Where the path is (this installation, PERF.md section 3): not in the
event's name, which is the HLO instruction without its metadata, and
not in the event's own stats, but in the stat ``tf_op`` of the event's
*metadata* (``<op_name>:<op type>``). ``jax.profiler.ProfileData`` does
not show those, so the few fields needed are read from the file's
protobuf wire format here.

``what``: ``ms_per_step``, the time of the scopes matching ``scope`` (a
regex) in ``direction`` (``any`` | ``fwd`` | ``bwd``) over the traced
window's steps; or ``unscoped_pct``, the share of the device's busy
time whose event carries no vocabulary scope, its own or a consumer's.
Nothing to read (no
``tf_op`` in the trace, or a program without these scopes): ``None``.
"""

from __future__ import annotations

import functools
import os
import re

from benchmarks import xplane
from benchmarks.spec import ROOT

VOCABULARY = (r"embed|attention|mlp|head_loss|stem|stage\d+_block\d+|head|"
              r"amp_cast|amp_scale|optimizer|collective")
_SCOPE = re.compile(f"(?:{VOCABULARY})$")
_DIRECTION = re.compile(r"^(?:jvp|transpose)\((.*)\)$")
_BACKWARD = "transpose("


def scope_of(path: str):
    """The vocabulary scope of an ``op_name`` path, or ``None``."""
    for part in path.split("/"):
        while (m := _DIRECTION.match(part)) is not None:
            part = m.group(1)
        if _SCOPE.match(part):
            return part
    return None


# -- the protobuf wire format, as far as XSpace needs it --------------------
# XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 (map: key = 1,
# value = 2), stat_metadata = 5 (the same); XEventMetadata: name = 2,
# stats = 5; XStatMetadata: name = 2; XStat: metadata_id = 1,
# str_value = 5, ref_value = 7 (a string kept as a stat_metadata's name).

def _varint(buf, i):
    n = shift = 0
    while True:
        b = buf[i]
        i += 1
        n |= (b & 0x7F) << shift
        if b < 0x80:
            return n, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint or a
    fixed-width field, a ``memoryview`` for a length-delimited one."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = int.from_bytes(buf[i:i + size], "little"), i + size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, value


def _map_entry(buf):
    key, value = 0, memoryview(b"")
    for no, v in _fields(buf):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


@functools.lru_cache(maxsize=2)
def op_names(path: str, plane=xplane.DEVICE_PLANE) -> dict:
    """``{device index: {event name: op_name}}`` from each matching
    plane's event metadata; a plane without ``tf_op`` gives ``{}``. Kept
    for the next metric of the same run: a cell reads the file once."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for no, plane_buf in _fields(space):
        if no != 1:
            continue
        name, events, stats = "", [], {}
        for no, v in _fields(plane_buf):
            if no == 2:
                name = _text(v)
            elif no == 4:
                events.append(_map_entry(v)[1])
            elif no == 5:
                key, md = _map_entry(v)
                stats[key] = next((_text(x) for n, x in _fields(md)
                                   if n == 2), "")
        m = plane.match(name)
        if not m:
            continue
        tf_op = {k for k, v in stats.items() if v == "tf_op"}
        names = {}
        for md in events:
            event_name, op = "", None
            for no, v in _fields(md):
                if no == 2:
                    event_name = _text(v)
                elif no == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) in tf_op:
                        op = _text(stat[5]) if 5 in stat \
                            else stats.get(stat.get(7), "")
            if op is not None:
                names[event_name] = op.rsplit(":", 1)[0]
        out[int(m.group(1))] = names
    return out


def trace_file(run) -> str:
    """Where ``run.py`` had the profiler write this run's trace:
    ``common.out_dir(<cell>, "trace")``, which is not called here because
    it empties the directory it names."""
    return xplane.find(os.path.join(ROOT, ".bench_out",
                                    run.ctx.cell["name"], "trace"))


_INSTRUCTION = re.compile(r"%[\w.\-]+")


def with_consumers(events, names: dict) -> dict:
    """``names`` with a path for the events that have none. An instruction
    the compiler makes itself (a layout copy, a ``copy-start`` /
    ``copy-done`` pair, a ``dynamic-update-slice`` fusion) carries no
    ``op_name``; it exists for the instruction that reads its result, so it
    takes the path of its first consumer in the trace's order that has
    one, followed through consumers that have none themselves. An event's
    name is the whole HLO instruction, so its operands are the
    ``%names`` after the ``=``. An event with a path of its own keeps it,
    vocabulary scope or none: only the compiler's work is handed on."""
    order = list(dict.fromkeys(
        name for name, _, _ in sorted(events, key=lambda e: e[1])))
    consumers = {}              # instruction -> the events that read it
    for name in order:
        for operand in dict.fromkeys(
                _INSTRUCTION.findall(name.partition(" = ")[2])):
            consumers.setdefault(operand, []).append(name)
    out = {}
    for name in reversed(order):        # a consumer starts after its operand
        path = names.get(name)
        if not path:
            instruction = name.partition(" = ")[0]
            path = next((out[c] for c in consumers.get(instruction, ())
                         if out.get(c)), "")
        out[name] = path
    return out


def split(events, names: dict) -> dict:
    """``{(scope or None, "fwd" | "bwd"): seconds}`` of the events' own
    times, each event named through ``names`` (event name -> op_name)."""
    names = with_consumers(events, names)
    out = {}
    for name, seconds in xplane.self_times(events).items():
        path = names.get(name, "")
        key = (scope_of(path), "bwd" if _BACKWARD in path else "fwd")
        out[key] = out.get(key, 0.0) + seconds
    return out


def read(run, what: str, scope: str = "", direction: str = "any"):
    dev = min(run.ops)
    names = op_names(trace_file(run)).get(dev, {})
    by_scope = split(run.ops[dev], names)
    if not any(s for s, _ in by_scope):
        return None             # a program, or a trace, without the scopes
    if what == "unscoped_pct":
        return 100.0 * sum(t for (s, _), t in by_scope.items() if s is None) \
            / sum(by_scope.values())
    if what != "ms_per_step":
        raise ValueError(f"what must be ms_per_step or unscoped_pct, "
                         f"not {what!r}")
    if direction not in ("any", "fwd", "bwd"):
        raise ValueError(f"direction must be any, fwd or bwd, "
                         f"not {direction!r}")
    rx = re.compile(scope)
    mine = [t for (s, d), t in by_scope.items()
            if s and rx.search(s) and direction in ("any", d)]
    if not mine:
        return None
    return 1e3 * sum(mine) / run.rec["steps"]
