"""From a profiler trace to numbers, with nothing but JAX.

``jax.profiler.ProfileData`` reads the ``.xplane.pb`` the profiler
writes. A device is a plane named ``/device:TPU:<n>``; its operations
are the events of the line ``XLA Ops`` (what the v5e's traces show; see
PERF.md). Everything below works on plain lists of
``(name, start_s, duration_s)`` so that the tests can feed it by hand.

Operations may nest on one line (a ``while`` holds its body's ops), so
busy time is the *union* of the intervals, and an operation's own time
is its duration less what its children cover.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"


def find(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str, plane=DEVICE_PLANE, line: str = OPS_LINE) -> dict:
    """``{device index: [(name, start_s, duration_s), ...]}`` for every
    plane whose name matches ``plane``, from its line named ``line``."""
    from jax.profiler import ProfileData
    out = {}
    for pl in ProfileData.from_file(path).planes:
        m = plane.match(pl.name)
        if not m:
            continue
        for ln in pl.lines:
            if ln.name == line:
                out[int(m.group(1))] = [
                    (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in ln.events]
    return out


def describe(path: str, top: int = 25) -> dict:
    """Planes, lines and the commonest event names: what a builder looks
    at by hand before trusting a pattern."""
    from jax.profiler import ProfileData
    out = {}
    for pl in ProfileData.from_file(path).planes:
        lines = {}
        for ln in pl.lines:
            total = {}
            n = 0
            for e in ln.events:
                n += 1
                total[e.name] = total.get(e.name, 0.0) + e.duration_ns * 1e-9
            lines[ln.name] = {"events": n, "top": sorted(
                total.items(), key=lambda kv: -kv[1])[:top]}
        out[pl.name] = lines
    return out


def _union(intervals):
    """Merged, sorted ``[start, end]`` intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_seconds(events) -> float:
    return sum(e - s for s, e in _union((s, s + d) for _, s, d in events))


def matching(events, pattern: str):
    rx = re.compile(pattern)
    return [ev for ev in events if rx.search(ev[0])]


def within(events, modules, pattern: str):
    """The events that start inside a run of a module (a jitted program,
    an event of the ``XLA Modules`` line) whose name matches ``pattern``."""
    rx = re.compile(pattern)
    runs = _union((s, s + d) for n, s, d in modules if rx.search(n))
    out, j = [], 0
    for ev in sorted(events, key=lambda ev: ev[1]):
        while j < len(runs) and runs[j][1] <= ev[1]:
            j += 1
        if j < len(runs) and runs[j][0] <= ev[1]:
            out.append(ev)
    return out


def op_seconds(events, pattern: str) -> float:
    """Device time of the operations whose name matches ``pattern``
    (the union, so a match nested in a match counts once)."""
    return busy_seconds(matching(events, pattern))


def exposed_seconds(events, pattern: str) -> float:
    """Of the matching operations' time, the part during which no other
    operation runs on the device."""
    rx = re.compile(pattern)
    mine = _union((s, s + d) for n, s, d in events if rx.search(n))
    other = _union((s, s + d) for n, s, d in events if not rx.search(n))
    hidden = 0.0
    j = 0
    for s, e in mine:
        while j < len(other) and other[j][1] <= s:
            j += 1
        k = j
        while k < len(other) and other[k][0] < e:
            hidden += min(e, other[k][1]) - max(s, other[k][0])
            k += 1
    return sum(e - s for s, e in mine) - hidden


_PS = 1e12      # a trace's times are whole picoseconds; ``load`` hands seconds


def self_times(events) -> dict:
    """``{name: seconds}`` of each operation's own time: its duration
    less the time its nested operations cover.

    Starts and ends are compared in whole picoseconds, the trace's own
    unit; the times themselves stay the seconds they came in. In float
    seconds an end reads an ulp past the start it abuts (``s + d > s'``
    where the trace has ``s + d == s'``): in a loop body whose operations
    abut, the finished sibling stayed open and the next operation was
    not taken off the ``while`` that holds both (5-17 ms a step in the
    hybrid cells, PR 37). What holds now, and a test holds it: **the own
    times of one device line sum to ``busy_seconds`` of that line**
    wherever operations nest or abut and never partly overlap."""
    out = {}
    stack = []          # [name, end in picoseconds, own seconds]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)
    spans = sorted(((round(s * _PS), round(d * _PS), name, d)
                    for name, s, d in events), key=lambda x: (x[0], -x[1]))
    for start, length, name, d in spans:
        close(start)
        if stack and start + length <= stack[-1][1]:    # nested, not just
            stack[-1][2] -= d                           # overlapping
        stack.append([name, start + length, d])
    close(float("inf"))
    return out


_INSTR = re.compile(r"^(%[\w.\-]+) = .*? ([\w\-]+)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short(name: str) -> str:
    """The v5e's traces name an operation by its whole HLO instruction;
    keep its name, its opcode and a custom call's target."""
    m = _INSTR.match(name)
    if not m:
        return name[:120]
    t = _TARGET.search(name)
    return f"{m.group(1)} {m.group(2)}" + (f":{t.group(1)}" if t else "")


def family(name: str) -> str:
    """``short`` without the instruction's number: the layers' copies of
    one operation (``%fusion.12``, ``%fusion.13``) are one family."""
    head, _, rest = short(name).partition(" ")
    return f"{re.sub(r'[.][0-9]+$', '', head)} {rest}".strip()


def top_ops(events, n: int = 10) -> list:
    """The operation families that took most of the device's time, by
    their own time (a ``while`` without the operations it holds)."""
    total = {}
    for name, seconds in self_times(events).items():
        total[family(name)] = total.get(family(name), 0.0) + seconds
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(events, n: int = 10) -> list:
    """The longest gaps between operations, each named by the operation
    that ended it (the host spans that would say what the host was doing
    are not in the profiler's trace yet: PERF.md, Open questions)."""
    merged = _union((s, s + d) for _, s, d in events)
    starts = {s: name for name, s, _ in sorted(events, key=lambda ev: -ev[2])}
    gaps = [(b[0] - a[1], b[0]) for a, b in zip(merged, merged[1:])]
    return [[f"before {short(starts.get(at, '?'))}", gap]
            for gap, at in sorted(gaps, reverse=True)[:n]]
