"""What every run shares: the lines it prints, the device it names, the
compilations it counts, the peak it reads, the percentile it takes."""

from __future__ import annotations

import json
import math
import os
import shutil

from benchmarks.spec import HERE, ROOT


class NoAccelerator(RuntimeError):
    pass


def device_line(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def say(device: dict, **fields) -> None:
    """One JSON line that names the device it was measured on."""
    print(json.dumps({**fields, "device": device}), flush=True)


def peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; a kind not in the table is an
    error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peak for {device_kind!r} in "
                       f"benchmarks/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def percentile(values, q: float) -> float:
    """Nearest rank: the smallest value with at least q% of the sample
    at or below it."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of nothing")
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return float(vals[rank - 1])


class CompileCounter:
    """Programs built since ``install``: backend compilations and reads
    of the persistent cache (either one inside a window is set-up that
    leaked into it)."""

    _EVENTS = ("/jax/core/compile/backend_compile_duration",
               "/jax/compilation_cache/cache_hits")

    def __init__(self):
        self.n = 0

    def _on(self, event, *a, **kw):
        if event in self._EVENTS:
            self.n += 1

    @classmethod
    def install(cls):
        import jax.monitoring as m
        c = cls()
        m.register_event_listener(c._on)
        m.register_event_duration_secs_listener(c._on)
        return c


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device (0 where the backend
    reports none, as the CPU does)."""
    stats = [d.memory_stats() or {} for d in devices]
    return max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)


def note_program(ctx, compiled) -> None:
    """Print a compiled program's memory analysis and keep its footprint
    (arguments + temporaries + code; donated outputs alias arguments)."""
    mem = compiled.memory_analysis()
    if mem is None:
        return
    parts = {"arguments": mem.argument_size_in_bytes,
             "temporaries": mem.temp_size_in_bytes,
             "code": mem.generated_code_size_in_bytes}
    ctx.say(program_bytes=parts)
    ctx.program_bytes.append(sum(parts.values()))


def out_dir(cell_name: str, sub: str) -> str:
    """A fresh directory for a run's leftovers, inside the checkout."""
    path = os.path.join(ROOT, ".bench_out", cell_name, sub)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path
