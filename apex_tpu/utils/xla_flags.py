"""XLA/libtpu scheduler + fusion flag presets — the r06 idle-slice A/B
knobs, applied before backend init.

The r05b headline trace carries 66 ms of on-device IDLE inside the
compiled step (TRACE_TOP_OPS_r05b.md); ``prof.gaps`` attributes the
seams, and the scheduler knobs here are the elimination levers XLA
exposes for them: the latency-hiding scheduler reorders the program so
outstanding DMAs cover fusion-boundary dead time, the async-collective
knobs keep cross-replica seams off the critical path, and the scoped
VMEM limit trades prefetch depth against fusion size.

Discipline (same as BENCH_DEFAULTS.json): every knob is **off unless
armed via env**, so a plain run measures the measured-default config and
an armed run is an A/B arm. Flags ride ``LIBTPU_INIT_ARGS`` (read by libtpu when the
TPU client initializes; inert on CPU-only runs), so ``apply()`` must run
before the first backend-touching jax call — ``setup_host_backend()``
calls it at startup.

Env surface:

- ``APEX_XLA_PRESET=perf`` — arm the recommended elimination set
  (latency-hiding scheduler + async collective fusion + compute/
  collective overlap); individual vars below override per knob.
- ``APEX_XLA_LHS=1|0`` — latency-hiding scheduler on/off.
- ``APEX_XLA_ASYNC_COLL=1|0`` — async collective fusion on/off.
- ``APEX_XLA_OVERLAP_CC=1|0`` — overlap compute with collectives.
- ``APEX_XLA_VMEM_KIB=N`` — scoped VMEM limit in KiB (int).

Unset vars leave the compiler default untouched.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Mapping, MutableMapping, Optional

__all__ = ["Knob", "KNOBS", "PRESETS", "armed_flags", "apply"]


@dataclasses.dataclass(frozen=True)
class Knob:
    """One A/B-able compiler knob: env var -> libtpu/XLA flag."""
    name: str
    env: str
    flag: str
    kind: str       # "bool" (env 1/0 -> true/false) or "int" (env N)
    rationale: str

    def render(self, raw: str) -> str:
        if self.kind == "bool":
            if raw not in ("0", "1"):
                raise ValueError(
                    f"{self.env} must be '1' or '0', got {raw!r}")
            return f"{self.flag}={'true' if raw == '1' else 'false'}"
        try:
            return f"{self.flag}={int(raw)}"
        except ValueError:
            raise ValueError(f"{self.env} must be an integer, got {raw!r}")


KNOBS: tuple[Knob, ...] = (
    Knob("latency_hiding_scheduler", "APEX_XLA_LHS",
         "--xla_tpu_enable_latency_hiding_scheduler", "bool",
         "reorder the program so in-flight DMAs cover fusion-boundary "
         "dead time (the r05b fusion-break gap class)"),
    Knob("async_collective_fusion", "APEX_XLA_ASYNC_COLL",
         "--xla_tpu_enable_async_collective_fusion", "bool",
         "keep cross-replica collectives off the critical path "
         "(the collective-boundary gap class)"),
    Knob("overlap_compute_collective", "APEX_XLA_OVERLAP_CC",
         "--xla_tpu_overlap_compute_collective_tc", "bool",
         "overlap tensor-core compute with collective DMA"),
    Knob("scoped_vmem_limit_kib", "APEX_XLA_VMEM_KIB",
         "--xla_tpu_scoped_vmem_limit_kib", "int",
         "prefetch depth vs fusion size (bigger fusions can close "
         "convert seams; too big starves double-buffering)"),
)

# Named presets arm a knob set; per-knob env vars still override.
PRESETS: dict[str, dict[str, str]] = {
    "perf": {"APEX_XLA_LHS": "1", "APEX_XLA_ASYNC_COLL": "1",
             "APEX_XLA_OVERLAP_CC": "1"},
}


def armed_flags(env: Optional[Mapping[str, str]] = None) -> list[str]:
    """Resolve preset + per-knob env vars into the flag strings to
    apply. Raises ValueError on malformed values (an A/B arm must fail
    loudly, not silently measure the default config)."""
    env = os.environ if env is None else env
    preset = env.get("APEX_XLA_PRESET", "")
    if preset and preset not in PRESETS:
        raise ValueError(f"APEX_XLA_PRESET={preset!r}; known presets: "
                         f"{sorted(PRESETS)}")
    effective = dict(PRESETS.get(preset, {}))
    for k in KNOBS:
        if k.env in env:
            effective[k.env] = env[k.env]
    return [k.render(effective[k.env]) for k in KNOBS
            if k.env in effective]


def apply(env: Optional[MutableMapping[str, str]] = None) -> list[str]:
    """Append the armed flags to ``LIBTPU_INIT_ARGS`` (idempotent:
    flags already present are not duplicated). Returns the flag strings
    that ended up applied — empty for a plain (unarmed) run.

    Must run before the first backend-touching jax call;
    ``setup_host_backend()`` calls it."""
    env = os.environ if env is None else env
    flags = armed_flags(env)
    if not flags:
        return []
    current = env.get("LIBTPU_INIT_ARGS", "")
    merged = current.split()
    for f in flags:
        name = f.split("=", 1)[0]
        # an armed knob replaces a stale setting of the same flag
        merged = [m for m in merged if not m.startswith(name + "=")
                  and m != name]
        merged.append(f)
    env["LIBTPU_INIT_ARGS"] = " ".join(merged)
    return flags
