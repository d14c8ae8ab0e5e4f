"""Precision-coverage audit — how much of a step actually runs in half.

Mixed precision that silently degrades to fp32 is invisible in every
artifact this repo ships: O1 autocast executes control-flow bodies at
their traced dtypes (amp/autocast.py ``_OPAQUE_CALL_PRIMS``), so a
scanned model gets NO mixed precision under O1 — a known gap (ROADMAP
"O1 autocast still skips control-flow bodies") that no number measured
until now. This module walks the jaxpr of a step function and reports,
per top-level module scope:

- the op count by compute-dtype class (``f16`` / ``bf16`` / ``f32`` /
  ``f64``), float ops only;
- estimated MXU FLOPs by dtype class (``dot_general`` and convolution
  only — the ops whose precision decides throughput; elementwise FLOPs
  would only dilute the share);
- every control-flow body (scan/while/cond) as its own scope, with an
  explicit flag when a body carrying float ops has ZERO half-precision
  ops while the surrounding program has some — the O1 gap as a number
  a regression test can pin (tests/test_numerics.py).

Scope attribution uses ``eqn.source_info.name_stack`` (the same
``jax.named_scope`` metadata XLA puts in HLO op names), so models
annotated with named scopes (models/resnet.py stem/stage/head) report
per-module; unannotated ops land in ``main``.

``tools/precision_audit.py`` is the CLI; ``format_coverage`` renders
the markdown table (NUMERICS_* artifacts); ``summary_dict`` feeds the
``numerics``/coverage telemetry record (prof.metrics schema 2).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from apex_tpu.analysis import walker as _walker

__all__ = ["HALF_CLASSES", "CoverageReport", "audit_jaxpr", "audit_fn",
           "format_coverage"]

HALF_CLASSES = ("f16", "bf16")

# Traversal now lives in apex_tpu.analysis.walker (r15: the coverage
# audit's scope machinery generalized into the static-analysis rule
# API); _CF_PRIMS kept as an alias — scan/while/cond bodies audit as
# their own scopes and are eligible for the fp32-only flag, everything
# else carrying a sub-jaxpr (pjit, shard_map, remat, custom_*) is
# TRANSPARENT: a plan-compiled step (parallel/plan.py) audits with the
# same per-module scopes as a plain jit step (tests/test_plan.py).
_CF_PRIMS = _walker.CF_PRIMS

_DTYPE_CLASS = {"float16": "f16", "bfloat16": "bf16",
                "float32": "f32", "float64": "f64"}


def _cls(dtype) -> Optional[str]:
    return _DTYPE_CLASS.get(jnp.dtype(dtype).name)


def _float_aval(v) -> Optional[Any]:
    aval = getattr(v, "aval", None)
    dt = getattr(aval, "dtype", None)
    if dt is not None and jnp.issubdtype(dt, jnp.floating):
        return aval
    return None


def _eqn_class(eqn) -> Optional[str]:
    """Compute-dtype class of one equation, or None for non-float ops.
    MXU ops classify by their lhs operand (the dtype the systolic array
    multiplies in — ``preferred_element_type`` only widens the
    accumulator); everything else by its first float output."""
    if eqn.primitive.name in ("dot_general", "conv_general_dilated"):
        a = _float_aval(eqn.invars[0])
        if a is not None:
            return _cls(a.dtype)
    for v in list(eqn.outvars) + list(eqn.invars):
        a = _float_aval(v)
        if a is not None:
            return _cls(a.dtype)
    return None


def _eqn_flops(eqn) -> float:
    """Estimated FLOPs for the MXU primitives (2 flops/MAC); 0 for
    everything else. Loop bodies are counted ONCE — trip counts are not
    modeled, matching XLA's HloCostAnalysis convention."""
    try:
        out = eqn.outvars[0].aval.shape
        if eqn.primitive.name == "dot_general":
            (contract, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            k = 1
            for d in contract:
                k *= lhs[d]
            n = 1
            for d in out:
                n *= d
            return 2.0 * n * k
        if eqn.primitive.name == "conv_general_dilated":
            rhs = eqn.invars[1].aval.shape
            dn = eqn.params["dimension_numbers"]
            k = rhs[dn.rhs_spec[1]]          # input-feature dim
            for d in dn.rhs_spec[2:]:        # kernel spatial dims
                k *= rhs[d]
            n = 1
            for d in out:
                n *= d
            return 2.0 * n * k
    except Exception:
        pass
    return 0.0


# Back-compat aliases: traversal moved to apex_tpu.analysis.walker.
_scope_of = _walker.scope_of
_sub_jaxprs = _walker.sub_jaxprs


@dataclasses.dataclass
class _Scope:
    ops: dict = dataclasses.field(default_factory=dict)    # class -> count
    flops: dict = dataclasses.field(default_factory=dict)  # class -> flops
    control_flow: bool = False

    def add(self, cls: str, flops: float) -> None:
        self.ops[cls] = self.ops.get(cls, 0) + 1
        if flops:
            self.flops[cls] = self.flops.get(cls, 0.0) + flops

    @property
    def float_ops(self) -> int:
        return sum(self.ops.values())

    @property
    def half_ops(self) -> int:
        return sum(self.ops.get(c, 0) for c in HALF_CLASSES)


@dataclasses.dataclass(frozen=True)
class CoverageReport:
    """Aggregate precision coverage over one step function."""
    scopes: dict            # scope name -> {"ops", "flops", "control_flow"}
    total_ops: dict         # class -> count (float ops only)
    total_flops: dict       # class -> estimated MXU flops
    cf_fp32_only: tuple     # control-flow scopes with floats but 0 half ops

    @property
    def half_op_share(self) -> float:
        tot = sum(self.total_ops.values())
        half = sum(self.total_ops.get(c, 0) for c in HALF_CLASSES)
        return half / max(tot, 1)

    @property
    def half_flop_share(self) -> float:
        tot = sum(self.total_flops.values())
        half = sum(self.total_flops.get(c, 0.0) for c in HALF_CLASSES)
        return half / max(tot, 1e-9)

    def summary_dict(self) -> dict:
        """The coverage telemetry-record / JSON-line fields."""
        return {
            "half_op_share": round(self.half_op_share, 4),
            "half_flop_share": round(self.half_flop_share, 4),
            "ops": dict(self.total_ops),
            "flops": {k: float(v) for k, v in self.total_flops.items()},
            "cf_fp32_only": list(self.cf_fp32_only),
        }


def audit_jaxpr(jaxpr, *, expect_half: bool = False) -> CoverageReport:
    """Walk a (Closed)Jaxpr and aggregate precision coverage. Control
    flow bodies become their own scopes named
    ``<prim>:<param>@<outer scope>``.

    The fp32-only flag fires for a float-carrying control-flow body
    with zero half ops when the surrounding program has some — or
    unconditionally with ``expect_half=True`` (callers that KNOW a
    half-precision policy was requested, e.g. tools/precision_audit.py
    under O1/O2: a fully-scanned model under O1 has zero half ops
    anywhere, which is the gap at its worst, not a clean audit)."""
    scopes: dict[str, _Scope] = {}
    for view in _walker.iter_eqns(jaxpr):
        # a control-flow container registers its body scopes up front,
        # so an empty body still appears in the table
        for name in view.cf_children:
            scopes.setdefault(name, _Scope()).control_flow = True
        if not view.leaf:
            continue
        cls = _eqn_class(view.eqn)
        if cls is None:
            continue
        scopes.setdefault(view.scope, _Scope()).add(
            cls, _eqn_flops(view.eqn))
    total_ops: dict = {}
    total_flops: dict = {}
    for s in scopes.values():
        for c, n in s.ops.items():
            total_ops[c] = total_ops.get(c, 0) + n
        for c, f in s.flops.items():
            total_flops[c] = total_flops.get(c, 0.0) + f
    any_half = expect_half or \
        sum(total_ops.get(c, 0) for c in HALF_CLASSES) > 0
    flags = tuple(name for name, s in scopes.items()
                  if s.control_flow and s.float_ops > 0
                  and s.half_ops == 0 and any_half)
    return CoverageReport(
        scopes={name: {"ops": dict(s.ops), "flops": dict(s.flops),
                       "control_flow": s.control_flow}
                for name, s in scopes.items()},
        total_ops=total_ops, total_flops=total_flops,
        cf_fp32_only=flags)


def audit_fn(fn: Callable, *example_args, expect_half: bool = False,
             **example_kwargs) -> CoverageReport:
    """Trace ``fn`` on the example args and audit its jaxpr (abstract —
    nothing executes, so auditing a TPU-sized step is free on any
    host)."""
    return audit_jaxpr(jax.make_jaxpr(fn)(*example_args,
                                          **example_kwargs),
                       expect_half=expect_half)


def format_coverage(report: CoverageReport, title: str = "step"
                    ) -> str:
    """Markdown coverage table (the NUMERICS_* artifact format)."""
    classes = [c for c in ("f16", "bf16", "f32", "f64")
               if report.total_ops.get(c) or report.total_flops.get(c)]
    lines = [f"precision coverage of `{title}`: "
             f"{100 * report.half_op_share:.1f}% of float ops / "
             f"{100 * report.half_flop_share:.1f}% of estimated MXU "
             f"FLOPs in half precision", ""]
    hdr = "| scope | " + " | ".join(f"{c} ops" for c in classes) + \
        " | half FLOP share |"
    lines += [hdr, "|" + "---|" * (len(classes) + 2)]

    def flop_share(flops: dict) -> str:
        tot = sum(flops.values())
        if tot <= 0:
            return "-"
        half = sum(flops.get(c, 0.0) for c in HALF_CLASSES)
        return f"{100 * half / tot:.1f}%"

    for name in sorted(report.scopes,
                       key=lambda n: -sum(
                           report.scopes[n]["flops"].values())):
        s = report.scopes[name]
        cells = " | ".join(str(s["ops"].get(c, 0)) for c in classes)
        mark = " ⚠ fp32-only" if name in report.cf_fp32_only else ""
        lines.append(f"| `{name}`{mark} | {cells} | "
                     f"{flop_share(s['flops'])} |")
    lines.append("")
    if report.cf_fp32_only:
        lines.append(
            f"FLAG: {len(report.cf_fp32_only)} control-flow "
            f"{'body executes' if len(report.cf_fp32_only) == 1 else 'bodies execute'} "
            f"ZERO half-precision ops while the surrounding "
            f"program is mixed precision (the O1 autocast control-flow "
            f"gap, ROADMAP):")
        lines += [f"- `{n}`" for n in report.cf_fp32_only]
    else:
        lines.append("no fp32-only control-flow bodies flagged")
    return "\n".join(lines)
