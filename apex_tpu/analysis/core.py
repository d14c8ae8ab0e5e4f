"""apex_lint core — findings, the rule registry, and program/source views.

The engine side of ``tools/apex_lint.py``: a *rule* is a named,
severity-tagged function over one of two view types —

- :class:`ProgramView`: a compiled-step program (a jitted callable +
  example arguments). The view traces the program ONCE (abstractly —
  nothing executes, donated buffers are not consumed) and exposes what
  every jaxpr rule needs: the closed jaxpr (walkable via
  ``analysis.walker``), flat in/out avals with pytree-path labels,
  per-input donation flags (read off the jit equation's
  ``donated_invars``), the ``parallel.Plan`` the program was compiled
  with (so a rule can reason about the selected lowering), and the
  scheduler-lineage metadata the serve engine declares. A trace that
  *fails* is itself evidence (``trace_error`` — e.g. ``NameError:
  unbound axis name`` when a named-axis collective can't bind under
  the program's lowering) and rules may match on it.
- :class:`SourceView`: a parsed Python source file for host-side
  hazard rules (AST + raw lines + inline-suppression table).

Suppression contract (docs/ANALYSIS.md): every suppression carries a
MANDATORY human reason —

- inline, for source findings::

      packed = np.asarray(packed)  # apex-lint: disable=host-sync-in-hot-loop -- the ONE sync per step

  (same line or the line above; a suppression without ``-- reason``
  is itself an error finding, rule ``bad-suppression``);
- the committed baseline file for program findings and accepted
  pre-existing debt: ``apex_lint_baseline.json`` maps finding
  fingerprints to reasons.

Source-finding fingerprints key on the *stripped source line text*,
not the line number, so baselines survive unrelated edits.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
import re
from typing import Any, Callable, Optional

__all__ = ["Finding", "Rule", "RULES", "rule", "ProgramView",
           "SourceView", "LintReport", "run_rules", "load_baseline",
           "apply_baseline", "SUPPRESS_RX"]

SEVERITIES = ("error", "warning", "info")

SUPPRESS_RX = re.compile(
    r"#\s*apex-lint:\s*disable=([\w,\-]+)(?:\s+--\s*(\S.*))?")


@dataclasses.dataclass
class Finding:
    """One rule violation (or suppressed violation) at one site."""
    rule: str
    severity: str
    target: str                    # program name or source path
    location: str                  # "in[3]", "out[1]", "line 42", scope
    message: str
    details: dict = dataclasses.field(default_factory=dict)
    suppressed: bool = False
    reason: Optional[str] = None   # the suppression's mandatory reason
    line_text: Optional[str] = None  # source findings: stripped line

    @property
    def fingerprint(self) -> str:
        """Stable id for baseline matching. Source findings key on the
        offending line's text (survives line-number drift); program
        findings key on (rule, program, location)."""
        tail = self.line_text if self.line_text is not None \
            else self.location
        return f"{self.rule}:{self.target}:{tail}"

    def to_dict(self) -> dict:
        d = {"rule": self.rule, "severity": self.severity,
             "target": self.target, "location": self.location,
             "message": self.message, "fingerprint": self.fingerprint,
             "suppressed": self.suppressed}
        if self.reason:
            d["reason"] = self.reason
        if self.details:
            d["details"] = self.details
        return d


@dataclasses.dataclass(frozen=True)
class Rule:
    name: str
    severity: str                  # default severity (rules may vary)
    kind: str                      # "program" | "source"
    doc: str
    fn: Callable

RULES: dict[str, Rule] = {}


def rule(name: str, *, severity: str, kind: str, doc: str = ""):
    """Register a rule: ``fn(view) -> list[Finding]``."""
    assert severity in SEVERITIES, severity

    def deco(fn):
        RULES[name] = Rule(name, severity, kind, doc or (fn.__doc__ or ""),
                           fn)
        return fn
    return deco


# -- program views ---------------------------------------------------------

def _tree_paths(tree) -> list[str]:
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [jax.tree_util.keystr(path) for path, _ in flat]


@dataclasses.dataclass
class ProgramView:
    """One compiled-step program as the jaxpr rules see it.

    ``fn`` should be the *jitted* callable (donation info comes from
    its jit equation); a plain callable still traces but reports no
    donation. ``lineages``/``warmup_lineages`` carry the scheduler
    dataflow a donated program participates in (the serve engine
    declares these — see ``ContinuousBatchingEngine.program_lineages``)
    and feed the layout-recompile-hazard rule. ``consumed_outputs``
    names the top-level output slots the registered caller actually
    reads (``None`` = unknown, the dead-output rule skips).
    """
    name: str
    fn: Callable
    example_args: tuple
    plan: Any = None               # parallel.Plan, when plan-compiled
    expect_half: bool = False      # a half-precision policy was asked
    lineages: Optional[frozenset] = None
    warmup_lineages: Optional[frozenset] = None
    consumed_outputs: Optional[frozenset] = None
    notes: dict = dataclasses.field(default_factory=dict)
    _cache: dict = dataclasses.field(default_factory=dict, repr=False)

    def _trace(self) -> None:
        if "traced" in self._cache:
            return
        import jax
        self._cache["traced"] = True
        try:
            cj = jax.make_jaxpr(self.fn)(*self.example_args)
        except Exception as e:            # the failure IS the evidence
            self._cache["error"] = e
            return
        self._cache["closed_jaxpr"] = cj
        donated = None
        eqns = cj.jaxpr.eqns
        if len(eqns) == 1 and eqns[0].primitive.name == "jit":
            donated = tuple(eqns[0].params.get("donated_invars") or ())
            if len(donated) != len(cj.in_avals):
                donated = None
        self._cache["donated"] = donated
        try:
            out_shape = jax.eval_shape(self.fn, *self.example_args)
            self._cache["out_shape"] = out_shape
        except Exception:
            self._cache["out_shape"] = None

    @property
    def trace_error(self) -> Optional[Exception]:
        self._trace()
        return self._cache.get("error")

    @property
    def closed_jaxpr(self):
        self._trace()
        return self._cache.get("closed_jaxpr")

    @property
    def donated_invars(self) -> Optional[tuple]:
        """Per-flat-input donation flags, or None when unknown (plain
        function, or donation info unavailable on this jax)."""
        self._trace()
        return self._cache.get("donated")

    @property
    def in_avals(self) -> list:
        return list(self.closed_jaxpr.in_avals) if self.closed_jaxpr \
            else []

    @property
    def out_avals(self) -> list:
        return list(self.closed_jaxpr.out_avals) if self.closed_jaxpr \
            else []

    @property
    def in_paths(self) -> list[str]:
        if "in_paths" not in self._cache:
            self._cache["in_paths"] = _tree_paths(self.example_args)
        return self._cache["in_paths"]

    def out_children(self) -> list[tuple[str, Any]]:
        """Top-level output slots as ``(slot_name, subtree)`` — the
        granularity the dead-output rule reports at."""
        self._trace()
        out = self._cache.get("out_shape")
        if out is None:
            return []
        if isinstance(out, (tuple, list)):
            return [(str(i), sub) for i, sub in enumerate(out)]
        return [("0", out)]

    def lowering_name(self) -> str:
        """The selected lowering: the Plan's choice when plan-compiled,
        else plain ``jit``."""
        if self.plan is not None:
            try:
                return self.plan.lowering()
            except Exception:
                return "jit"
        return "jit"


# -- source views ----------------------------------------------------------

@dataclasses.dataclass
class SourceView:
    """One parsed Python file for the AST (host-side) rules."""
    path: str                      # as reported in findings
    text: str
    tree: ast.AST
    lines: list[str]

    @classmethod
    def from_file(cls, path: str, root: Optional[str] = None
                  ) -> "SourceView":
        with open(path) as fh:
            text = fh.read()
        rel = os.path.relpath(path, root) if root else path
        return cls.from_text(rel, text)

    @classmethod
    def from_text(cls, path: str, text: str) -> "SourceView":
        return cls(path=path, text=text, tree=ast.parse(text),
                   lines=text.splitlines())

    def suppressions_at(self, lineno: int) -> dict[str, Optional[str]]:
        """Inline suppressions covering 1-indexed ``lineno`` (same line
        or the line above): rule name -> reason (None = missing)."""
        out: dict[str, Optional[str]] = {}
        for ln in (lineno - 1, lineno):      # line above, then same
            if 1 <= ln <= len(self.lines):
                m = SUPPRESS_RX.search(self.lines[ln - 1])
                if m:
                    reason = (m.group(2) or "").strip() or None
                    for r in m.group(1).split(","):
                        out[r.strip()] = reason
        return out

    def bad_suppressions(self) -> list[Finding]:
        """Every inline suppression missing its mandatory reason."""
        out = []
        for i, line in enumerate(self.lines, 1):
            m = SUPPRESS_RX.search(line)
            if m and not (m.group(2) or "").strip():
                out.append(Finding(
                    rule="bad-suppression", severity="error",
                    target=self.path, location=f"line {i}",
                    message="suppression without a reason — append "
                            "' -- <why this is safe>'",
                    line_text=line.strip()))
        return out

    def line(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""


# -- the engine ------------------------------------------------------------

def _select(rules: Optional[list], kind: str) -> list[Rule]:
    names = list(RULES) if rules is None else list(rules)
    missing = [n for n in names if n not in RULES]
    if missing:
        raise KeyError(f"unknown rule(s): {missing}; "
                       f"known: {sorted(RULES)}")
    return [RULES[n] for n in names if RULES[n].kind == kind]


def run_rules(targets, rules: Optional[list] = None) -> "LintReport":
    """Run the (selected) registry over program and source views.
    Inline suppressions are applied here; baseline suppression is a
    separate pass (:func:`apply_baseline`) so callers control which
    baseline file governs."""
    if rules is not None:            # validate even with no targets
        _select(rules, "program")
    findings: list[Finding] = []
    for t in targets:
        if isinstance(t, ProgramView):
            for r in _select(rules, "program"):
                findings.extend(r.fn(t))
        elif isinstance(t, SourceView):
            findings.extend(t.bad_suppressions())
            for r in _select(rules, "source"):
                for f in r.fn(t):
                    lineno = None
                    if f.location.startswith("line "):
                        try:
                            lineno = int(f.location.split()[1])
                        except ValueError:
                            pass
                    if lineno is not None:
                        sup = t.suppressions_at(lineno)
                        if f.rule in sup:
                            reason = sup[f.rule]
                            if reason:   # reasonless ones already err'd
                                f.suppressed, f.reason = True, reason
                    findings.append(f)
        else:
            raise TypeError(f"not a lintable view: {t!r}")
    return LintReport(findings=findings)


def load_baseline(path: str) -> tuple[dict, list[Finding]]:
    """Read a baseline file -> (fingerprint -> reason, error findings
    for malformed entries). Missing file = empty baseline."""
    if not os.path.exists(path):
        return {}, []
    with open(path) as fh:
        data = json.load(fh)
    table: dict = {}
    bad: list[Finding] = []
    for ent in data.get("suppressions", []):
        fp = ent.get("fingerprint", "")
        reason = (ent.get("reason") or "").strip()
        if not fp or not reason:
            bad.append(Finding(
                rule="bad-suppression", severity="error", target=path,
                location=fp or "<missing fingerprint>",
                message="baseline entry without a fingerprint+reason "
                        "pair — every accepted finding must say why"))
            continue
        table[fp] = reason
    return table, bad


def apply_baseline(report: "LintReport", baseline: dict
                   ) -> "LintReport":
    for f in report.findings:
        if not f.suppressed and f.fingerprint in baseline:
            f.suppressed = True
            f.reason = baseline[f.fingerprint]
    return report


@dataclasses.dataclass
class LintReport:
    findings: list

    def errors(self) -> list[Finding]:
        return [f for f in self.findings
                if f.severity == "error" and not f.suppressed]

    def counts(self) -> dict:
        out = {"error": 0, "warning": 0, "info": 0, "suppressed": 0}
        for f in self.findings:
            if f.suppressed:
                out["suppressed"] += 1
            else:
                out[f.severity] += 1
        return out

    def to_json(self, **extra) -> dict:
        return {"version": 1,
                "counts": self.counts(),
                "findings": [f.to_dict() for f in self.findings],
                **extra}

    def format_human(self) -> str:
        sev_rank = {"error": 0, "warning": 1, "info": 2}
        live = sorted((f for f in self.findings if not f.suppressed),
                      key=lambda f: (sev_rank.get(f.severity, 3),
                                     f.target, f.location))
        lines = []
        for f in live:
            lines.append(f"{f.severity.upper():7s} {f.rule}  "
                         f"{f.target} @ {f.location}")
            lines.append(f"        {f.message}")
        sup = [f for f in self.findings if f.suppressed]
        if sup:
            lines.append("")
            lines.append(f"{len(sup)} suppressed finding(s):")
            for f in sup:
                lines.append(f"  - {f.rule} {f.target} @ {f.location}"
                             f" — {f.reason}")
        c = self.counts()
        lines.append("")
        lines.append(f"apex_lint: {c['error']} unsuppressed error(s), "
                     f"{c['warning']} warning(s), {c['info']} info, "
                     f"{c['suppressed']} suppressed")
        return "\n".join(lines)
