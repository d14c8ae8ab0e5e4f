"""Generalized jaxpr traversal — the rule API's view of a program.

The scope-attribution machinery r09's precision-coverage audit built
(``prof/coverage.py``: named-scope modules, autodiff-transform
stripping, control-flow bodies as their own scopes, transparent
pjit/remat/custom_* bodies) generalized into one reusable walker so a
static-analysis rule doesn't re-implement traversal: :func:`iter_eqns`
yields every equation of a (Closed)Jaxpr — containers before their
bodies — as an :class:`EqnView` carrying

- ``scope``: the attribution scope (first ``jax.named_scope``
  component, transform wrappers stripped; a control-flow body's label
  wins over the named scope — exactly coverage.py's convention);
- ``cf_scope``: the innermost scan/while/cond body label, or ``None``
  at top level (``<prim>:<param>@<outer scope>``);
- ``cf_children``: for a control-flow *container* equation, the labels
  of the body scopes it creates (so a consumer can register an empty
  body as a scope, matching the r09 table output);
- ``bound_axes``: the named mesh axes in scope at this equation —
  accumulated from enclosing ``shard_map`` equations — which is what
  lets a rule decide whether a ``psum``'s axis name can actually bind
  under the program's lowering (the collective-misuse rule).

``prof.coverage`` is reimplemented on top of this walker; both keep
byte-identical report output (pinned by tests/test_numerics.py).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Iterator, Optional

__all__ = ["CF_PRIMS", "EqnView", "iter_eqns", "scope_of", "sub_jaxprs"]

# Sub-jaxpr-carrying primitives whose bodies autocast executes at
# traced dtypes (amp/autocast.py _OPAQUE_CALL_PRIMS) — each body walks
# as its own scope. Everything else carrying a sub-jaxpr (pjit,
# shard_map, remat, custom_*) is TRANSPARENT: its body keeps the
# surrounding scope.
CF_PRIMS = ("scan", "while", "cond")

_TRANSFORM_RX = re.compile(r"^\w+\((.*)\)$")


def sub_jaxprs(eqn) -> list:
    """(label, jaxpr) sub-computations of an equation, any primitive."""
    out = []
    for key, val in eqn.params.items():
        vals = val if isinstance(val, (list, tuple)) else [val]
        for i, v in enumerate(vals):
            j = getattr(v, "jaxpr", None)    # ClosedJaxpr
            if j is None and hasattr(v, "eqns"):
                j = v                        # raw Jaxpr
            if j is not None and hasattr(j, "eqns"):
                label = key if len(vals) == 1 else f"{key}[{i}]"
                out.append((label, j))
    return out


def scope_of(eqn) -> str:
    """Top-level module scope: first ``jax.named_scope`` component,
    with autodiff transform wrappers stripped so a module's forward
    (``jvp(stem)``) and backward (``transpose(jvp(stem))``) ops
    aggregate under one scope (``stem``)."""
    try:
        stack = str(eqn.source_info.name_stack)
    except Exception:
        stack = ""
    scope = stack.split("/", 1)[0] if stack else ""
    while True:
        m = _TRANSFORM_RX.match(scope)
        if m is None:
            break
        scope = m.group(1)
    return scope or "main"


@dataclasses.dataclass(frozen=True)
class EqnView:
    """One equation in traversal order, with its attribution context."""
    eqn: Any
    scope: str                     # cf label if inside one, else module
    cf_scope: Optional[str]        # innermost control-flow body label
    bound_axes: frozenset          # named axes bound at this point
    leaf: bool                     # True = no sub-jaxprs
    cf_children: tuple = ()        # cf body labels this eqn creates


def iter_eqns(jaxpr) -> Iterator[EqnView]:
    """Walk a (Closed)Jaxpr depth-first, yielding every equation —
    containers before their bodies. Control-flow bodies become scopes
    named ``<prim>:<param>@<outer scope>``; pjit/shard_map/remat/
    custom_* bodies are transparent (keep the surrounding scope), with
    ``shard_map`` additionally binding its mesh's axis names for its
    subtree."""
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr

    def walk(j, cf_label: Optional[str], axes: frozenset,
             around: str = "main") -> Iterator[EqnView]:
        for eqn in j.eqns:
            subs = sub_jaxprs(eqn)
            is_cf = eqn.primitive.name in CF_PRIMS
            # a body's name stacks start anew (pjit, linear_call,
            # pallas_call): an equation with no scope of its own is in
            # the module that holds the equation around it
            module = scope_of(eqn)
            if module == "main":
                module = around
            scope = cf_label if cf_label else module
            children = ()
            if subs and is_cf:
                outer = cf_label or module
                children = tuple(
                    f"{eqn.primitive.name}:{label}@{outer}"
                    for label, _ in subs)
            yield EqnView(eqn, scope, cf_label, axes, not subs, children)
            if not subs:
                continue
            new_axes = axes
            if eqn.primitive.name == "shard_map":
                mesh = eqn.params.get("mesh")
                names = getattr(mesh, "axis_names", ()) or ()
                new_axes = axes | frozenset(str(a) for a in names)
            for (label, sub), child in zip(
                    subs, children or [None] * len(subs)):
                yield from walk(sub, child if is_cf else cf_label,
                                new_axes, module)

    yield from walk(jaxpr, None, frozenset())
