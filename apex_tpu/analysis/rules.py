"""The apex_lint rule catalog — thirteen bug classes this repo actually
hit.

Every rule is grounded in an incident from r06-r19 (docs/ANALYSIS.md
maps each to its round):

- ``donation-miss`` (error): an input buffer shape/dtype-matches an
  output but isn't donated — the per-step copy the r06 donation audit
  hunted in HLO, now checked at the aval level for every program.
- ``layout-recompile-hazard`` (error): a donated jitted program is
  reachable from more input-layout lineages than its ``warmup()``
  covers — the r14 mid-run ~1.2 s recompile stall (donated-program
  jit caches key on concrete input LAYOUTS), as a rule.
- ``host-sync-in-hot-loop`` (error in production paths, warning in
  measurement tools): a blocking fetch / implicit device->host
  conversion inside a timed loop — the class span forensics kept
  finding at the bottom of tail-latency tables.
- ``precision-gap`` (error): a float-carrying control-flow body with
  ZERO half-precision ops under a half policy — the O1 autocast
  control-flow gap (ROADMAP; strict xfail in tests/test_numerics.py),
  via the same ``prof.coverage`` audit that pinned it in r09.
- ``collective-misuse`` (error): a named-axis collective bound under a
  Plan lowering that can't carry it — jax binds a named axis only
  under shard_map, the lowering ``parallel/plan.py`` gives a Plan
  with in_specs/out_specs.
- ``dead-output`` (warning): a program output its registered caller
  never reads — computed, shipped, dropped.
- ``bare-json-line`` (error, tools only): a measurement tool printing
  a ``{"metric", "value"}`` result line without the r16
  ``run_meta``/``format`` stamp — the artifact self-description gap
  serve_bench/decode_bench had until the trajectory store needed
  provenance (``BENCH_TRAJECTORY.json``).
- ``snapshot-on-step-path`` (error): synchronous snapshot
  serialization (``.state_dict()`` host fetches, ``pickle.dump`` /
  ``np.save*`` / ``json.dump``) inside a timed loop — the r17
  ``apex_tpu.runtime`` async-snapshot contract as a static rule.
- ``blocking-emit-on-step-path`` (error): socket ``send*``/``connect``
  or a blocking ``Queue.put`` inside a timed loop — the r18
  ``prof.live.LiveEmitter`` non-blocking contract as a static rule
  (the step path may ``put_nowait`` into a bounded queue; everything
  that can block belongs on the background sender thread).
- ``unattributed-shed`` (error): a shed/drop bookkeeping site (a
  ``*shed*`` counter bump or ``*shed*`` list append) in a function
  that never writes the attribution naming the triggering ``rule``
  and the ``replica`` — the r19 router load-shedding contract as a
  static rule (shedding trades completion for tail latency, and the
  trade is only honest when every dropped request is counted AND
  named; an unattributed drop is indistinguishable from a LOST one,
  which is exactly what the zero-drop contract flags).
- ``page-gather-hazard`` (error): a page-map operand of the paged KV
  gather rebuilt or fetched inside a timed loop — the r14
  layout-recompile landmine applied to the r20 paged arena's new
  gather operand. The page table must be a loop-invariant HOST
  ``np.int32`` buffer mutated in place: ``jnp.asarray``/``jnp.array``/
  ``device_put`` of a page-named value per step mints a fresh device
  buffer whose layout lineage the donated gather program has never
  seen (layout-keyed jit caches -> ~1.2 s recompile landing in TTFT),
  and ``np.asarray`` of a page-named bare name is a host fetch if the
  table ever went device-resident — a sync on the decode path.
- ``orphan-span`` (error): a span opened by a string-literal
  ``tracer.begin("...")`` / ``tracer.instant("...")`` that carries
  none of ``request=`` / ``trace=`` / ``parent=`` — the r22 fleet
  trace-merge contract as a static rule. A span with no request, no
  trace id, and no parent chain can NEVER join a merged cross-process
  timeline: it resolves to no trace at merge time and lands in the
  merge's ``orphans`` list, which the distributed-trace CI smoke
  asserts empty. Scheduler-scope spans (``decode_step``,
  ``prefill_batch``) are shared across requests by design and say so
  with an inline suppression.
- ``spec-shape-hazard`` (error): a spec/draft-named buffer sliced to a
  RUNTIME length inside a timed loop — the r21 speculative-decoding
  shape contract as a static rule. The fused spec step scores k+1
  query positions in one donated program; jit caches key on concrete
  input SHAPES, so a candidate block whose length varies per step
  (``cand[:n_acc]``, ``draft_toks[:, :n]``) hands the decode program a
  new query-dim k every acceptance outcome — one recompile per
  distinct k, un-warmed, landing mid-stream. k is pinned at engine
  construction; acceptance must mask on-device, never re-shape.
"""

from __future__ import annotations

import ast
import re

from apex_tpu.analysis import walker
from apex_tpu.analysis.core import Finding, ProgramView, SourceView, rule
from apex_tpu.analysis.donation import donation_gaps

__all__ = ["COLLECTIVE_PRIMS"]

# named-axis collective primitives and where their axis names live
COLLECTIVE_PRIMS = ("psum", "pmax", "pmin", "ppermute", "all_gather",
                    "reduce_scatter", "all_to_all", "axis_index",
                    "pbroadcast", "pgather")

_UNBOUND_AXIS_RX = re.compile(r"unbound axis name:?\s*['\"]?(\w+)")


def _axis_names(eqn) -> list[str]:
    for key in ("axes", "axis_name"):
        v = eqn.params.get(key)
        if v is None:
            continue
        if isinstance(v, (tuple, list)):
            return [str(a) for a in v]
        return [str(v)]
    return []


# -- donation-miss ---------------------------------------------------------

@rule("donation-miss", severity="error", kind="program")
def donation_miss(view: ProgramView) -> list:
    """Non-donated inputs that shape/dtype-match an output no donated
    input covers: each is a buffer XLA must copy every step instead of
    updating in place (the r06 hlo_audit donation table, per-aval)."""
    if view.trace_error is not None or view.donated_invars is None:
        return []
    paths = view.in_paths
    if len(paths) != len(view.in_avals):
        paths = None
    out = []
    for gap in donation_gaps(view.in_avals, view.out_avals,
                             view.donated_invars, paths):
        out.append(Finding(
            rule="donation-miss", severity="error", target=view.name,
            location=f"in{gap['path']}",
            message=f"input {gap['path']} "
                    f"({gap['dtype']}{gap['shape']}, {gap['bytes']} B) "
                    f"matches an output but is not donated — a "
                    f"per-step copy; add it to donate_argnums",
            details=gap))
    return out


# -- layout-recompile-hazard ----------------------------------------------

@rule("layout-recompile-hazard", severity="error", kind="program")
def layout_recompile_hazard(view: ProgramView) -> list:
    """A donated jitted program whose input state can arrive from more
    producers (input-layout lineages) than warmup() drives. On this
    jax, jit caches key donated programs on concrete input LAYOUTS, so
    the first call on an uncovered lineage recompiles mid-run (~1.2 s
    in r14, landing in TTFT). Applies to programs that declare their
    lineage graph (``ProgramView.lineages``)."""
    if view.lineages is None:
        return []
    donated = any(view.donated_invars or ())
    if not donated and view.donated_invars is not None:
        return []                     # undonated programs cache by aval
    if view.warmup_lineages is None:
        if len(view.lineages) > 1:
            return [Finding(
                rule="layout-recompile-hazard", severity="error",
                target=view.name, location="warmup",
                message=f"donated program reachable from "
                        f"{len(view.lineages)} input-layout lineages "
                        f"({sorted(view.lineages)}) but declares NO "
                        f"warmup coverage — first call on each "
                        f"lineage may recompile mid-run",
                details={"lineages": sorted(view.lineages)})]
        return []
    missing = sorted(set(view.lineages) - set(view.warmup_lineages))
    if not missing:
        return []
    return [Finding(
        rule="layout-recompile-hazard", severity="error",
        target=view.name, location="warmup",
        message=f"warmup misses lineage(s) {missing}: the first call "
                f"whose input state comes from {missing} recompiles "
                f"mid-run (the r14 stall); drive the full predecessor "
                f"set {sorted(view.lineages)} in warmup()",
        details={"lineages": sorted(view.lineages),
                 "warmup": sorted(view.warmup_lineages),
                 "missing": missing})]


# -- precision-gap ---------------------------------------------------------

@rule("precision-gap", severity="error", kind="program")
def precision_gap(view: ProgramView) -> list:
    """fp32-only control-flow bodies under a half policy — the O1
    autocast control-flow gap (ROADMAP) via prof.coverage. The full
    CoverageReport is cached on ``view.notes['coverage']`` so callers
    (tools/precision_audit.py) reuse one audit."""
    if view.trace_error is not None:
        return []
    from apex_tpu.prof import coverage
    rep = coverage.audit_jaxpr(view.closed_jaxpr,
                               expect_half=view.expect_half)
    view.notes["coverage"] = rep
    out = []
    for scope in rep.cf_fp32_only:
        ops = rep.scopes[scope]["ops"]
        out.append(Finding(
            rule="precision-gap", severity="error", target=view.name,
            location=scope,
            message=f"control-flow body `{scope}` carries "
                    f"{sum(ops.values())} float op(s) but ZERO "
                    f"half-precision ops under a half policy — the O1 "
                    f"autocast control-flow gap (autocast executes "
                    f"scan/while/cond bodies at traced dtypes)",
            details={"ops": dict(ops),
                     "half_op_share": rep.half_op_share}))
    return out


# -- collective-misuse -----------------------------------------------------

@rule("collective-misuse", severity="error", kind="program")
def collective_misuse(view: ProgramView) -> list:
    """Named-axis collectives under a lowering that can't bind them.
    Two detection paths: (a) the trace itself failed with jax's
    ``unbound axis name`` — a psum/all_gather reached jit/pjit with no
    shard_map to bind its axis (the exact runtime failure, caught
    before any device sees it); (b) the body binds collectives under
    a shard_map of its own but the Plan carries in/out_shardings, so
    the Plan takes the pjit path, where named axes do not bind."""
    err = view.trace_error
    low = view.lowering_name()
    if err is not None:
        m = _UNBOUND_AXIS_RX.search(str(err))
        if not m:
            return [Finding(
                rule="collective-misuse", severity="error",
                target=view.name, location="trace",
                message=f"program does not trace under the "
                        f"'{low}' lowering: "
                        f"{type(err).__name__}: {err}",
                details={"lowering": low})]
        ax = m.group(1)
        return [Finding(
            rule="collective-misuse", severity="error",
            target=view.name, location=f"axis '{ax}'",
            message=f"named-axis collective over '{ax}' cannot bind "
                    f"under the '{low}' lowering (no shard_map binds "
                    f"it) — give the Plan in_specs/out_specs so it "
                    f"lowers via shard_map (parallel/plan.py)",
            details={"axis": ax, "lowering": low})]
    used: dict[str, str] = {}        # axis -> primitive (first seen)
    unbound: dict[str, str] = {}
    for v in walker.iter_eqns(view.closed_jaxpr):
        if v.eqn.primitive.name not in COLLECTIVE_PRIMS:
            continue
        for ax in _axis_names(v.eqn):
            used.setdefault(ax, v.eqn.primitive.name)
            if ax not in v.bound_axes:
                unbound.setdefault(ax, v.eqn.primitive.name)
    out = []
    for ax, prim in unbound.items():
        out.append(Finding(
            rule="collective-misuse", severity="error",
            target=view.name, location=f"axis '{ax}'",
            message=f"`{prim}` binds axis '{ax}' outside any "
                    f"shard_map — unbindable under the '{low}' "
                    f"lowering",
            details={"axis": ax, "primitive": prim, "lowering": low}))
    plan = view.plan
    if used and plan is not None and not unbound \
            and getattr(plan, "in_shardings", None) is not None:
        axes = sorted(used)
        out.append(Finding(
            rule="collective-misuse", severity="error",
            target=view.name, location=f"plan axes {axes}",
            message=f"body binds named-axis collectives over {axes} "
                    f"but the Plan also carries in/out_shardings: this "
                    f"Plan takes the pjit lowering, where these "
                    f"collectives cannot bind — drop the shardings or "
                    f"the named collectives",
            details={"axes": axes, "lowering": low}))
    return out


# -- dead-output -----------------------------------------------------------

@rule("dead-output", severity="warning", kind="program")
def dead_output(view: ProgramView) -> list:
    """Top-level output slots the registered caller never reads —
    computed and fetched (or at least allocated) every call for
    nothing. Needs the caller's declared consumption
    (``consumed_outputs``); unknown callers skip."""
    if view.consumed_outputs is None or view.trace_error is not None:
        return []
    out = []
    for slot, sub in view.out_children():
        if slot in view.consumed_outputs:
            continue
        import jax
        leaves = jax.tree_util.tree_leaves(sub)
        nbytes = sum(getattr(l, "size", 0)
                     * getattr(getattr(l, "dtype", None), "itemsize", 0)
                     for l in leaves)
        out.append(Finding(
            rule="dead-output", severity="warning", target=view.name,
            location=f"out[{slot}]",
            message=f"output slot {slot} ({len(leaves)} leaves, "
                    f"{nbytes} B) is never consumed by the registered "
                    f"caller — drop it from the program or read it",
            details={"slot": slot, "leaves": len(leaves),
                     "bytes": int(nbytes)}))
    return out


# -- host-sync-in-hot-loop (AST) ------------------------------------------

_TIMER_ATTRS = ("perf_counter", "monotonic", "perf_counter_ns")
# production paths gate (error); measurement tools time syncs on
# purpose — a warning keeps them visible without gating --strict.
# Repo-root bench.py is a measurement tool that merely lives outside
# tools/ (r16, when it joined the source set for bare-json-line).
_TOOL_PATH_RX = re.compile(r"(^|/)tools/|(^|[\\/])bench\.py$")


def _is_timer_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr in _TIMER_ATTRS:
        return True
    if isinstance(f, ast.Attribute) and f.attr == "time" and \
            isinstance(f.value, ast.Name) and f.value.id == "time":
        return True
    if isinstance(f, ast.Name) and f.id == "now":
        return True                 # the engine/tool-local convention
    if isinstance(f, ast.Attribute) and f.attr == "begin":
        return True                 # span tracer: the loop is timed
    return False


def _sync_site(node: ast.AST):
    """(idiom, lineno) when ``node`` is a blocking-fetch idiom."""
    if not isinstance(node, ast.Call):
        return None
    f = node.func
    if isinstance(f, ast.Attribute):
        # the fetch idiom is np.asarray(x) on a bare name (one arg, no
        # dtype): converting host data into program INPUTS always
        # passes a dtype or a composite expression — not a sync
        if f.attr == "asarray" and isinstance(f.value, ast.Name) \
                and f.value.id in ("np", "numpy") \
                and len(node.args) == 1 and not node.keywords \
                and isinstance(node.args[0], ast.Name):
            return ("np.asarray", node.lineno)
        if f.attr == "device_get":
            return ("jax.device_get", node.lineno)
        if f.attr == "block_until_ready":
            return (".block_until_ready()", node.lineno)
        if f.attr == "item" and not node.args:
            return (".item()", node.lineno)
    if isinstance(f, ast.Name) and f.id in ("int", "float") \
            and len(node.args) == 1 \
            and isinstance(node.args[0], ast.Name):
        return (f"{f.id}()", node.lineno)
    return None


# -- bare-json-line (AST) --------------------------------------------------

_STAMP_FNS = ("stamp_result", "emit_result", "_stamp")


def _fn_name(call: ast.AST) -> "str | None":
    if not isinstance(call, ast.Call):
        return None
    f = call.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


def _is_result_dict(node: ast.AST) -> bool:
    """A dict literal carrying both ``"metric"`` and ``"value"`` keys —
    the repo's result-line shape since r02 (BASELINE.md contract)."""
    if not isinstance(node, ast.Dict):
        return False
    keys = {k.value for k in node.keys
            if isinstance(k, ast.Constant) and isinstance(k.value, str)}
    return {"metric", "value"} <= keys


def _printed_dumps_arg(node: ast.AST) -> "ast.AST | None":
    """``print(json.dumps(X), ...) -> X`` (else None)."""
    if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
            and node.func.id == "print" and node.args):
        return None
    inner = node.args[0]
    if isinstance(inner, ast.Call) and isinstance(inner.func,
                                                  ast.Attribute) \
            and inner.func.attr == "dumps" and inner.args:
        return inner.args[0]
    return None


@rule("bare-json-line", severity="error", kind="source")
def bare_json_line(view: SourceView) -> list:
    """A measurement tool printing a ``{"metric", "value", ...}``
    result line without the r16 ``run_meta``/``format`` stamp
    (``tools/_perf_common.stamp_result`` / ``emit_result``): the line
    becomes a committed artifact that can't say what git rev, jax
    version, or platform produced it — exactly the self-description
    gap the r16 trajectory store closed for serve_bench/decode_bench —
    and its points silently fall out of ``BENCH_TRAJECTORY.json``'s
    provenance. New bench tools can't regress out of the trajectory.

    Heuristic by design: it recognizes the repo's one result-line
    idiom — a dict literal (or a name assigned one) with both
    ``"metric"`` and ``"value"`` keys reaching ``print(json.dumps(
    ...))`` unwrapped. Tools that build lines another way should emit
    through ``emit_result`` anyway, which is the funnel this rule
    points at."""
    if not _TOOL_PATH_RX.search(view.path):
        return []                    # the rule is about tool artifacts
    result_names: set = set()
    stamped_names: set = set()
    for node in ast.walk(view.tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            if _is_result_dict(node.value):
                result_names.add(node.targets[0].id)
            if _fn_name(node.value) in _STAMP_FNS:
                stamped_names.add(node.targets[0].id)
        # stamp_result(out, ...) / emit_result(out, ...) anywhere in
        # the module marks `out` stamped (stamp_result mutates in place)
        if isinstance(node, ast.Call) and _fn_name(node) in _STAMP_FNS \
                and node.args and isinstance(node.args[0], ast.Name):
            stamped_names.add(node.args[0].id)
    out = []
    for node in ast.walk(view.tree):
        dumped = _printed_dumps_arg(node)
        if dumped is None or _fn_name(dumped) in _STAMP_FNS:
            continue
        if _is_result_dict(dumped):
            what = "a literal result dict"
        elif isinstance(dumped, ast.Name) and dumped.id in result_names \
                and dumped.id not in stamped_names:
            what = f"result dict `{dumped.id}`"
        else:
            continue
        out.append(Finding(
            rule="bare-json-line", severity="error", target=view.path,
            location=f"line {node.lineno}",
            message=f"{what} printed without run_meta/format stamping "
                    f"— wrap it in _perf_common.stamp_result (or emit "
                    f"through emit_result) so the artifact is "
                    f"self-describing and lands in the perf trajectory",
            details={"what": what},
            line_text=view.line(node.lineno)))
    return out


def _timed_loop_targets(view: SourceView) -> "list[ast.AST]":
    """The shared hot-code discovery of the AST timing rules
    (``host-sync-in-hot-loop``, ``snapshot-on-step-path``): every TIMED
    loop — a loop whose subtree reads a wall clock or opens spans, or
    that sits in a function which reads one (the ``t0 =
    perf_counter(); for ...; dt = perf_counter() - t0`` sandwich times
    the loop from outside) — plus every local function such loops call,
    transitively."""
    # local function defs, by name (module + nested scopes)
    defs: dict[str, ast.AST] = {}
    for node in ast.walk(view.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs[node.name] = node

    def calls_in(node):
        for n in ast.walk(node):
            if isinstance(n, ast.Call) and isinstance(n.func, ast.Name):
                yield n.func.id

    timed_fns = {id(fn) for fn in defs.values()
                 if any(_is_timer_call(n) for n in ast.walk(fn))}

    hot_roots: list[ast.AST] = []

    def scan_scope(scope: ast.AST, timed: bool) -> None:
        for node in ast.iter_child_nodes(scope):
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                scan_scope(node, id(node) in timed_fns)
                continue
            if isinstance(node, (ast.For, ast.While, ast.AsyncFor)) \
                    and (timed or any(_is_timer_call(n)
                                      for n in ast.walk(node))):
                hot_roots.append(node)
                continue              # subtree already covered
            scan_scope(node, timed)

    scan_scope(view.tree, False)
    # propagate: functions called from hot code are hot (transitively)
    hot_fns: set[str] = set()
    frontier = list(hot_roots)
    while frontier:
        node = frontier.pop()
        for name in calls_in(node):
            if name in defs and name not in hot_fns:
                hot_fns.add(name)
                frontier.append(defs[name])
    return hot_roots + [defs[n] for n in hot_fns]


@rule("host-sync-in-hot-loop", severity="error", kind="source")
def host_sync_in_hot_loop(view: SourceView) -> list:
    """Blocking fetches / implicit device->host conversions inside
    TIMED loops (loops whose subtree reads a wall clock or opens
    spans), including local functions such loops call. Every
    intentional sync point — the engine's one-sync-per-step contract,
    a bench's anchoring fetch — must say so with an inline
    suppression + reason; everything else is a latency bug waiting
    for a span table to find it."""
    sites: dict[int, str] = {}
    for root in _timed_loop_targets(view):
        for n in ast.walk(root):
            hit = _sync_site(n)
            if hit:
                sites.setdefault(hit[1], hit[0])
    severity = "warning" if _TOOL_PATH_RX.search(view.path) else "error"
    out = []
    for lineno in sorted(sites):
        out.append(Finding(
            rule="host-sync-in-hot-loop", severity=severity,
            target=view.path, location=f"line {lineno}",
            message=f"{sites[lineno]} inside a timed loop blocks the "
                    f"host on the device — if this sync is the "
                    f"design (e.g. the one sync per decode step), "
                    f"suppress it with a reason",
            details={"idiom": sites[lineno]},
            line_text=view.line(lineno)))
    return out


# -- blocking-emit-on-step-path (AST) --------------------------------------

# blocking emission sinks: socket writes/handshakes and queue puts
# that may wait. A ``put_nowait`` (or ``put(..., block=False)`` /
# ``put(..., timeout=...)``) is the sanctioned step-path idiom — it
# fails fast into a counted drop instead of stalling the decode step.
_SOCKET_EMIT_ATTRS = ("send", "sendall", "sendto", "connect")


def _blocking_emit_site(node: ast.AST):
    """(idiom, lineno) when ``node`` is a potentially-blocking emit:
    any ``.send``/``.sendall``/``.sendto``/``.connect`` call, or a
    ``.put`` whose arguments don't prove it non-blocking."""
    if not isinstance(node, ast.Call):
        return None
    f = node.func
    if not isinstance(f, ast.Attribute):
        return None
    if f.attr in _SOCKET_EMIT_ATTRS:
        return (f".{f.attr}()", node.lineno)
    if f.attr == "put":
        for kw in node.keywords:
            if kw.arg == "block" and isinstance(kw.value, ast.Constant) \
                    and kw.value.value is False:
                return None
            if kw.arg == "timeout":
                return None
        if len(node.args) >= 2 and isinstance(node.args[1],
                                              ast.Constant) \
                and node.args[1].value is False:
            return None              # q.put(x, False)
        return (".put()", node.lineno)
    return None


@rule("blocking-emit-on-step-path", severity="error", kind="source")
def blocking_emit_on_step_path(view: SourceView) -> list:
    """Blocking emission inside TIMED loops — the live telemetry
    plane's producer contract (``prof.live.LiveEmitter``) as a static
    rule. A socket ``send*``/``connect`` blocks on the peer's receive
    window (a slow collector stalls every decode step it watches —
    the observer becoming the straggler), and an unbounded/blocking
    ``Queue.put`` blocks on the consumer; the step path may only
    ``put_nowait`` into a bounded queue and count the drop. Error
    everywhere (tools included): emission is never a measurement. A
    deliberate blocking emit (a close-time drain, a handshake outside
    the measured region) says so with a suppression + reason."""
    sites: dict[int, str] = {}
    for root in _timed_loop_targets(view):
        for n in ast.walk(root):
            hit = _blocking_emit_site(n)
            if hit:
                sites.setdefault(hit[1], hit[0])
    out = []
    for lineno in sorted(sites):
        out.append(Finding(
            rule="blocking-emit-on-step-path", severity="error",
            target=view.path, location=f"line {lineno}",
            message=f"{sites[lineno]} inside a timed loop can block "
                    f"the step path on a peer/consumer — emit through "
                    f"a bounded-queue put_nowait (drops counted, "
                    f"prof.live.LiveEmitter) and let a background "
                    f"thread own the socket",
            details={"idiom": sites[lineno]},
            line_text=view.line(lineno)))
    return out


# -- unattributed-shed (AST) -----------------------------------------------

_SHED_NAME_RX = re.compile(r"shed", re.IGNORECASE)


def _name_of(node: ast.AST) -> "str | None":
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Subscript):
        return _name_of(node.value)
    return None


def _shed_site(node: ast.AST):
    """(idiom, lineno) when ``node`` books a shed: an augmented
    assignment to a ``*shed*``-named counter (``self.shed_count[i] +=
    1``) or an ``.append`` onto a ``*shed*``-named list
    (``shed_log.append(...)``)."""
    if isinstance(node, ast.AugAssign):
        name = _name_of(node.target)
        if name and _SHED_NAME_RX.search(name):
            return (f"{name} +=", node.lineno)
    if isinstance(node, ast.Call) and \
            isinstance(node.func, ast.Attribute) and \
            node.func.attr == "append":
        name = _name_of(node.func.value)
        if name and _SHED_NAME_RX.search(name):
            return (f"{name}.append", node.lineno)
    return None


def _has_shed_attribution(fn: ast.AST) -> bool:
    """True when the function writes a shed record naming BOTH the
    triggering rule and the target replica: a dict literal with
    ``"rule"`` and ``"replica"`` string keys, or any call carrying
    ``rule=`` and ``replica=`` keywords."""
    for node in ast.walk(fn):
        if isinstance(node, ast.Dict):
            keys = {k.value for k in node.keys
                    if isinstance(k, ast.Constant)
                    and isinstance(k.value, str)}
            if {"rule", "replica"} <= keys:
                return True
        if isinstance(node, ast.Call):
            kws = {kw.arg for kw in node.keywords}
            if {"rule", "replica"} <= kws:
                return True
    return False


@rule("unattributed-shed", severity="error", kind="source")
def unattributed_shed(view: SourceView) -> list:
    """Shed bookkeeping without attribution — the router tier's
    load-shedding contract (r19). A function that counts a shed
    (``*shed*`` counter bump / ``*shed*`` list append) must, in the
    same scope, write the record that names the triggering ``rule``
    and the culprit/target ``replica`` (a dict literal with both
    keys, or a call with both keywords — ``Router._route_one``'s
    shed row and ``MetricsLogger.log_router``'s payload are the
    shipped shapes). Without the attribution, a deliberate admission
    decision is indistinguishable from a LOST request, and the
    zero-drop contract (``telemetry_report``'s DROPPED flag) can no
    longer separate policy from bug."""
    out = []
    fns = [n for n in ast.walk(view.tree)
           if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    covered: set = set()
    for fn in fns:
        sites = []
        for node in ast.walk(fn):
            hit = _shed_site(node)
            if hit:
                sites.append(hit)
        for sub in ast.walk(fn):
            if sub is not fn and isinstance(
                    sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # nested defs audit as their own scope
                sites = [s for s in sites
                         if not (sub.lineno <= s[1] <=
                                 max(getattr(sub, "end_lineno",
                                             sub.lineno), sub.lineno))]
        if not sites:
            continue
        key = tuple(s[1] for s in sites)
        if key in covered:
            continue
        covered.add(key)
        if _has_shed_attribution(fn):
            continue
        for idiom, lineno in sites:
            out.append(Finding(
                rule="unattributed-shed", severity="error",
                target=view.path, location=f"line {lineno}",
                message=f"`{idiom}` counts a shed but the enclosing "
                        f"function never writes the attribution "
                        f"(rule + replica) — an unattributed drop "
                        f"reads as a LOST request; record "
                        f"{{'rule': ..., 'replica': ...}} where the "
                        f"shed is booked",
                details={"idiom": idiom},
                line_text=view.line(lineno)))
    return out


# -- page-gather-hazard (AST, r20) -----------------------------------------

_PAGE_NAME_RX = re.compile(r"page", re.IGNORECASE)


def _page_gather_site(node: ast.AST):
    """(idiom, lineno) when ``node`` rebuilds/fetches a page-map
    operand: ``jnp.asarray``/``jnp.array``/``jax.device_put`` (or
    ``jax.numpy.*``) over a page-named value — a fresh device buffer
    whose layout lineage the donated gather has never seen — or
    ``np.asarray`` of a page-named bare name (the blocking-fetch
    idiom pointed at the page table)."""
    if not isinstance(node, ast.Call) or not node.args:
        return None
    f = node.func
    if not isinstance(f, ast.Attribute) or \
            not isinstance(f.value, ast.Name):
        return None
    name = _name_of(node.args[0])
    if not name or not _PAGE_NAME_RX.search(name):
        return None
    mod = f.value.id
    if mod in ("jnp", "jax") and f.attr in ("asarray", "array",
                                            "device_put"):
        return (f"{mod}.{f.attr}({name})", node.lineno)
    if mod in ("np", "numpy") and f.attr == "asarray" \
            and isinstance(node.args[0], ast.Name):
        return (f"{mod}.asarray({name})", node.lineno)
    return None


@rule("page-gather-hazard", severity="error", kind="source")
def page_gather_hazard(view: SourceView) -> list:
    """Hazardous page-map operands inside TIMED loops — the paged KV
    arena's gather contract (r20) as a static rule. The decode/prefill
    programs gather K/V by page indices every step; on this jax,
    donated jit caches key on concrete input LAYOUTS, so the page-
    index operand must be the SAME loop-invariant host buffer every
    call (mutated in place at admission/retirement). Minting a fresh
    device array per step (``jnp.asarray(page_table)`` and friends)
    creates a new layout lineage -> mid-run recompile (~1.2 s, lands
    in TTFT — the r14 stall on the r20 operand); ``np.asarray`` of a
    device-resident table is a host sync on the decode path. Keep the
    table host-side np.int32 and let the dispatch layer ship it."""
    sites: dict[int, str] = {}
    for root in _timed_loop_targets(view):
        for n in ast.walk(root):
            hit = _page_gather_site(n)
            if hit:
                sites.setdefault(hit[1], hit[0])
    out = []
    for lineno in sorted(sites):
        out.append(Finding(
            rule="page-gather-hazard", severity="error",
            target=view.path, location=f"line {lineno}",
            message=f"{sites[lineno]} inside a timed loop rebuilds/"
                    f"fetches the page map on the decode path — a "
                    f"fresh device buffer per step gives the donated "
                    f"KV gather a new input-layout lineage (layout-"
                    f"keyed recompile, the r14 stall) and a host "
                    f"conversion can sync; keep the page table a "
                    f"loop-invariant host np.int32 buffer mutated in "
                    f"place",
            details={"idiom": sites[lineno]},
            line_text=view.line(lineno)))
    return out


# -- spec-shape-hazard (AST, r21) ------------------------------------------

_SPEC_NAME_RX = re.compile(r"spec|draft|cand", re.IGNORECASE)


def _static_bound(node) -> bool:
    """True when a slice bound is shape-static: absent, a literal, or
    a signed literal (``x[:4]``, ``x[:-1]``)."""
    if node is None or isinstance(node, ast.Constant):
        return True
    return isinstance(node, ast.UnaryOp) and \
        isinstance(node.operand, ast.Constant)


def _spec_shape_site(node: ast.AST):
    """(idiom, lineno) when ``node`` slices a spec/draft-named buffer
    to a runtime-variable length: an ``ast.Slice`` anywhere in the
    subscript whose lower or upper bound is a non-literal expression
    (``cand[:n_acc]``, ``draft_toks[:, :n_emit]``). Plain integer
    indexing (``hist[na]``) is not a shape change and stays silent."""
    if not isinstance(node, ast.Subscript):
        return None
    name = _name_of(node.value)
    if not name or not _SPEC_NAME_RX.search(name):
        return None
    dims = node.slice.elts if isinstance(node.slice, ast.Tuple) \
        else [node.slice]
    for dim in dims:
        if isinstance(dim, ast.Slice) and not (
                _static_bound(dim.lower) and _static_bound(dim.upper)):
            return (f"{name}[...variable slice...]", node.lineno)
    return None


@rule("spec-shape-hazard", severity="error", kind="source")
def spec_shape_hazard(view: SourceView) -> list:
    """Runtime-variable-length slices of spec/draft-named buffers
    inside TIMED loops — the speculative decode shape contract (r21)
    as a static rule. The fused spec step scores all k+1 candidate
    positions in ONE donated program whose query dim is k+1; jit
    caches key on concrete input shapes, so trimming the candidate
    block to the accepted length on the host (``cand[:n_acc]``) and
    re-entering the program mints a fresh query-dim shape per
    acceptance outcome — one un-warmed recompile (~1.2 s, the r14
    stall) per distinct k, mid-stream. Pin k at construction, keep
    every device block full-width, and mask acceptance on-device
    (``n_emit`` counters, not shorter arrays); slice to the accepted
    length only AFTER the step's one host sync, on host buffers."""
    sites: dict[int, str] = {}
    for root in _timed_loop_targets(view):
        for n in ast.walk(root):
            hit = _spec_shape_site(n)
            if hit:
                sites.setdefault(hit[1], hit[0])
    out = []
    for lineno in sorted(sites):
        out.append(Finding(
            rule="spec-shape-hazard", severity="error",
            target=view.path, location=f"line {lineno}",
            message=f"{sites[lineno]} inside a timed loop trims a "
                    f"spec/draft buffer to a runtime length — the "
                    f"donated spec program's query dim k is shape-"
                    f"keyed, so a per-step length change recompiles "
                    f"un-warmed mid-stream; keep device blocks full "
                    f"width and mask acceptance on-device, slicing "
                    f"only post-sync host buffers",
            details={"idiom": sites[lineno]},
            line_text=view.line(lineno)))
    return out


# -- orphan-span (AST, r22) ------------------------------------------------

# the span-linking kwargs: any ONE of these ties the span into a
# merged timeline (request -> the fleet-wide request->trace map,
# trace -> direct identity, parent -> the parent-chain walk)
_SPAN_LINK_KWARGS = ("request", "trace", "parent")
_SPAN_OPEN_ATTRS = ("begin", "instant")

# the rule is a SERVING-tier contract: only serve/* modules (engine,
# router) and the tools that drive them participate in merged request
# traces. Training examples open step-interval spans with no request
# lifecycle to link to — firing there would be a false positive class.
_SERVE_PATH_RX = re.compile(r"(^|[\\/])serve[\\/]|(^|[\\/])tools[\\/]")


def _orphan_span_site(node: ast.AST):
    """(span name, lineno) when ``node`` opens a span that can never
    join a merged trace: a ``.begin(...)``/``.instant(...)`` call whose
    first argument is a string literal (the repo's tracer idiom —
    internal forwarding like ``self.begin(name, ...)`` passes a Name
    and stays silent) carrying none of the linking kwargs. A ``**kw``
    splat may carry them dynamically, so it stays silent too."""
    if not isinstance(node, ast.Call):
        return None
    f = node.func
    if not isinstance(f, ast.Attribute) or \
            f.attr not in _SPAN_OPEN_ATTRS:
        return None
    if not node.args or not isinstance(node.args[0], ast.Constant) \
            or not isinstance(node.args[0].value, str):
        return None
    for kw in node.keywords:
        if kw.arg is None:            # **ctx may carry trace/hop
            return None
        if kw.arg in _SPAN_LINK_KWARGS:
            return None
    return (node.args[0].value, node.lineno)


@rule("orphan-span", severity="error", kind="source")
def orphan_span(view: SourceView) -> list:
    """Span opens that can never join a merged fleet trace — the r22
    trace-propagation contract (``prof.spans.merge_process_traces``)
    as a static rule. The merge resolves every span's trace identity
    three ways: a direct ``trace=`` attr, a parent-chain walk to an
    ancestor that has one, or the fleet-wide ``request -> trace`` map
    via a ``request=`` attr. A ``tracer.begin("name", ...)`` /
    ``tracer.instant("name", ...)`` that passes NONE of
    ``request=``/``trace=``/``parent=`` opens a span all three paths
    dead-end on — at merge time it lands in the ``orphans`` list the
    distributed-trace CI smoke asserts empty, and in a Perfetto view
    it renders on the traceless track where nobody looks. Scheduler-
    scope spans (``decode_step``, ``prefill_batch`` — shared across
    requests by design, REQUEST_SCOPE_SPANS excludes them) declare
    that with an inline suppression + reason."""
    if not _SERVE_PATH_RX.search(view.path):
        return []                    # serving-tier contract only
    sites: dict[int, str] = {}
    for node in ast.walk(view.tree):
        hit = _orphan_span_site(node)
        if hit:
            sites.setdefault(hit[1], hit[0])
    out = []
    for lineno in sorted(sites):
        out.append(Finding(
            rule="orphan-span", severity="error", target=view.path,
            location=f"line {lineno}",
            message=f"span `{sites[lineno]}` opens with none of "
                    f"request=/trace=/parent= — it can never resolve "
                    f"to a trace in a merged fleet timeline (orphan at "
                    f"merge time); link it to its request's lifecycle, "
                    f"or suppress with a reason if it is scheduler-"
                    f"scope by design",
            details={"span": sites[lineno]},
            line_text=view.line(lineno)))
    return out


# -- snapshot-on-step-path (AST) -------------------------------------------

# serialization sinks that block the step path when a snapshot takes
# them synchronously: python/numpy persistence plus the state_dict()
# host fetch itself (it np.asarray's every leaf)
_SERIALIZE_MODS = ("pickle", "np", "numpy", "json")
_SERIALIZE_FNS = ("dump", "dumps", "save", "savez", "savez_compressed")


def _snapshot_sync_site(node: ast.AST):
    """(idiom, lineno) when ``node`` synchronously serializes run
    state: ``pickle.dump/dumps``, ``np.save/savez[_compressed]``,
    ``json.dump`` (the file-writing variant), or a ``.state_dict()``
    call (a host fetch of every optimizer/scaler leaf)."""
    if not isinstance(node, ast.Call):
        return None
    f = node.func
    if isinstance(f, ast.Attribute):
        if f.attr == "state_dict" and not node.keywords:
            return (".state_dict()", node.lineno)
        if isinstance(f.value, ast.Name) and \
                f.value.id in _SERIALIZE_MODS and \
                f.attr in _SERIALIZE_FNS:
            if f.value.id == "json" and f.attr == "dumps":
                return None          # a string build, not a file write
            return (f"{f.value.id}.{f.attr}", node.lineno)
    return None


@rule("snapshot-on-step-path", severity="error", kind="source")
def snapshot_on_step_path(view: SourceView) -> list:
    """Synchronous snapshot work inside TIMED loops — the async
    contract of ``apex_tpu.runtime.SnapshotWriter`` as a static rule
    (the r17 standing order: new runtime bug classes become lint
    rules). A ``.state_dict()`` call fetches every optimizer/scaler
    leaf to host, and ``pickle.dump``/``np.save*``/``json.dump``
    serialize + fsync on the calling thread; either one inside a timed
    loop stalls the step path for exactly the latency the background
    writer exists to hide. Snapshot through
    ``SnapshotWriter.submit`` (device-side staging copy + background
    fetch/write) or move the save off the timed region — and if a
    synchronous save IS the design (a final checkpoint inside a
    grace-period handler), suppress with a reason."""
    sites: dict[int, str] = {}
    for root in _timed_loop_targets(view):
        for n in ast.walk(root):
            hit = _snapshot_sync_site(n)
            if hit:
                sites.setdefault(hit[1], hit[0])
    out = []
    for lineno in sorted(sites):
        out.append(Finding(
            rule="snapshot-on-step-path", severity="error",
            target=view.path, location=f"line {lineno}",
            message=f"{sites[lineno]} inside a timed loop serializes "
                    f"state on the step path — snapshot through the "
                    f"async SnapshotWriter.submit (device-side "
                    f"staging + background write) or move the save "
                    f"off the timed region",
            details={"idiom": sites[lineno]},
            line_text=view.line(lineno)))
    return out
