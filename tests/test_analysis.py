"""apex_lint fixture tests: every rule proven to FIRE on an injected
violation, plus the suppression/baseline machinery and the runtime
cross-check harness.

The acceptance contract (ISSUE r15): each of the six rules has a
violation fixture — including a reconstruction of the r14
layout-recompile hazard caught statically (the serve engine with a
pre-r14 'one call per program' warmup) and the O1 control-flow gap
reported as a precision-gap finding consistent with the strict xfail
in tests/test_numerics.py. The serve engine's canonical trio must
lint CLEAN, and its declared warmup coverage must equal its declared
program lineages (the runtime half of that agreement is
tests/test_serve.py's frozen-cache tests)."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import analysis
from apex_tpu.analysis import walker as W
from apex_tpu.analysis.core import ProgramView, SourceView
from apex_tpu.analysis.donation import audit_donation, donation_gaps

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


def lint(targets, rules=None, baseline_path=None):
    return analysis.lint(targets, rules=rules,
                         baseline_path=baseline_path)


# -- walker ----------------------------------------------------------------

class TestWalker:
    def test_scopes_and_cf_children(self):
        def f(w, x):
            with jax.named_scope("stem"):
                h = x @ w

            def body(c, _):
                return jnp.tanh(c @ w), None
            out, _ = jax.lax.scan(body, h, None, length=2)
            return out.sum()

        views = list(W.iter_eqns(
            jax.make_jaxpr(f)(jnp.ones((4, 4)), jnp.ones((2, 4)))))
        scopes = {v.scope for v in views if v.leaf}
        assert "stem" in scopes
        cf = [v for v in views if v.cf_children]
        assert cf and cf[0].cf_children[0].startswith("scan:")
        # body eqns carry the cf label as their scope
        assert any(v.cf_scope and v.cf_scope.startswith("scan:")
                   for v in views)

    def test_shard_map_binds_axes(self):
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("dp",))
        fn = jax.jit(jax.shard_map(
            lambda x: jax.lax.psum(x, "dp"), mesh=mesh,
            in_specs=jax.sharding.PartitionSpec("dp"),
            out_specs=jax.sharding.PartitionSpec(), check_vma=False))
        views = list(W.iter_eqns(jax.make_jaxpr(fn)(jnp.ones((2,)))))
        psums = [v for v in views if v.eqn.primitive.name == "psum"]
        assert psums and "dp" in psums[0].bound_axes


# -- donation-miss ---------------------------------------------------------

class TestDonationMiss:
    def _step(self):
        def step(state, x):
            return state + x, x.sum()
        return step

    def test_fires_on_undonated_state(self):
        v = ProgramView("p", jax.jit(self._step()),
                        (jnp.ones((4, 4)), jnp.ones((4, 4))))
        fs = lint([v], rules=["donation-miss"]).findings
        # ONE match: the (4,4) output demand is satisfied once; both
        # undonated inputs match but only one copy is avoidable
        assert len(fs) == 1 and fs[0].severity == "error"
        assert fs[0].location.startswith("in[0]")

    def test_clean_when_donated(self):
        v = ProgramView("p", jax.jit(self._step(), donate_argnums=(0,)),
                        (jnp.ones((4, 4)), jnp.ones((4, 4))))
        assert lint([v], rules=["donation-miss"]).findings == []

    def test_scalars_never_match(self):
        def step(s, lr):
            return s * lr, s.sum()
        v = ProgramView("p", jax.jit(step, donate_argnums=(0,)),
                        (jnp.ones((4,)), jnp.asarray(0.1)))
        assert lint([v], rules=["donation-miss"]).findings == []

    def test_gaps_helper_and_stablehlo_audit_agree(self):
        """One code path (analysis.donation) serves both the rule and
        hlo_audit's lowered-signature table: the same program audits
        the same undonated bytes both ways."""
        step = self._step()
        jstep = jax.jit(step, donate_argnums=(0,))
        args = (jnp.ones((4, 4)), jnp.ones((4, 4)))
        d = audit_donation(jstep.lower(*args).as_text())
        assert d["n_args"] == 2 and d["n_donated"] == 1
        cj = jax.make_jaxpr(jstep)(*args)
        gaps = donation_gaps(cj.in_avals, cj.out_avals, (True, False))
        assert gaps == []            # x feeds no matching output


# -- layout-recompile-hazard ----------------------------------------------

@pytest.fixture(scope="module")
def tiny_engine():
    from apex_tpu.models import TransformerLM
    from apex_tpu.serve import ContinuousBatchingEngine
    m = TransformerLM(vocab_size=32, max_seq_len=16, embed_dim=16,
                      num_heads=2, num_layers=1)
    return ContinuousBatchingEngine(m, m.init(jax.random.key(0)),
                                    slots=2, max_len=16,
                                    prefill_chunk=4)


class TestLayoutRecompileHazard:
    def test_fires_on_missing_lineage(self):
        v = ProgramView(
            "p", jax.jit(lambda s: (s + 1,), donate_argnums=(0,)),
            (jnp.ones((4,)),),
            lineages=frozenset({"fresh", "decode"}),
            warmup_lineages=frozenset({"fresh"}))
        fs = lint([v], rules=["layout-recompile-hazard"]).findings
        assert len(fs) == 1 and fs[0].severity == "error"
        assert fs[0].details["missing"] == ["decode"]

    def test_fires_when_no_warmup_declared(self):
        v = ProgramView(
            "p", jax.jit(lambda s: (s + 1,), donate_argnums=(0,)),
            (jnp.ones((4,)),),
            lineages=frozenset({"fresh", "decode"}))
        fs = lint([v], rules=["layout-recompile-hazard"]).findings
        assert len(fs) == 1 and "NO" in fs[0].message

    def test_undonated_programs_skip(self):
        v = ProgramView("p", jax.jit(lambda s: (s + 1,)),
                        (jnp.ones((4,)),),
                        lineages=frozenset({"fresh", "decode"}),
                        warmup_lineages=frozenset({"fresh"}))
        assert lint([v], rules=["layout-recompile-hazard"]).findings \
            == []

    def test_r14_hazard_reconstructed_statically(self, tiny_engine):
        """The r14 bug as the rule sees it: the pre-r14 warmup drove
        each program ONCE from fresh state, leaving every in-cycle
        lineage (prefill<-commit, decode<-decode, ...) uncovered — the
        ~1.2 s mid-run recompile span forensics found. The same
        engine's REAL warmup coverage lints clean."""
        descs = tiny_engine.lint_programs()
        pre_r14 = [ProgramView(
            name=d["name"], fn=d["fn"], example_args=d["args"],
            lineages=d["lineages"],
            warmup_lineages=frozenset({"fresh"})) for d in descs]
        fs = lint(pre_r14, rules=["layout-recompile-hazard"]).findings
        assert len(fs) == len(descs)     # EVERY donated program flags
        prefill = [f for f in fs if "prefill" in f.target][0]
        assert set(prefill.details["missing"]) == \
            {"commit", "decode", "prefill"}

        fixed = [ProgramView(
            name=d["name"], fn=d["fn"], example_args=d["args"],
            lineages=d["lineages"],
            warmup_lineages=d["warmup_lineages"]) for d in descs]
        assert lint(fixed,
                    rules=["layout-recompile-hazard"]).findings == []

    def test_engine_declarations_agree(self, tiny_engine):
        """The static half of the lint<->runtime agreement satellite:
        warmup covers exactly the declared scheduler lineages (the
        runtime half — frozen jit caches through every width and
        transition — is tests/test_serve.py)."""
        assert tiny_engine.warmup_coverage() == \
            tiny_engine.program_lineages()

    def test_serve_canonical_trio_lints_clean(self, tiny_engine):
        views = [ProgramView(
            name=d["name"], fn=d["fn"], example_args=d["args"],
            lineages=d["lineages"],
            warmup_lineages=d["warmup_lineages"],
            consumed_outputs=d["consumed_outputs"])
            for d in tiny_engine.lint_programs()]
        rep = lint(views)
        assert rep.errors() == [], [f.to_dict() for f in rep.errors()]


# -- precision-gap ---------------------------------------------------------

class TestPrecisionGap:
    def test_o1_scan_gap_fires_consistent_with_xfail(self):
        """The O1 control-flow gap as a lint finding: same vehicle,
        same flag as tools/precision_audit.py --model rnn --opt-level
        O1 and the strict xfail in tests/test_numerics.py
        (test_o1_scan_body_gets_half_precision). When autocast learns
        control flow, that xfail XPASSes and THIS fixture must flip to
        expecting zero findings alongside it."""
        from apex_tpu.analysis.programs import rnn_step_program
        v = rnn_step_program("O1", batch=2)
        fs = lint([v], rules=["precision-gap"]).findings
        assert fs and all(f.severity == "error" for f in fs)
        rep = v.notes["coverage"]          # ONE audit, cached
        assert tuple(f.location for f in fs) == rep.cf_fp32_only
        assert rep.half_op_share == 0.0    # the gap at its worst

    def test_clean_without_half_policy(self):
        from apex_tpu.analysis.programs import rnn_step_program
        v = rnn_step_program("O0", batch=2)
        assert lint([v], rules=["precision-gap"]).findings == []


# -- collective-misuse -----------------------------------------------------

class TestCollectiveMisuse:
    def _mesh(self):
        return jax.sharding.Mesh(np.array(jax.devices()[:1]), ("dp",))

    def test_fires_under_plain_jit_plan(self):
        from apex_tpu.parallel import Plan, compile_step_with_plan
        plan = Plan(mesh=self._mesh())
        fn = compile_step_with_plan(
            lambda x: jax.lax.psum(x, "dp"), plan)
        v = ProgramView("p", fn, (jnp.ones((2,)),), plan=plan)
        fs = lint([v], rules=["collective-misuse"]).findings
        assert len(fs) == 1 and fs[0].severity == "error"
        assert fs[0].details["axis"] == "dp"
        assert fs[0].details["lowering"] == "jit"

    def test_fires_under_pjit_plan(self):
        """Named-axis collectives cannot bind under the pjit lowering
        (jax binds them only under shard_map)."""
        from jax.sharding import PartitionSpec as P

        from apex_tpu.parallel import Plan, compile_step_with_plan
        plan = Plan(mesh=self._mesh(), in_shardings=P("dp"),
                    out_shardings=P())
        fn = compile_step_with_plan(
            lambda x: jax.lax.psum(x, "dp"), plan)
        v = ProgramView("p", fn, (jnp.ones((2,)),), plan=plan)
        fs = lint([v], rules=["collective-misuse"]).findings
        assert len(fs) == 1 and fs[0].details["axis"] == "dp"
        assert fs[0].details["lowering"] == "pjit"

    def test_clean_under_shard_map_plan(self):
        from jax.sharding import PartitionSpec as P

        from apex_tpu.parallel import Plan, compile_step_with_plan
        plan = Plan(mesh=self._mesh(), in_specs=P("dp"), out_specs=P())
        fn = compile_step_with_plan(
            lambda x: jax.lax.psum(x, "dp"), plan)
        v = ProgramView("p", fn, (jnp.ones((2,)),), plan=plan)
        assert lint([v], rules=["collective-misuse"]).findings == []


# -- dead-output -----------------------------------------------------------

class TestDeadOutput:
    def test_fires_on_unconsumed_slot(self):
        v = ProgramView("p", jax.jit(lambda x: (x + 1, x * 2)),
                        (jnp.ones((3,)),),
                        consumed_outputs=frozenset({"0"}))
        fs = lint([v], rules=["dead-output"]).findings
        assert len(fs) == 1 and fs[0].severity == "warning"
        assert fs[0].location == "out[1]"

    def test_skips_without_declared_consumption(self):
        v = ProgramView("p", jax.jit(lambda x: (x + 1, x * 2)),
                        (jnp.ones((3,)),))
        assert lint([v], rules=["dead-output"]).findings == []


# -- bare-json-line (AST, r16) --------------------------------------------

_BARE_SRC = """\
import json
out = {"metric": "my_tool_tok_s", "value": 12.5, "unit": "tok/s"}
out["extra"] = 1
print(json.dumps(out))
"""

_STAMPED_SRC = """\
import json
from _perf_common import stamp_result
out = {"metric": "my_tool_tok_s", "value": 12.5, "unit": "tok/s"}
print(json.dumps(stamp_result(out, "my_tool")))
"""


class TestBareJsonLine:
    def _findings(self, src, path="tools/my_tool.py"):
        return lint([SourceView.from_text(path, src)],
                    rules=["bare-json-line"]).findings

    def test_bare_result_line_flagged(self):
        fs = self._findings(_BARE_SRC)
        assert len(fs) == 1 and fs[0].severity == "error"
        assert "run_meta" in fs[0].message

    def test_stamped_twin_is_clean(self):
        assert self._findings(_STAMPED_SRC) == []

    def test_emit_result_funnel_is_clean(self):
        src = ("from _perf_common import emit_result\n"
               "out = {\"metric\": \"m\", \"value\": 1.0}\n"
               "emit_result(out, \"my_tool\")\n")
        assert self._findings(src) == []

    def test_stamp_before_separate_print_is_clean(self):
        # stamp_result mutates in place; a later bare dumps is fine
        src = ("import json\n"
               "from _perf_common import stamp_result\n"
               "out = {\"metric\": \"m\", \"value\": 1.0}\n"
               "stamp_result(out, \"my_tool\")\n"
               "print(json.dumps(out))\n")
        assert self._findings(src) == []

    def test_literal_dict_flagged(self):
        src = ("import json\n"
               "print(json.dumps({\"metric\": \"m\", \"value\": 0.0,"
               " \"error\": \"x\"}))\n")
        assert len(self._findings(src)) == 1

    def test_non_result_json_not_flagged(self):
        src = ("import json\n"
               "payload = {\"findings\": [], \"counts\": {}}\n"
               "print(json.dumps(payload))\n")
        assert self._findings(src) == []

    def test_rule_scoped_to_tool_paths(self):
        assert self._findings(_BARE_SRC,
                              path="apex_tpu/serve/engine.py") == []
        assert len(self._findings(_BARE_SRC, path="bench.py")) == 1

    def test_repo_tools_are_clean(self):
        """Every committed tool emits through the stamp funnel — the
        satellite's 'new bench tools can't regress' contract holds on
        the repo itself."""
        import glob as _g
        views = []
        for pat in ("tools/*.py", "bench.py"):
            for p in sorted(_g.glob(os.path.join(os.path.dirname(TOOLS), pat))):
                if os.path.basename(p).startswith("_"):
                    continue
                views.append(SourceView.from_file(p, root=os.path.dirname(TOOLS)))
        fs = lint(views, rules=["bare-json-line"]).findings
        assert [f for f in fs if not f.suppressed] == [], fs


# -- host-sync-in-hot-loop (AST) ------------------------------------------

_HOT_SRC = """\
import time
import numpy as np

def run(fn, xs):
    t0 = time.perf_counter()
    out = []
    for x in xs:
        y = fn(x)
        out.append(np.asarray(y))
    return out, time.perf_counter() - t0
"""


class TestHostSyncInHotLoop:
    def _findings(self, src, path="apex_tpu/serve/fake.py"):
        return lint([SourceView.from_text(path, src)],
                    rules=["host-sync-in-hot-loop"]).findings

    def test_fires_in_timed_loop(self):
        fs = self._findings(_HOT_SRC)
        assert len(fs) == 1 and fs[0].severity == "error"
        assert fs[0].details["idiom"] == "np.asarray"
        assert not fs[0].suppressed

    def test_tools_paths_are_warnings(self):
        fs = self._findings(_HOT_SRC, path="tools/fake_bench.py")
        assert len(fs) == 1 and fs[0].severity == "warning"

    def test_untimed_loop_is_clean(self):
        src = _HOT_SRC.replace("time.perf_counter()", "0.0")
        assert self._findings(src) == []

    def test_propagates_into_called_local_functions(self):
        src = """\
import time
import numpy as np

def main(fn, xs):
    def fetch(y):
        return float(y)
    t0 = time.perf_counter()
    for x in xs:
        fetch(fn(x))
    return time.perf_counter() - t0
"""
        fs = self._findings(src)
        assert len(fs) == 1 and fs[0].details["idiom"] == "float()"

    def test_inline_suppression_with_reason(self):
        src = _HOT_SRC.replace(
            "out.append(np.asarray(y))",
            "out.append(np.asarray(y))  "
            "# apex-lint: disable=host-sync-in-hot-loop -- anchor")
        fs = self._findings(src)
        assert len(fs) == 1 and fs[0].suppressed
        assert fs[0].reason == "anchor"

    def test_reasonless_suppression_is_an_error(self):
        src = _HOT_SRC.replace(
            "out.append(np.asarray(y))",
            "out.append(np.asarray(y))  "
            "# apex-lint: disable=host-sync-in-hot-loop")
        fs = self._findings(src)
        bad = [f for f in fs if f.rule == "bad-suppression"]
        live = [f for f in fs if f.rule == "host-sync-in-hot-loop"]
        assert bad and bad[0].severity == "error"
        assert live and not live[0].suppressed   # reasonless != covered

    def test_fingerprint_survives_line_drift(self):
        fs1 = self._findings(_HOT_SRC)
        fs2 = self._findings("# moved down\n\n" + _HOT_SRC)
        assert fs1[0].fingerprint == fs2[0].fingerprint
        assert fs1[0].location != fs2[0].location

    def test_input_conversions_not_flagged(self):
        src = """\
import time
import numpy as np

def run(fn, prompts):
    t0 = time.perf_counter()
    for p in prompts:
        toks = np.asarray(p, np.int32)      # host->host, has dtype
        mask = np.asarray([x > 0 for x in p] + [False])
        fn(toks, mask)
    return time.perf_counter() - t0
"""
        assert self._findings(src) == []


# -- snapshot-on-step-path (AST) ------------------------------------------

# the injected violation: a synchronous state_dict fetch + pickle write
# INSIDE the timed train loop — the exact shape the r17 async
# SnapshotWriter contract forbids
_SNAP_SYNC_SRC = """\
import pickle
import time

def train(step_fn, opt, state, n):
    t0 = time.perf_counter()
    for step in range(n):
        state = step_fn(state)
        if step % 10 == 9:
            sd = opt.state_dict(state)
            with open(f"snap_{step}.bin", "wb") as fh:
                pickle.dump(sd, fh)
    return time.perf_counter() - t0
"""

# the async twin: staging + background write through the runtime's
# writer — nothing blocking reaches the loop, so the rule stays silent
_SNAP_ASYNC_SRC = """\
import time

def train(step_fn, writer, state, n):
    t0 = time.perf_counter()
    for step in range(n):
        state = step_fn(state)
        if step % 10 == 9:
            writer.submit(step + 1, step + 1, {"state": state})
    return time.perf_counter() - t0
"""


class TestSnapshotOnStepPath:
    def _findings(self, src, path="apex_tpu/runtime/fake.py",
                  rules=("snapshot-on-step-path",)):
        return lint([SourceView.from_text(path, src)],
                    rules=list(rules)).findings

    def test_sync_snapshot_in_timed_loop_fires(self):
        fs = self._findings(_SNAP_SYNC_SRC)
        assert {f.details["idiom"] for f in fs} == \
            {".state_dict()", "pickle.dump"}
        assert all(f.severity == "error" and not f.suppressed
                   for f in fs)

    def test_async_writer_twin_is_clean(self):
        assert self._findings(_SNAP_ASYNC_SRC) == []

    def test_error_even_in_tools_paths(self):
        # unlike host-sync (tools time syncs on purpose), a sync
        # snapshot is never a measurement: error everywhere
        fs = self._findings(_SNAP_SYNC_SRC, path="tools/fake_bench.py")
        assert fs and all(f.severity == "error" for f in fs)

    def test_untimed_loop_is_clean(self):
        src = _SNAP_SYNC_SRC.replace("time.perf_counter()", "0.0")
        assert self._findings(src) == []

    def test_np_save_and_json_dump_flagged_dumps_not(self):
        src = """\
import json
import time
import numpy as np

def run(fn, state, n):
    t0 = time.perf_counter()
    lines = []
    for i in range(n):
        state = fn(state)
        np.savez("ckpt.npz", **state)
        json.dump(state, open("s.json", "w"))
        lines.append(json.dumps({"i": i}))      # string build: fine
    return lines, time.perf_counter() - t0
"""
        fs = self._findings(src)
        assert {f.details["idiom"] for f in fs} == \
            {"np.savez", "json.dump"}

    def test_propagates_into_called_local_functions(self):
        src = """\
import pickle
import time

def train(step_fn, state, n):
    def persist(s):
        pickle.dump(s, open("s.bin", "wb"))
    t0 = time.perf_counter()
    for step in range(n):
        state = step_fn(state)
        persist(state)
    return time.perf_counter() - t0
"""
        fs = self._findings(src)
        assert len(fs) == 1 and fs[0].details["idiom"] == "pickle.dump"

    def test_suppression_with_reason(self):
        src = _SNAP_SYNC_SRC.replace(
            "pickle.dump(sd, fh)",
            "pickle.dump(sd, fh)  "
            "# apex-lint: disable=snapshot-on-step-path -- grace save")
        fs = self._findings(src)
        sup = [f for f in fs if f.suppressed]
        assert len(sup) == 1 and sup[0].reason == "grace save"

    def test_runtime_and_smoke_sources_are_clean(self):
        """The shipped async implementation and its smoke driver obey
        their own contract."""
        repo = os.path.dirname(TOOLS)
        views = [SourceView.from_file(p, root=repo) for p in
                 (os.path.join(repo, "apex_tpu/runtime/snapshot.py"),
                  os.path.join(repo, "apex_tpu/runtime/supervisor.py"),
                  os.path.join(repo, "tools/fleet_smoke.py"))]
        fs = lint(views, rules=["snapshot-on-step-path"]).findings
        assert [f for f in fs if not f.suppressed] == [], fs


# -- blocking-emit-on-step-path (AST) --------------------------------------

# the injected violation: a socket write + a blocking queue put INSIDE
# the timed decode loop — the exact shape the r18 LiveEmitter contract
# forbids (the observer becoming the straggler)
_EMIT_SYNC_SRC = """\
import time

def serve(step_fn, sock, q, state, n):
    t0 = time.perf_counter()
    for step in range(n):
        state, out = step_fn(state)
        sock.sendall(out)
        q.put(out)
    return time.perf_counter() - t0
"""

# the non-blocking twin: bounded-queue put_nowait (the LiveEmitter
# step-path idiom) — the rule stays silent
_EMIT_ASYNC_SRC = """\
import queue
import time

def serve(step_fn, q, state, n):
    t0 = time.perf_counter()
    drops = 0
    for step in range(n):
        state, out = step_fn(state)
        try:
            q.put_nowait(out)
        except queue.Full:
            drops += 1
    return drops, time.perf_counter() - t0
"""


class TestBlockingEmitOnStepPath:
    def _findings(self, src, path="apex_tpu/serve/fake.py"):
        return lint([SourceView.from_text(path, src)],
                    rules=["blocking-emit-on-step-path"]).findings

    def test_socket_send_and_blocking_put_fire(self):
        fs = self._findings(_EMIT_SYNC_SRC)
        assert {f.details["idiom"] for f in fs} == \
            {".sendall()", ".put()"}
        assert all(f.severity == "error" and not f.suppressed
                   for f in fs)

    def test_put_nowait_twin_is_clean(self):
        assert self._findings(_EMIT_ASYNC_SRC) == []

    def test_nonblocking_put_forms_are_clean(self):
        src = """\
import time

def serve(step_fn, q, state, n):
    t0 = time.perf_counter()
    for step in range(n):
        state, out = step_fn(state)
        q.put(out, block=False)
        q.put(out, False)
        q.put(out, timeout=0.01)
    return time.perf_counter() - t0
"""
        assert self._findings(src) == []

    def test_connect_in_timed_loop_fires(self):
        src = """\
import socket
import time

def poll(addrs, n):
    t0 = time.perf_counter()
    for a in addrs:
        s = socket.socket()
        s.connect(a)
        s.close()
    return time.perf_counter() - t0
"""
        fs = self._findings(src)
        assert len(fs) == 1 and fs[0].details["idiom"] == ".connect()"

    def test_error_even_in_tools_paths(self):
        # emission is never a measurement: error everywhere, same
        # policy as snapshot-on-step-path
        fs = self._findings(_EMIT_SYNC_SRC, path="tools/fake_bench.py")
        assert fs and all(f.severity == "error" for f in fs)

    def test_untimed_loop_is_clean(self):
        src = _EMIT_SYNC_SRC.replace("time.perf_counter()", "0.0")
        assert self._findings(src) == []

    def test_suppression_with_reason(self):
        # suppress the LAST sink (a comment covers its own line and
        # the next, so suppressing sendall would sweep the put too)
        src = _EMIT_SYNC_SRC.replace(
            "q.put(out)",
            "q.put(out)  "
            "# apex-lint: disable=blocking-emit-on-step-path -- drain")
        fs = self._findings(src)
        sup = [f for f in fs if f.suppressed]
        live = [f for f in fs if not f.suppressed]
        assert len(sup) == 1 and sup[0].reason == "drain"
        assert sup[0].details["idiom"] == ".put()"
        assert live and live[0].details["idiom"] == ".sendall()"

    def test_live_plane_sources_are_clean(self):
        """The shipped emitter/collector and the engine's live wiring
        obey their own contract (live.py's sender thread owns every
        socket call, and its loop is untimed by construction)."""
        repo = os.path.dirname(TOOLS)
        views = [SourceView.from_file(p, root=repo) for p in
                 (os.path.join(repo, "apex_tpu/prof/live.py"),
                  os.path.join(repo, "apex_tpu/serve/engine.py"),
                  os.path.join(repo, "tools/serve_top.py"),
                  os.path.join(repo, "tools/fleet_smoke.py"))]
        fs = lint(views,
                  rules=["blocking-emit-on-step-path"]).findings
        assert [f for f in fs if not f.suppressed] == [], fs


# -- unattributed-shed (AST, r19) ------------------------------------------

# the injected violation: a router shedding load with a bare counter —
# the drop is counted but attributed to nothing, so the telemetry
# cannot distinguish this admission decision from a LOST request
_SHED_BARE_SRC = """\
class Router:
    def route(self, req, overloaded):
        if overloaded:
            self.shed_count += 1
            return None
        return self.pick(req)
"""

# the attributed twin: same shed, but the function writes the record
# naming the triggering rule and the replica the load was heading for
_SHED_ATTRIBUTED_SRC = """\
class Router:
    def route(self, req, overloaded, rule, replica):
        if overloaded:
            self.shed_count += 1
            self.shed_log.append({"request": req.id, "rule": rule,
                                  "replica": replica})
            return None
        return self.pick(req)
"""


class TestUnattributedShed:
    def _findings(self, src, path="apex_tpu/serve/fake_router.py"):
        return lint([SourceView.from_text(path, src)],
                    rules=["unattributed-shed"]).findings

    def test_bare_shed_counter_fires(self):
        fs = self._findings(_SHED_BARE_SRC)
        assert len(fs) == 1 and fs[0].severity == "error"
        assert fs[0].details["idiom"] == "shed_count +="
        assert "rule + replica" in fs[0].message

    def test_attributed_twin_is_clean(self):
        assert self._findings(_SHED_ATTRIBUTED_SRC) == []

    def test_bare_append_fires_and_kwargs_attribution_clears(self):
        src = """\
def drop(reqs, shed_log):
    for r in reqs:
        shed_log.append(r.id)
"""
        fs = self._findings(src)
        assert len(fs) == 1
        assert fs[0].details["idiom"] == "shed_log.append"
        src_ok = src.replace(
            "shed_log.append(r.id)",
            "shed_log.append(r.id)\n"
            "        log_shed(request=r.id, rule=rule, "
            "replica=target)")
        assert self._findings(src_ok) == []

    def test_non_shed_counters_are_clean(self):
        # the LiveEmitter's telemetry-sample drop counter is NOT a
        # request shed — the rule must not reach it
        src = """\
class Emitter:
    def enqueue(self, msg):
        try:
            self.q.put_nowait(msg)
        except Full:
            self.drops += 1
"""
        assert self._findings(src) == []

    def test_suppression_with_reason(self):
        src = _SHED_BARE_SRC.replace(
            "self.shed_count += 1",
            "self.shed_count += 1  "
            "# apex-lint: disable=unattributed-shed -- probe twin")
        fs = self._findings(src)
        assert len(fs) == 1 and fs[0].suppressed
        assert fs[0].reason == "probe twin"

    def test_shipped_router_is_clean(self):
        """The shipped router books every shed with its rule+replica
        attribution — its own contract, audited."""
        repo = os.path.dirname(TOOLS)
        views = [SourceView.from_file(
            os.path.join(repo, "apex_tpu/serve/router.py"),
            root=repo)]
        fs = lint(views, rules=["unattributed-shed"]).findings
        assert [f for f in fs if not f.suppressed] == [], fs


# -- page-gather-hazard (AST, r20) -----------------------------------------

# the injected violation: the decode loop rebuilds the page map as a
# fresh device array every step — a new input-layout lineage for the
# donated KV gather (the r14 layout-keyed recompile landmine applied
# to the r20 paged arena's new operand) — and fetches it back
_PAGE_HAZARD_SRC = """\
import time

def serve(decode_fn, params, state, page_table, n):
    t0 = time.perf_counter()
    for step in range(n):
        pages = jnp.asarray(page_table)
        state, out = decode_fn(params, state, pages)
        page_table = np.asarray(pages)
    return time.perf_counter() - t0
"""

# the compliant twin (the shipped engine's shape): the page map is a
# loop-invariant HOST np buffer mutated in place — the rule is silent
_PAGE_CLEAN_SRC = """\
import time

def serve(decode_fn, params, state, page_table, retire, n):
    t0 = time.perf_counter()
    for step in range(n):
        state, out = decode_fn(params, state, page_table)
        retire(page_table)          # in-place host mutation only
    return time.perf_counter() - t0
"""


class TestPageGatherHazard:
    def _findings(self, src, path="apex_tpu/serve/fake_engine.py"):
        return lint([SourceView.from_text(path, src)],
                    rules=["page-gather-hazard"]).findings

    def test_device_rebuild_and_host_fetch_fire(self):
        fs = self._findings(_PAGE_HAZARD_SRC)
        assert {f.details["idiom"] for f in fs} == \
            {"jnp.asarray(page_table)", "np.asarray(pages)"}
        assert all(f.severity == "error" and not f.suppressed
                   for f in fs)
        assert all("layout" in f.message for f in fs)

    def test_host_buffer_twin_is_clean(self):
        assert self._findings(_PAGE_CLEAN_SRC) == []

    def test_non_page_operands_are_clean(self):
        # jnp.asarray of ordinary step inputs is how data ENTERS a
        # program — only page-named operands are the gather's index
        src = _PAGE_HAZARD_SRC.replace("page_table", "tok_mat") \
                              .replace("pages", "chunk")
        assert self._findings(src) == []

    def test_untimed_loop_is_clean(self):
        src = _PAGE_HAZARD_SRC.replace("time.perf_counter()", "0.0")
        assert self._findings(src) == []

    def test_device_put_fires(self):
        src = _PAGE_CLEAN_SRC.replace(
            "state, out = decode_fn(params, state, page_table)",
            "state, out = decode_fn(params, state, "
            "jax.device_put(page_table))")
        fs = self._findings(src)
        assert len(fs) == 1 \
            and fs[0].details["idiom"] == "jax.device_put(page_table)"

    def test_suppression_with_reason(self):
        src = _PAGE_HAZARD_SRC.replace(
            "pages = jnp.asarray(page_table)",
            "pages = jnp.asarray(page_table)  "
            "# apex-lint: disable=page-gather-hazard -- warm transfer")
        fs = self._findings(src)
        sup = [f for f in fs if f.suppressed]
        assert len(sup) == 1 and sup[0].reason == "warm transfer"

    def test_shipped_engine_is_clean_and_paged_programs_lint(self):
        """The shipped engine obeys its own contract (host page table,
        mutated in place), and the paged canonical trio lints clean —
        including layout-recompile-hazard over the paged lineage
        declarations (warmup() must cover the same predecessor graph
        as the dense engine)."""
        from apex_tpu.analysis.programs import serve_programs
        repo = os.path.dirname(TOOLS)
        views = [SourceView.from_file(
            os.path.join(repo, "apex_tpu/serve/engine.py"), root=repo)]
        fs = lint(views, rules=["page-gather-hazard"]).findings
        assert [f for f in fs if not f.suppressed] == [], fs
        progs = serve_programs(fused=True, paged=True)
        assert any("paged" in p.name for p in progs)
        rep = lint(progs, rules=["layout-recompile-hazard",
                                 "donation-miss", "dead-output"])
        assert rep.errors() == [], rep.findings


# -- spec-shape-hazard (AST, r21) ------------------------------------------

# the injected violation: the spec decode loop trims the candidate
# block to the ACCEPTED length on the host and re-enters the donated
# program — one fresh query-dim shape (and one un-warmed recompile)
# per distinct acceptance outcome
_SPEC_HAZARD_SRC = """\
import time

def serve(spec_fn, params, state, cand, draft_toks, n):
    t0 = time.perf_counter()
    for step in range(n):
        n_acc = int(state.n_acc)
        cand = cand[:n_acc]
        params, state = params, state
        state, out = spec_fn(params, state, draft_toks[:, :n_acc])
    return time.perf_counter() - t0
"""

# the compliant twin (the shipped engine's shape): device blocks stay
# full width k+1, acceptance is an on-device n_emit mask, and host
# slicing happens only on the post-sync packed output — silent
_SPEC_CLEAN_SRC = """\
import time

def serve(spec_fn, params, state, cand, n):
    t0 = time.perf_counter()
    for step in range(n):
        state, packed = spec_fn(params, state, cand)
        rows = np.asarray(packed)      # the step's one host sync
        ne = int(rows[5, 0])
        emitted = rows[:4]             # static k rows, host buffer
    return time.perf_counter() - t0
"""


class TestSpecShapeHazard:
    def _findings(self, src, path="apex_tpu/serve/fake_engine.py"):
        return lint([SourceView.from_text(path, src)],
                    rules=["spec-shape-hazard"]).findings

    def test_variable_length_slices_fire(self):
        fs = self._findings(_SPEC_HAZARD_SRC)
        assert {f.details["idiom"] for f in fs} == \
            {"cand[...variable slice...]",
             "draft_toks[...variable slice...]"}
        assert all(f.severity == "error" and not f.suppressed
                   for f in fs)
        assert all("query dim" in f.message for f in fs)

    def test_full_width_masked_twin_is_clean(self):
        assert self._findings(_SPEC_CLEAN_SRC) == []

    def test_static_slices_are_clean(self):
        # literal-bound slices are shape-static — no recompile
        src = _SPEC_HAZARD_SRC.replace("[:n_acc]", "[:4]") \
                              .replace("[:, :n_acc]", "[:, :-1]")
        assert self._findings(src) == []

    def test_non_spec_names_are_clean(self):
        # variable-length slicing of ordinary buffers is not this
        # rule's business (ragged host bookkeeping is everywhere)
        src = _SPEC_HAZARD_SRC.replace("cand", "tok_mat") \
                              .replace("draft_toks", "chunk")
        assert self._findings(src) == []

    def test_untimed_loop_is_clean(self):
        src = _SPEC_HAZARD_SRC.replace("time.perf_counter()", "0.0")
        assert self._findings(src) == []

    def test_suppression_with_reason(self):
        src = _SPEC_HAZARD_SRC.replace(
            "cand = cand[:n_acc]",
            "cand = cand[:n_acc]  "
            "# apex-lint: disable=spec-shape-hazard -- host replay")
        fs = self._findings(src)
        sup = [f for f in fs if f.suppressed]
        assert len(sup) == 1 and sup[0].reason == "host replay"

    def test_shipped_engine_is_clean_and_spec_caches_pinned(self):
        """The shipped spec engine obeys its own contract two ways:
        (a) statically — the rule finds no variable-width spec slices
        in engine.py; (b) at runtime — draft/target k-switching (the
        draft's 2-query catch-up + 1-query chain and the target's
        (k+1)-query scoring live inside ONE donated program) adds ZERO
        jit-cache entries after warmup, the r14 pin on the r21
        program."""
        import jax
        import numpy as np
        from apex_tpu.models import TransformerLM
        from apex_tpu.serve import (ContinuousBatchingEngine, Request,
                                    draft_from_prefix)
        repo = os.path.dirname(TOOLS)
        views = [SourceView.from_file(
            os.path.join(repo, "apex_tpu/serve/engine.py"), root=repo)]
        fs = lint(views, rules=["spec-shape-hazard"]).findings
        assert [f for f in fs if not f.suppressed] == [], fs

        m = TransformerLM(vocab_size=41, max_seq_len=64, embed_dim=16,
                          num_heads=2, num_layers=2)
        p = m.init(jax.random.key(0))
        eng = ContinuousBatchingEngine(
            m, p, slots=2, max_len=24, prefill_chunk=4,
            draft=draft_from_prefix(m, p, 1), spec_k=3)
        eng.warmup()
        before = eng._decode_fn._cache_size()
        reqs = [Request(id=i, prompt=np.arange(1, 6 + i,
                                               dtype=np.int32) % 41,
                        max_new=6) for i in range(3)]
        eng.run(reqs)
        assert eng._decode_fn._cache_size() == before, \
            "the fused spec program recompiled across k-switching"


# -- orphan-span (AST, r22) ------------------------------------------------

# the injected violation: two spans opened with string-literal names
# and NONE of request=/trace=/parent= — at merge time all three trace
# resolution paths (direct attr, parent chain, request->trace map)
# dead-end and they land in the orphans list
_ORPHAN_SRC = """\
def handle(tr, req):
    rid = tr.begin("request", request=req.id, trace=req.trace)
    q = tr.begin("queue")
    tr.instant("reroute")
    tr.end(q)
    tr.end(rid)
"""

# the compliant twin: every span carries at least one linking kwarg
_LINKED_SRC = """\
def handle(tr, req, ctx):
    rid = tr.begin("request", request=req.id)
    q = tr.begin("queue", parent=rid)
    tr.instant("reroute", trace=req.trace)
    tr.instant("replay_hop", **ctx)
    tr.end(q)
    tr.end(rid)

def begin(self, name, **attrs):
    return self._fwd.begin(name, **attrs)
"""


class TestOrphanSpan:
    def _findings(self, src, path="apex_tpu/serve/fake_router.py"):
        return lint([SourceView.from_text(path, src)],
                    rules=["orphan-span"]).findings

    def test_unlinked_spans_fire(self):
        fs = self._findings(_ORPHAN_SRC)
        assert {f.details["span"] for f in fs} == {"queue", "reroute"}
        assert all(f.severity == "error" and not f.suppressed
                   for f in fs)
        assert all("merged fleet timeline" in f.message for f in fs)

    def test_each_linking_kwarg_silences(self):
        # any ONE of request=/trace=/parent= ties the span into a
        # merged timeline; a **kw splat may carry them dynamically and
        # a Name first arg is internal forwarding — all silent
        assert self._findings(_LINKED_SRC) == []
        for kw in ("request=1", "trace=t", "parent=p"):
            assert self._findings(
                f"def f(tr, t, p):\n"
                f"    tr.begin('queue', {kw})\n") == []

    def test_serving_tier_only(self):
        # training examples open step-interval spans with no request
        # lifecycle to link to — the rule is path-gated to serve/* and
        # tools/ so that false-positive class never fires
        for path in ("examples/dcgan/train.py",
                     "apex_tpu/prof/spans.py"):
            assert self._findings(_ORPHAN_SRC, path=path) == []
        assert self._findings(_ORPHAN_SRC,
                              path="tools/serve_bench.py") != []

    def test_suppression_with_reason(self):
        src = _ORPHAN_SRC.replace(
            'tr.instant("reroute")',
            'tr.instant("reroute")  '
            '# apex-lint: disable=orphan-span -- scheduler-scope')
        fs = self._findings(src)
        sup = [f for f in fs if f.suppressed]
        assert len(sup) == 1 and sup[0].reason == "scheduler-scope"
        assert [f.details["span"] for f in fs if not f.suppressed] \
            == ["queue"]

    def test_shipped_serving_tier_is_clean(self):
        """The shipped engine/router/tools carry no unsuppressed
        orphan spans — every span the serving tier opens can join a
        merged fleet trace (or declares scheduler scope inline)."""
        repo = os.path.dirname(TOOLS)
        views = [SourceView.from_file(os.path.join(repo, p), root=repo)
                 for p in ("apex_tpu/serve/engine.py",
                           "apex_tpu/serve/router.py",
                           "tools/serve_bench.py",
                           "tools/fleet_smoke.py")]
        fs = lint(views, rules=["orphan-span"]).findings
        assert [f for f in fs if not f.suppressed] == [], fs
        # the two scheduler-scope engine spans declare themselves
        sup = [f for f in fs if f.suppressed]
        assert {f.details["span"] for f in sup} >= \
            {"prefill_batch", "decode_step"}


# -- baseline machinery ----------------------------------------------------

class TestBaseline:
    def test_baseline_suppresses_with_reason(self, tmp_path):
        v = ProgramView("p", jax.jit(lambda x: (x + 1, x * 2)),
                        (jnp.ones((3,)),),
                        consumed_outputs=frozenset({"0"}))
        fp = lint([v], rules=["dead-output"]).findings[0].fingerprint
        base = tmp_path / "base.json"
        base.write_text(json.dumps({"version": 1, "suppressions": [
            {"fingerprint": fp, "reason": "kept for the A/B tool"}]}))
        rep = lint([v], rules=["dead-output"],
                   baseline_path=str(base))
        assert rep.findings[0].suppressed
        assert rep.findings[0].reason == "kept for the A/B tool"
        assert rep.errors() == []

    def test_reasonless_baseline_entry_is_an_error(self, tmp_path):
        base = tmp_path / "base.json"
        base.write_text(json.dumps({"version": 1, "suppressions": [
            {"fingerprint": "x:y:z"}]}))
        rep = lint([], baseline_path=str(base))
        assert [f.rule for f in rep.errors()] == ["bad-suppression"]


# -- the CLI + the committed repo state ------------------------------------

class TestCli:
    def test_source_scan_strict_passes_on_this_repo(self):
        """The committed state is the acceptance artifact: the AST
        rules over serve/tools/examples plus the committed baseline
        and inline suppressions leave ZERO unsuppressed errors."""
        import subprocess
        r = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "apex_lint.py"),
             "--programs", "none", "--strict", "--json", "-",
             "--devices", "1"],
            capture_output=True, text=True, timeout=240,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-800:])
        payload = json.loads(r.stdout.splitlines()[0])
        assert payload["counts"]["error"] == 0
        # the repo demonstrates both suppression flavors, with reasons
        sup = [f for f in payload["findings"] if f["suppressed"]]
        assert sup and all(f.get("reason") for f in sup)
        assert any(f["target"].endswith("serve/engine.py")
                   for f in sup)

    def test_unknown_rule_and_program_refused(self):
        with pytest.raises(KeyError):
            lint([], rules=["no-such-rule"])
        from apex_tpu.analysis.programs import build_programs
        with pytest.raises(KeyError):
            build_programs(["no_such_program"])


# -- the runtime cross-check harness (--lint-xref) ------------------------

class TestLintXref:
    def _tr(self):
        sys.path.insert(0, TOOLS)
        try:
            import telemetry_report as TR
        finally:
            sys.path.remove(TOOLS)
        return TR

    def test_covered_and_missed(self):
        TR = self._tr()
        records = [
            {"kind": "header", "schema": 5},
            {"kind": "recompile", "fn": "train_step"},
            {"kind": "amp_overflow", "culprits": ["w"]},
            {"kind": "alert", "rule": "stall"},
        ]
        payload = {"findings": [
            {"rule": "layout-recompile-hazard", "suppressed": False},
            {"rule": "host-sync-in-hot-loop", "suppressed": False}]}
        x = TR.lint_xref(records, payload)
        assert x["missed"] == ["amp_overflow"]
        by = {r["incident"]: r for r in x["rows"]}
        assert by["recompile"]["covered"]
        assert by["stall"]["covered"]
        assert not by["amp_overflow"]["covered"]
        md = TR.render_lint_xref(x, "t.jsonl", "lint.json")
        assert "MISSED" in md and "amp_overflow" in md

    def test_all_clear_and_empty(self):
        TR = self._tr()
        x = TR.lint_xref([{"kind": "header"}, {"kind": "step"}],
                         {"findings": []})
        assert x["rows"] == [] and x["missed"] == []
        assert "no recompile" in TR.render_lint_xref(x, "a", "b")
