"""Guards for the measurement-harness plumbing (tools/).

The harness is load-bearing: the watchdog must kill a stalled tool
quickly, the start-up gate must tell a CPU run from a chip run, and
every tool must be importable from a bare environment (no PYTHONPATH).
These tests pin that behavior on CPU; no TPU required.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")

BARE_ENV = {
    # deliberately NO PYTHONPATH pointing at the repo: the tools find
    # it themselves
    "PATH": os.environ.get("PATH", ""),
    "HOME": os.environ.get("HOME", "/root"),
    "JAX_PLATFORMS": "cpu",     # the explicit CPU request
}


class TestWatchdog:
    def test_fires_on_stall_with_exit_3(self):
        code = textwrap.dedent("""
            import sys, time
            sys.path.insert(0, %r)
            from _perf_common import arm_watchdog
            feed = arm_watchdog("t", seconds=0.3)
            time.sleep(30)   # never feeds -> watchdog must kill us
            print("survived")
        """ % TOOLS)
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=25)
        assert r.returncode == 3, (r.returncode, r.stderr)
        assert "WATCHDOG" in r.stderr
        assert "survived" not in r.stdout

    def test_feeding_keeps_process_alive(self):
        code = textwrap.dedent("""
            import sys, time
            sys.path.insert(0, %r)
            from _perf_common import arm_watchdog
            feed = arm_watchdog("t", seconds=2.0)
            for _ in range(8):
                time.sleep(0.4)   # 5x scheduling margin vs the window
                feed()
            print("survived")
        """ % TOOLS)
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=25)
        assert r.returncode == 0, r.stderr
        assert "survived" in r.stdout

    def test_allow_grants_one_long_gap_then_tightens(self):
        code = textwrap.dedent("""
            import sys, time
            sys.path.insert(0, %r)
            from _perf_common import arm_watchdog
            feed = arm_watchdog("t", seconds=0.8)
            feed(allow=8.0)
            time.sleep(2.5)  # would die under the tight window
            print("long-gap-ok", flush=True)
            feed()           # back to the tight window
            time.sleep(30)
            print("survived")
        """ % TOOLS)
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=25)
        assert "long-gap-ok" in r.stdout
        assert r.returncode == 3, (r.returncode, r.stderr)
        assert "survived" not in r.stdout


class TestToolsSelfContained:
    """Every on-chip tool must come up without a repo PYTHONPATH —
    --help exercises the module top level including the sys.path
    bootstrap."""

    @pytest.mark.parametrize("tool", ["kernel_bench.py", "decode_bench.py",
                                      "perf_probe.py", "../chip_smoke.py",
                                      "trace_top_ops.py", "hlo_audit.py",
                                      "serve_top.py"])
    def test_help_from_foreign_cwd(self, tool, tmp_path):
        r = subprocess.run(
            [sys.executable, os.path.join(TOOLS, tool), "--help"],
            capture_output=True, text=True, timeout=120,
            cwd=tmp_path, env=BARE_ENV)
        assert r.returncode == 0, (tool, r.stderr[-500:])

    def test_decode_bench_cpu_smoke(self, tmp_path):
        """decode_bench's full run path (CPU config override, jitted
        generate variants, differenced decode-only timing, JSON
        contract) must work off-chip under the explicit CPU request —
        a regression must not first surface as a failed chip call."""
        import json
        r = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "decode_bench.py")],
            capture_output=True, text=True, timeout=600,
            cwd=tmp_path, env=BARE_ENV)
        assert r.returncode == 0, r.stderr[-800:]
        out = json.loads(r.stdout.strip().splitlines()[-1])
        assert out["unit"] == "decoded_tokens/s" and out["value"] > 0
        assert out["decode_ms_per_step"] > 0
        assert out["e2e_tok_s"] > 0
        # decode-only throughput should exceed the prefill-inclusive
        # e2e rate (the differencing exists to separate exactly these),
        # but 2-iteration CPU timings are noisy enough that the
        # differenced rate occasionally lands a hair BELOW e2e — allow
        # 10% slack rather than flake (the strict inequality still
        # holds on any real-length run)
        assert out["value"] >= 0.9 * out["e2e_tok_s"]
        assert out["metric"].startswith("lm_decode_tok_s_P16_N8_b2")

    def test_decode_bench_refuses_tiny_new(self, tmp_path):
        """--new < 4 must die at argparse time with a descriptive error
        (a degenerate 1-3 token spread makes the differenced decode rate
        meaningless), before any backend spin-up."""
        r = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "decode_bench.py"),
             "--new", "2"],
            capture_output=True, text=True, timeout=120,
            cwd=tmp_path, env=BARE_ENV)
        assert r.returncode != 0
        assert "--new must be >= 4" in r.stderr
        assert not r.stdout.strip()          # no JSON line emitted


class TestHloAudit:
    """audit_hlo_text: the parse that turns an optimized-HLO dump into
    the structure summary must count top-level vs in-fusion ops
    separately and size shape literals correctly."""

    HLO = textwrap.dedent("""\
        HloModule jit_step

        %fused_computation.1 (p0: bf16[256,1024]) -> f32[256,1024] {
          %p0 = bf16[256,1024]{1,0} parameter(0)
          %c = f32[256,1024]{1,0} convert(%p0)
          ROOT %m = f32[256,1024]{1,0} multiply(%c, %c)
        }

        ENTRY %main (a: bf16[256,1024], w: bf16[1024,1024]) -> f32[256,1024] {
          %a = bf16[256,1024]{1,0} parameter(0)
          %w = bf16[1024,1024]{1,0} parameter(1)
          %conv0 = f32[256,1024]{1,0} convert(%a)
          %d = bf16[256,1024]{1,0} dot(%a, %w)
          %fus = f32[256,1024]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.1
          %cp = f32[256,1024]{1,0} copy(%fus)
          ROOT %r = f32[256,1024]{1,0} add(%cp, %conv0)
        }
    """)

    def test_parse_counts_and_bytes(self):
        sys.path.insert(0, TOOLS)
        from hlo_audit import audit_hlo_text, shape_bytes
        s = audit_hlo_text(self.HLO)
        assert s["n_fusions"] == 1
        assert s["n_top_level_converts"] == 1
        assert s["n_top_level_copies"] == 1
        # the in-fusion convert is counted separately, not at top level
        assert s["inside_fusions_histogram"]["convert"] == 1
        assert s["top_level_histogram"]["dot"] == 1
        # optimized-HLO instruction lines carry only the OUTPUT shape
        # literal (operands are bare names), so the byte metric is
        # output bytes: f32[256,1024] = 1 MiB
        assert s["top_level_convert_bytes"] == 256 * 1024 * 4
        # shape_bytes itself sums every literal present in the text
        assert shape_bytes("f32[2,3]{1,0} x(bf16[4]{0})") == 24 + 8

    def test_audit_donation_from_lowered_signature(self):
        """The donation audit reads tf.aliasing_output off a REAL
        jax-lowered signature (not a hand-written fixture): donated
        state args are aliased, stream inputs are the only undonated
        bytes."""
        import functools

        import jax
        import jax.numpy as jnp
        sys.path.insert(0, TOOLS)
        from hlo_audit import audit_donation

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def step(state, stats, x):
            return state + x.sum(), stats * 2.0, x * 1.5

        text = step.lower(jnp.zeros((128, 64), jnp.float32),
                          jnp.zeros((16,), jnp.bfloat16),
                          jnp.ones((128, 64), jnp.float32)).as_text()
        d = audit_donation(text)
        assert d["n_args"] == 3 and d["n_donated"] == 2
        assert d["donated_bytes"] == 128 * 64 * 4 + 16 * 2
        assert d["undonated_bytes"] == 128 * 64 * 4
        assert d["undonated"][0]["type"] == "128x64xf32"

    def test_cross_reference_gaps(self):
        """Gap sites from a trace join against the compiled module:
        fusions resolve to their called computation, a seam bounded by
        a convert-carrying fusion (or a top-level convert) is flagged —
        the per-gap question the cast-coalescing A/B needs answered."""
        sys.path.insert(0, TOOLS)
        from hlo_audit import cross_reference_gaps
        sites = [
            # fus calls fused_computation.1, which contains a convert
            {"before": "fus", "after": "d", "dur_us": 120.0,
             "category": "fusion-break"},
            # top-level convert bounds the gap directly
            {"before": "conv0", "after": "cp", "dur_us": 40.0,
             "category": "convert-seam"},
            # neither side in this module (another program's ops)
            {"before": "fusion.999", "after": "fusion.998",
             "dur_us": 10.0, "category": "fusion-break"},
            # dot -> copy: resolved, no convert at the seam
            {"before": "d", "after": "cp", "dur_us": 5.0,
             "category": "fusion-break"},
        ]
        xref = cross_reference_gaps(self.HLO, sites)
        assert xref[0]["before"]["op"] == "fusion"
        assert xref[0]["before"]["calls"] == "fused_computation.1"
        assert xref[0]["convert_at_seam"] and xref[0]["resolved"]
        assert xref[1]["before"]["op"] == "convert"
        assert xref[1]["convert_at_seam"]
        assert not xref[2]["resolved"]
        assert not xref[2]["convert_at_seam"]
        assert xref[3]["resolved"] and not xref[3]["convert_at_seam"]

    def test_trace_top_ops_cli_emits_gaps_table(self, tmp_path):
        """The CLI prints the GAPS attribution section for a real
        capture and writes the machine-readable gap sites for
        hlo_audit --gaps."""
        import json

        import jax
        import jax.numpy as jnp
        from apex_tpu import prof

        @jax.jit
        def f(a, b):
            return (a @ b).sum()

        a = jnp.ones((128, 128), jnp.float32)
        f(a, a).block_until_ready()
        logdir = str(tmp_path / "trace")
        with prof.trace(logdir):
            for _ in range(3):
                f(a, a).block_until_ready()
        gaps_json = str(tmp_path / "gaps.json")
        r = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "trace_top_ops.py"),
             logdir, "--min-gap-us", "0.5", "--gaps-json", gaps_json],
            capture_output=True, text=True, timeout=300,
            cwd=tmp_path, env=dict(BARE_ENV))
        assert r.returncode == 0, r.stderr[-800:]
        assert "| op | type |" in r.stdout       # per-op table intact
        assert "## GAPS" in r.stdout
        assert "gap attribution:" in r.stdout
        sites = json.loads(open(gaps_json).read())
        assert "gaps" in sites and "by_category" in sites
        for g in sites["gaps"]:
            assert g["category"] and g["dur_us"] > 0


class TestHostInit:
    """utils.host_init/ship (the one-bulk-transfer init pattern the
    benches use) and the strict start-up gate in front of them."""

    def test_host_init_runs_on_cpu_and_ship_commits(self):
        import jax
        import jax.numpy as jnp
        from apex_tpu.utils import host_init, ship

        with host_init():
            x = jnp.arange(8, dtype=jnp.float32) * 2.0
        assert list(x.devices())[0].platform == "cpu"
        y = ship(x)
        assert list(y.devices())[0] == jax.devices()[0]
        assert float(jnp.sum(y)) == 56.0

    def test_rng_bit_identical_under_host_init(self):
        import jax
        import numpy as np
        from apex_tpu.utils import host_init

        direct = jax.random.normal(jax.random.key(7), (16,))
        with host_init():
            hosted = jax.random.normal(jax.random.key(7), (16,))
        np.testing.assert_array_equal(np.asarray(direct),
                                      np.asarray(hosted))

    def test_ship_pytree(self):
        import jax.numpy as jnp
        from apex_tpu.utils import host_init, ship

        with host_init():
            tree = {"a": jnp.ones((4,)), "b": (jnp.zeros((2, 2)),)}
        out = ship(tree)
        assert float(out["a"].sum()) == 4.0
        assert out["b"][0].shape == (2, 2)

    def test_gate_raises_with_nothing_pinned_and_cpu_default(
            self, monkeypatch):
        """jax's own fall-back: no platform pinned, the chip did not
        come up, the default backend is the CPU. Never a smaller run."""
        import jax
        from apex_tpu.utils import require_accelerator
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        prev = jax.config.jax_platforms
        try:
            jax.config.update("jax_platforms", None)
            with pytest.raises(RuntimeError, match="no accelerator"):
                require_accelerator()
        finally:
            jax.config.update("jax_platforms", prev)

    def test_gate_passes_under_explicit_cpu_request(self, monkeypatch):
        import jax
        from apex_tpu.utils import require_accelerator, setup_host_backend
        assert jax.config.jax_platforms == "cpu"   # conftest pinned it
        assert require_accelerator() == "cpu"
        # ... and by the environment alone
        prev = jax.config.jax_platforms
        try:
            jax.config.update("jax_platforms", None)
            monkeypatch.setenv("JAX_PLATFORMS", "cpu")
            assert setup_host_backend() == "cpu"
        finally:
            jax.config.update("jax_platforms", prev)
        # a CPU run keeps no compile cache (setup enables it on the chip)
        assert jax.config.jax_compilation_cache_dir is None

    def test_gate_raises_when_a_named_platform_fell_back(self):
        """A platform list that names more than the CPU is not a request
        for the CPU: coming up on it is the silent fall-back."""
        import jax
        from apex_tpu.utils import require_accelerator
        prev = jax.config.jax_platforms
        try:
            jax.config.update("jax_platforms", "fake_remote,cpu")
            with pytest.raises(RuntimeError, match="no accelerator"):
                require_accelerator()
        finally:
            jax.config.update("jax_platforms", prev)

    def test_host_init_without_cpu_backend_fails_loudly(self):
        # JAX_PLATFORMS=fake: no cpu backend can be found — host_init
        # must raise, not run the init somewhere else in silence
        code = textwrap.dedent("""
            from apex_tpu.utils import host_init
            try:
                with host_init():
                    pass
            except RuntimeError as e:
                print("RAISED", e)
        """)
        env = dict(BARE_ENV, JAX_PLATFORMS="fake", PYTHONPATH=REPO)
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0 and "RAISED" in r.stdout, r.stderr


class TestTraceTopOpsStrict:
    """`trace_top_ops.py --strict` (r07 satellite): exit 1 when the gap
    classifier leaves more than the threshold unattributed, exit 0
    otherwise — the chip-window gate that stops a blind GAPS table from
    being committed as a clean attribution."""

    def _capture(self, tmp_path, names):
        pytest.importorskip("google.protobuf")
        import importlib
        sys.path.insert(0, REPO)
        try:
            G = importlib.import_module("apex_tpu.prof.gaps")
            try:
                xp = G._xplane_pb2()
            except ImportError:
                pytest.skip("no xplane_pb2 in this environment")
        finally:
            sys.path.remove(REPO)
        space = xp.XSpace()
        plane = space.planes.add()
        plane.name = "/device:TPU:0"
        for i, nm in enumerate(names, start=1):
            md = plane.event_metadata[i]
            md.id, md.name = i, nm
        line = plane.lines.add()
        line.name = "XLA Ops"
        line.timestamp_ns = 0
        for i in range(len(names)):   # 100us ops with 100us gaps
            ev = line.events.add()
            ev.metadata_id = i + 1
            ev.offset_ps = int(i * 200.0 * 1e6)
            ev.duration_ps = int(100.0 * 1e6)
        d = tmp_path / "plugins" / "profile" / "run1"
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(space.SerializeToString())
        return str(tmp_path)

    def _run(self, logdir, *flags):
        env = dict(BARE_ENV)
        env["PYTHONPATH"] = REPO
        return subprocess.run(
            [sys.executable, os.path.join(TOOLS, "trace_top_ops.py"),
             logdir, *flags],
            capture_output=True, text=True, timeout=120, env=env)

    def test_strict_fails_on_unattributed_capture(self, tmp_path):
        # an empty-name neighbor makes every gap unattributed (100%)
        logdir = self._capture(tmp_path, ["mystery.1", "", "mystery.2"])
        r = self._run(logdir, "--strict")
        assert r.returncode == 1, (r.returncode, r.stderr)
        assert "unattributed" in r.stderr
        # footer made it into the table with the seam names
        assert "unattributed:" in r.stdout and "_RULES" in r.stdout

    @pytest.mark.slow
    def test_strict_passes_on_attributed_capture(self, tmp_path):
        # slow marker: a second full-jax-import subprocess; the pass
        # path (threshold arithmetic, non-strict no-gate default) is
        # unit-covered via GapReport.unattributed_pct in test_prof.py
        logdir = self._capture(tmp_path,
                               ["fusion.1", "convert.2", "infeed.3"])
        r = self._run(logdir, "--strict")
        assert r.returncode == 0, (r.returncode, r.stderr)
