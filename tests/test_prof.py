"""Profiling facade tests (reference analog: apex/pyprof — here annotation
is named scopes, analysis is XLA cost analysis)."""

import jax
import jax.numpy as jnp
import pytest
import numpy as np

from apex_tpu import prof


def _scoped_hlo_text(fn, *args):
    """HLO text that carries named-scope metadata: newer jax exposes it
    in the lowered StableHLO under debug_info=True; older jax only in
    the compiled module's op_name metadata."""
    lowered = jax.jit(fn).lower(*args)
    try:
        return lowered.as_text(debug_info=True)
    except TypeError:
        return lowered.compile().as_text()


def test_annotate_preserves_semantics_and_names_hlo():
    @prof.annotate("my_marked_block")
    def f(x):
        return jnp.sin(x) * 2.0

    x = jnp.arange(8.0)
    np.testing.assert_allclose(np.asarray(f(x)),
                               np.sin(np.arange(8.0)) * 2.0, rtol=1e-6)
    assert "my_marked_block" in _scoped_hlo_text(f, x)


def test_annotate_bare_decorator():
    @prof.annotate
    def block(x):
        return x + 1

    assert float(block(jnp.asarray(1.0))) == 2.0
    assert "block" in _scoped_hlo_text(block, jnp.asarray(1.0))


def test_mark_context():
    def f(x):
        with prof.mark("inner_region"):
            return x * x
    assert "inner_region" in _scoped_hlo_text(f, jnp.ones((4,)))


def test_analyze_matmul_flops():
    def f(a, b):
        return a @ b

    a = jnp.ones((128, 256), jnp.float32)
    b = jnp.ones((256, 64), jnp.float32)
    rep = prof.analyze(f, a, b)
    # 2*M*N*K FLOPs
    assert rep.flops == 2 * 128 * 256 * 64
    assert rep.bytes_accessed > 0
    assert rep.arithmetic_intensity > 0
    assert "flops" in rep.summary()


def test_init_is_noop():
    assert prof.init() is None


def test_top_ops_table_on_jitted_matmul(tmp_path):
    """The pyprof/prof capability as a library API (VERDICT r3 missing
    #3): capture a trace of a jitted matmul, get per-op rows back."""
    @jax.jit
    def f(a, b):
        return (a @ b).sum()

    a = jnp.ones((256, 256), jnp.float32)
    b = jnp.ones((256, 256), jnp.float32)
    f(a, b).block_until_ready()  # compile outside the capture
    logdir = str(tmp_path / "trace")
    with prof.trace(logdir):
        for _ in range(3):
            f(a, b).block_until_ready()

    stats = prof.top_ops(logdir)
    assert stats, "no op rows parsed from the capture"
    # sorted by descending self time
    times = [s.self_time_us for s in stats]
    assert times == sorted(times, reverse=True)
    assert all(s.occurrences >= 1 for s in stats)
    # the dot shows up under some op name containing dot/matmul/fusion
    names = " ".join((s.op + " " + s.op_type).lower() for s in stats)
    assert any(k in names for k in ("dot", "matmul", "fusion", "jit"))
    # top=N truncates
    assert len(prof.top_ops(logdir, top=1)) == 1
    # derived metrics are consistent
    s0 = stats[0]
    assert s0.flops == s0.flops_per_s * s0.self_time_us * 1e-6
    assert s0.efficiency(peak_flops_per_s=1e12) == s0.flops_per_s / 1e12

    table = prof.format_top_ops(stats[:5])
    assert table.splitlines()[0].startswith("| op | type |")
    assert len(table.splitlines()) == 2 + min(5, len(stats))


class TestGaps:
    """prof.gaps — trace-gap attribution (the r05b 66 ms IDLE slice made
    attributable). Offline: synthetic timelines and a synthetic xplane
    protobuf fixture, no chip or xprof tool-data conversion needed."""

    def _ev(self, name, start, dur):
        return prof.TimelineEvent(name=name, start_us=start, dur_us=dur)

    def test_classify_pair_rule_priority(self):
        from apex_tpu.prof import gaps as G
        # infeed outranks convert: a gap bounded by both is an infeed gap
        assert G.classify_pair("infeed.3", "convert.9")[0] == "infeed"
        assert G.classify_pair("fusion.1", "outfeed.2")[0] == "outfeed"
        assert G.classify_pair("copy-start.1", "fusion.2")[0] == \
            "host-sync"
        assert G.classify_pair("all-reduce.7", "fusion.2")[0] == \
            "collective-boundary"
        assert G.classify_pair("fusion.1", "convert.4")[0] == \
            "convert-seam"
        # r09 numerics seams outrank convert (the overflow check reads
        # half grads next to fp32 scaler state), lose to infeed
        assert G.classify_pair("convert.1",
                               "apex_numerics_census/reduce.2")[0] == \
            "overflow-check"
        assert G.classify_pair("infeed.1",
                               "apex_overflow_check/and.2")[0] == "infeed"
        assert G.classify_pair("while.1", "fusion.2")[0] == \
            "loop-boundary"
        assert G.classify_pair("fusion.1", "fusion.2")[0] == \
            "fusion-break"
        assert G.classify_pair("", "fusion.2")[0] == "unattributed"

    def test_collective_bound_rule(self):
        """r10 satellite: framework-collective named scopes
        (parallel/collectives.py `apex_collective_*`, the fleet probe's
        `apex_fleet_probe`/`apex_desync` gathers) classify as
        `collective-bound` — ranked below infeed, above overflow-check,
        and ABOVE the generic collective-boundary rule (the scope names
        contain "psum"/"collective" and would otherwise bin there)."""
        from apex_tpu.prof import gaps as G
        assert G.classify_pair("apex_collective_psum/all-reduce.3",
                               "fusion.1")[0] == "collective-bound"
        assert G.classify_pair("fusion.9",
                               "apex_collective_all_gather/g.2")[0] == \
            "collective-bound"
        assert G.classify_pair("apex_fleet_probe/psum.2",
                               "fusion.1")[0] == "collective-bound"
        assert G.classify_pair("apex_desync_fingerprint/abs.1",
                               "fusion.2")[0] == "collective-bound"
        # infeed outranks it; it outranks the overflow-check seam
        assert G.classify_pair("infeed.1",
                               "apex_collective_psum/a.2")[0] == "infeed"
        assert G.classify_pair("apex_numerics_census/reduce.1",
                               "apex_collective_psum/a.2")[0] == \
            "collective-bound"
        # raw HLO collective names (no framework scope) keep binning as
        # collective-boundary — the r07 behavior is unchanged
        assert G.classify_pair("all-reduce.7", "fusion.2")[0] == \
            "collective-boundary"

    def test_find_gaps_threshold_and_overlap_merge(self):
        from apex_tpu.prof import gaps as G
        evs = [
            self._ev("fusion.1", 0.0, 100.0),
            # nested/overlapping slice must not fabricate a gap at 100
            self._ev("fusion.1.inner", 10.0, 150.0),
            self._ev("fusion.2", 200.0, 50.0),      # 40us gap at 160
            self._ev("convert.3", 250.5, 10.0),     # 0.5us: sub-threshold
        ]
        gaps = G.find_gaps(evs, min_gap_us=1.0)
        assert len(gaps) == 1
        g = gaps[0]
        assert g.start_us == 160.0 and g.dur_us == 40.0
        # the bounding op is the one whose END bordered the gap (the
        # overlapping inner slice, not the first-started fusion.1)
        assert g.before == "fusion.1.inner" and g.after == "fusion.2"
        assert g.category == "fusion-break"

    def test_attribute_bins_and_report(self):
        from apex_tpu.prof import gaps as G
        evs = [
            self._ev("fusion.1", 0.0, 1000.0),
            self._ev("infeed.1", 1500.0, 10.0),       # 500us infeed gap
            self._ev("fusion.2", 1515.0, 100.0),      # 5us infeed gap
            self._ev("convert.9", 1655.0, 50.0),      # 40us convert seam
            self._ev("fusion.10", 1705.0, 100.0),     # adjacent: no gap
            self._ev("fusion.3", 3805.0, 100.0),      # 2ms fusion break
        ]
        rep = G.attribute(events=evs)
        assert rep.total_gap_us == 500.0 + 5.0 + 40.0 + 2000.0
        assert rep.busy_us == 1360.0
        assert rep.span_us == 3905.0
        assert rep.by_category["infeed"]["count"] == 2
        assert rep.by_category["infeed"]["total_us"] == 505.0
        assert rep.by_category["convert-seam"]["total_us"] == 40.0
        assert rep.by_category["fusion-break"]["total_us"] == 2000.0
        # duration bins: 5us -> <10us, 40us -> 10-100, 500us -> 100-1000,
        # 2000us -> >=1000
        assert rep.by_duration_bin["<10us"]["count"] == 1
        assert rep.by_duration_bin["10us-100us"]["count"] == 1
        assert rep.by_duration_bin["100us-1000us"]["count"] == 1
        assert rep.by_duration_bin[">=1000us"]["count"] == 1
        # gaps sorted by descending duration; json round-trips
        assert [g.dur_us for g in rep.gaps] == [2000.0, 500.0, 40.0, 5.0]
        import json
        decoded = json.loads(rep.to_json())
        assert decoded["gaps"][0]["category"] == "fusion-break"
        table = prof.format_gaps(rep)
        assert "| category | count |" in table
        assert "infeed" in table and "convert-seam" in table

    def _fixture_xplane(self, tmp_path, plane_name="/device:TPU:0",
                        line_name="XLA Ops"):
        """Serialize a synthetic XSpace capture: op, 60us gap, convert,
        op — the r05b convert-seam pattern in miniature."""
        from apex_tpu.prof import gaps as G
        try:
            xp = G._xplane_pb2()
        except ImportError:
            pytest.skip("no xplane_pb2 module in this environment")
        space = xp.XSpace()
        plane = space.planes.add()
        plane.name = plane_name
        names = ["fusion.100", "convert.200", "fusion.300", "infeed.400"]
        for i, nm in enumerate(names, start=1):
            md = plane.event_metadata[i]
            md.id, md.name = i, nm
        line = plane.lines.add()
        line.name = line_name
        line.timestamp_ns = 5_000_000
        spec = [(1, 0.0, 100.0),     # fusion.100
                (2, 160.0, 20.0),    # convert.200 after a 60us gap
                (3, 181.0, 300.0),   # fusion.300 after 1us (sub-thresh)
                (4, 981.0, 5.0)]     # infeed.400 after a 500us gap
        for mid, off_us, dur_us in spec:
            ev = line.events.add()
            ev.metadata_id = mid
            ev.offset_ps = int(off_us * 1e6)
            ev.duration_ps = int(dur_us * 1e6)
        d = tmp_path / "plugins" / "profile" / "run1"
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(space.SerializeToString())
        return str(tmp_path)

    def test_attribute_on_xplane_fixture(self, tmp_path):
        """The acceptance-criteria path: gaps from a recorded/synthetic
        xplane capture are binned AND classified."""
        from apex_tpu.prof import gaps as G
        logdir = self._fixture_xplane(tmp_path)
        events = G.load_timeline(logdir)
        assert [e.name for e in events] == \
            ["fusion.100", "convert.200", "fusion.300", "infeed.400"]
        rep = G.attribute(logdir, min_gap_us=2.0)
        cats = {(g.before, g.after): g.category for g in rep.gaps}
        assert cats[("fusion.100", "convert.200")] == "convert-seam"
        assert cats[("fusion.300", "infeed.400")] == "infeed"
        assert rep.by_category["convert-seam"]["total_us"] == 60.0
        assert rep.by_category["infeed"]["total_us"] == 500.0
        assert len(rep.gaps) == 2  # the 1us seam stays sub-threshold

    def test_load_timeline_host_fallback(self, tmp_path):
        """CPU smoke captures (no device plane) fall back to the host
        plane's XLA client lane — and 'python' interpreter lanes are
        never picked."""
        from apex_tpu.prof import gaps as G
        logdir = self._fixture_xplane(tmp_path, plane_name="/host:CPU",
                                      line_name="tf_client/123")
        events = G.load_timeline(logdir)
        assert len(events) == 4

    def test_attribute_real_cpu_capture(self, tmp_path):
        """End-to-end on a genuine jax.profiler capture: parse must not
        depend on xprof tool-data conversion being importable."""
        from apex_tpu.prof import gaps as G
        try:
            G._xplane_pb2()
        except ImportError:
            pytest.skip("no xplane_pb2 module in this environment")

        @jax.jit
        def f(a, b):
            return (a @ b).sum()

        a = jnp.ones((128, 128), jnp.float32)
        f(a, a).block_until_ready()
        logdir = str(tmp_path / "trace")
        with prof.trace(logdir):
            for _ in range(3):
                f(a, a).block_until_ready()
        rep = G.attribute(logdir)
        assert rep.span_us > 0 and rep.busy_us > 0
        assert prof.format_gaps(rep).startswith("gap attribution:")


def test_roofline_summary(tmp_path):
    """prof.roofline: synthetic device rows aggregate to a consistent
    verdict; counter-less (CPU) captures raise instead of reporting a
    0 TF/s 'HBM-bound' non-result."""
    mk = lambda **kw: prof.OpStats(**{**dict(
        op="op", op_type="fusion", self_time_us=0.0, time_pct=0.0,
        occurrences=1, flops_per_s=0.0, bytes_per_s=0.0, bound_by="",
        on_device=True), **kw})
    stats = [
        mk(op="conv", self_time_us=60_000.0, flops_per_s=60e12,
           bytes_per_s=680e9, bound_by="HBM"),
        mk(op="elem", self_time_us=40_000.0, flops_per_s=1e12,
           bytes_per_s=700e9, bound_by="HBM"),
        mk(op="IDLE", op_type="IDLE", self_time_us=20_000.0),
    ]
    with pytest.raises(ValueError, match="no published peak"):
        prof.roofline(stats=stats)   # cpu: no peak is assumed
    r = prof.roofline(stats=stats, device_kind="TPU v5 lite")
    assert r.peak_flops_per_s == 197e12 and r.peak_bytes_per_s == 819e9
    assert r.busy_us == 100_000.0 and r.idle_us == 20_000.0
    # time-weighted rates over busy time
    exp_f = (60e12 * 0.06 + 1e12 * 0.04) / 0.1
    assert abs(r.achieved_flops_per_s - exp_f) / exp_f < 1e-9
    assert r.hbm_bound_pct == 100.0
    assert r.bound_by == "HBM"
    assert r.mfu == r.achieved_flops_per_s / r.peak_flops_per_s
    assert r.bandwidth_util == r.achieved_bytes_per_s / r.peak_bytes_per_s
    # explicit peak override honored (and 0.0 is not treated as unset)
    assert prof.roofline(stats=stats, peak_flops_per_s=1e12,
                         peak_bytes_per_s=1e9).peak_flops_per_s == 1e12

    # a real CPU capture carries no device counters -> ValueError
    @jax.jit
    def f(a, b):
        return (a @ b).sum()

    a = jnp.ones((256, 256), jnp.float32)
    f(a, a).block_until_ready()
    logdir = str(tmp_path / "trace")
    with prof.trace(logdir):
        f(a, a).block_until_ready()
    with pytest.raises(ValueError, match="counters"):
        prof.roofline(logdir, device_kind="TPU v5 lite")


class TestScopesUnderJit:
    """prof.annotate / prof.mark INSIDE jax.jit (r07 satellite): named
    scopes must be transparent to tracing — jit, grad-of-jit, and scan
    bodies all trace and execute through them unchanged."""

    def test_annotate_executes_under_jit(self):
        @jax.jit
        @prof.annotate("jitted_block")
        def f(x):
            return jnp.sin(x) * 2.0

        x = jnp.arange(8.0)
        np.testing.assert_allclose(np.asarray(f(x)),
                                   np.sin(np.arange(8.0)) * 2.0,
                                   rtol=1e-6)
        # scope name survives into the jitted HLO
        assert "jitted_block" in _scoped_hlo_text(f, x)

    def test_mark_inside_jit_and_grad(self):
        def f(x):
            with prof.mark("grad_region"):
                return jnp.sum(x ** 2)

        g = jax.jit(jax.grad(f))
        np.testing.assert_allclose(np.asarray(g(jnp.arange(4.0))),
                                   2.0 * np.arange(4.0), rtol=1e-6)

    def test_annotate_inside_scan_body(self):
        @prof.annotate
        def body(carry, x):
            return carry + x, carry

        @jax.jit
        def f(xs):
            tot, ys = jax.lax.scan(body, jnp.float32(0.0), xs)
            return tot, ys

        tot, ys = f(jnp.arange(5.0))
        assert float(tot) == 10.0
        np.testing.assert_allclose(np.asarray(ys),
                                   [0.0, 0.0, 1.0, 3.0, 6.0])

    def test_nested_scopes_under_jit(self):
        @jax.jit
        def f(x):
            with prof.mark("outer"):
                with prof.mark("inner"):
                    y = x * 3.0
                return y + 1.0

        assert float(f(jnp.float32(2.0))) == 7.0
        txt = _scoped_hlo_text(f, jnp.float32(2.0))
        assert "outer" in txt and "inner" in txt


class TestUnattributedFooter:
    """GAPS footer (r07 satellite): the unattributed fraction is stated
    explicitly, with the seam names to extend _RULES from."""

    def _ev(self, name, start, dur):
        return prof.TimelineEvent(name=name, start_us=start, dur_us=dur)

    def test_footer_reports_unattributed_share_and_names(self):
        from apex_tpu.prof import gaps as G
        evs = [
            self._ev("mystery.opaque.1", 0.0, 100.0),
            self._ev("", 400.0, 50.0),           # 300us unattributed gap
            self._ev("convert.2", 550.0, 50.0),  # 100us convert-seam
        ]
        rep = G.attribute(events=evs)
        assert rep.by_category["unattributed"]["total_us"] == 300.0
        assert abs(rep.unattributed_us - 300.0) < 1e-9
        assert abs(rep.unattributed_pct - 100.0 * 300.0 / 400.0) < 1e-6
        names = rep.unattributed_names()
        assert names and "mystery.opaque.1" in names[0]
        table = prof.format_gaps(rep)
        assert "unattributed: 0.30 ms (75.0% of dead time)" in table
        assert "_RULES" in table   # the extend-the-table pointer

    def test_footer_present_even_when_fully_attributed(self):
        from apex_tpu.prof import gaps as G
        evs = [self._ev("fusion.1", 0.0, 10.0),
               self._ev("fusion.2", 30.0, 10.0)]
        rep = G.attribute(events=evs)
        assert rep.unattributed_us == 0.0
        assert "unattributed: 0.00 ms (0.0% of dead time)" in \
            prof.format_gaps(rep)


# -- the scope vocabulary on both training steps (PR 24) --------------------
# A device trace names every instruction by its op_name path; the
# benchmark's trace_scope reader buckets device time by the first component
# of that path that is in prof.SCOPES, and by direction. So every op that
# does real work has to sit under a scope, forward and backward.

def _tool(directory: str, module: str):
    """A module of the repo's root or of ``tools/`` (no packages)."""
    import importlib
    import os
    import sys
    path = os.path.join(os.path.dirname(__file__), "..", directory)
    if path not in sys.path:
        sys.path.insert(0, path)
    return importlib.import_module(module)


def _scope_of(op_name: str):
    """The scope the benchmark's reader puts a path to: what the program
    promises is what ``trace_scope`` finds."""
    from benchmarks.readers import trace_scope
    return trace_scope.scope_of(op_name)


def _op_names(step, *args) -> list:
    import re
    text = jax.jit(step).lower(*args).compile().as_text()
    return re.findall(r'op_name="([^"]+)"', text)


@pytest.fixture(scope="module")
def lm_op_names():
    """The tiny twin of the benchmark's LM step: ``tools/lm_bench.
    build_train_step`` on one device, flash attention, chunked head."""
    lm_bench = _tool("tools", "lm_bench")
    from apex_tpu.models import TransformerLM
    from apex_tpu.parallel import make_mesh
    lm = TransformerLM(vocab_size=256, max_seq_len=64, embed_dim=128,
                       num_heads=1, num_layers=2, attn_impl="fast",
                       head_chunk=128)
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    _, state, step, _ = lm_bench.build_train_step(
        lm, lm.init(jax.random.key(0)), mesh, half=jnp.bfloat16)
    return _op_names(step, state, jnp.zeros((2, 65), jnp.int32))


@pytest.fixture(scope="module")
def rn_op_names():
    """The tiny twin of the benchmark's ResNet step: ``bench.
    build_train_step``, O2 with dynamic loss scaling, FusedLAMB."""
    from apex_tpu import amp
    from apex_tpu.models import ResNet
    build_train_step = _tool("", "bench").build_train_step
    model = ResNet(block_sizes=(1, 1), bottleneck=True, num_classes=10,
                   width=8, stem="space_to_depth")
    params, bn = model.init(jax.random.key(0))
    _, handle = amp.initialize(opt_level="O2", loss_scale="dynamic",
                               verbosity=0)
    opt, _, step = build_train_step(model, params, handle)
    return _op_names(step, opt.init_state(), bn, handle.init_state(),
                     jnp.zeros((2, 32, 32, 3), jnp.bfloat16),
                     jnp.zeros((2,), jnp.int32))


@pytest.fixture
def op_names(request, which):
    return request.getfixturevalue(
        {"lm": "lm_op_names", "rn50": "rn_op_names"}[which])


def _heavy(op_name: str) -> bool:
    """A matmul, a convolution, or anything inside a named Pallas kernel
    (interpreted on the CPU, so the kernel is the ops under its name)."""
    parts = op_name.split("/")
    return parts[-1] in ("dot_general", "conv_general_dilated") \
        or any(p.startswith("apex_") for p in parts)


@pytest.mark.parametrize("which", ["lm", "rn50"])
def test_every_matmul_conv_and_kernel_sits_under_a_vocabulary_scope(
        which, op_names):
    heavy = [n for n in op_names if _heavy(n)]
    assert len(heavy) > 20
    assert [n for n in heavy if _scope_of(n) is None] == []


@pytest.mark.parametrize("which, scope", [
    ("lm", "embed"), ("lm", "attention"), ("lm", "mlp"), ("lm", "head_loss"),
    ("lm", "amp_cast"),
    ("rn50", "stem"), ("rn50", "stage0_block0"), ("rn50", "stage1_block0"),
    ("rn50", "head"), ("rn50", "amp_cast")])
def test_scope_names_both_directions(which, scope, op_names):
    mine = [n for n in op_names if _scope_of(n) == scope]
    assert any(f"/jvp({scope})/" in n for n in mine)             # forward
    assert any(f"/transpose(jvp({scope}))/" in n for n in mine)  # backward


@pytest.mark.parametrize("which, scope", [
    ("lm", "optimizer"), ("rn50", "optimizer"), ("rn50", "amp_scale")])
def test_step_phases_outside_autodiff_are_scoped(which, scope, op_names):
    mine = [n for n in op_names if _scope_of(n) == scope]
    assert mine and not any("transpose(" in n for n in mine)


def test_collective_scope_on_ddp_and_zero_steps():
    """DDP's gradient average and ZeRO's gather / scatter land in
    ``collective`` (read by no cell yet: ``cgpt_train_ddp4`` will)."""
    import re
    lm_bench = _tool("tools", "lm_bench")
    from apex_tpu.models import TransformerLM
    from apex_tpu.parallel import compile_step_with_plan, make_mesh
    lm = TransformerLM(vocab_size=256, max_seq_len=32, embed_dim=128,
                       num_heads=1, num_layers=1)
    mesh = make_mesh({"data": 2}, devices=jax.devices()[:2])
    toks = jnp.zeros((4, 33), jnp.int32)
    for zero, prims in ((False, {"psum"}),
                        (True, {"all_gather", "reduce_scatter"})):
        _, state, step, plan = lm_bench.build_train_step(
            lm, lm.init(jax.random.key(0)), mesh, half=jnp.bfloat16,
            zero=zero)
        state, toks_p = lm_bench.place_for_plan(state, toks, plan)
        text = compile_step_with_plan(step, plan).lower(
            state, toks_p).as_text(debug_info=True)
        paths = re.findall(r'loc\("([^"]+)"', text)
        got = {p.rsplit("/", 1)[-1] for p in paths
               if _scope_of(p) == "collective"}
        assert prims <= got, (zero, got)
