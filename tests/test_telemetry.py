"""Runtime-telemetry smoke (r07 tentpole acceptance): a 3-step toy train
loop on CPU must leave a schema-valid TELEM_*.jsonl sidecar whose records
carry step timings, loss-scale events, and compile counts — and
``tools/telemetry_report.py`` must render it. Plus unit coverage for the
watchdog's stall path, recompile flagging, and the collective-bytes
tally; r10 adds the fleet layer — per-process sidecar paths, fleet
aggregation/straggler ranking, desync record shape, and a real
forced-host-device-count multiproc run. All tier-1 (no chip, seconds
not minutes).
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import amp, prof
from apex_tpu.prof import metrics as M

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


def _toy_train_sidecar(path: str) -> list[dict]:
    """The acceptance loop: 3 jitted steps of a toy model under a
    dynamic fp16 scaler, fully telemetered."""
    logger = prof.MetricsLogger(path, run="toy", meta={"batch": 4},
                                flush_every=2)
    wd = prof.Watchdog(logger, min_interval_s=60.0, label="toy").start()

    _, handle = amp.initialize(opt_level="O2", half_dtype=jnp.float16,
                               verbosity=0)
    amp_state = handle.init_state()
    w = jnp.ones((8, 8), jnp.float32)

    def step(w, amp_state, x, inject_inf):
        def loss_fn(w):
            loss = jnp.mean((x @ w) ** 2) * jnp.where(
                inject_inf, jnp.inf, 1.0)
            return handle.scale_loss(loss, amp_state), loss

        g, loss = jax.grad(loss_fn, has_aux=True)(w)
        g, found_inf = handle.unscale(g.reshape(-1), amp_state)
        w = jnp.where(found_inf, w, w - 0.01 * g.reshape(w.shape))
        return w, handle.update(amp_state, found_inf), loss

    jstep = logger.track_recompiles(jax.jit(step), "toy_step")
    x = jnp.ones((4, 8), jnp.float32)
    for i in range(3):
        t0 = time.perf_counter()
        w, amp_state, loss = jstep(w, amp_state,
                                   x, jnp.bool_(i == 1))  # step 1 skips
        jax.block_until_ready(loss)
        logger.log_step(i, step_ms=(time.perf_counter() - t0) * 1e3,
                        throughput=4.0 / max(time.perf_counter() - t0,
                                             1e-9),
                        unit="img/s", loss=loss,
                        loss_scale=amp_state[0].scale)
        wd.heartbeat()
    logger.log_amp(handle.scalers[0], amp_state[0])
    wd.stop()
    logger.close()
    return M.read_sidecar(path)


class TestToyLoopSidecar:
    @pytest.fixture(scope="class")
    def records(self, tmp_path_factory):
        path = str(tmp_path_factory.mktemp("telem") / "TELEM_toy.jsonl")
        return _toy_train_sidecar(path)

    def test_schema_valid_and_header_first(self, records):
        for r in records:
            M.validate_record(r)   # raises on violation
        assert records[0]["kind"] == "header"
        assert records[0]["schema"] == f"{M.SCHEMA_NAME}/{M.SCHEMA_VERSION}"
        assert records[-1]["kind"] == "close"

    def test_step_records_carry_timings(self, records):
        steps = [r for r in records if r["kind"] == "step"]
        assert len(steps) == 3
        assert all(isinstance(r["step_ms"], float) and r["step_ms"] > 0
                   for r in steps)
        assert all(isinstance(r["loss"], float) for r in steps)
        # the injected overflow halved the scale on step 1
        scales = [r["loss_scale"] for r in steps]
        assert scales[0] == 2.0 ** 16 and scales[2] == 2.0 ** 15

    def test_amp_record_counts_the_skip(self, records):
        amps = [r for r in records if r["kind"] == "amp"]
        assert amps, "no amp record in sidecar"
        a = amps[-1]
        assert a["step_count"] == 3
        assert a["overflow_count"] == 1   # the injected inf
        assert a["growth_count"] == 0

    def test_compile_counts_present(self, records):
        comps = [r for r in records if r["kind"] == "compile"]
        if not comps:
            pytest.skip("no jax.monitoring listener API in this env")
        assert comps[-1]["backend_compiles"] >= 1
        assert comps[-1]["jaxpr_traces"] >= 1

    def test_memory_records_present(self, records):
        mems = [r for r in records if r["kind"] == "memory"]
        assert mems, "memory watermarks not sampled at close"
        # CPU devices report no stats; the record says so explicitly
        assert all("available" in r for r in mems)

    def test_report_tool_renders(self, records, tmp_path):
        sys.path.insert(0, TOOLS)
        try:
            import telemetry_report as tr
        finally:
            sys.path.remove(TOOLS)
        summary = tr.summarize(records)
        assert summary["steps"] == 3
        assert summary["amp"]["skip_rate"] == pytest.approx(1.0 / 3.0,
                                                            abs=1e-4)
        table = tr.render(summary)
        assert table.startswith("| metric | value |")
        assert "skip rate" in table and "recompiles" in table

    @pytest.mark.slow   # a full jax-import subprocess; tier-1 keeps the
    # in-process summarize/render coverage above
    def test_report_cli_end_to_end(self, tmp_path):
        import subprocess
        path = str(tmp_path / "TELEM_cli.jsonl")
        _toy_train_sidecar(path)
        r = subprocess.run(
            [sys.executable,
             os.path.join(TOOLS, "telemetry_report.py"), path, "--json"],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert r.returncode == 0, r.stderr
        summary = json.loads(r.stdout)
        assert summary["steps"] == 3 and "step_ms" in summary


class TestRecompileFlagging:
    def test_aval_change_emits_recompile_record(self, tmp_path):
        path = str(tmp_path / "TELEM_rc.jsonl")
        logger = prof.MetricsLogger(path, run="rc")
        f = logger.track_recompiles(jax.jit(lambda x: x * 2), "f")
        f(jnp.ones(4))
        f(jnp.ones(4))          # same avals: no event
        f(jnp.ones((2, 2)))     # new avals: recompile flagged
        logger.close()
        recs = M.read_sidecar(path)
        rcs = [r for r in recs if r["kind"] == "recompile"]
        assert len(rcs) == 1
        assert rcs[0]["fn"] == "f" and rcs[0]["n_signatures"] == 2
        assert [[2, 2], "float32"] in rcs[0]["avals"]


class TestWatchdogStall:
    def test_stall_snapshot_recorded_and_rearms(self, tmp_path):
        path = str(tmp_path / "TELEM_stall.jsonl")
        logger = prof.MetricsLogger(path, run="stall")
        fired = []
        wd = prof.Watchdog(logger, k=2.0, min_interval_s=0.2,
                           poll_s=0.05, label="t",
                           on_stall=fired.append).start()
        for _ in range(5):       # rapid cadence: EMA stays ~0, so the
            wd.heartbeat()       # deadline is the min_interval floor
        time.sleep(1.0)          # > deadline -> stall
        assert wd.stall_count == 1, "watchdog did not fire"
        assert len(fired) == 1   # ONE snapshot per episode, no spam
        for _ in range(5):       # recovery re-arms + re-learns cadence
            wd.heartbeat()
        time.sleep(1.0)
        assert wd.stall_count == 2
        wd.stop()
        logger.close()
        stalls = [r for r in M.read_sidecar(path) if r["kind"] == "stall"]
        assert len(stalls) == 2
        s = stalls[0]
        assert s["silent_s"] >= 0.2 and s["label"] == "t"
        assert "last_records" in s   # the what-was-it-doing context

    def test_k_must_exceed_one(self):
        with pytest.raises(ValueError):
            prof.Watchdog(None, k=0.5)


class TestCollectiveAccounting:
    def test_grouped_psum_tallies_traced_bytes(self):
        from apex_tpu.parallel import collectives as C
        C.reset_collective_bytes()
        devs = jax.devices()
        if len(devs) < 2:
            pytest.skip("needs a multi-device mesh")
        from apex_tpu.parallel import make_mesh
        mesh = make_mesh({"data": len(devs)})
        from jax.sharding import PartitionSpec as P

        def f(x):
            return C.grouped_psum(x, "data", None)

        x = jnp.ones((len(devs), 16), jnp.float32)
        y = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                                  out_specs=P("data")))(x)
        np.testing.assert_allclose(np.asarray(y), len(devs))
        snap = C.collective_bytes()
        assert snap["total_calls"] >= 1
        # per-device payload of the traced psum: (1, 16) f32 = 64 B
        assert snap["ops"]["psum[data]"]["bytes"] >= 64

    def test_mesh_note_reaches_next_logger_flush(self, tmp_path):
        from apex_tpu.parallel import make_mesh
        make_mesh()   # notes into the pending queue (no logger yet)
        path = str(tmp_path / "TELEM_mesh.jsonl")
        logger = prof.MetricsLogger(path, run="mesh")
        logger.flush()
        logger.close()
        recs = M.read_sidecar(path)
        meshes = [r for r in recs if r["kind"] == "event"
                  and r.get("name") == "mesh_created"]
        assert meshes and meshes[-1]["devices"] == len(jax.devices())


class TestSchemaGuards:
    def test_validate_rejects_bad_records(self):
        M.validate_record({"v": 1, "kind": "step", "t": 1.0})
        with pytest.raises(ValueError, match="version"):
            M.validate_record({"v": 99, "kind": "step", "t": 1.0})
        with pytest.raises(ValueError, match="kind"):
            M.validate_record({"v": 1, "kind": "nope", "t": 1.0})
        with pytest.raises(ValueError, match="'t'"):
            M.validate_record({"v": 1, "kind": "step"})

    def test_v3_fleet_kinds_validate(self):
        M.validate_record({"v": 3, "kind": "fleet_skew", "t": 1.0,
                           "slowest": 1, "lag_ms": 2.5})
        M.validate_record({"v": 3, "kind": "desync", "t": 1.0,
                           "path": "layers/w", "processes": [2]})
        # old sidecars stay readable (the r07-r09 artifacts)
        for v in M.SUPPORTED_VERSIONS:
            M.validate_record({"v": v, "kind": "step", "t": 1.0})

    def test_read_sidecar_rejects_garbage(self, tmp_path):
        p = tmp_path / "bad.jsonl"
        p.write_text('{"v": 1, "kind": "header", "t": 1.0}\nnot json\n')
        with pytest.raises(ValueError, match="not JSON"):
            M.read_sidecar(str(p))
        p2 = tmp_path / "headless.jsonl"
        p2.write_text('{"v": 1, "kind": "step", "t": 1.0}\n')
        with pytest.raises(ValueError, match="header"):
            M.read_sidecar(str(p2))


# ---------------------------------------------------------------------------
# r10 fleet observability
# ---------------------------------------------------------------------------

from apex_tpu.prof import fleet as FL  # noqa: E402


class TestPerProcessSidecarPath:
    """r10 satellite: the default (and any explicit) sidecar path is
    collision-prone under multiproc — every process of a fleet must get
    its own ``.p{process_index}`` file."""

    def test_suffix_applied_under_multiproc(self, tmp_path):
        lg = M.MetricsLogger(str(tmp_path / "TELEM_x.jsonl"), run="t",
                             process_index=1, process_count=2,
                             track_compiles=False)
        lg.close()
        assert lg.path.endswith("TELEM_x.p1.jsonl")
        hdr = M.read_sidecar(lg.path)[0]
        assert hdr["process_index"] == 1 and hdr["process_count"] == 2
        assert hdr["schema"] == f"{M.SCHEMA_NAME}/{M.SCHEMA_VERSION}"

    def test_single_process_path_unchanged(self, tmp_path):
        p = str(tmp_path / "TELEM_y.jsonl")
        lg = M.MetricsLogger(p, run="t", track_compiles=False)
        lg.close()
        assert lg.path == p
        hdr = M.read_sidecar(p)[0]
        assert hdr["process_index"] == 0 and hdr["process_count"] == 1

    def test_two_processes_do_not_collide(self, tmp_path):
        p = str(tmp_path / "TELEM_z.jsonl")
        paths = set()
        for pi in range(2):
            lg = M.MetricsLogger(p, run="t", process_index=pi,
                                 process_count=2, track_compiles=False)
            lg.close()
            paths.add(lg.path)
        assert len(paths) == 2   # no clobbering

    def test_suffix_idempotent(self):
        assert M.per_process_path("TELEM_a.p1.jsonl", 1) == \
            "TELEM_a.p1.jsonl"
        assert M.per_process_path("TELEM_a.jsonl", 3) == \
            "TELEM_a.p3.jsonl"

    def test_env_fallback_resolution(self, monkeypatch):
        # jax is initialized single-process here, so the launcher env
        # (parallel.launch.multiproc's exports) decides
        monkeypatch.setenv("RANK", "2")
        monkeypatch.setenv("WORLD_SIZE", "4")
        assert M.process_identity() == (2, 4)
        monkeypatch.setenv("WORLD_SIZE", "1")
        assert M.process_identity() == (0, 1)
        # explicit args always win
        assert M.process_identity(1, 8) == (1, 8)


def _mk_sidecar(pi, pc, step_ms, *, skip=None, waits=None, skews=(),
                desyncs=(), run="fleet"):
    """A synthetic validated per-process record list."""
    recs = [{"v": M.SCHEMA_VERSION, "kind": "header", "t": 0.0,
             "schema": f"{M.SCHEMA_NAME}/{M.SCHEMA_VERSION}",
             "run": run, "process_index": pi, "process_count": pc}]
    for s, ms in enumerate(step_ms):
        r = {"v": M.SCHEMA_VERSION, "kind": "step", "t": float(s),
             "step": s, "step_ms": float(ms)}
        if waits is not None:
            r["input_wait_ms"] = float(waits[s])
        recs.append(r)
    if skip is not None:
        recs.append({"v": M.SCHEMA_VERSION, "kind": "amp", "t": 9.0,
                     "loss_id": 0, "step_count": len(step_ms),
                     "overflow_count": skip})
    for r in skews:
        recs.append({"v": M.SCHEMA_VERSION, "kind": "fleet_skew",
                     "t": 9.0, **r})
    for r in desyncs:
        recs.append({"v": M.SCHEMA_VERSION, "kind": "desync", "t": 9.0,
                     **r})
    recs.append({"v": M.SCHEMA_VERSION, "kind": "close", "t": 10.0,
                 "run": run})
    for r in recs:
        M.validate_record(r)
    return recs


class TestFleetAggregation:
    """Pure-function coverage of prof.fleet.aggregate_fleet: skew,
    straggler ranking by cumulative excess, per-process deltas, record
    dedup, and the refusal guards."""

    def _fleet(self):
        base = [10.0, 10.0, 10.0, 10.0]
        skew = {"step": 3, "every": 2, "ema_ms": [10.0, 10.1, 15.2],
                "slowest": 2, "lag_ms": 5.1, "lag_frac": 0.5}
        dsy = {"step": 2, "path": "layers/w", "processes": [1],
               "value": 9.0, "ref": 4.0, "loss_scale_ok": True,
               "step_count_ok": True}
        return [
            _mk_sidecar(0, 3, base, skip=0, waits=[1, 1, 1, 1],
                        skews=[skew]),
            _mk_sidecar(1, 3, [11.0, 10.5, 11.0, 10.5], skip=2,
                        waits=[1, 1, 1, 1], skews=[skew],
                        desyncs=[dsy]),
            _mk_sidecar(2, 3, [15.0, 15.0, 15.0, 15.0], skip=0,
                        waits=[6, 6, 6, 6], desyncs=[dsy]),
        ]

    def test_straggler_ranking_and_skew(self):
        s = FL.aggregate_fleet(self._fleet())
        assert s["process_count"] == 3 and s["aligned_steps"] == 4
        assert s["straggler"]["process"] == 2
        assert s["straggler"]["excess_ms"] == pytest.approx(20.0)
        assert s["straggler"]["excess_pct"] == pytest.approx(50.0)
        assert s["skew"]["spread_ms_p50"] == pytest.approx(5.0)
        assert s["skew"]["spread_ms_max"] == pytest.approx(5.0)
        rows = {r["process"]: r for r in s["per_process"]}
        assert rows[0]["excess_ms"] == pytest.approx(0.0)
        assert rows[1]["excess_ms"] == pytest.approx(3.0)
        # ranking is by CUMULATIVE excess over the per-step fleet min
        assert rows[2]["excess_ms"] > rows[1]["excess_ms"] > \
            rows[0]["excess_ms"]

    def test_per_process_deltas(self):
        s = FL.aggregate_fleet(self._fleet())
        rows = {r["process"]: r for r in s["per_process"]}
        # skip-rate deltas vs the fleet median (0.0)
        assert rows[1]["skip_rate"] == pytest.approx(0.5)
        assert rows[1]["skip_rate_delta"] == pytest.approx(0.5)
        assert rows[0]["skip_rate_delta"] == pytest.approx(0.0)
        # input-wait share deltas: p2 waits 6/15, median is 0.1
        assert rows[2]["input_wait_share"] == pytest.approx(0.4)
        assert rows[2]["input_wait_share_delta"] == pytest.approx(0.3)

    def test_record_dedup_and_votes(self):
        s = FL.aggregate_fleet(self._fleet())
        # the same fleet_skew/desync view logged by several processes
        # collapses to one copy
        assert s["fleet_skew"]["records"] == 1
        assert s["fleet_skew"]["slowest_votes"] == {2: 1}
        assert s["desync"]["count"] == 1
        d = s["desync"]["records"][0]
        assert d["path"] == "layers/w" and d["processes"] == [1]

    def test_render_names_straggler_and_desync(self):
        txt = FL.render_fleet(FL.aggregate_fleet(self._fleet()))
        assert "straggler: process 2" in txt
        assert "DESYNC: 1" in txt and "`layers/w`" in txt
        assert "| p0 |" in txt and "| p2 |" in txt

    def test_missing_process_is_flagged(self):
        s = FL.aggregate_fleet(self._fleet()[:2])
        assert s["missing_processes"] == [2]
        assert "partial fleet" in FL.render_fleet(s)

    def test_refusals(self):
        fleet = self._fleet()
        with pytest.raises(ValueError, match="duplicate"):
            FL.aggregate_fleet([fleet[0], fleet[0]])
        untagged = [dict(r) for r in fleet[0]]
        untagged[0] = {k: v for k, v in untagged[0].items()
                       if k not in ("process_index", "process_count")}
        with pytest.raises(ValueError, match="process_index"):
            FL.aggregate_fleet([untagged])
        other = [dict(r) for r in fleet[1]]
        other[0] = dict(other[0], process_count=2)
        with pytest.raises(ValueError, match="process_count"):
            FL.aggregate_fleet([fleet[0], other])

    def test_probe_vote_fallback_without_aligned_steps(self):
        skew = {"step": 1, "ema_ms": [1.0, 9.0], "slowest": 1,
                "lag_ms": 4.0, "lag_frac": 0.8}
        a = _mk_sidecar(0, 2, [], skews=[skew])
        b = _mk_sidecar(1, 2, [], skews=[skew])
        s = FL.aggregate_fleet([a, b])
        assert s["aligned_steps"] == 0
        assert s["straggler"] == {"process": 1, "excess_ms": None,
                                  "excess_pct": None, "from_probe": True}


class TestCollectiveLatency:
    """r10: host-observed collective latency histogram
    (parallel/collectives.py) and its sidecar record."""

    def test_tally_and_bins(self):
        from apex_tpu.parallel import collectives as C
        C.reset_collective_latency()
        with C.time_collective("psum[test]", 64):
            time.sleep(0.002)
        C.record_collective_latency("psum[test]", 0.05, 8)
        snap = C.collective_latency()
        e = snap["ops"]["psum[test]"]
        assert e["calls"] == 2 and e["bytes"] == 72
        assert e["ms_total"] >= 2.0 and e["ms_max"] >= 2.0
        # 2ms lands in the (1, 10] bin, 0.05ms in the first
        assert e["hist"][0] == 1 and e["hist"][2] == 1
        assert sum(e["hist"]) == 2
        assert snap["bins_ms"] == list(C.LATENCY_BINS_MS)
        C.reset_collective_latency()
        assert C.collective_latency() == {}

    def test_latency_reaches_sidecar(self, tmp_path):
        from apex_tpu.parallel import collectives as C
        C.reset_collective_latency()
        C.record_collective_latency("fleet_probe_psum[fleet]", 1.5, 12)
        lg = M.MetricsLogger(str(tmp_path / "TELEM_lat.jsonl"),
                             run="lat", track_compiles=False)
        lg.log_collectives()
        lg.close()
        C.reset_collective_latency()
        colls = [r for r in M.read_sidecar(lg.path)
                 if r["kind"] == "collectives"]
        assert colls and "latency" in colls[0]
        assert "fleet_probe_psum[fleet]" in colls[0]["latency"]["ops"]


class TestFleetProbeSingleProcess:
    """FleetProbe/DesyncProbe degenerate (process_count == 1) paths —
    the shape every entry point can arm unconditionally."""

    def test_probe_cadence_and_record(self, tmp_path):
        lg = M.MetricsLogger(str(tmp_path / "TELEM_fp.jsonl"),
                             run="fp", track_compiles=False)
        probe = FL.FleetProbe(lg, every=2, process_index=0,
                              process_count=1)
        assert probe.observe(0, 10.0) is None   # cadence: every 2nd
        rec = probe.observe(1, 20.0)
        assert rec is not None and rec["slowest"] == 0
        assert rec["lag_ms"] == pytest.approx(0.0)
        assert len(rec["ema_ms"]) == 1
        lg.close()
        skews = [r for r in M.read_sidecar(lg.path)
                 if r["kind"] == "fleet_skew"]
        assert len(skews) == 1 and skews[0]["step"] == 1

    def test_desync_agreement_is_silent(self, tmp_path):
        import jax.numpy as jnp
        lg = M.MetricsLogger(str(tmp_path / "TELEM_ds.jsonl"),
                             run="ds", track_compiles=False)
        params = {"a": jnp.ones((3,)), "b": {"c": jnp.ones((2, 2))}}
        probe = FL.DesyncProbe(params, lg, process_index=0,
                               process_count=1)
        assert probe.check(params, loss_scale=2.0, step_count=1,
                           step=1) is None
        assert probe.checks == 1
        lg.close()
        assert not [r for r in M.read_sidecar(lg.path)
                    if r["kind"] == "desync"]

    def test_desync_names_flat_master_paths(self):
        # SegmentTable template: the flat-master case names leaves via
        # the table's own treedef (the prof.numerics labeling path)
        import jax.numpy as jnp
        from apex_tpu.ops import flat as F
        params = {"w1": jnp.ones((4,)), "w2": jnp.ones((2, 3))}
        buf, table = F.flatten(params)
        probe = FL.DesyncProbe(table, None, process_index=0,
                               process_count=1)
        assert probe.meta.paths == ("w1", "w2")
        assert probe.check(buf, step=0) is None


class TestFleetMultiproc:
    """The acceptance path: a REAL multi-process run (forced host
    platform devices, jax.distributed over localhost) with an injected
    per-process sleep and an injected parameter perturbation — the
    fleet view must name the straggler and the divergent leaf."""

    WORLD, SLEEP_RANK, DESYNC_RANK = 2, 1, 1

    @pytest.fixture(scope="class")
    def fleet_run(self, tmp_path_factory):
        import subprocess
        tmp = tmp_path_factory.mktemp("fleet")
        out = str(tmp / "TELEM_fleet.jsonl")
        repo = os.path.dirname(TOOLS)
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": "",   # fleet_smoke forces its own count
               "PYTHONPATH": repo}
        env.pop("RANK", None)
        r = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "fleet_smoke.py"),
             "--world", str(self.WORLD), "--steps", "6",
             "--probe-every", "2", "--desync-every", "2",
             "--sleep-rank", str(self.SLEEP_RANK), "--sleep-ms", "30",
             "--desync-rank", str(self.DESYNC_RANK),
             "--desync-step", "2", "--out", out,
             "--log-dir", str(tmp)],
            capture_output=True, text=True, timeout=300, env=env,
            cwd=str(tmp))
        logs = "".join((tmp / f"rank{i}.log").read_text()
                       for i in range(1, self.WORLD)
                       if (tmp / f"rank{i}.log").exists())
        assert r.returncode == 0, (r.stdout, r.stderr[-2000:],
                                   logs[-2000:])
        line = json.loads(r.stdout.strip().splitlines()[-1])
        assert line["rc"] == 0
        return line["sidecars"]

    def test_per_process_sidecars_written(self, fleet_run):
        assert len(fleet_run) == self.WORLD
        for i, p in enumerate(fleet_run):
            assert p.endswith(f".p{i}.jsonl")
            hdr = M.read_sidecar(p)[0]
            assert hdr["process_index"] == i
            assert hdr["process_count"] == self.WORLD

    def test_straggler_named(self, fleet_run):
        s = FL.read_fleet(fleet_run)
        assert s["straggler"]["process"] == self.SLEEP_RANK
        assert s["fleet_skew"]["records"] >= 1
        votes = s["fleet_skew"]["slowest_votes"]
        assert max(votes, key=votes.get) == self.SLEEP_RANK

    def test_desync_record_shape(self, fleet_run):
        s = FL.read_fleet(fleet_run)
        assert s["desync"]["count"] >= 1
        d = s["desync"]["records"][0]
        assert d["path"] == "layers/w_perturb"
        # a 2-process fleet cannot break the median tie: both named
        assert self.DESYNC_RANK in d["processes"]
        assert d["loss_scale_ok"] and d["step_count_ok"]
        assert d["n_divergent_paths"] == 1   # w_stable stayed in sync
        for p in fleet_run:   # every record in every sidecar validates
            for r in M.read_sidecar(p):
                M.validate_record(r)

    def test_report_fleet_renders(self, fleet_run):
        txt = FL.render_fleet(FL.read_fleet(fleet_run))
        assert f"straggler: process {self.SLEEP_RANK}" in txt
        assert "`layers/w_perturb`" in txt
        assert "in-run probe:" in txt
