"""Live telemetry plane tests (r18, ``apex_tpu/prof/live.py``).

The contracts that make the plane trustworthy: emission is NON-BLOCKING
(a full queue or dead collector costs a counted drop, never a stall —
zero drops in steady state, nonzero+counted under a throttled-sender
injection, both pinned here); fleet-scope SLO rules catch degradations
EVERY per-process monitor is silent on (the acceptance scenario: one
replica's occupancy collapse behind healthy per-replica latencies —
both verdicts pinned in one test); the Prometheus /metrics exposition
and the serve_top frame render from the same snapshot; and the
collector's final state flushes as ordinary schema-7 records that
``telemetry_report.py`` renders as the LIVE table. Everything here is
sockets + synthetic samples — no engines, no jit — so the whole module
stays in the tier-1 budget (~seconds)."""

import json
import os
import sys
import time
import urllib.request

import pytest

from apex_tpu.prof import metrics as M
from apex_tpu.prof.live import (LiveCollector, LiveEmitter,
                                parse_endpoint, prometheus_name)
from apex_tpu.prof.slo import SLOMonitor

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


def wait_for(cond, timeout=5.0, interval=0.02):
    """Poll instead of sleeping a fixed budget — keeps the suite fast
    on a fast box and honest on a loaded one."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        v = cond()
        if v:
            return v
        time.sleep(interval)
    raise AssertionError("condition not met within timeout")


@pytest.fixture()
def collector():
    col = LiveCollector(http_port=None).start()
    yield col
    col.close()


class TestEndpoints:
    def test_parse_tcp_unix_and_bare(self):
        assert parse_endpoint("tcp:127.0.0.1:9444") == \
            ("tcp", ("127.0.0.1", 9444))
        assert parse_endpoint("127.0.0.1:9444") == \
            ("tcp", ("127.0.0.1", 9444))
        assert parse_endpoint("unix:/tmp/x.sock") == \
            ("unix", "/tmp/x.sock")
        with pytest.raises(ValueError):
            parse_endpoint("nonsense")

    def test_unix_socket_transport(self, tmp_path):
        col = LiveCollector(address=str(tmp_path / "live.sock"),
                            http_port=None).start()
        assert col.endpoint.startswith("unix:")
        em = LiveEmitter(col.endpoint, process_index=3)
        em.observe("step_ms", 1.5)
        wait_for(lambda: col.snapshot()["replicas"])
        assert col.snapshot()["replicas"][0]["process"] == 3
        assert em.close()["drops"] == 0
        col.close()


class TestFleetScopeVerdicts:
    def test_occupancy_collapse_trips_fleet_rule_while_process_monitors_stay_silent(self, tmp_path):
        """THE acceptance scenario, both verdicts in one test: replica
        1's occupancy collapses (a starved replica — its few requests
        are served FAST, so its own latency windows are green) while
        replica 0 is healthy. Per-process monitors with reasonable
        budgets stay SILENT; the fleet-scope ``occupancy_min`` rule —
        computable only where every replica's window is visible —
        trips, carries ``scope: "fleet"``, and names the collapsing
        process."""
        log = M.MetricsLogger(str(tmp_path / "live.jsonl"),
                              run="collector", track_compiles=False,
                              process_index=0, process_count=1)
        col = LiveCollector(rules="occupancy_min>=0.2@4",
                            logger=log, min_samples=4).start()
        # the per-process view: same budgets a per-replica deployment
        # would set — and the degraded replica's latencies are BETTER
        mon0 = SLOMonitor("ttft_p95_ms<=100,token_lat_p95_ms<=50",
                          min_samples=4)
        mon1 = SLOMonitor("ttft_p95_ms<=100,token_lat_p95_ms<=50",
                          min_samples=4)
        e0 = LiveEmitter(col.endpoint, process_index=0,
                         process_count=2)
        e1 = LiveEmitter(col.endpoint, process_index=1,
                         process_count=2)
        for i in range(32):
            for mon, em, occ, ttft in ((mon0, e0, 0.7, 40.0),
                                       (mon1, e1, 0.0, 8.0)):
                mon.observe("ttft_ms", ttft)
                mon.observe("token_lat_ms", ttft / 4)
                em.observe("occupancy", occ)
                em.observe("ttft_ms", ttft)
        alert = wait_for(lambda: col.alerts and col.alerts[0])
        # verdict 1: the fleet saw it — scoped, named, measured
        assert alert["rule"] == "occupancy_min"
        assert alert["scope"] == "fleet"
        assert alert["process"] == 1
        assert alert["measured"] < 0.2
        # verdict 2: every per-process monitor stayed silent
        assert mon0.alerts == [] and mon1.alerts == []
        assert e0.close()["drops"] == 0
        assert e1.close()["drops"] == 0
        col.close()
        log.close()
        # the alert record persisted with its fleet scope
        recs = M.read_sidecar(str(tmp_path / "live.jsonl"))
        # (filter by rule: other tests' loggerless alerts may drain into
        # this logger when a worker ran tests/test_spans.py before it)
        (arec,) = [r for r in recs if r["kind"] == "alert"
                   and r.get("rule") == "occupancy_min"]
        assert arec["scope"] == "fleet" and arec["process"] == 1

    def test_merged_stream_percentile_rule(self):
        """A ttft_p95_ms fleet rule evaluates over the MERGED stream:
        each replica alone is under budget at p95, the merge is not
        (one replica contributes the tail)."""
        col = LiveCollector(rules="ttft_p95_ms<=50@64",
                            min_samples=8).start()
        e0 = LiveEmitter(col.endpoint, process_index=0)
        e1 = LiveEmitter(col.endpoint, process_index=1)
        for _ in range(20):
            e0.observe("ttft_ms", 10.0)
        for _ in range(20):
            e1.observe("ttft_ms", 80.0)   # 50% of merge, 100% of p1
        alert = wait_for(lambda: col.alerts and col.alerts[0])
        assert alert["rule"] == "ttft_p95_ms"
        assert alert["scope"] == "fleet"
        e0.close(), e1.close()
        col.close()

    def test_suffixed_derived_rules_evaluate_on_the_derived_stream(
            self):
        """r19 regression: ``queue_depth_max``/``occupancy_mean``
        rule names parse as strip-the-suffix aggregations over raw
        metrics the collector never forwards, so before the remap
        these fleet rules could NEVER trip — and the router's
        queue-depth admission control keyed on exactly this rule."""
        col = LiveCollector(rules="queue_depth_max<=6@4",
                            min_samples=2, http_port=None).start()
        e0 = LiveEmitter(col.endpoint, process_index=0)
        for _ in range(40):
            e0.observe("queue_depth", 30.0)
        alert = wait_for(lambda: col.alerts and col.alerts[0])
        assert alert["rule"] == "queue_depth_max"
        assert alert["scope"] == "fleet"
        assert alert["measured"] > 6
        e0.close()
        col.close()

    def test_step_skew_derived_metric_names_slow_replica(self):
        col = LiveCollector(rules="step_skew_frac<=0.5@4",
                            min_samples=4, http_port=None).start()
        e0 = LiveEmitter(col.endpoint, process_index=0)
        e1 = LiveEmitter(col.endpoint, process_index=1)
        for _ in range(40):
            e0.observe("step_ms", 1.0)
            e1.observe("step_ms", 10.0)
        alert = wait_for(lambda: col.alerts and col.alerts[0])
        assert alert["rule"] == "step_skew_frac"
        assert alert["process"] == 1 and alert["scope"] == "fleet"
        e0.close(), e1.close()
        col.close()


class TestDropAccounting:
    def test_steady_state_zero_drops_with_record(self, tmp_path,
                                                 collector):
        log = M.MetricsLogger(str(tmp_path / "t.jsonl"), run="x",
                              track_compiles=False, process_index=0,
                              process_count=1)
        em = LiveEmitter(collector.endpoint, run="x").attach(log)
        for i in range(200):
            em.observe("step_ms", 1.0)
        s = em.close()
        assert s["drops"] == 0 and s["sent"] >= 200
        log.close()
        recs = M.read_sidecar(str(tmp_path / "t.jsonl"))
        (ld,) = [r for r in recs if r["kind"] == "live_drop"]
        assert ld["drops"] == 0 and ld["sent"] >= 200

    def test_throttled_sender_drops_counted_everywhere(self, tmp_path,
                                                       collector):
        """The injection arm: a throttled sender + tiny queue MUST
        drop — and the count must agree between the emitter's return,
        its live_drop record, and the collector's view (the bye
        message carries the final number)."""
        log = M.MetricsLogger(str(tmp_path / "t.jsonl"), run="x",
                              track_compiles=False, process_index=0,
                              process_count=1)
        em = LiveEmitter(collector.endpoint, queue_size=8,
                         throttle_ms=20, run="x").attach(log)
        for i in range(300):
            em.observe("step_ms", 1.0)
        s = em.close(timeout=15)
        assert s["drops"] > 0
        log.close()
        recs = M.read_sidecar(str(tmp_path / "t.jsonl"))
        (ld,) = [r for r in recs if r["kind"] == "live_drop"]
        assert ld["drops"] == s["drops"]
        wait_for(lambda: collector.snapshot()["replicas"][0]["closed"])
        assert collector.snapshot()["replicas"][0]["drops"] == \
            s["drops"]

    def test_dead_collector_never_blocks_the_producer(self):
        """No collector listening at all: every observe returns
        immediately (the step path is unaffected) and the samples are
        counted as drops once the sender gives up on them."""
        em = LiveEmitter("tcp:127.0.0.1:1", queue_size=16)
        t0 = time.perf_counter()
        for i in range(1000):
            em.observe("step_ms", 1.0)
        produced_in = time.perf_counter() - t0
        assert produced_in < 0.5        # 1000 enqueues, no socket waits
        s = em.close(timeout=5)
        assert s["drops"] > 0


class TestTee:
    def test_logger_tee_streams_step_records(self, collector, tmp_path):
        log = M.MetricsLogger(str(tmp_path / "t.jsonl"), run="x",
                              track_compiles=False, process_index=0,
                              process_count=1)
        em = LiveEmitter(collector.endpoint).attach(log)

        class FakeDeviceScalar:      # held by reference until flush —
            pass                     # the tee must NOT try to fetch it

        for i in range(10):
            log.log_step(i, step_ms=2.0, queue_depth=3,
                         loss=FakeDeviceScalar())
        wait_for(lambda: collector.snapshot()["replicas"]
                 and collector.snapshot()["replicas"][0]["samples"]
                 >= 20)
        row = collector.snapshot()["replicas"][0]
        assert row["step_p50_ms"] == 2.0
        assert row["queue_depth"] == 3
        em.close()
        log.close()

    def test_raising_tee_is_dropped_not_fatal(self, tmp_path):
        log = M.MetricsLogger(str(tmp_path / "t.jsonl"), run="x",
                              track_compiles=False, process_index=0,
                              process_count=1)

        def bad_tee(rec):
            raise RuntimeError("boom")

        log.add_tee(bad_tee)
        log.log_step(0, step_ms=1.0)      # must not raise
        log.log_step(1, step_ms=1.0)
        log.close()
        assert len(M.read_sidecar(str(tmp_path / "t.jsonl"))) >= 3


class TestExportsAndRenders:
    def _populated(self, rules=None, logger=None):
        col = LiveCollector(rules=rules, logger=logger,
                            min_samples=4).start()
        e0 = LiveEmitter(col.endpoint, process_index=0, run="serve")
        e1 = LiveEmitter(col.endpoint, process_index=1, run="serve")
        for i in range(24):
            e0.observe("occupancy", 0.6)
            e0.observe("ttft_ms", 12.0)
            e0.observe("step_ms", 0.8)
            e1.observe("occupancy", 0.1)
            e1.observe("ttft_ms", 6.0)
            e1.observe("step_ms", 0.9)
        wait_for(lambda: len(col.snapshot()["replicas"]) == 2
                 and all(r["samples"] >= 72
                         for r in col.snapshot()["replicas"]))
        e0.close(), e1.close()
        return col

    def test_prometheus_exposition_and_http_scrape(self):
        col = self._populated()
        text = col.prometheus()
        assert f'{prometheus_name("occupancy")}{{process="0"}}' in text
        assert f'{prometheus_name("ttft_ms")}{{quantile="0.95"}}' \
            in text
        assert f"# TYPE {prometheus_name('drops_total')} counter" \
            in text
        assert prometheus_name("fleet_alerts_total") in text
        # the HTTP endpoint serves the same exposition + the snapshot
        scraped = urllib.request.urlopen(col.metrics_url,
                                         timeout=5).read().decode()
        assert f"# TYPE {prometheus_name('occupancy')} gauge" in scraped
        snap_url = col.metrics_url.replace("/metrics", "/snapshot")
        snap = json.loads(urllib.request.urlopen(
            snap_url, timeout=5).read().decode())
        assert len(snap["replicas"]) == 2
        col.close()

    def test_serve_top_frame_renders_rows(self):
        sys.path.insert(0, TOOLS)
        try:
            import serve_top as ST
        finally:
            sys.path.remove(TOOLS)
        col = self._populated(rules="occupancy_min>=0.2@4")
        wait_for(lambda: col.alerts)
        frame = ST.render_frame(col.snapshot())
        assert "2 replica(s)" in frame
        assert "fleet alerts 1 (occupancy_min)" in frame
        assert "p0" in frame and "p1" in frame
        assert "occupancy min/mean" in frame
        col.close()

    def test_collector_flush_renders_live_table_in_report(self,
                                                          tmp_path):
        """The schema-7 story end to end: collector final state ->
        ordinary records -> telemetry_report renders the LIVE table
        with no new record kinds beyond live_drop."""
        sys.path.insert(0, TOOLS)
        try:
            import telemetry_report as TR
        finally:
            sys.path.remove(TOOLS)
        path = str(tmp_path / "live.jsonl")
        log = M.MetricsLogger(path, run="collector",
                              track_compiles=False, process_index=0,
                              process_count=1)
        col = self._populated(rules="occupancy_min>=0.2@4", logger=log)
        wait_for(lambda: col.alerts)
        col.close()
        log.close()
        recs = M.read_sidecar(path)          # validates every record
        kinds = {r["kind"] for r in recs}
        assert "live_drop" in kinds and "alert" in kinds
        s = TR.summarize(recs)
        assert len(s["live"]["replicas"]) == 2
        assert s["live"]["fleet"]["alerts"] == 1
        assert s["live_drops"]["drops"] == 0
        out = TR.render(s)
        assert "LIVE plane" in out and "| p0 |" in out
        assert "live drops" in out


class TestSchema7:
    def test_live_drop_validates_and_version_bumped(self):
        assert M.SCHEMA_VERSION >= 7
        assert {7, 8} <= set(M.SUPPORTED_VERSIONS)
        M.validate_record({"v": 7, "kind": "live_drop", "t": 1.0,
                           "process": 0, "drops": 0, "sent": 10})
        M.validate_record({"v": 7, "kind": "alert", "t": 1.0,
                           "rule": "occupancy_min", "scope": "fleet",
                           "process": 1, "measured": 0.05,
                           "threshold": 0.2})
        with pytest.raises(ValueError):
            M.validate_record({"v": M.SCHEMA_VERSION + 1,
                               "kind": "live_drop", "t": 1.0})
