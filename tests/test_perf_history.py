"""Cross-round perf trajectory (r16): ingestion forward-compat over
EVERY committed artifact, the append-only store, noise-aware regression
verdicts (injected-regression FAILs, inside-noise stays PASS), suite
-duration ingestion, run_meta stamping, and the telemetry_report
machine-readable satellites.

Mirrors the r13 schema round-trip test's contract: the committed
artifact set IS the fixture — if a future round changes a tool's line
shape in a way the ingester can't read, this file breaks before the
trajectory silently goes blind. Budget: pure parsing + in-process
checks, ~2 s, plus two short subprocess smokes.
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")
sys.path.insert(0, TOOLS)

import telemetry_report as TR            # noqa: E402
import _perf_common as PC                # noqa: E402

from apex_tpu.prof import history as H   # noqa: E402
from apex_tpu.prof import metrics as M   # noqa: E402


def _committed_artifacts() -> "list[str]":
    files = []
    for g in ("BENCH_r*.json", "LMBENCH_r*.json", "DECODEBENCH_r*.json",
              "SERVE_r*.json", "DATABENCH_r*.json", "VITBENCH_r*.json",
              "TELEM_r*.jsonl"):
        files += sorted(glob.glob(os.path.join(REPO, g)))
    return [f for f in files
            if not os.path.basename(f).startswith(("SERVE_TRACE_",
                                                   "SERVE_COMPARE_"))]


def _pt(round, value, *, tool="serve_bench", scenario="s",
        metric="decode_step_p50_ms", spread=None, prov=None):
    return H.PerfPoint(round=round, tool=tool, scenario=scenario,
                       metric=metric, value=value, spread=spread,
                       provenance=prov or f"t{round}")


# -- ingestion forward-compat ----------------------------------------------

class TestIngestion:
    def test_every_committed_artifact_ingests(self):
        """The r16 acceptance mirror of r13's schema round-trip: every
        committed BENCH_r*/LMBENCH_r*/DECODEBENCH_r*/SERVE_r*/
        DATABENCH_r*/TELEM_r* artifact — five rounds of format drift —
        parses into nonzero PerfPoints with zero errors."""
        files = _committed_artifacts()
        assert len(files) >= 40, files
        rounds = set()
        for f in files:
            pts = H.parse_artifact(f, summarize=TR.summarize,
                                   read_sidecar=M.read_sidecar)
            assert pts, f"no PerfPoints from {f}"
            for p in pts:
                assert p.round >= 1 and p.tool and p.scenario \
                    and p.metric, (f, p)
                assert isinstance(p.value, float), (f, p)
            rounds.update(p.round for p in pts)
        # the store must span the repo's history, not a recent slice
        assert len(rounds) >= 10, sorted(rounds)

    def test_round_and_tool_from_name(self):
        assert H.round_from_name("BENCH_r05_batch448.json") == 5
        assert H.round_from_name("TELEM_r10_fleet_smoke.p1.jsonl") == 10
        assert H.round_from_name("BASELINE.json") is None
        assert H.tool_from_name("DECODEBENCH_r05_p512.json") \
            == "decode_bench"
        assert H.tool_from_name("SERVE_r12_static.json") == "serve_bench"

    def test_legacy_untagged_equals_stamped(self):
        """The backfill contract: an untagged legacy line and its
        stamped twin canonicalize to identical (metric, value) points —
        run_meta rides along as provenance, never as a parse
        requirement."""
        legacy = {"metric": "m", "value": 3.5, "unit": "img/s",
                  "ms_per_step": 12.0}
        stamped = dict(legacy, format="bench@1",
                       run_meta={"tool": "bench", "git": "abc"})
        a = H.points_from_result_line(legacy, tool="bench", round=7)
        b = H.points_from_result_line(stamped, tool="bench", round=7)
        assert [(p.metric, p.value) for p in a] \
            == [(p.metric, p.value) for p in b]
        assert all(p.run_meta is None for p in a)
        assert all(p.run_meta for p in b)

    def test_format_tag_overrides_tool(self):
        (p, *_) = H.points_from_result_line(
            {"metric": "m", "value": 1.0, "format": "decode_bench@1"},
            tool="bench", round=3)
        assert p.tool == "decode_bench"

    def test_percentile_subdicts_and_twin_spread(self):
        line = {"metric": "m", "value": 100.0, "unit": "img/s",
                "fori_img_s": 100.0, "percall_img_s": 96.0,
                "ttft_ms": {"p50": 1.0, "p95": 2.5, "max": 4.0}}
        pts = {p.metric: p for p in H.points_from_result_line(
            line, tool="bench", round=5)}
        assert pts["img_s"].spread == pytest.approx(0.04)
        assert pts["img_s"].repeats == 2
        assert pts["ttft_p95_ms"].value == 2.5
        assert "ttft_max_ms" in pts

    def test_wrapper_without_result_line_yields_rc(self, tmp_path):
        """A dead chip window (the BENCH_r01 shape — rc!=0, traceback
        tail, no JSON line) still becomes a trajectory fact."""
        p = tmp_path / "BENCH_r01.json"
        p.write_text(json.dumps({"n": 1, "cmd": "python bench.py",
                                 "rc": 1, "tail": "Traceback ..."}))
        (pt,) = H.parse_artifact(str(p))
        assert (pt.metric, pt.value, pt.unit) == ("rc", 1.0,
                                                  "exit_code")

    def test_unparseable_raises(self, tmp_path):
        p = tmp_path / "BENCH_r09_junk.json"
        p.write_text("not json at all")
        with pytest.raises(ValueError):
            H.parse_artifact(str(p))


# -- the store -------------------------------------------------------------

class TestTrajectory:
    def test_append_only_roundtrip(self, tmp_path):
        path = str(tmp_path / "T.json")
        t = H.Trajectory(path=path)
        assert t.append([_pt(1, 1.0), _pt(2, 1.1)]) == 2
        # same key again: dropped (append-only, idempotent re-ingest)
        assert t.append([_pt(2, 9.9)]) == 0
        # same round, different provenance: coexists (variant artifact)
        assert t.append([_pt(2, 1.3, prov="variant")]) == 1
        t.save()
        t2 = H.Trajectory.load(path)
        assert len(t2.points) == 3
        assert t2.max_round() == 2
        ((key, rounds),) = [kv for kv in t2.series().items()]
        assert key == ("serve_bench", "s", "decode_step_p50_ms")
        assert sorted(rounds) == [1, 2]
        assert H.round_value(rounds[2]) == pytest.approx(1.2)

    def test_format_guard(self, tmp_path):
        p = tmp_path / "T.json"
        p.write_text(json.dumps({"format": "something_else@9",
                                 "points": []}))
        with pytest.raises(ValueError, match="format"):
            H.Trajectory.load(str(p))


# -- trend rules (the slo.py grammar + the relative form) ------------------

class TestRules:
    def test_relative_absolute_scoped(self):
        r1, r2, r3 = H.parse_check_rules(
            "decode_step_p50_ms<=1.10x@last3,suite_seconds<=870;"
            "serve_bench:tokens_per_s>=0.90x")
        assert (r1.relative, r1.threshold, r1.window) == (True, 1.10, 3)
        assert (r2.relative, r2.threshold) == (False, 870.0)
        assert (r3.tool, r3.op, r3.relative) == ("serve_bench", ">=",
                                                 True)

    def test_bad_spec_raises(self):
        with pytest.raises(ValueError, match="bad trend rule"):
            H.parse_check_rules("what<=is<=this")
        parsed = H.parse_check_rules(H.DEFAULT_RULES)
        assert len(parsed) >= 10     # the shipped set stays parseable


class TestCheck:
    def _base(self):
        t = H.Trajectory()
        t.append([_pt(12, 0.62), _pt(13, 0.61), _pt(14, 0.51)])
        return t

    def test_injected_regression_fails(self):
        """The acceptance fixture: a 10x decode-step regression at a
        new round must flip the verdict to FAIL."""
        t = self._base()
        t.append([_pt(15, 5.1)])
        (v,) = [v for v in H.check_trajectory(t)["verdicts"]
                if v.get("scenario") == "s"]
        assert v["verdict"] == "FAIL" and v["ratio"] > 5

    def test_inside_noise_band_passes(self):
        """+3% against a 5% default band: noise, not a regression."""
        t = self._base()
        t.append([_pt(15, 0.61 * 1.03)])
        (v,) = [v for v in H.check_trajectory(t)["verdicts"]
                if v.get("scenario") == "s"]
        assert v["verdict"] == "PASS"

    def test_over_factor_inside_recorded_band_warns(self):
        """Past the declared factor but inside the series' RECORDED
        repeat spread -> WARN: visible, not gating."""
        t = H.Trajectory()
        t.append([_pt(12, 0.60, spread=0.20), _pt(13, 0.60),
                  _pt(14, 0.60)])
        t.append([_pt(15, 0.60 * 1.15)])
        (v,) = [v for v in H.check_trajectory(t)["verdicts"]
                if v.get("scenario") == "s"]
        assert v["verdict"] == "WARN"
        assert v["band"] == pytest.approx(0.20)

    def test_single_round_series_skips(self):
        t = H.Trajectory()
        t.append([_pt(14, 0.51)])
        c = H.check_trajectory(t, "decode_step_p50_ms<=1.10x@last3")
        assert [v["verdict"] for v in c["verdicts"]] == ["SKIP"]

    def test_tier1_headroom_named_and_dots_gated(self):
        t = self._base()
        t.append([
            _pt(15, 617.0, tool="suite", scenario="tier1",
                metric="suite_seconds"),
            _pt(16, 700.0, tool="suite", scenario="tier1",
                metric="suite_seconds", prov="t16"),
            _pt(16, 741.0, tool="suite", scenario="tier1",
                metric="dots", prov="t16"),
        ])
        c = H.check_trajectory(t)
        assert c["tier1_headroom_s"] == pytest.approx(170.0)
        assert c["tier1_budget_s"] == 870.0
        (dv,) = [v for v in c["verdicts"] if v["metric"] == "dots"
                 and v["verdict"] != "SKIP"]
        assert dv["verdict"] == "PASS"

    def test_fail_verdicts_emit_schema5_alerts(self, tmp_path):
        """FAIL verdicts ride the EXISTING alert channel: written via
        MetricsLogger.log_alert, read back by read_sidecar, rendered
        by telemetry_report with zero new render code."""
        t = self._base()
        t.append([_pt(15, 5.1)])
        check = H.check_trajectory(t)
        alerts = H.verdict_alerts(check)
        assert len(alerts) == 1 and alerts[0]["source"] == "perf_history"
        side = str(tmp_path / "TELEM_hist.jsonl")
        # the pending-note channel is the process's: an alert a logger-less
        # SLOMonitor of an earlier test file on this worker left there would
        # be drained into this sidecar too (which files share a worker goes
        # by their durations)
        M._PENDING_NOTES.clear()
        lg = M.MetricsLogger(side, run="perf_history")
        for a in alerts:
            lg.log_alert(**a)
        lg.close()
        recs = M.read_sidecar(side)
        summary = TR.summarize(recs)
        assert summary["alerts"]["count"] == 1
        assert "decode_step_p50_ms<=1.10x@last3" in \
            summary["alerts"]["rules"][0]
        assert "ALERTS" in TR.render(summary)

    def test_committed_trajectory_checks_clean(self):
        """THE acceptance pin: the committed BENCH_TRAJECTORY.json
        passes the shipped rule set with zero FAILs — main never ships
        a store that gates its own CI red."""
        path = os.path.join(REPO, "BENCH_TRAJECTORY.json")
        t = H.Trajectory.load(path)
        assert len(t.points) > 400, "committed store missing/empty"
        assert len({p.round for p in t.points}) >= 10
        c = H.check_trajectory(t)
        fails = [v for v in c["verdicts"] if v["verdict"] == "FAIL"]
        assert not fails, fails
        # the r14->r16 suite trend is in the store, headroom is named
        assert {14, 15, 16} <= set(c["tier1_rounds"])
        assert c["tier1_headroom_s"] > 0


# -- suite-duration ingestion ----------------------------------------------

class TestSuiteLog:
    LOG = (
        "......x..F...  [ 40%]\n"
        ".............  [100%]\n"
        "12.50s call tests/test_a.py::t1\n"
        "3.20s call tests/test_b.py::t2\n"
        "=== 700 passed, 5 failed, 3 skipped in 615.22s ===\n"
        "DOTS_PASSED=700\n")

    def test_parses_dots_seconds_durations(self):
        pts = {p.metric: p.value for p in H.points_from_pytest_log(
            self.LOG, round=16)}
        assert pts["dots"] == 700.0          # DOTS_PASSED wins
        assert pts["suite_seconds"] == pytest.approx(615.22)
        assert pts["suite_failed"] == 5.0
        assert pts["slowest_test_s"] == pytest.approx(12.5)

    def test_quiet_summary_without_equals(self):
        pts = {p.metric: p.value for p in H.points_from_pytest_log(
            "...\n700 passed, 2 xfailed in 612.01s\n", round=16)}
        assert pts["suite_seconds"] == pytest.approx(612.01)

    def test_counts_dots_when_no_marker(self):
        pts = {p.metric: p.value for p in H.points_from_pytest_log(
            "..x..  [ 50%]\n.....  [100%]\n"
            "9 passed in 1.00s\n", round=16)}
        assert pts["dots"] == 9.0

    def test_garbage_raises(self):
        with pytest.raises(ValueError, match="tier-1 log"):
            H.points_from_pytest_log("hello world", round=16)


# -- run_meta stamping (tools/_perf_common) --------------------------------

class TestStamping:
    def test_stamp_result_fields(self):
        line = PC.stamp_result({"metric": "m", "value": 1.0}, "toolx")
        assert line["format"] == "toolx@1"
        meta = line["run_meta"]
        assert meta["tool"] == "toolx"
        assert meta["jax"]                  # jax is imported in-suite
        assert meta["telemetry_schema"] == M.SCHEMA_VERSION
        assert "utc" in meta

    def test_stamp_disabled_by_env(self, monkeypatch):
        monkeypatch.setenv("APEX_RUN_META", "0")
        line = PC.stamp_result({"metric": "m", "value": 1.0}, "toolx")
        assert "format" not in line and "run_meta" not in line

    def test_stamp_does_not_clobber(self):
        line = PC.stamp_result({"metric": "m", "value": 1.0,
                                "format": "old@0"}, "toolx")
        assert line["format"] == "old@0"

    def test_emit_result_appends_trajectory(self, tmp_path,
                                            monkeypatch, capsys):
        store = str(tmp_path / "T.json")
        monkeypatch.setenv("APEX_TRAJECTORY", store)
        monkeypatch.setenv("APEX_ROUND", "16")
        PC.emit_result({"metric": "serve_x", "value": 2.5,
                        "unit": "ms/token(p95, arrival-inclusive)"},
                       "serve_bench")
        out = capsys.readouterr().out
        line = json.loads(out)
        assert line["format"] == "serve_bench@1"
        doc = json.load(open(store))
        assert doc["format"] == H.TRAJECTORY_FORMAT
        pts = [H.PerfPoint.from_dict(d) for d in doc["points"]]
        assert any(p.metric == "token_lat_p95_ms" and p.round == 16
                   and p.provenance == "live" for p in pts)

    def test_append_trajectory_unarmed_is_noop(self, monkeypatch):
        monkeypatch.delenv("APEX_TRAJECTORY", raising=False)
        assert PC.append_trajectory({"metric": "m", "value": 1.0},
                                    tool="bench") is None


# -- telemetry_report machine-readable satellites --------------------------

class TestReportSatellites:
    def test_compare_payload_rows(self):
        ra = M.read_sidecar(os.path.join(REPO, "TELEM_r13_serve.jsonl"))
        rb = M.read_sidecar(os.path.join(REPO, "TELEM_r14_serve.jsonl"))
        payload = TR.compare_payload(TR.summarize(ra), TR.summarize(rb),
                                     "A", "B")
        assert payload["names"] == {"a": "A", "b": "B"}
        metrics = [r["metric"] for r in payload["rows"]]
        assert "decode step p50 ms" in metrics
        for row in payload["rows"]:
            assert set(row) == {"metric", "a", "b", "delta"}

    def test_refusal_shape(self):
        r = TR.refusal("per-process-sidecar", "detail here", use="--fleet")
        assert r["error"]["reason"] == "per-process-sidecar"
        assert r["error"]["use"] == "--fleet"

    def test_compare_refuses_per_process_with_structured_reason(
            self, monkeypatch, capsys):
        """--compare --json on a fleet sidecar: exit 2 AND a
        machine-readable reason on stdout (the r16 satellite — a
        consumer must see WHY, not a stderr string)."""
        monkeypatch.setattr(sys, "argv", [
            "telemetry_report.py", "--json", "--compare",
            os.path.join(REPO, "TELEM_r10_fleet_smoke.p0.jsonl"),
            os.path.join(REPO, "TELEM_r10_fleet_smoke.p1.jsonl")])
        with pytest.raises(SystemExit) as ex:
            TR.main()
        assert ex.value.code == 2
        payload = json.loads(capsys.readouterr().out.splitlines()[0])
        err = payload["error"]
        assert err["reason"] == "per-process-sidecar"
        assert err["process_count"] == 3 and err["use"] == "--fleet"


# -- the CLI over the committed store --------------------------------------

class TestCli:
    def _run(self, monkeypatch, capsys, *argv) -> "tuple[int, str]":
        import perf_history as PH
        monkeypatch.setattr(sys, "argv", ["perf_history.py", *argv])
        rc = PH.main()
        return rc, capsys.readouterr().out

    def test_check_strict_passes_then_fails_on_injected(
            self, tmp_path, monkeypatch, capsys):
        """Both verdicts through the real CLI (the CI job's shape):
        strict check is green on the committed store, red once an
        injected regression point lands."""
        rc, out = self._run(monkeypatch, capsys, "check", "--strict",
                            "--json")
        assert rc == 0, out[-1500:]
        check = json.loads(out.splitlines()[-1])
        assert check["fail"] == 0
        assert check["tier1_headroom_s"] > 0     # named as a number
        # inject: copy the store, append a 10x decode-step regression
        bad = str(tmp_path / "T.json")
        t = H.Trajectory.load(os.path.join(REPO,
                                           "BENCH_TRAJECTORY.json"))
        key = ("serve_bench", "serve_continuous_p95_token_lat_ms"
               "_r64_s4", "decode_step_p50_ms")
        rounds = t.series()[key]
        last = H.round_value(rounds[max(rounds)])
        t.append([H.PerfPoint(round=t.max_round() + 1,
                              tool=key[0], scenario=key[1],
                              metric=key[2], value=last * 10,
                              provenance="injected")])
        t.save(bad)
        rc, out = self._run(monkeypatch, capsys, "--trajectory", bad,
                            "check", "--strict", "--json")
        assert rc == 1, out[-1500:]
        check = json.loads(out.splitlines()[-1])
        assert check["fail"] >= 1

    def test_render_trend_table(self, monkeypatch, capsys):
        rc, out = self._run(monkeypatch, capsys, "render")
        assert rc == 0
        assert out.startswith("| round |")
        assert "tier-1 s" in out.splitlines()[0]
        assert any(ln.startswith("| r05 |") for ln in out.splitlines())
