"""Profiling / observability (the apex.pyprof equivalent, TPU-native).

The reference pyprof (apex/pyprof/, deprecated upstream) has three parts:
(1) ``nvtx.init()`` monkey-patches every torch callable to wrap calls in
nvtx ranges carrying JSON op metadata (nvmarker.py:67-108); (2) ``parse``
reads the nvprof SQLite kernel database; (3) ``prof`` computes per-op
FLOPs/bytes/efficiency from recorded signatures (one analyzer class per op
category).

On TPU the platform already provides the first two: ``jax.profiler`` emits
Perfetto/TensorBoard traces and ``jax.named_scope`` attaches op metadata at
trace time — no monkey-patching (XLA programs are traced once, so
annotation happens at trace time, not call time). What this module adds:

- :func:`annotate` / :func:`mark` — named-scope annotation analogs of the
  reference's manual nvtx ranges (distributed.py:359-360 etc.);
- :func:`trace` — context manager around ``jax.profiler`` trace capture
  (the nvprof session);
- :func:`analyze` — the ``pyprof.prof`` analog: per-program FLOPs / bytes
  accessed / arithmetic intensity / projected roofline time computed from
  XLA's own cost analysis of the compiled HLO, instead of parsing a kernel
  database.
- :func:`top_ops` — the per-op table (reference pyprof/prof/ computes one
  analyzer class per op category over nvprof SQLite records): parse a
  :func:`trace` capture into per-op rows of (self time, %, occurrences,
  FLOPs, bytes, achieved FLOP/s and B/s, bound-by) via xprof's
  framework_op_stats conversion. ``tools/trace_top_ops.py`` is a thin CLI
  over it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Callable, Optional

import jax

from apex_tpu.prof.peaks import ChipPeak, PEAKS, chip_peak  # noqa: F401

__all__ = ["annotate", "mark", "SCOPES", "REGIONS", "trace", "analyze",
           "CostReport", "init",
           "OpStats", "top_ops", "format_top_ops", "RooflineSummary",
           "roofline", "gaps", "Gap", "GapReport", "TimelineEvent",
           "attribute_gaps", "format_gaps",
           "MetricsLogger", "Watchdog", "metrics", "watchdog",
           "SCHEMA_VERSION", "numerics", "coverage",
           "fleet", "FleetProbe", "DesyncProbe",
           "spans", "slo", "SpanTracer", "SLOMonitor", "SLORule",
           "parse_slo_rules",
           "merge_process_traces", "merged_chrome_trace",
           "write_merged_chrome_trace",
           "flightrec", "FlightRecorder",
           "history", "PerfPoint", "Trajectory", "check_trajectory",
           "live", "LiveEmitter", "LiveCollector"]


def init(*args, **kwargs):
    """Reference-parity stub of ``pyprof.nvtx.init()`` (nvmarker.py:206).
    There is nothing to patch: jitted computations are annotated at trace
    time via :func:`annotate`. Kept so reference scripts port cleanly."""
    return None


def annotate(name_or_fn=None):
    """Decorator wrapping a function body in a named scope that shows up in
    XLA traces and profiler timelines (the nvtx range analog).

    Usage::

        @annotate               # scope named after the function
        def attention_block(...): ...

        @annotate("fused_step")
        def step(...): ...
    """
    if callable(name_or_fn):
        fn, name = name_or_fn, name_or_fn.__name__

        @functools.wraps(fn)
        def wrapped(*a, **k):
            with jax.named_scope(name):
                return fn(*a, **k)
        return wrapped

    name = name_or_fn

    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*a, **k):
            with jax.named_scope(name or fn.__name__):
                return fn(*a, **k)
        return wrapped
    return deco


@contextlib.contextmanager
def mark(name: str):
    """Context-manager named scope (the hand nvtx ranges on hot paths,
    reference distributed.py:359-360, sync_batchnorm.py:69)."""
    with jax.named_scope(name):
        yield


# The one scope vocabulary: the outermost ``jax.named_scope`` of every op a
# training step issues, opened where the work is issued (models/, ops/flat.py,
# amp/, optimizers/base.py, parallel/, contrib/optimizers/). Each entry is a
# regex for one whole component of an op's name path; a device trace names
# every HLO instruction by that path (``jit(step)/transpose(jvp(mlp))/...``),
# so the benchmark's ``trace_scope`` reader buckets device time by these
# names and by direction. Applied with ``jax.named_scope`` / :func:`mark` /
# :func:`annotate`; a new model appends its scopes here and lists them,
# with where it opens them, in ``benchmarks/scopes/<family>.json`` (a test
# holds the two sets equal).
SCOPES = ("embed", "attention", "mlp", "head_loss",     # models/transformer
          "stem", r"stage\d+_block\d+", "head",          # models/resnet
          "amp_cast", "amp_scale", "optimizer", "collective",
          "linear_attention", "delta_rule",             # models/hybrid_lm
          "latent_attention", "short_conv", "window_attention",
          "sparse_attention", "sparse_index", "kda_attention",
          "diffusion_attention",
          "moe_route", "moe_experts")                   # contrib/moe

# Regions: names of *structure that encloses scopes*, opened with the same
# ``jax.named_scope`` / :func:`mark` around what a program adds around its
# scoped work, where that machinery has a cost of its own (a run of like
# layers as one ``lax.scan`` over stacked parameters). A region is no scope
# and is in no ``benchmarks/scopes/*.json``: an op under a scope is its
# scope's wherever the scope sits in the path (``trace_scope`` steps over
# every other component), and only an op with no scope, its own or its
# reader's, goes to the innermost region of its path. Rule: scope first,
# else innermost region, else unowned. Both are opened in
# ``models/hybrid_lm.py`` ``HybridLM.hidden_states``, siblings:
# ``layer_stack`` around a run's ``jnp.stack`` of its layers' leaves, the
# selection biases' rows and the ``concatenate`` of the runs' counters
# (backward: the stacked gradients taken apart), ``layer_scan`` around the
# run's ``lax.scan`` (the ``while`` less its body, the loop-level
# ``dynamic_slice`` / ``dynamic_update_slice`` of stacked operands, results,
# residuals and gradients, the compiler's copies at the loop's boundary).
# The benchmark's copy is ``benchmarks/regions/<family>.json`` (a test holds
# the sets equal); its ``trace_region`` reader gives each region's own time
# a step and what is left with neither name (``unowned_pct``).
REGIONS = ("layer_stack", "layer_scan")                 # models/hybrid_lm


@contextlib.contextmanager
def trace(logdir: str = "/tmp/apex_tpu_trace",
          create_perfetto_link: bool = False):
    """Capture a profiler trace of the enclosed block (the nvprof/nsys
    session the reference's parse step consumed; output is viewable in
    TensorBoard/Perfetto/XProf instead of SQLite)."""
    jax.profiler.start_trace(logdir,
                             create_perfetto_link=create_perfetto_link)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# Cost analysis (the pyprof.prof analog)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CostReport:
    """Whole-program cost summary from XLA's analytical model."""
    flops: float
    bytes_accessed: float
    peak_flops_per_s: Optional[float]
    hbm_bw_bytes_per_s: Optional[float]

    @property
    def arithmetic_intensity(self) -> float:
        """flops / byte — compare against the hardware ridge point to see
        whether the program is compute- or bandwidth-bound (the roofline
        judgment pyprof's per-op 'efficiency' columns approximate)."""
        return self.flops / max(self.bytes_accessed, 1.0)

    def projected_seconds(self) -> Optional[float]:
        if not (self.peak_flops_per_s and self.hbm_bw_bytes_per_s):
            return None
        return max(self.flops / self.peak_flops_per_s,
                   self.bytes_accessed / self.hbm_bw_bytes_per_s)

    def summary(self) -> str:
        lines = [f"flops:                {self.flops:.3e}",
                 f"bytes accessed:       {self.bytes_accessed:.3e}",
                 f"arithmetic intensity: {self.arithmetic_intensity:.2f} "
                 f"flops/byte"]
        t = self.projected_seconds()
        if t is not None:
            lines.append(f"roofline time:        {t * 1e6:.1f} us")
        return "\n".join(lines)


def analyze(fn: Callable, *example_args,
            peak_flops_per_s: Optional[float] = None,
            hbm_bw_bytes_per_s: Optional[float] = None,
            static_argnums=(), **example_kwargs) -> CostReport:
    """Compile ``fn`` on the example args and report XLA cost analysis
    (the pyprof.prof FLOP/byte tables computed from HLO instead of from an
    nvprof database — SURVEY.md §5 tracing)."""
    compiled = jax.jit(fn, static_argnums=static_argnums) \
        .lower(*example_args, **example_kwargs).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    ca = ca or {}
    if (peak_flops_per_s is None or hbm_bw_bytes_per_s is None) \
            and jax.default_backend() == "tpu":
        peak = chip_peak()
        peak_flops_per_s = peak_flops_per_s or peak.bf16_flops_per_s
        hbm_bw_bytes_per_s = hbm_bw_bytes_per_s or peak.hbm_bytes_per_s
    return CostReport(
        flops=float(ca.get("flops", 0.0)),
        bytes_accessed=float(ca.get("bytes accessed", 0.0)),
        peak_flops_per_s=peak_flops_per_s,
        hbm_bw_bytes_per_s=hbm_bw_bytes_per_s)


# ---------------------------------------------------------------------------
# Per-op trace tables (the pyprof/prof per-op analyzers)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class OpStats:
    """One row of the per-op table: where the time went and what the op
    achieved while it ran (the reference's per-category FLOP/byte
    'efficiency' columns, pyprof/prof/)."""
    op: str
    op_type: str
    self_time_us: float        # total device (or host) self time
    time_pct: float            # % of plane total self time
    occurrences: int
    flops_per_s: float         # achieved, from the profiler's counters
    bytes_per_s: float
    bound_by: str              # xprof's roofline judgment for the op
    on_device: bool

    @property
    def flops(self) -> float:
        """Total FLOPs attributed to this op over the capture."""
        return self.flops_per_s * self.self_time_us * 1e-6

    @property
    def bytes_accessed(self) -> float:
        return self.bytes_per_s * self.self_time_us * 1e-6

    def efficiency(self, peak_flops_per_s: Optional[float] = None) -> float:
        """Achieved / peak FLOP rate (MFU of this op's busy time);
        the peak defaults to the attached chip's (``prof.peaks``)."""
        if peak_flops_per_s is None:
            peak_flops_per_s = chip_peak().bf16_flops_per_s
        return self.flops_per_s / peak_flops_per_s


def _find_xplanes(logdir: str) -> list[str]:
    import glob
    import os
    hits = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no *.xplane.pb under {logdir}")
    # newest capture directory only
    newest_dir = os.path.dirname(hits[-1])
    return [h for h in hits if os.path.dirname(h) == newest_dir]


def _raw_to_tool_data():
    """xprof's tool-data converter under whichever package name this
    environment ships it (standalone ``xprof`` vs the older
    ``tensorboard_plugin_profile`` wheel)."""
    try:
        from xprof.convert import raw_to_tool_data as _r
        return _r
    except ImportError:
        # the older wheel can also fail at import time with an
        # AttributeError when its bundled TF pywrap doesn't match —
        # treat any failure as "converter unavailable"
        try:
            from tensorboard_plugin_profile.convert import \
                raw_to_tool_data as _r
            return _r
        except Exception as e:
            raise ImportError(f"no xprof tool-data converter: {e}")


def top_ops(trace_dir: str, top: Optional[int] = None) -> list[OpStats]:
    """Parse a :func:`trace` capture into per-op rows sorted by descending
    device self-time (the reference pipeline ``pyprof.parse`` +
    ``pyprof.prof`` in one call, over xprof's framework_op_stats instead
    of an nvprof SQLite db).

    Per-op FLOP/bandwidth counters exist only for device (TPU) planes.
    CPU-only captures carry no framework-op stats at all, so they fall
    back to aggregating raw trace events by name — op timings without
    rate counters (``flops_per_s``/``bytes_per_s`` are 0 there)."""
    import json

    paths = _find_xplanes(trace_dir)
    try:
        _r = _raw_to_tool_data()
        data, _ = _r.xspace_to_tool_data(paths, "framework_op_stats", {})
        if isinstance(data, bytes):
            data = data.decode()
        tables = json.loads(data)
        table = tables[0] if isinstance(tables, list) else tables
        cols = [c["id"] for c in table["cols"]]
        rows = [dict(zip(cols, [c["v"] for c in row["c"]]))
                for row in table["rows"]]
    except ImportError:
        # no converter in this environment: aggregate the raw timeline
        # instead (op timings without rate counters)
        rows = []

    def build(r, on_device):
        # xprof's measured_flop_rate / measured_memory_bw come in G-units
        # (a 68 ms conv reports 59952 = 60 TF/s), and its *_percent
        # columns are FRACTIONS of the plane total (0.4956 = 49.6%) —
        # both verified against hand-computed totals on the r4 RN50
        # trace. time_pct is recomputed from our own sum below anyway.
        return OpStats(
            op=str(r.get("operation", "")),
            op_type=str(r.get("type", "")),
            self_time_us=float(r.get("total_self_time", 0.0)),
            time_pct=0.0,
            occurrences=int(float(r.get("occurrences", 0))),
            flops_per_s=float(r.get("measured_flop_rate", 0.0) or 0.0)
            * 1e9,
            bytes_per_s=float(r.get("measured_memory_bw", 0.0) or 0.0)
            * 1e9,
            bound_by=str(r.get("bound_by", "") or ""),
            on_device=on_device)

    dev = [build(r, True) for r in rows
           if r.get("host_or_device") == "Device"]
    if not dev:
        dev = [build(r, False) for r in rows
               if r.get("host_or_device") == "Host"]
    dev = [s for s in dev if s.self_time_us > 0.0]
    if not dev:
        dev = _top_ops_from_events(paths)
    total_us = sum(s.self_time_us for s in dev) or 1.0
    dev = [dataclasses.replace(s, time_pct=100.0 * s.self_time_us
                               / total_us) for s in dev]
    dev.sort(key=lambda s: -s.self_time_us)
    return dev[:top] if top else dev


def _top_ops_from_events(xplane_paths: list[str]) -> list[OpStats]:
    """CPU/converter-less fallback: aggregate the raw xplane timeline by
    event name via the ``prof.gaps`` XSpace walker (python-frame lanes
    are never picked by the walker). Op timings without rate counters."""
    import os

    from apex_tpu.prof import gaps as _g
    trace_dir = os.path.dirname(xplane_paths[0])
    totals: dict[str, list[float]] = {}
    for e in _g.load_timeline(trace_dir):
        if e.name.startswith("$"):
            continue
        t = totals.setdefault(e.name, [0.0, 0])
        t[0] += e.dur_us
        t[1] += 1
    grand = sum(t[0] for t in totals.values()) or 1.0
    return [OpStats(op=name, op_type="trace_event", self_time_us=t[0],
                    time_pct=100.0 * t[0] / grand, occurrences=t[1],
                    flops_per_s=0.0, bytes_per_s=0.0, bound_by="",
                    on_device=False)
            for name, t in totals.items() if t[0] > 0.0]


@dataclasses.dataclass(frozen=True)
class RooflineSummary:
    """Whole-capture roofline verdict from a :func:`trace` directory —
    the analysis that pinned the r4 RN50 step at ~96% of the v5e HBM
    roofline (docs/PERF.md r04), as a library call."""
    busy_us: float             # device busy (non-IDLE) self time
    idle_us: float
    flops: float               # total attributed FLOPs over the capture
    bytes_accessed: float      # total attributed HBM bytes
    achieved_flops_per_s: float   # over busy time
    achieved_bytes_per_s: float
    peak_flops_per_s: float
    peak_bytes_per_s: float
    hbm_bound_pct: float       # busy-time % xprof marks HBM-bound

    @property
    def mfu(self) -> float:
        return self.achieved_flops_per_s / self.peak_flops_per_s

    @property
    def bandwidth_util(self) -> float:
        return self.achieved_bytes_per_s / self.peak_bytes_per_s

    @property
    def bound_by(self) -> str:
        """"HBM" when the capture runs closer to the bandwidth roof than
        the compute roof, else "MXU"."""
        return ("HBM" if self.bandwidth_util >= self.mfu else "MXU")


def roofline(trace_dir: Optional[str] = None, *,
             stats: Optional[list[OpStats]] = None,
             peak_flops_per_s: Optional[float] = None,
             peak_bytes_per_s: Optional[float] = None,
             device_kind: Optional[str] = None) -> RooflineSummary:
    """Aggregate a :func:`top_ops` capture into one roofline verdict.

    Answers "is this program bandwidth- or compute-bound, and how close
    to the roof?" — totals each op's attributed FLOPs/bytes (rate x its
    own busy time) and divides by total busy time, so idle/dispatch gaps
    don't dilute the achieved rates.

    Pass ``stats`` (an un-truncated :func:`top_ops` result) to reuse an
    already-parsed capture — xplane parsing is the expensive step.

    Peaks come from the ``prof.peaks`` table for ``device_kind``
    (default: the attached device). Captures are usually analyzed away
    from the chip that produced them: name that chip's kind there, or
    pass explicit peaks — an unknown kind raises.

    Raises ``ValueError`` on captures without device rate counters
    (host/CPU fallback rows) — a 0 TF/s, 0 GB/s "verdict" would be
    noise presented as analysis."""
    if stats is None:
        if trace_dir is None:
            raise ValueError("pass trace_dir or stats")
        stats = top_ops(trace_dir)
    if peak_flops_per_s is None or peak_bytes_per_s is None:
        peak = chip_peak(device_kind)
        if peak_flops_per_s is None:
            peak_flops_per_s = peak.bf16_flops_per_s
        if peak_bytes_per_s is None:
            peak_bytes_per_s = peak.hbm_bytes_per_s
    idle = sum(s.self_time_us for s in stats if s.op_type == "IDLE")
    busy_rows = [s for s in stats if s.op_type != "IDLE"]
    busy = sum(s.self_time_us for s in busy_rows)
    flops = sum(s.flops for s in busy_rows)
    byts = sum(s.bytes_accessed for s in busy_rows)
    if not any(s.on_device for s in busy_rows) or \
            (flops == 0.0 and byts == 0.0):
        raise ValueError(
            "capture carries no device FLOP/bandwidth counters (host or "
            "CPU-event fallback rows) — roofline needs a TPU-device "
            "capture")
    hbm = sum(s.self_time_us for s in busy_rows if s.bound_by == "HBM")
    busy_s = max(busy, 1e-9) * 1e-6
    return RooflineSummary(
        busy_us=busy, idle_us=idle, flops=flops, bytes_accessed=byts,
        achieved_flops_per_s=flops / busy_s,
        achieved_bytes_per_s=byts / busy_s,
        peak_flops_per_s=peak_flops_per_s,
        peak_bytes_per_s=peak_bytes_per_s,
        hbm_bound_pct=100.0 * hbm / max(busy, 1e-9))


# Gap attribution (prof.gaps) rides the same public surface: top_ops
# answers "how much time is idle", gaps answers "where and why".
from apex_tpu.prof import gaps  # noqa: E402
from apex_tpu.prof.gaps import (Gap, GapReport,  # noqa: E402,F401
                                TimelineEvent,
                                attribute as attribute_gaps,
                                format_gaps)

# Runtime telemetry (prof.metrics / prof.watchdog, r07): the *live*
# half of observability — capture-based tools above answer questions
# about a trace someone took; the MetricsLogger sidecar + Watchdog
# record what every run did without one.
from apex_tpu.prof import metrics, watchdog  # noqa: E402,F401
from apex_tpu.prof.metrics import (MetricsLogger,  # noqa: E402,F401
                                   SCHEMA_VERSION)
from apex_tpu.prof.watchdog import Watchdog  # noqa: E402,F401

# Numerics observability (r09): overflow provenance + underflow census
# (prof.numerics) and the precision-coverage auditor (prof.coverage) —
# the records behind the schema-2 ``amp_overflow``/``numerics`` kinds.
from apex_tpu.prof import coverage, numerics  # noqa: E402,F401

# Fleet observability (r10): cross-process aggregation of per-process
# sidecars, the in-run straggler probe, and desync detection — the
# schema-3 ``fleet_skew``/``desync`` kinds (prof.fleet).
from apex_tpu.prof import fleet  # noqa: E402,F401
from apex_tpu.prof.fleet import (DesyncProbe,  # noqa: E402,F401
                                 FleetProbe)

# Lifecycle tracing + in-run alerting (r13): host-side begin/end span
# tracer (Chrome-trace exportable, schema-5 ``span`` records) and the
# rolling-window SLO monitor emitting ``alert`` records — the
# detect→alert seam of the ROADMAP's self-healing runtime.
from apex_tpu.prof import slo, spans  # noqa: E402,F401
from apex_tpu.prof.slo import (SLOMonitor,  # noqa: E402,F401
                               SLORule,
                               parse_rules as parse_slo_rules)
from apex_tpu.prof.spans import (SpanTracer,  # noqa: E402,F401
                                 merge_process_traces,
                                 merged_chrome_trace,
                                 write_merged_chrome_trace)

# Distributed tracing + flight recorder (r22, schema 11): trace-context
# propagation across the router's process boundary, the fleet trace
# merger above, and the alert-triggered flight recorder — a bounded
# in-memory ring of recent records/spans dumped to FLIGHTREC_*.json on
# any ``on_alert`` at zero steady-state disk cost.
from apex_tpu.prof import flightrec  # noqa: E402,F401
from apex_tpu.prof.flightrec import FlightRecorder  # noqa: E402,F401

# Cross-round perf trajectory (r16): every committed BENCH_*/LMBENCH_*/
# DECODEBENCH_*/SERVE_*/DATABENCH_*/TELEM_* artifact canonicalized into
# PerfPoint records in an append-only committed store
# (BENCH_TRAJECTORY.json), with noise-aware trend-rule verdicts — the
# time axis of the observability stack (tools/perf_history.py is the
# CLI).
from apex_tpu.prof import history  # noqa: E402,F401
from apex_tpu.prof.history import (PerfPoint,  # noqa: E402,F401
                                   Trajectory,
                                   check_trajectory)

# Live fleet telemetry plane (r18): per-process non-blocking streaming
# emitters tee'd off MetricsLogger, a fleet collector with rolling
# (process, metric) windows + fleet-scope SLO evaluation (schema-7
# ``scope: "fleet"`` alerts through the same on_alert seam) + a
# Prometheus /metrics endpoint — what tools/serve_top.py renders.
from apex_tpu.prof import live  # noqa: E402,F401
from apex_tpu.prof.live import (LiveCollector,  # noqa: E402,F401
                                LiveEmitter)


def format_top_ops(stats: list[OpStats], name_width: int = 60) -> str:
    """Markdown table of :func:`top_ops` rows (the PERF_r{N}.md format)."""
    lines = ["| op | type | self us | % | count | GFLOP/s | GB/s | "
             "bound by |", "|---|---|---|---|---|---|---|---|"]
    for s in stats:
        name = s.op if len(s.op) <= name_width else \
            s.op[:name_width - 3] + "..."
        lines.append(
            f"| `{name}` | {s.op_type} | {s.self_time_us:.0f} | "
            f"{s.time_pct:.1f} | {s.occurrences} | "
            f"{s.flops_per_s / 1e9:.1f} | {s.bytes_per_s / 1e9:.1f} | "
            f"{s.bound_by} |")
    return "\n".join(lines)
