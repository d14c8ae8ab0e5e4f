"""Runtime telemetry — structured per-step metrics as schema-versioned JSONL.

The capture-based half of observability (prof.trace / prof.gaps /
tools/trace_top_ops.py) answers "where did the time go" *after* someone
attached a profiler. This module is the *runtime* half — TorchTitan's
thesis (arXiv:2410.06511) that a production training stack needs a
first-class metrics subsystem, not ad-hoc prints: every run leaves a
machine-readable sidecar (``TELEM_*.jsonl``) recording what actually
happened — per-step/interval timings and throughput, AMP loss-scale
events (overflow/skip/growth counters from :class:`ScalerState`),
compile and *re*compile events, per-device memory watermarks, and
traced collective bytes — so a regressed bench number or a stalled
chip-window run is attributable from its artifact alone
(``tools/telemetry_report.py`` renders the summary).

Overhead discipline (the <2% budget):

- ``log_step`` only appends to an in-memory buffer; nothing is
  formatted or written per step.
- device scalars (loss, loss-scale, scaler counters) are accepted as
  jax arrays and held by REFERENCE; the host fetch happens once per
  :meth:`~MetricsLogger.flush`, never per step — no extra host syncs
  on the step path.
- compile tracking rides ``jax.monitoring`` listeners, which fire only
  when XLA actually traces/compiles.
- memory watermarks (``device.memory_stats()``) and the collective-bytes
  tally (:mod:`apex_tpu.parallel.collectives`) are sampled at flush
  boundaries only.

Schema (``docs/OBSERVABILITY.md`` is the normative reference): one JSON
object per line, every record carrying ``{"v": SCHEMA_VERSION, "kind":
..., "t": unix_seconds}``. Kinds: ``header``, ``step``, ``event``,
``amp``, ``compile``, ``recompile``, ``memory``, ``collectives``,
``stall``, ``close`` — plus ``amp_overflow``/``numerics`` (v2),
``fleet_skew``/``desync`` (v3), ``serving`` (v4), ``span``/``alert``
(v5), ``snapshot``/``restore`` (v6), ``live_drop`` (v7, the live
telemetry plane's drop accounting — ``prof.live``), ``router``
(v8, the multi-replica router tier's decision ledger —
``apex_tpu.serve.router``), and ``flightrec`` (v11, one
flight-recorder dump announcement — ``prof.flightrec``).
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Optional

__all__ = ["SCHEMA_VERSION", "SUPPORTED_VERSIONS", "SCHEMA_NAME",
           "MetricsLogger", "CompileTracker", "validate_record",
           "read_sidecar", "default_sidecar_path", "per_process_path",
           "process_identity", "note", "note_kind",
           "tracked_bytes_per_device"]

# v2 (numerics observability): adds the ``amp_overflow`` (overflow
# provenance: per-parameter culprit list) and ``numerics`` (underflow
# census / precision coverage) record kinds. v3 (fleet observability,
# r10): headers carry ``process_index``/``process_count`` so N
# per-process sidecars of one run pair into a fleet view
# (prof/fleet.py), and the ``fleet_skew`` (in-run straggler probe) and
# ``desync`` (cross-process agreement check) kinds exist. v4 (serving
# tier, r12): the ``serving`` kind — request-level latency aggregates
# of one serving run (TTFT / normalized-token-latency / inter-token
# percentiles, tokens/s, slot occupancy, queue depth — written by
# ``apex_tpu.serve`` via :meth:`MetricsLogger.log_serving`). v5
# (lifecycle tracing + in-run alerting, r13): the ``span`` kind — one
# completed host-side phase span (``prof.spans.SpanTracer``, written
# via :meth:`MetricsLogger.log_spans`) — and the ``alert`` kind — an
# in-run SLO-rule violation (``prof.slo.SLOMonitor``) or watchdog
# stall, the machine-consumable trigger seam of the ROADMAP's
# self-healing runtime. v6 (self-healing runtime, r17): the
# ``snapshot`` kind — one committed async snapshot generation
# (``apex_tpu.runtime.SnapshotWriter``: generation, step, bytes,
# async write latency) — and the ``restore`` kind — one
# restore-from-last-good (``apex_tpu.runtime.Supervisor`` / the
# startup resume path: generation, restored step, trigger reason +
# rule, steps lost), the remediation half of the detect→alert→act
# loop. v7 (live telemetry plane, r18): the ``live_drop`` kind — one
# process's live-stream drop accounting (``prof.live.LiveEmitter``:
# bounded-queue/dead-collector drops counted, never blocked on; the
# collector's close-time flush writes one per replica too) — and
# fleet-scope ``alert`` fields: alerts evaluated by
# ``prof.live.LiveCollector`` over FLEET aggregates carry
# ``scope: "fleet"`` (plus the culprit ``process`` where a derived
# metric names one), distinguishing them from per-process monitors'
# alerts. v8 (router tier, r19): the ``router`` kind — one routing
# run's decision ledger (``serve.router.Router.summary``: policy,
# per-replica routed/completed/shed/redirected counts, shed
# attribution by rule, scale events, routed balance) — and the
# ``serving`` record's shed accounting: ``shed`` (drops the router
# COUNTED and attributed to a rule + replica) is distinct from
# ``dropped`` (LOST requests nobody accounted for — the only kind
# telemetry_report flags as DROPPED, so the zero-drop contract stays
# checkable in shed mode). v9 (paged KV arena, r20): the ``serving``
# record splits ``arena_bytes`` into ``kv_reserved_bytes`` (what the
# arena preallocates) vs ``kv_resident_peak_bytes`` (KV actually
# holding live tokens), and paged runs add ``page_size`` /
# ``kv_pages`` / ``kv_pages_free[_min]`` plus the shared-prefix
# ledger (``prefix_hits``/``prefix_lookups``/``prefix_entries``/
# ``prefix_evictions``/``prefix_hit_requests`` and
# ``prefix_hit_ttft_p95`` — the cache-hit TTFT cliff by name). v10
# (speculative decoding, r21): spec-mode ``serving`` records add the
# acceptance ledger — ``spec_k`` (draft tokens proposed per step),
# ``spec_draft_tokens`` / ``spec_accepted_tokens`` (proposed vs
# accepted totals), ``spec_accept_mean`` (mean accepted length per
# (slot, step) sample, of k), and ``spec_accept_hist`` (accepted-
# length histogram, index 0..k) — the numbers that turn "tokens/s
# went up" into "because the draft was right this often". v11
# (distributed tracing + flight recorder, r22): ``span`` records may
# carry ``attrs.trace`` (the fleet-wide trace id the router stamps on
# every submit) and ``attrs.hop`` (0 on first routing, +1 per
# replay/redirect re-enqueue) so ``prof.spans.merge_process_traces``
# can join one request's spans across N per-process sidecars; NEW
# router-side span names (``route``/``admission``/``shed``/
# ``replay_hop``/``replay_stitch``) join the engine's request
# lifecycle; and the ``flightrec`` kind — one flight-recorder dump
# announcement (``prof.flightrec.FlightRecorder``: trigger alert,
# dump path, records/spans/open-span counts, window seconds) written
# when an ``on_alert`` fires and the black box hits disk. Old
# sidecars (r07-r21 artifacts) remain readable — SUPPORTED_VERSIONS
# is the parse contract; SCHEMA_VERSION is what new sidecars are
# written at.
SCHEMA_VERSION = 11
SUPPORTED_VERSIONS = (1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11)
SCHEMA_NAME = "apex_tpu.telemetry"

_KINDS = ("header", "step", "event", "amp", "compile", "recompile",
          "memory", "collectives", "stall", "close",
          "amp_overflow", "numerics", "fleet_skew", "desync",
          "serving", "span", "alert", "snapshot", "restore",
          "live_drop", "router", "flightrec")


def default_sidecar_path(tag: str, directory: Optional[str] = None) -> str:
    """``TELEM_<tag>_<utc>.jsonl`` next to the BENCH_* artifacts (repo
    root by default) — the sidecar naming convention the report tool and
    the chip-window scripts glob for. (Multi-process runs additionally
    get a ``.p{process_index}`` suffix — applied by
    :class:`MetricsLogger` itself so explicit paths are covered too.)"""
    stamp = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
    base = directory or os.getcwd()
    return os.path.join(base, f"TELEM_{tag}_{stamp}.jsonl")


def process_identity(process_index: Optional[int] = None,
                     process_count: Optional[int] = None
                     ) -> "tuple[int, int]":
    """Resolve ``(process_index, process_count)`` for telemetry tagging.

    Priority: explicit arguments > an initialized multi-process jax
    runtime > the launcher environment (``RANK``/``WORLD_SIZE``, which
    ``parallel.launch.multiproc`` exports to every child) > ``(0, 1)``.
    Never forces a backend init: jax is consulted only when its
    backends already exist."""
    if process_index is not None or process_count is not None:
        return int(process_index or 0), int(process_count or 1)
    try:
        from jax._src import xla_bridge as _xb
        if _xb.backends_are_initialized():
            import jax
            if jax.process_count() > 1:
                return int(jax.process_index()), int(jax.process_count())
    except Exception:
        pass
    try:
        pc = int(os.environ.get("WORLD_SIZE", 1))
        pi = int(os.environ.get("RANK", 0))
    except ValueError:
        return 0, 1
    return (pi, pc) if pc > 1 else (0, 1)


def per_process_path(path: str, process_index: int) -> str:
    """``TELEM_run.jsonl`` -> ``TELEM_run.p3.jsonl``: the per-process
    sidecar naming under multiproc. Every process of a fleet writing the
    SAME path (the pre-v3 default) silently interleaved/clobbered N
    runs' records into one file; the suffix keeps them apart and is what
    ``telemetry_report.py --fleet`` pairs on. Idempotent for paths that
    already carry the suffix."""
    root, ext = os.path.splitext(path)
    tag = f".p{int(process_index)}"
    if root.endswith(tag) or f"{tag}." in os.path.basename(path):
        return path
    return root + tag + ext


def validate_record(rec: Any) -> None:
    """Raise ``ValueError`` unless ``rec`` is a well-formed telemetry
    record of this schema version (the parse contract the smoke test and
    the report tool both enforce)."""
    if not isinstance(rec, dict):
        raise ValueError(f"record is not an object: {rec!r}")
    v = rec.get("v")
    if v not in SUPPORTED_VERSIONS:
        raise ValueError(f"schema version {v!r} not in "
                         f"{SUPPORTED_VERSIONS}")
    kind = rec.get("kind")
    if kind not in _KINDS:
        raise ValueError(f"unknown record kind {kind!r}")
    if not isinstance(rec.get("t"), (int, float)):
        raise ValueError(f"record missing numeric 't': {rec!r}")


def read_sidecar(path: str) -> list[dict]:
    """Parse + validate a telemetry sidecar; raises on any malformed
    line. Returns the record list (header first)."""
    out = []
    with open(path) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                raise ValueError(f"{path}:{i + 1}: not JSON: {e}")
            validate_record(rec)
            out.append(rec)
    if not out:
        raise ValueError(f"{path}: empty sidecar")
    if out[0]["kind"] != "header":
        raise ValueError(f"{path}: first record is {out[0]['kind']!r}, "
                        f"expected 'header'")
    return out


# Framework-internal announcement channel: subsystems with no logger
# reference (parallel.mesh, …) drop notes here; any active MetricsLogger
# drains them into ``event`` records at its next flush. Bounded — with
# no logger running, old notes fall off instead of leaking.
_PENDING_NOTES: deque = deque(maxlen=256)


def note(name: str, **fields) -> None:
    """Record a framework event for whichever telemetry logger flushes
    next (no-op cost when telemetry is off: one deque append)."""
    _PENDING_NOTES.append((time.time(), "event", name, fields))


def note_kind(kind: str, name: Optional[str] = None, **fields) -> None:
    """Like :func:`note` but with an explicit record kind — the channel
    the legacy FP16_Optimizer / fp16_utils scalers use to emit
    ``amp_overflow`` records identical to the amp path's
    (:meth:`MetricsLogger.log_overflow`) without holding a logger
    reference."""
    if kind not in _KINDS:
        raise ValueError(f"unknown record kind {kind!r}")
    _PENDING_NOTES.append((time.time(), kind, name, fields))


def tracked_bytes_per_device(tree) -> int:
    """PER-DEVICE bytes of a pytree of (possibly sharded) arrays:
    replicated leaves count full size, sharded leaves count their
    ``sharding.shard_shape``. Pure metadata — no host sync."""
    import jax
    import numpy as np
    total = 0
    for x in jax.tree_util.tree_leaves(tree):
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is None or dtype is None:
            continue
        shape = tuple(shape)
        sh = getattr(x, "sharding", None)
        if sh is not None:
            try:
                shape = tuple(sh.shard_shape(shape))
            except Exception:
                pass
        total += (int(np.prod(shape, dtype=np.int64)) if shape else 1) \
            * np.dtype(dtype).itemsize
    return total


def _to_python(x):
    """Host-fetch a possibly-device scalar. This is THE sync point —
    called only inside flush()."""
    if x is None or isinstance(x, (bool, int, float, str)):
        return x
    try:
        return float(x)
    except Exception:
        return str(x)


def _sanitize(v):
    """Make any buffered field JSON-ready: plain types pass through,
    containers recurse, everything else (device arrays held by
    reference) is fetched."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if isinstance(v, (list, tuple)):
        return [_sanitize(i) for i in v]
    if isinstance(v, dict):
        return {k: _sanitize(x) for k, x in v.items()}
    return _to_python(v)


class CompileTracker:
    """Count tracing/compile activity via ``jax.monitoring`` listeners.

    jax emits ``/jax/core/compile/*_duration`` events on every jaxpr
    trace / MLIR lowering / backend compile. One tracker registers ONE
    pair of listeners process-wide (jax 0.4.x has no per-listener
    unregister, only ``clear_event_listeners``), and deactivated
    trackers drop out by flag — so repeated MetricsLogger lifecycles
    don't stack dead callbacks doing work.
    """

    _installed: "CompileTracker | None" = None
    _lock = threading.Lock()

    def __init__(self):
        self.active = True
        self.counts: dict[str, int] = {}
        self.durations_s: dict[str, float] = {}
        self._mu = threading.Lock()

    # -- listener bodies (must be cheap: they run on the compile path) --
    def _on_event(self, event: str, **kw) -> None:
        if not self.active:
            return
        with self._mu:
            self.counts[event] = self.counts.get(event, 0) + 1

    def _on_duration(self, event: str, duration_s: float, **kw) -> None:
        if not self.active:
            return
        with self._mu:
            self.counts[event] = self.counts.get(event, 0) + 1
            self.durations_s[event] = (
                self.durations_s.get(event, 0.0) + duration_s)

    def snapshot(self) -> dict:
        with self._mu:
            counts = dict(self.counts)
            durs = {k: round(v, 4) for k, v in self.durations_s.items()}
        short = {k.rsplit("/", 1)[-1]: v for k, v in counts.items()}
        return {
            "backend_compiles": short.get("backend_compile_duration", 0),
            "jaxpr_traces": short.get("jaxpr_trace_duration", 0),
            "counts": counts,
            "durations_s": durs,
        }

    def stop(self) -> None:
        self.active = False

    @classmethod
    def install(cls) -> "CompileTracker | None":
        """Register a fresh tracker (deactivating any previous one)."""
        import jax.monitoring as _m
        with cls._lock:
            if cls._installed is not None:
                cls._installed.stop()
            t = cls()
            _m.register_event_listener(t._on_event)
            _m.register_event_duration_secs_listener(t._on_duration)
            cls._installed = t
        return t


class MetricsLogger:
    """Schema-versioned JSONL telemetry writer.

    ::

        logger = MetricsLogger("TELEM_run.jsonl", run="bench",
                               meta={"batch": 384})
        for step in range(n):
            ... train ...
            logger.log_step(step, step_ms=dt * 1e3, throughput=img_s,
                            unit="img/s", loss=loss,        # device ok
                            loss_scale=amp_state[0].scale)  # device ok
        logger.log_amp(handle.scalers[0], amp_state[0])
        logger.close()

    ``loss``/``loss_scale``/counter arguments may be device arrays; they
    are fetched at flush boundaries only (one host sync per
    ``flush_every`` steps), never on the step path.
    """

    def __init__(self, path: str, *, run: str = "train",
                 meta: Optional[dict] = None, flush_every: int = 50,
                 track_compiles: bool = True, tail_len: int = 32,
                 process_index: Optional[int] = None,
                 process_count: Optional[int] = None):
        self.process_index, self.process_count = process_identity(
            process_index, process_count)
        if self.process_count > 1:
            # multiproc: every process handed the same (default or
            # explicit) path must not clobber its peers' sidecars
            path = per_process_path(path, self.process_index)
        self.path = path
        self.run = run
        self.flush_every = max(int(flush_every), 1)
        self._buf: list[dict] = []
        self._tees: list[Callable] = []
        self._mu = threading.RLock()
        self._tail: deque = deque(maxlen=tail_len)  # for stall snapshots
        self._closed = False
        self._steps_since_flush = 0
        self._last_compile_snapshot: dict = {}
        self._recompile_sigs: dict[str, list] = {}
        self.compile_tracker = (CompileTracker.install()
                                if track_compiles else None)
        # truncate: one sidecar = one run (header first, close last) —
        # a reused fixed path must not interleave two runs' records
        self._fh = open(path, "w")
        header = {"schema": f"{SCHEMA_NAME}/{SCHEMA_VERSION}",
                  "run": run, "pid": os.getpid(),
                  # v3 fleet tags: which process of how many wrote this
                  # sidecar — what prof.fleet pairs/aligns on
                  "process_index": self.process_index,
                  "process_count": self.process_count}
        try:  # backend identity is best-effort: no backend init forced
            import jax
            from jax._src import xla_bridge as _xb
            if _xb.backends_are_initialized():
                header["backend"] = jax.default_backend()
                header["devices"] = len(jax.devices())
        except Exception:
            pass
        if meta:
            header["meta"] = meta
        self._emit("header", header)
        self.flush()

    # -- record plumbing ---------------------------------------------------
    def add_tee(self, fn: Callable) -> None:
        """Register a per-record tee (v7: how a ``prof.live.
        LiveEmitter`` rides the logger). The callback sees every
        buffered record dict AS BUFFERED — device scalars still held by
        reference — and runs on the emitting (possibly step) path, so
        it must be O(1) and non-blocking: filter, enqueue, return. A
        raising tee is dropped rather than allowed to cost the run its
        sidecar."""
        self._tees.append(fn)

    def _emit(self, kind: str, fields: dict) -> None:
        with self._mu:
            if self._closed:
                return
            rec = {"v": SCHEMA_VERSION, "kind": kind,
                   "t": round(time.time(), 3)}
            rec.update(fields)
            self._buf.append(rec)
        for fn in tuple(self._tees):
            try:
                fn(rec)
            except Exception:
                try:
                    self._tees.remove(fn)
                except ValueError:
                    pass

    # -- per-step ----------------------------------------------------------
    def log_step(self, step: int, *, step_ms=None, throughput=None,
                 unit: Optional[str] = None, loss=None, loss_scale=None,
                 input_wait_ms=None, steps: int = 1, **extra) -> None:
        """Buffer one step (or interval: ``steps`` > 1 for a fori-loop
        dispatch of N fused steps) record. Scalar args may be device
        arrays — deferred to flush.

        ``input_wait_ms`` is the host-input-pipeline stall accounted to
        this step (``DevicePrefetcher.last_input_wait_ms``); for an
        interval record it is the PER-STEP mean, same basis as
        ``step_ms``, so ``input_wait_ms / step_ms`` is the input-bound
        fraction the report derives."""
        fields = {"step": int(step)}
        if steps != 1:
            fields["steps"] = int(steps)
        if step_ms is not None:
            fields["step_ms"] = step_ms
        if throughput is not None:
            fields["throughput"] = throughput
        if unit is not None:
            fields["unit"] = unit
        if loss is not None:
            fields["loss"] = loss
        if loss_scale is not None:
            fields["loss_scale"] = loss_scale
        if input_wait_ms is not None:
            fields["input_wait_ms"] = input_wait_ms
        fields.update(extra)
        self._emit("step", fields)
        with self._mu:
            self._steps_since_flush += 1
            if self._steps_since_flush >= self.flush_every:
                self.flush()

    def event(self, name: str, **fields) -> None:
        """Buffer a free-form event record (phase transitions, errors)."""
        self._emit("event", dict(fields, name=name))

    # -- AMP / scaler ------------------------------------------------------
    def log_amp(self, scaler, state, loss_id: int = 0) -> None:
        """Record a :class:`~apex_tpu.amp.scaler.ScalerState`'s event
        counters (overflow/skip/growth — device i32s held by reference,
        fetched at the next flush; no host sync here). Call at flush
        boundaries, not per step."""
        import dataclasses as _dc
        fields = {f.name: getattr(state, f.name)
                  for f in _dc.fields(state)}
        fields["loss_scale"] = fields.pop("scale", None)
        fields = {k: v for k, v in fields.items() if v is not None}
        self._emit("amp", {"loss_id": loss_id,
                           "dynamic": bool(getattr(scaler, "dynamic",
                                                   True)), **fields})

    # -- numerics (prof.numerics, schema 2) --------------------------------
    def log_overflow(self, meta, census, *, loss_id: int = 0,
                     loss_scale=None, source: str = "amp",
                     **extra) -> None:
        """Emit an ``amp_overflow`` record naming the parameters whose
        gradients went nonfinite: ``meta`` is the
        :func:`~apex_tpu.prof.numerics.tree_meta` of the grads pytree,
        ``census`` a (carried) :class:`~apex_tpu.prof.numerics.GradCensus`.

        This is the ONE host sync of the provenance path — call it only
        when a skip actually happened (``overflow_count`` moved), never
        per step."""
        from apex_tpu.prof import numerics as _n
        fields = {"loss_id": loss_id, "source": source,
                  "culprits": _n.culprit_table(meta, census)}
        step = int(census.step)
        if step >= 0:
            fields["step"] = step
        if loss_scale is not None:
            fields["loss_scale"] = loss_scale   # device ref ok (flush)
        fields.update(extra)
        self._emit("amp_overflow", fields)

    def log_numerics(self, meta, census, *, step=None, **extra) -> None:
        """Emit a ``numerics``/underflow record from an
        :class:`~apex_tpu.prof.numerics.UnderflowCensus` (host fetch
        here — call at the sampling cadence, not per step)."""
        from apex_tpu.prof import numerics as _n
        fields = {"what": "underflow",
                  **_n.underflow_summary(meta, census)}
        if step is not None:
            fields["step"] = int(step)
        fields.update(extra)
        self._emit("numerics", fields)

    def log_coverage(self, report, label: str = "step", **extra) -> None:
        """Emit a ``numerics``/coverage record from a
        :class:`~apex_tpu.prof.coverage.CoverageReport`."""
        self._emit("numerics", {"what": "coverage", "fn": label,
                                **report.summary_dict(), **extra})

    # -- fleet (prof.fleet, schema 3) --------------------------------------
    def log_fleet_skew(self, **fields) -> None:
        """Emit a ``fleet_skew`` record (the in-run straggler probe's
        all-gathered per-process step-duration EMAs + the slowest
        process and its lag). Called by
        :class:`~apex_tpu.prof.fleet.FleetProbe` at its own cadence —
        never per step."""
        self._emit("fleet_skew", fields)

    def log_desync(self, **fields) -> None:
        """Emit a ``desync`` record (cross-process parameter-fingerprint
        / loss-scale / step-counter disagreement, naming the divergent
        process and the first divergent pytree path). Called by
        :class:`~apex_tpu.prof.fleet.DesyncProbe` only when a check
        actually disagreed."""
        self._emit("desync", fields)
        self.flush()   # a desync is an incident: persist it immediately

    # -- serving (apex_tpu.serve, schema 4) --------------------------------
    def log_serving(self, **fields) -> None:
        """Emit a ``serving`` record — the request-level latency
        aggregates of ONE finished serving run (the
        ``apex_tpu.serve.traffic.summarize_serving`` payload: mode,
        completed/dropped counts, TTFT and normalized token-latency
        percentiles, inter-token percentiles, tokens/s, slot occupancy,
        queue depth). Written once per run, never per step — the
        per-step decode cadence rides ordinary ``step`` records."""
        self._emit("serving", fields)
        self.flush()   # the run's headline: persist before any crash

    # -- spans / alerts (prof.spans / prof.slo, schema 5) ------------------
    def log_spans(self, tracer_or_records) -> int:
        """Emit ``span`` records — accepts a
        :class:`~apex_tpu.prof.spans.SpanTracer` (its completed ring)
        or an iterable of already-built span field dicts. Each record
        keeps the span's own wall-clock ``t`` (tracer epoch + offset)
        so the sidecar's phase timeline sorts against its step records.
        Call once per run/phase boundary, never per span."""
        recs = (tracer_or_records.records()
                if hasattr(tracer_or_records, "records")
                else list(tracer_or_records))
        for fields in recs:
            self._emit("span", dict(fields))
        if recs:
            self.flush()
        return len(recs)

    def log_alert(self, **fields) -> None:
        """Emit an ``alert`` record — an in-run SLO violation
        (``prof.slo.SLOMonitor``: rule name, window, measured vs
        threshold) or a watchdog stall (``rule: "stall"``). An alert is
        an incident: flushed immediately, same policy as ``desync``."""
        self._emit("alert", fields)
        self.flush()

    # -- runtime recovery (apex_tpu.runtime, schema 6) ---------------------
    def log_snapshot(self, **fields) -> None:
        """Emit a ``snapshot`` record — one committed async snapshot
        generation (``runtime.SnapshotWriter``: generation, step,
        payload bytes, async write latency, path). Written by the
        background writer thread when the commit marker lands — never
        on the step path."""
        self._emit("snapshot", fields)

    def log_restore(self, **fields) -> None:
        """Emit a ``restore`` record — one restore-from-last-good
        (``runtime.Supervisor`` on an alert/desync trigger, or the
        startup resume path after a preemption): generation, restored
        step, trigger ``reason``/``rule``, ``steps_lost``. A restore is
        an incident: flushed immediately, same policy as ``desync``."""
        self._emit("restore", fields)
        self.flush()

    # -- live telemetry plane (prof.live, schema 7) ------------------------
    def log_live_drop(self, **fields) -> None:
        """Emit a ``live_drop`` record — one process's live-stream drop
        accounting (``process``, ``drops``, ``sent``, ``endpoint``).
        Written once at ``LiveEmitter.close()`` (and per replica by the
        collector's final flush) — a zero is evidence of a clean steady
        state, a nonzero says exactly how much of the live view was
        shed to protect the step path."""
        self._emit("live_drop", fields)

    # -- router tier (serve.router, schema 8) ------------------------------
    def log_router(self, **fields) -> None:
        """Emit a ``router`` record — one routing run's decision
        ledger (``serve.router.Router.summary``: policy, per-replica
        routed/completed/shed/redirected counts, shed attribution by
        rule + replica, scale events, routed balance). Written once
        per run, never per request; flushed immediately — it is the
        run's admission headline, same policy as ``serving``."""
        self._emit("router", fields)
        self.flush()

    # -- flight recorder (prof.flightrec, schema 11) -----------------------
    def log_flightrec(self, **fields) -> None:
        """Emit a ``flightrec`` record — one flight-recorder dump
        announcement (``prof.flightrec.FlightRecorder.dump``: the
        triggering alert's rule/scope, the dump ``path``, counts of
        buffered records/spans/open-span snapshots, the ring's window
        seconds). The dump itself is a separate JSON artifact; this
        record is how a sidecar reader discovers it. A dump is an
        incident: flushed immediately, same policy as ``alert``."""
        self._emit("flightrec", fields)
        self.flush()

    # -- compile -----------------------------------------------------------
    def log_compiles(self) -> None:
        """Emit the cumulative compile-counter snapshot (delta vs the
        previous snapshot included, so intervals are attributable)."""
        if self.compile_tracker is None:
            return
        snap = self.compile_tracker.snapshot()
        prev = self._last_compile_snapshot
        delta = snap["backend_compiles"] - prev.get("backend_compiles", 0)
        self._last_compile_snapshot = snap
        self._emit("compile", {**snap, "backend_compiles_delta": delta})

    def track_recompiles(self, fn: Callable, name: str) -> Callable:
        """Wrap a (jitted) callable so a post-first-call change in its
        argument avals — the classic silent-recompile trigger — emits a
        ``recompile`` record naming the offending avals.

        The signature probe is shapes/dtypes only (no host sync); use on
        step functions, not hot inner lambdas."""
        import jax

        def _sig(args, kwargs):
            leaves = jax.tree_util.tree_leaves((args, kwargs))
            return tuple(
                (tuple(x.shape) if hasattr(x, "shape") else None,
                 str(getattr(x, "dtype", type(x).__name__)))
                for x in leaves)

        def wrapped(*args, **kwargs):
            sig = _sig(args, kwargs)
            seen = self._recompile_sigs.setdefault(name, [])
            if sig not in seen:
                seen.append(sig)
                if len(seen) > 1:
                    self._emit("recompile", {
                        "fn": name,
                        "n_signatures": len(seen),
                        "avals": [list(s) for s in sig],
                    })
            return fn(*args, **kwargs)

        wrapped.__name__ = f"telemetry[{name}]"
        return wrapped

    # -- memory ------------------------------------------------------------
    def log_memory(self) -> None:
        """Sample ``device.memory_stats()`` per addressable device (HBM
        watermarks on TPU; CPU devices report none — recorded as
        unavailable rather than dropped, so the sidecar says *why* the
        column is empty)."""
        try:
            import jax
            from jax._src import xla_bridge as _xb
            if not _xb.backends_are_initialized():
                return
            devices = jax.local_devices()
        except Exception:
            return
        for d in devices:
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if not stats:
                self._emit("memory", {"device": str(d.id),
                                      "available": False})
                continue
            keep = {k: stats[k] for k in
                    ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                     "largest_alloc_size", "num_allocs") if k in stats}
            self._emit("memory", {"device": str(d.id), "available": True,
                                  **keep})

    def log_state_bytes(self, *, params=None, opt_state=None,
                        label: Optional[str] = None, **extra) -> None:
        """Emit a ``memory`` record with the PER-DEVICE bytes of the
        run's persistent state, derived from each array's sharding
        (``sharding.shard_shape``): a replicated buffer counts its full
        size on every device, a ZeRO-sharded flat buffer counts 1/n.

        This is the platform-independent half of the HBM story: CPU
        devices report no ``memory_stats()`` watermarks, but the
        tracked state bytes prove the same per-device footprint delta —
        ``telemetry_report.py --compare`` derives its
        ``params+opt_state bytes/device`` row from this record. No host
        sync: shapes/dtypes/shardings are metadata."""
        fields: dict = {"tracked": True}
        if label is not None:
            fields["label"] = label
        total = 0
        for name, tree in (("params", params), ("opt_state", opt_state)):
            if tree is not None:
                b = tracked_bytes_per_device(tree)
                fields[f"{name}_bytes_per_device"] = b
                total += b
        fields["state_bytes_per_device"] = total
        try:
            import jax
            from jax._src import xla_bridge as _xb
            if _xb.backends_are_initialized():
                fields["devices"] = len(jax.devices())
        except Exception:
            pass
        fields.update(extra)
        self._emit("memory", fields)

    # -- collectives -------------------------------------------------------
    def log_collectives(self) -> None:
        """Snapshot the trace-time collective-bytes tally
        (:func:`apex_tpu.parallel.collectives.collective_bytes`) — bytes
        are per *traced program*, i.e. per-step cost of the compiled
        step, not a runtime counter. Lazy import: prof must not pull the
        parallel stack at import."""
        try:
            from apex_tpu.parallel import collectives as _c
        except Exception:
            return
        snap = dict(_c.collective_bytes())
        try:  # r10: host-measured dispatch+fetch latency histogram
            lat = _c.collective_latency()
        except Exception:
            lat = {}
        if lat:
            snap["latency"] = lat
        if snap:
            self._emit("collectives", snap)

    # -- stall (called by prof.watchdog) -----------------------------------
    def log_stall(self, snapshot: dict) -> None:
        self._emit("stall", snapshot)
        self.flush()

    def tail(self, n: int = 10) -> list[dict]:
        """Last ``n`` already-written records (the watchdog's 'what was
        the run doing' snapshot source)."""
        with self._mu:
            return list(self._tail)[-n:]

    # -- flush / close -----------------------------------------------------
    def flush(self) -> None:
        """THE host-sync boundary: fetch buffered device scalars, write
        JSONL, sample nothing (memory/collectives are explicit calls so
        the caller controls when device queries happen)."""
        # drain framework notes (mesh topology, legacy-path overflow
        # provenance, ...) into records of their declared kind
        while _PENDING_NOTES:
            try:
                t, kind, name, fields = _PENDING_NOTES.popleft()
            except IndexError:
                break
            with self._mu:
                if not self._closed:
                    rec = {"v": SCHEMA_VERSION, "kind": kind,
                           "t": round(t, 3)}
                    if name is not None:
                        rec["name"] = name
                    rec.update(fields)
                    self._buf.append(rec)
        with self._mu:
            if self._closed and not self._buf:
                return
            buf, self._buf = self._buf, []
            self._steps_since_flush = 0
        out_lines = []
        for rec in buf:
            rec = {k: _sanitize(v) for k, v in rec.items()}
            if rec.get("kind") == "amp":
                # device i32 counters came back as floats; normalize
                for k, v in rec.items():
                    if isinstance(v, float) and k.endswith(
                            ("_count", "unskipped")):
                        rec[k] = int(v)
            out_lines.append(json.dumps(rec))
            self._tail.append(rec)
        if out_lines:
            self._fh.write("\n".join(out_lines) + "\n")
            self._fh.flush()

    def close(self) -> None:
        """Final flush: compile totals, memory watermarks, collective
        bytes, then the ``close`` record."""
        with self._mu:
            if self._closed:
                return
        self.log_compiles()
        self.log_memory()
        self.log_collectives()
        self._emit("close", {"run": self.run})
        self.flush()
        with self._mu:
            self._closed = True
        if self.compile_tracker is not None:
            self.compile_tracker.stop()
        try:
            self._fh.close()
        except Exception:
            pass

    def __enter__(self) -> "MetricsLogger":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
