"""Fleet observability — the distributed layer of the telemetry stack.

rounds 7-9 made a SINGLE process attributable from its sidecar; a
multi-process run (the MULTICHIP bench, a pod job through
``parallel.launch``) left N unrelated ``TELEM_*.jsonl`` files and no way
to answer the questions that actually kill distributed runs (TorchTitan,
arXiv:2410.06511, treats fleet metrics + debuggability as a first-class
subsystem; veScale's SPMD consistency checking motivates the desync
probe):

- **which host is the straggler?** Every collective runs at the pace of
  the slowest participant, so one slow process taxes the whole fleet —
  and from any single sidecar the run just looks uniformly slow.
- **have the replicas silently diverged?** A data-parallel step is only
  correct while parameters/loss-scale/step counters agree across
  processes; divergence surfaces as unexplained loss drift long after
  the offending step.

Four pieces (schema 3, ``prof.metrics``):

- :func:`aggregate_fleet` / :func:`render_fleet` — post-hoc: step-align
  N per-process sidecars (headers carry ``process_index`` /
  ``process_count`` since v3) into per-step cross-process skew
  (p50/p95/max-min step time), a straggler ranking by cumulative excess
  over the fleet-min path, and per-process input-wait / skip-rate
  deltas. ``tools/telemetry_report.py --fleet *.jsonl`` is the CLI.
- :class:`FleetProbe` — in-run: every K observed steps, all-gather the
  per-process step-duration EMAs (one traced psum inside the
  ``apex_fleet_probe`` named scope) and emit a ``fleet_skew`` record
  naming the slowest process and its lag — skew is visible DURING the
  run, not only post-hoc.
- :class:`DesyncProbe` — periodic cross-process agreement check: a
  per-leaf abs-sum fingerprint of the parameter tree (path labels via
  :func:`prof.numerics.tree_meta`, flat-master buffers supported via
  their ``SegmentTable``) plus loss-scale / step-counter equality; a
  disagreement emits a ``desync`` record naming the divergent process
  and the FIRST divergent pytree path.
- collective latency attribution — the probes time their gathers into
  :func:`parallel.collectives.collective_latency` (histogram in the
  sidecar's ``collectives`` record), and ``prof.gaps`` classifies trace
  gaps at ``apex_collective_*`` / ``apex_fleet_probe`` seams as
  ``collective-bound``.

Overhead discipline: probes run at caller-chosen cadence (every K steps
/ print intervals), never inside a timed fori dispatch; the gather is
one scalar-vector psum; the first (compiling) gather is excluded from
the latency histogram. Measured on the CPU bench loop: within run noise
(<1%, docs/PERF.md).

Offline provability: the gathers ride a ``pmap`` psum over every
device once ``jax.distributed`` is initialized; on runtimes whose
backend refuses multiprocess computations (a CPU client with no
cross-process collectives), they feature-probe and
degrade to the jax.distributed coordination-service key-value store —
a real cross-process exchange with identical record output, so the
whole layer is provable with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` CPU multiproc
runs (``tools/fleet_smoke.py``; the committed
``TELEM_r10_fleet.p*.jsonl`` artifacts). Records carry which
``transport`` served them.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import numpy as np

from apex_tpu.prof.metrics import process_identity

__all__ = ["FleetProbe", "DesyncProbe", "aggregate_fleet",
           "render_fleet", "read_fleet"]


# ---------------------------------------------------------------------------
# The gather substrate
# ---------------------------------------------------------------------------

_GATHER_CACHE: dict = {}
# gather transport, resolved on first cross-process use: "psum" (the
# traced collective under the `apex_fleet_probe` scope) or "kv" (the
# jax.distributed coordination-service key-value store — the degrade
# path for backends whose runtime refuses multiprocess computations,
# e.g. a CPU client that raises "Multiprocess computations aren't
# implemented on the CPU backend"). Same records either way; the
# traced named scope only exists on the psum path.
_TRANSPORT: dict = {"mode": None}
_KV_GEN = {"n": 0}


def gather_transport() -> str:
    """Which cross-process transport the gathers resolved to
    ('psum' until proven otherwise)."""
    return _TRANSPORT["mode"] or "psum"


def _psum_allgather(vec: np.ndarray, process_index: int,
                    process_count: int) -> np.ndarray:
    """ONE traced psum over every device — each process's local devices
    contribute its vector one-hot at its own row (the row index rides
    as a traced argument so all processes compile the identical
    program). Assumes uniform local device counts (true for TPU pods
    and the CPU-simulated fleet)."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.parallel import collectives as C

    m = int(vec.shape[0])
    n_local = jax.local_device_count()
    pc = int(process_count)
    key = (m, pc, n_local)
    fn = _GATHER_CACHE.get(key)
    if fn is None:
        def f(v, pi):
            with jax.named_scope("apex_fleet_probe"):
                C.record_collective("psum", pc * m * 4, "fleet")
                z = jnp.zeros((pc, m), jnp.float32)
                z = z.at[pi].set(v)
                return jax.lax.psum(z, "fleet")
        fn = jax.pmap(f, axis_name="fleet")
        _GATHER_CACHE[key] = fn
    x = np.broadcast_to(vec, (n_local, m))
    pi = np.full((n_local,), int(process_index), np.int32)
    out = np.asarray(fn(x, pi)[0])
    return out / max(n_local, 1)   # each process contributed n_local rows


def _kv_allgather(vec: np.ndarray, process_index: int,
                  process_count: int,
                  timeout_ms: Optional[int] = None) -> np.ndarray:
    """Exchange vectors through the jax.distributed coordination
    service (the runtime every multi-process job already brings up):
    each process publishes its row under a per-call generation key and
    blocking-gets its peers'. Lockstep calls keep the generation
    counters aligned across processes.

    ``timeout_ms`` defaults to APEX_FLEET_GATHER_TIMEOUT_MS (env) or
    60 s. A timed-out get raises — under the r17 supervised runtime
    that exception IS the peer-loss signal: the survivor records the
    incident and exits so the fleet supervisor can relaunch+resume,
    instead of hanging a full collective timeout per probe."""
    import os as _os
    if timeout_ms is None:
        timeout_ms = int(_os.environ.get(
            "APEX_FLEET_GATHER_TIMEOUT_MS", 60_000))
    import json as _json
    from jax._src import distributed
    client = getattr(distributed.global_state, "client", None)
    if client is None:
        raise RuntimeError(
            "cross-process gather needs jax.distributed.initialize "
            "(parallel.launch.initialize) — no coordination client")
    gen = _KV_GEN["n"]
    _KV_GEN["n"] += 1
    base = f"apex_fleet/g{gen}"
    client.key_value_set(f"{base}/p{int(process_index)}",
                         _json.dumps([float(x) for x in vec]))
    rows = np.zeros((int(process_count), int(vec.shape[0])), np.float32)
    for p in range(int(process_count)):
        val = client.blocking_key_value_get(f"{base}/p{p}", timeout_ms)
        rows[p] = np.asarray(_json.loads(val), np.float32)
    return rows


def _allgather_rows(vec: Any, process_index: int,
                    process_count: int) -> np.ndarray:
    """All-gather a per-process f32 vector into a dense
    ``[process_count, m]`` host matrix (row i = process i's vector).
    Traced-psum first; coordination-service KV fallback when the
    backend's runtime cannot run multiprocess computations."""
    vec = np.asarray(vec, np.float32).reshape(-1)
    mode = _TRANSPORT["mode"]
    if mode != "kv":
        try:
            out = _psum_allgather(vec, process_index, process_count)
            _TRANSPORT["mode"] = "psum"
            return out
        except Exception:
            if mode == "psum" or int(process_count) <= 1:
                raise   # the psum path worked before (or there is no
                # fleet to fall back through): this is a real error
            _TRANSPORT["mode"] = "kv"
    return _kv_allgather(vec, process_index, process_count)


def _percentile(sorted_vals: Sequence[float], q: float) -> float:
    if not sorted_vals:
        return float("nan")
    idx = min(len(sorted_vals) - 1,
              max(0, round(q / 100.0 * (len(sorted_vals) - 1))))
    return float(sorted_vals[idx])


# ---------------------------------------------------------------------------
# In-run straggler probe
# ---------------------------------------------------------------------------

class FleetProbe:
    """Every ``every`` observed steps, all-gather the per-process
    step-duration EMAs and emit a ``fleet_skew`` record naming the
    slowest process and its lag over the fleet median.

    ::

        probe = FleetProbe(logger, every=10)
        for step in range(n):
            ... train ...
            logger.log_step(step, step_ms=dt_ms)
            probe.observe(step, dt_ms)     # gathers every 10th call

    All processes must call :meth:`observe` in lockstep (same count of
    calls) — the gather is a collective. Works degenerately at
    ``process_count == 1`` (a single-row gather), so single-process
    entry points can arm it unconditionally."""

    def __init__(self, logger=None, *, every: int = 10,
                 ema_alpha: float = 0.3,
                 process_index: Optional[int] = None,
                 process_count: Optional[int] = None):
        self.pi, self.pc = process_identity(process_index, process_count)
        self.logger = logger
        self.every = max(int(every), 1)
        self.alpha = float(ema_alpha)
        self.ema_ms: Optional[float] = None
        self.last_skew: Optional[dict] = None
        self._n = 0
        self._compiled = False

    def observe(self, step: int, step_ms: float) -> Optional[dict]:
        """Fold one step duration into the EMA; every ``every``-th call
        runs the gather and returns (and logs) the skew record."""
        step_ms = float(step_ms)
        self.ema_ms = (step_ms if self.ema_ms is None else
                       self.alpha * step_ms
                       + (1.0 - self.alpha) * self.ema_ms)
        self._n += 1
        if self._n % self.every:
            return None
        return self.probe(step)

    def probe(self, step: int) -> dict:
        """Run the gather now (outside any timed region)."""
        import contextlib
        from apex_tpu.parallel import collectives as C
        # the first gather compiles (or resolves the transport); keep
        # it out of the latency histogram
        timer = (C.time_collective(
                     f"fleet_probe_{gather_transport()}[fleet]",
                     4 * self.pc)
                 if self._compiled else contextlib.nullcontext())
        with timer:
            rows = _allgather_rows([self.ema_ms or 0.0], self.pi, self.pc)
        self._compiled = True
        emas = [float(r[0]) for r in rows]
        slowest = max(range(self.pc), key=lambda i: emas[i])
        med = _percentile(sorted(emas), 50)
        lag = emas[slowest] - med
        rec = {"step": int(step), "every": self.every,
               "ema_ms": [round(e, 3) for e in emas],
               "slowest": int(slowest),
               "lag_ms": round(lag, 3),
               "lag_frac": round(lag / max(med, 1e-9), 4),
               "transport": gather_transport()}
        self.last_skew = rec
        if self.logger is not None:
            self.logger.log_fleet_skew(**rec)
        return rec


# ---------------------------------------------------------------------------
# Desync detection
# ---------------------------------------------------------------------------

class DesyncProbe:
    """Periodic cross-process replica-agreement check.

    ``template`` is the parameter pytree (or a
    :class:`~apex_tpu.ops.flat.SegmentTable` for flat-master buffers);
    its path labels (``prof.numerics.tree_meta``) name the divergent
    leaf. :meth:`check` computes a per-leaf abs-sum fingerprint ON
    DEVICE (one jitted pass under the ``apex_desync_fingerprint``
    scope), appends the loss-scale / step-counter scalars, all-gathers
    the vectors, and compares every process's row against the
    element-wise fleet MEDIAN (so with >= 3 processes the minority
    diverger is named; with 2, both candidates are). Agreement costs no
    record; a disagreement emits ``desync`` and returns it.

    Tolerances default to EXACT equality: replicas computing the same
    program on the same data produce bitwise-identical fingerprints, so
    any difference is real divergence. Pass ``rtol``/``atol`` for
    substrates with nondeterministic reduction orders."""

    def __init__(self, template, logger=None, *, rtol: float = 0.0,
                 atol: float = 0.0,
                 process_index: Optional[int] = None,
                 process_count: Optional[int] = None):
        from apex_tpu.prof import numerics as _n
        from apex_tpu.ops.flat import SegmentTable
        self.meta = _n.tree_meta(template)
        self.table = template if isinstance(template, SegmentTable) \
            else None
        self.logger = logger
        self.rtol, self.atol = float(rtol), float(atol)
        self.pi, self.pc = process_identity(process_index, process_count)
        self.checks = 0
        self._fp = None

    def _fingerprint(self, params) -> np.ndarray:
        import jax
        import jax.numpy as jnp
        from apex_tpu.prof import numerics as _n
        if self._fp is None:
            table = self.table

            def fp(tree):
                with jax.named_scope("apex_desync_fingerprint"):
                    return jnp.stack(
                        [jnp.sum(jnp.abs(g.astype(jnp.float32)))
                         for g in _n._leaves(tree, table)])
            self._fp = jax.jit(fp)
        return np.asarray(self._fp(params), np.float32)

    def check(self, params, *, loss_scale=None, step_count=None,
              step: Optional[int] = None) -> Optional[dict]:
        """Collective: ALL processes must call in lockstep. Returns the
        desync record when the fleet disagrees, else None."""
        from apex_tpu.parallel import collectives as C
        fp = self._fingerprint(params)
        vec = np.concatenate([
            fp, np.asarray([0.0 if loss_scale is None else
                            float(loss_scale),
                            0.0 if step_count is None else
                            float(step_count)], np.float32)])
        timer_ok = self.checks > 0   # first gather compiles
        import contextlib
        timer = (C.time_collective(
                     f"desync_{gather_transport()}[fleet]",
                     4 * vec.size * self.pc)
                 if timer_ok else contextlib.nullcontext())
        with timer:
            rows = _allgather_rows(vec, self.pi, self.pc)
        self.checks += 1
        ref = np.median(rows, axis=0)
        tol = self.atol + self.rtol * np.abs(ref)
        bad = np.abs(rows - ref) > tol          # [pc, n_leaves + 2]
        if not bad.any():
            return None
        n = self.meta.n
        divergent = sorted({int(p) for p, _ in zip(*np.nonzero(bad))})
        # the first divergent LEAF (parameter divergence names a path;
        # a scalar-only disagreement still records which scalar)
        leaf_bad = np.nonzero(bad[:, :n])
        rec: dict = {
            "processes": divergent,
            "n_divergent_paths": int(len({int(j) for j
                                          in leaf_bad[1]})),
            "checked_paths": n,
            "loss_scale_ok": not bool(bad[:, n].any()),
            "step_count_ok": not bool(bad[:, n + 1].any()),
            "transport": gather_transport(),
        }
        if step is not None:
            rec["step"] = int(step)
        if leaf_bad[0].size:
            p0, j0 = int(leaf_bad[0][0]), int(leaf_bad[1][0])
            rec["path"] = self.meta.paths[j0]
            rec["value"] = round(float(rows[p0, j0]), 6)
            rec["ref"] = round(float(ref[j0]), 6)
        if self.logger is not None:
            self.logger.log_desync(**rec)
        return rec


# ---------------------------------------------------------------------------
# Post-hoc fleet aggregation (the read side of N sidecars)
# ---------------------------------------------------------------------------

def read_fleet(paths: Sequence[str]) -> dict:
    """Parse + aggregate per-process sidecars in one call."""
    from apex_tpu.prof import metrics as _m
    return aggregate_fleet([_m.read_sidecar(p) for p in paths],
                           names=list(paths))


def _process_digest(records: list[dict]) -> dict:
    """Per-process per-step table + summary scalars (the half of
    telemetry_report.summarize the fleet view needs, kept here so the
    library has no tools/ dependency)."""
    steps: dict[int, float] = {}
    wait_shares: list[float] = []
    for r in records:
        if r["kind"] != "step":
            continue
        if r.get("step_ms") is not None and r.get("step") is not None:
            steps[int(r["step"])] = float(r["step_ms"])
        if r.get("input_wait_ms") is not None and \
                r.get("step_ms") is not None:
            wait_shares.append(float(r["input_wait_ms"])
                               / max(float(r["step_ms"]), 1e-9))
    amps = [r for r in records if r["kind"] == "amp"]
    skip_rate = None
    if amps:
        last = amps[-1]
        sc, ov = last.get("step_count"), last.get("overflow_count")
        if sc and ov is not None:
            skip_rate = float(ov) / float(sc)
    colls = [r for r in records if r["kind"] == "collectives"]
    return {
        "steps": steps,
        "step_ms_sorted": sorted(steps.values()),
        "skip_rate": skip_rate,
        "input_wait_share": (sum(wait_shares) / len(wait_shares)
                             if wait_shares else None),
        "stalls": sum(1 for r in records if r["kind"] == "stall"),
        "collectives": colls[-1] if colls else None,
        "fleet_skew": [r for r in records if r["kind"] == "fleet_skew"],
        "desync": [r for r in records if r["kind"] == "desync"],
        "serving": [r for r in records if r["kind"] == "serving"],
        "live_drops": sum(int(r.get("drops") or 0) for r in records
                          if r["kind"] == "live_drop"),
        "restore": [r for r in records if r["kind"] == "restore"],
        "snapshots": sum(1 for r in records
                         if r["kind"] == "snapshot"),
        "incident_alerts": [r for r in records if r["kind"] == "alert"
                            and r.get("rule") in ("peer_lost",
                                                  "stall")],
        "closed": bool(records) and records[-1]["kind"] == "close",
    }


def aggregate_fleet(record_lists: Sequence[list], *,
                    names: Optional[Sequence[str]] = None) -> dict:
    """Step-align N per-process sidecars into the fleet summary dict
    that :func:`render_fleet` renders. Pure function over validated
    record lists (``metrics.read_sidecar`` output) — unit-testable
    without files.

    Refuses sidecars whose headers carry no process tags (schema < 3)
    or duplicate ``process_index`` values: silently merging untagged
    files is exactly the mis-pairing this layer exists to prevent."""
    if not record_lists:
        raise ValueError("no sidecars given")
    names = list(names or [f"<sidecar {i}>"
                           for i in range(len(record_lists))])
    # r19: a ROUTER sidecar (the routing tier's driver — carries
    # ``router`` records) is not a replica: pull it aside before the
    # process-index checks, keep its last router record to join the
    # SERVING table on (per_replica["replica"] == process index)
    router_rec = None
    replica_lists, replica_names = [], []
    for name, recs in zip(names, record_lists):
        routers = [r for r in recs if r.get("kind") == "router"]
        if routers:
            router_rec = routers[-1]
        else:
            replica_lists.append(recs)
            replica_names.append(name)
    if router_rec is not None:
        record_lists, names = replica_lists, replica_names
        if not record_lists:
            raise ValueError(
                "only a router sidecar was given — the fleet view "
                "needs the replica sidecars too")
    procs: dict[int, dict] = {}
    pcs = set()
    for name, recs in zip(names, record_lists):
        hdr = recs[0]
        pi, pc = hdr.get("process_index"), hdr.get("process_count")
        if pi is None or pc is None:
            raise ValueError(
                f"{name}: header carries no process_index/process_count "
                f"(schema {hdr.get('schema')}) — fleet aggregation "
                f"needs v3 per-process sidecars")
        if pi in procs:
            raise ValueError(f"{name}: duplicate process_index {pi} "
                             f"(already seen in {procs[pi]['name']})")
        pcs.add(int(pc))
        procs[int(pi)] = {"name": name, "run": hdr.get("run"),
                          **_process_digest(recs)}
    if len(pcs) > 1:
        raise ValueError(f"sidecars disagree on process_count: "
                         f"{sorted(pcs)} — they are not one fleet")
    pc = pcs.pop()
    pis = sorted(procs)

    # -- step alignment + skew + straggler ranking ----------------------
    aligned = sorted(set.intersection(
        *[set(procs[pi]["steps"]) for pi in pis])) if pis else []
    spreads: list[float] = []
    excess = {pi: 0.0 for pi in pis}
    base_ms = 0.0
    worst = None
    for s in aligned:
        vals = {pi: procs[pi]["steps"][s] for pi in pis}
        lo = min(vals.values())
        base_ms += lo
        spread = max(vals.values()) - lo
        spreads.append(spread)
        if worst is None or spread > worst["spread_ms"]:
            worst = {"step": s, "spread_ms": round(spread, 3),
                     "slowest": max(vals, key=vals.get)}
        for pi in pis:
            excess[pi] += vals[pi] - lo
    spreads.sort()

    def med(vals):
        vals = sorted(v for v in vals if v is not None)
        return _percentile(vals, 50) if vals else None

    skip_med = med([procs[pi]["skip_rate"] for pi in pis])
    wait_med = med([procs[pi]["input_wait_share"] for pi in pis])
    per_process = []
    for pi in pis:
        d = procs[pi]
        row = {"process": pi, "sidecar": d["name"],
               "step_records": len(d["steps"]),
               "step_ms_p50": (round(_percentile(
                   d["step_ms_sorted"], 50), 3)
                   if d["step_ms_sorted"] else None),
               "excess_ms": round(excess[pi], 3),
               "excess_pct": (round(100.0 * excess[pi]
                                    / max(base_ms, 1e-9), 2)
                              if aligned else None),
               "skip_rate": d["skip_rate"],
               "input_wait_share": d["input_wait_share"],
               "stalls": d["stalls"],
               "closed": d["closed"]}
        if d["skip_rate"] is not None and skip_med is not None:
            row["skip_rate_delta"] = round(d["skip_rate"] - skip_med, 5)
        if d["input_wait_share"] is not None and wait_med is not None:
            row["input_wait_share_delta"] = round(
                d["input_wait_share"] - wait_med, 4)
        per_process.append(row)

    straggler = None
    if aligned:
        worst_pi = max(pis, key=lambda p: excess[p])
        straggler = {"process": worst_pi,
                     "excess_ms": round(excess[worst_pi], 3),
                     "excess_pct": round(100.0 * excess[worst_pi]
                                         / max(base_ms, 1e-9), 2)}

    # -- in-run probe records (dedup: every process logs the same view;
    # keep the lowest-index process's copies) ---------------------------
    skew_recs: list[dict] = []
    seen_steps: set = set()
    for pi in pis:
        for r in procs[pi]["fleet_skew"]:
            key = r.get("step")
            if key in seen_steps:
                continue
            seen_steps.add(key)
            skew_recs.append(r)
    skew_recs.sort(key=lambda r: r.get("step", -1))
    slowest_votes: dict[int, int] = {}
    for r in skew_recs:
        s = r.get("slowest")
        if s is not None:
            slowest_votes[int(s)] = slowest_votes.get(int(s), 0) + 1
    if straggler is None and slowest_votes:
        # no aligned post-hoc steps: fall back to the in-run probe vote
        worst_pi = max(slowest_votes, key=slowest_votes.get)
        straggler = {"process": worst_pi, "excess_ms": None,
                     "excess_pct": None, "from_probe": True}

    # -- serving records (r18): the fleet the serve tier actually is —
    # per-replica occupancy / latency / completed-vs-offered rows from
    # each process's ``serving`` record (multi-replica serve runs had
    # no joined render before this; the train-only skew alignment
    # above says nothing about a replica the router starved) ----------
    by_replica = {}
    if router_rec is not None:
        by_replica = {int(p["replica"]): p
                      for p in router_rec.get("per_replica") or []}
    srows = []
    for pi in pis:
        srecs = procs[pi]["serving"]
        if not srecs and pi not in by_replica:
            continue
        last = srecs[-1] if srecs else {}
        row = {
            "process": pi,
            "mode": last.get("mode"),
            "offered": last.get("requests"),
            "completed": last.get("completed"),
            "dropped": last.get("dropped"),
            "occupancy": last.get("slot_occupancy"),
            "ttft_p95_ms": (last.get("ttft_ms") or {}).get("p95"),
            "token_lat_p95_ms": (last.get("token_lat_ms")
                                 or {}).get("p95"),
            "tokens_per_s": last.get("tokens_per_s"),
            "live_drops": procs[pi]["live_drops"],
        }
        rrow = by_replica.get(pi)
        if rrow is not None:
            # the router's ledger for this replica joins the row:
            # routed/shed/redirected counts + its scheduling state
            row["routed"] = rrow.get("routed")
            row["shed"] = rrow.get("shed")
            row["redirected"] = rrow.get("redirected")
            row["router_state"] = ("dead" if rrow.get("dead") else
                                   "active" if rrow.get("active")
                                   else "standby")
        srows.append(row)
    serving = None
    if srows:
        occs = [r["occupancy"] for r in srows
                if r["occupancy"] is not None]
        serving = {
            "replicas": srows,
            "offered": sum(r["offered"] or 0 for r in srows),
            "completed": sum(r["completed"] or 0 for r in srows),
            "tokens_per_s": round(sum(r["tokens_per_s"] or 0.0
                                      for r in srows), 2),
            "occupancy_min": round(min(occs), 4) if occs else None,
            "occupancy_max": round(max(occs), 4) if occs else None,
        }
        if router_rec is not None:
            serving["router"] = {k: router_rec.get(k) for k in
                                 ("policy", "replicas", "offered",
                                  "routed", "completed", "shed",
                                  "redirected", "shed_rate",
                                  "routed_balance", "shed_by_rule",
                                  "scale_events")
                                 if k in router_rec}

    # -- desync records (dedup by step+path+processes) ------------------
    desyncs: list[dict] = []
    seen_d: set = set()
    for pi in pis:
        for r in procs[pi]["desync"]:
            key = (r.get("step"), r.get("path"),
                   tuple(r.get("processes", ())))
            if key in seen_d:
                continue
            seen_d.add(key)
            desyncs.append(r)
    desyncs.sort(key=lambda r: r.get("step", -1))

    # -- recovery records (r17): restores dedup'd by restore point
    # (every process of a supervised fleet logs the same rollback; a
    # startup resume is logged once per process too), incidents kept
    # per-process (a peer_lost alert names WHICH survivor saw it) -----
    restores: list[dict] = []
    seen_r: set = set()
    for pi in pis:
        for r in procs[pi]["restore"]:
            key = (r.get("generation"), r.get("at_step"),
                   r.get("reason"), r.get("rule"))
            if key in seen_r:
                continue
            seen_r.add(key)
            restores.append(r)
    restores.sort(key=lambda r: (r.get("at_step") or -1,
                                 r.get("generation") or -1))
    incidents = [dict(r, process=pi) for pi in pis
                 for r in procs[pi]["incident_alerts"]]
    snapshots = sum(procs[pi]["snapshots"] for pi in pis)

    colls = {pi: {"total_bytes": procs[pi]["collectives"].get(
                      "total_bytes", 0),
                  "total_calls": procs[pi]["collectives"].get(
                      "total_calls", 0),
                  "latency": procs[pi]["collectives"].get("latency")}
             for pi in pis if procs[pi]["collectives"]}

    out = {
        "process_count": pc,
        "sidecars": len(pis),
        "aligned_steps": len(aligned),
        "per_process": per_process,
        "straggler": straggler,
        "skew": ({"spread_ms_p50": round(_percentile(spreads, 50), 3),
                  "spread_ms_p95": round(_percentile(spreads, 95), 3),
                  "spread_ms_max": round(spreads[-1], 3),
                  "worst_step": worst} if spreads else None),
        "fleet_skew": ({"records": len(skew_recs),
                        "slowest_votes": slowest_votes,
                        "last": skew_recs[-1]} if skew_recs else None),
        "serving": serving,
        "desync": {"count": len(desyncs), "records": desyncs},
        "recovery": ({"restores": len(restores),
                      "steps_lost": sum(int(r.get("steps_lost") or 0)
                                        for r in restores),
                      "records": restores,
                      "snapshots": snapshots,
                      "incidents": incidents}
                     if (restores or snapshots or incidents)
                     else None),
        "collectives": colls or None,
    }
    missing = sorted(set(range(pc)) - set(pis))
    if missing:
        out["missing_processes"] = missing
    return out


def render_fleet(summary: dict) -> str:
    """Markdown fleet tables (skew / straggler / desync / collectives)
    — the ``telemetry_report.py --fleet`` output."""
    lines = [f"fleet: {summary['sidecars']}/{summary['process_count']} "
             f"process sidecars, {summary['aligned_steps']} aligned "
             f"steps"]
    if summary.get("missing_processes"):
        lines.append(f"WARNING: missing sidecars for processes "
                     f"{summary['missing_processes']} — partial fleet "
                     f"view")
    sk = summary.get("skew")
    if sk:
        lines.append(
            f"cross-process step skew (max-min): p50 "
            f"{sk['spread_ms_p50']} ms / p95 {sk['spread_ms_p95']} ms "
            f"/ max {sk['spread_ms_max']} ms (worst at step "
            f"{sk['worst_step']['step']}: process "
            f"{sk['worst_step']['slowest']})")
    st = summary.get("straggler")
    if st:
        if st.get("from_probe"):
            lines.append(f"straggler: process {st['process']} (named by "
                         f"the in-run probe; no aligned step records)")
        else:
            lines.append(f"straggler: process {st['process']} "
                         f"(+{st['excess_ms']} ms cumulative excess, "
                         f"+{st['excess_pct']}% over the fleet-min "
                         f"path)")
    lines += ["", "| process | step p50 ms | cum excess ms | excess % |"
              " skip rate | input-wait share | stalls | closed |",
              "|---|---|---|---|---|---|---|---|"]

    def fmt(v, pat="{}"):
        return "n/a" if v is None else pat.format(v)

    for row in summary["per_process"]:
        skip = fmt(row.get("skip_rate"), "{:.4f}")
        if row.get("skip_rate_delta") is not None:
            skip += f" ({row['skip_rate_delta']:+.4f})"
        wait = fmt(row.get("input_wait_share"), "{:.3f}")
        if row.get("input_wait_share_delta") is not None:
            wait += f" ({row['input_wait_share_delta']:+.3f})"
        lines.append(
            f"| p{row['process']} | {fmt(row['step_ms_p50'])} | "
            f"{fmt(row['excess_ms'])} | {fmt(row['excess_pct'])} | "
            f"{skip} | {wait} | {row['stalls']} | "
            f"{'yes' if row['closed'] else 'NO (died mid-run)'} |")

    fs = summary.get("fleet_skew")
    if fs:
        votes = ", ".join(f"p{k}: {v}" for k, v in
                          sorted(fs["slowest_votes"].items()))
        last = fs["last"]
        lines += ["", f"in-run probe: {fs['records']} fleet_skew "
                  f"record(s); slowest votes: {votes}; last lag "
                  f"{last.get('lag_ms')} ms "
                  f"({100.0 * last.get('lag_frac', 0):.1f}% of median "
                  f"EMA) at step {last.get('step')}"]
    sv = summary.get("serving")
    if sv:
        rt = sv.get("router")
        head = (f"SERVING fleet: {len(sv['replicas'])} replica(s), "
                f"{sv['completed']}/{sv['offered']} completed, "
                f"{sv['tokens_per_s']} tok/s aggregate")
        if sv.get("occupancy_min") is not None:
            head += (f", occupancy {sv['occupancy_min']}-"
                     f"{sv['occupancy_max']}")
        if sv["completed"] != sv["offered"]:
            head += (f" — {sv['offered'] - sv['completed']} DROPPED "
                     f"(zero-drop contract violated)")
        lines += ["", head]
        if rt:
            rhead = (f"router: policy `{rt.get('policy')}` — "
                     f"{rt.get('routed')} routed, "
                     f"{rt.get('shed', 0)} shed, "
                     f"{rt.get('redirected', 0)} redirected")
            if rt.get("routed_balance") is not None:
                rhead += f", balance {rt['routed_balance']} (max/mean)"
            if rt.get("shed_by_rule"):
                rhead += (" — shed attribution: " + ", ".join(
                    f"`{k}` x{v}" for k, v in
                    sorted(rt["shed_by_rule"].items())))
            if rt.get("scale_events"):
                rhead += (f", {len(rt['scale_events'])} scale "
                          f"event(s)")
            lines.append(rhead)
        router_cols = rt is not None
        hdr = ("| replica | mode | offered | completed | occupancy "
               "| TTFT p95 ms | token-lat p95 ms | tok/s | "
               "live drops |")
        sep = "|---|---|---|---|---|---|---|---|---|"
        if router_cols:
            hdr += " routed | shed | redirected | state |"
            sep += "---|---|---|---|"
        lines += ["", hdr, sep]
        for r in sv["replicas"]:
            line = (
                f"| p{r['process']} | {r.get('mode') or 'n/a'} | "
                f"{fmt(r['offered'])} | {fmt(r['completed'])} | "
                f"{fmt(r.get('occupancy'), '{:.3f}')} | "
                f"{fmt(r.get('ttft_p95_ms'))} | "
                f"{fmt(r.get('token_lat_p95_ms'))} | "
                f"{fmt(r.get('tokens_per_s'))} | "
                f"{r.get('live_drops', 0)} |")
            if router_cols:
                line += (f" {fmt(r.get('routed'))} | "
                         f"{fmt(r.get('shed'))} | "
                         f"{fmt(r.get('redirected'))} | "
                         f"{r.get('router_state') or 'n/a'} |")
            lines.append(line)
    de = summary["desync"]
    if de["count"]:
        lines += ["", f"DESYNC: {de['count']} disagreement record(s) — "
                  f"replicas are NOT consistent:", "",
                  "| step | first divergent path | processes | value | "
                  "ref | loss-scale ok | step-counter ok |",
                  "|---|---|---|---|---|---|---|"]
        for r in de["records"]:
            lines.append(
                f"| {r.get('step', 'n/a')} | "
                f"`{r.get('path', '<scalars only>')}` | "
                f"{','.join('p%d' % p for p in r.get('processes', []))}"
                f" | {r.get('value', 'n/a')} | {r.get('ref', 'n/a')} | "
                f"{'yes' if r.get('loss_scale_ok') else 'NO'} | "
                f"{'yes' if r.get('step_count_ok') else 'NO'} |")
    else:
        lines += ["", "desync: no disagreement recorded"]
    rec = summary.get("recovery")
    if rec:
        head = (f"RECOVERY: {rec['restores']} restore(s), "
                f"{rec['steps_lost']} step(s) lost, "
                f"{rec['snapshots']} snapshot(s) committed across the "
                f"fleet")
        lines += ["", head]
        if rec["incidents"]:
            named = ", ".join(
                f"p{i.get('process')}:{i.get('rule')}@step "
                f"{i.get('step', '?')}" for i in rec["incidents"])
            lines.append(f"incident alert(s): {named}")
        if rec["records"]:
            lines += ["", "| incident | trigger rule | restore "
                      "generation | restored to step | steps lost |",
                      "|---|---|---|---|---|"]
            for r in rec["records"]:
                lines.append(
                    f"| {r.get('reason', '?')} | "
                    f"`{r.get('rule') or 'n/a'}` | "
                    f"g{r.get('generation')} | {r.get('step')} | "
                    f"{r.get('steps_lost', 'n/a')} |")
    co = summary.get("collectives")
    if co:
        lines += ["", "| process | traced collective bytes/step | calls "
                  "| timed gathers | gather ms mean/max |",
                  "|---|---|---|---|---|"]
        for pi, c in sorted(co.items()):
            lat = c.get("latency") or {}
            calls = ms_mean = ms_max = None
            if lat:
                ops = lat.get("ops", {})
                calls = sum(o["calls"] for o in ops.values())
                tot = sum(o["ms_total"] for o in ops.values())
                ms_mean = round(tot / max(calls, 1), 3)
                ms_max = max((o["ms_max"] for o in ops.values()),
                             default=None)
            lines.append(
                f"| p{pi} | {c['total_bytes']} | {c['total_calls']} | "
                f"{calls if calls is not None else 'n/a'} | "
                f"{ms_mean if ms_mean is not None else 'n/a'}/"
                f"{ms_max if ms_max is not None else 'n/a'} |")
    return "\n".join(lines)
