"""Fleet-observability + self-healing smoke: an N-process telemetered
toy train loop with injectable failure modes — the offline proof (and
CI gate) for ``apex_tpu/prof/fleet.py`` and, since r17, the
``apex_tpu/runtime`` snapshot/restore/supervise vertical.

Parent mode (no RANK in the environment): spawns itself ``--world``
times via ``parallel.launch.multiproc`` (each child gets RANK /
WORLD_SIZE / JAX_PLATFORMS=cpu and the forced-host-device-count XLA
flag), waits, and prints ONE JSON line naming the per-process sidecars.
Under ``--supervise`` the parent is also the process-level half of the
self-healing runtime: when an attempt dies (a killed/preempted child),
it relaunches the whole fleet up to ``--restarts`` times — the
children rediscover the last complete snapshot generation and resume.
Child mode: brings up ``jax.distributed`` against the parent-chosen
coordinator port and runs a small train loop with a MetricsLogger,
FleetProbe, DesyncProbe, a dynamic-scaler state, and (when armed) a
SnapshotWriter + Supervisor.

Injections:

- ``--sleep-rank R --sleep-ms M`` (r10) — process R sleeps M ms inside
  every measured step: the fleet view and the in-run probe must name R
  as the straggler.
- ``--desync-rank R --desync-step S`` (r10) — process R perturbs one
  parameter leaf after step S: the next desync check must emit a
  ``desync`` record naming R (fleets of 2: both candidates — the
  median reference cannot break a tie) and the leaf's pytree path.
  Under ``--supervise`` the record additionally TRIGGERS a
  fleet-coordinated restore-from-last-good; the perturbation is
  injected once, so the healed run completes bit-equal to a clean one.
- ``--kill-rank R --kill-at S [--preempt SIGTERM]`` (r17) — process R
  sends itself the given signal (default SIGKILL) after step S of
  attempt 0: survivors observe the peer loss at their next gather
  (``APEX_FLEET_GATHER_TIMEOUT_MS``-bounded), record a ``peer_lost``
  alert, and exit; the parent relaunches and every process resumes
  from the last complete generation (``restore`` record, reason
  ``preemption``).
- ``--starve-rank R [--starve-frac F]`` (r18, ``--serve`` mode) —
  replica R is offered only fraction F of the request load: its OWN
  latency monitors stay green (few requests, served instantly) while
  its rolling occupancy collapses — the degradation only a FLEET view
  can see, which the live plane's ``--fleet-slo`` rules (e.g.
  ``occupancy_min>=0.15@4``) must catch with a ``scope: "fleet"``
  alert.

r18 live plane (``--live``): the parent hosts a
``prof.live.LiveCollector`` (rolling per-replica windows, fleet-scope
SLO evaluation, Prometheus ``/metrics``); every child streams its
telemetry through a non-blocking ``LiveEmitter`` tee. The parent
writes the collector's sidecar (``<out root>.live.jsonl`` — the LIVE
table), a final ``/metrics`` scrape (``<out root>.metrics.txt``), and
a ``/snapshot`` dump (``<out root>.snapshot.json`` — what
``tools/serve_top.py --from`` renders), then ASSERTS the live
contract: armed starvation must produce the fleet-scope alert while
every per-process monitor stays silent, and drop counts must be zero
unless ``--live-throttle-ms`` injected backpressure.

``--serve`` swaps the toy train loop for a serving workload: each
child runs a tiny ``ContinuousBatchingEngine`` under Poisson traffic
(no ``jax.distributed``, no collectives — the live plane streams out
of band), writing the standard ``serving`` record so
``telemetry_report.py --fleet`` renders the per-replica serving
table.

r19 router tier (``--router``, with ``--serve --live``): the parent
becomes the REQUEST ROUTER — it hosts a ``serve.router.RouterServer``
next to the live collector, children serve whatever the router sends
them (externally-fed engines over the socket transport, every
retirement acked back), and the collector's fleet-scope alerts drive
admission control: ``--shed`` arms attributed load-shedding,
``--starve-rank`` becomes a router-side skew injection. The parent
writes the schema-8 ``router`` record into the live sidecar, injects
the routing ledger into ``<out>.snapshot.json`` (the serve_top ROUTER
line), and ASSERTS the router contract before exiting 0 (exit 7):
zero LOST requests, shed counted + rule/replica-attributed (shed arm)
or zero shed (shed-free arm), and the starved rank actually starved.

r22 distributed tracing (``--trace``, with ``--serve``): every replica
runs its engine under a ``SpanTracer`` and persists the span records
into its sidecar; under ``--router`` the parent's Router traces its
own decisions (route/admission/shed/redirect/replay_hop) into the live
sidecar. After the run the parent clock-aligns ALL lanes into one
merged Perfetto-loadable timeline (``<out root>.trace.json`` — one
``pid`` lane per process, one ``tid`` track per trace id) and ASSERTS
the trace contract (exit 8): zero orphan request-scope spans,
span-recomputed serving percentiles equal to each replica's
``serving`` record, and — with ``--kill-rank R`` in serve shape, which
makes replica R ``os._exit(0)`` mid-generation after ``--kill-at``
retirements (default 2) — a killed request whose merged timeline
crosses two process lanes through a named ``replay_hop``.
``--flightrec`` additionally arms alert-triggered flight recorders
(``prof.flightrec``) on every replica and on the parent's live
collector: zero steady-state disk cost, a full
records+spans+open-spans dump (``*.flightrec.json``) the moment any
alert fires.

Under ``--supervise`` with an armed injection the parent ASSERTS the
telemetry contract before exiting 0: the aggregated sidecars must name
the incident (``desync`` record / ``preempt`` event / ``peer_lost``
alert), carry the ``restore`` record with its trigger reason, and end
every final-attempt sidecar with ``close``.

Example (the committed TELEM_r17 artifacts)::

    python tools/fleet_smoke.py --world 2 --steps 12 --supervise \
        --snapshot-every 2 --kill-rank 1 --kill-at 6 \
        --out TELEM_r17_kill.jsonl
    python tools/telemetry_report.py --fleet TELEM_r17_kill.a1.p*.jsonl
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--world", type=int, default=2,
                    help="number of processes to spawn")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--probe-every", type=int, default=2,
                    help="FleetProbe cadence (observed steps per gather)")
    ap.add_argument("--desync-every", type=int, default=2,
                    help="DesyncProbe cadence (0 disables)")
    ap.add_argument("--sleep-rank", type=int, default=-1,
                    help="rank to inject a per-step sleep into (-1 off)")
    ap.add_argument("--sleep-ms", type=float, default=25.0)
    ap.add_argument("--desync-rank", type=int, default=-1,
                    help="rank to inject a parameter perturbation into "
                         "(-1 off)")
    ap.add_argument("--desync-step", type=int, default=4)
    # -- r17 preemption / self-healing knobs -------------------------------
    ap.add_argument("--kill-rank", type=int, default=-1,
                    help="rank to preempt mid-run on attempt 0 (-1 "
                         "off); under --serve --router the replica "
                         "instead dies mid-generation after --kill-at "
                         "retirements (the replay-hop injection)")
    ap.add_argument("--kill-at", type=int, default=-1,
                    help="step after which --kill-rank dies (--serve "
                         "--router: retirements before the kill, "
                         "default 2)")
    ap.add_argument("--preempt", default="SIGKILL",
                    help="signal the preempted rank sends itself "
                         "(SIGKILL | SIGTERM | ...)")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="async snapshot cadence in steps (0 disables; "
                         "submitted AFTER the desync check of the same "
                         "step, so committed generations are "
                         "certified-good)")
    ap.add_argument("--snapshot-dir", default=None,
                    help="snapshot directory (default <out>_snaps; "
                         "wiped by the parent at attempt 0)")
    ap.add_argument("--supervise", action="store_true",
                    help="arm the self-healing runtime: startup resume "
                         "from the last complete generation, "
                         "alert/desync-triggered restore, parent "
                         "relaunch on attempt death, and the r17 "
                         "telemetry-contract assertions")
    ap.add_argument("--restarts", type=int, default=2,
                    help="max fleet relaunches under --supervise")
    ap.add_argument("--max-restores", type=int, default=3,
                    help="in-run restore retry budget per attempt")
    ap.add_argument("--backoff-ms", type=float, default=100.0,
                    help="supervisor restore backoff base")
    ap.add_argument("--gather-timeout-ms", type=int, default=15000,
                    help="fleet gather timeout under --supervise (the "
                         "peer-loss detection bound)")
    ap.add_argument("--dim", type=int, default=4,
                    help="toy model width (w_perturb is dim x dim) — "
                         "raise it for overhead A/Bs so the step cost "
                         "is realistic relative to snapshot staging")
    # -- r18 live-plane / serve-workload knobs -----------------------------
    ap.add_argument("--live", action="store_true",
                    help="arm the live telemetry plane: the parent "
                         "hosts a LiveCollector (+ /metrics), children "
                         "stream through non-blocking LiveEmitters")
    ap.add_argument("--fleet-slo", default=None,
                    help="fleet-scope SLO rules for the collector "
                         "(e.g. 'occupancy_min>=0.15@4'); alerts "
                         "carry scope:\"fleet\"")
    ap.add_argument("--slo", default=None,
                    help="PER-PROCESS SLO rules each child evaluates "
                         "locally (the silence baseline the fleet "
                         "verdict is pinned against)")
    ap.add_argument("--serve", action="store_true",
                    help="run a serving workload (tiny continuous-"
                         "batching engine under Poisson traffic) "
                         "instead of the toy train loop")
    ap.add_argument("--requests", type=int, default=24,
                    help="--serve: requests offered per unstarved "
                         "replica")
    ap.add_argument("--rate", type=float, default=24.0,
                    help="--serve: Poisson arrival rate per unstarved "
                         "replica (req/s)")
    ap.add_argument("--starve-rank", type=int, default=-1,
                    help="--serve: replica offered only --starve-frac "
                         "of the load (-1 off) — the occupancy-"
                         "collapse injection")
    ap.add_argument("--starve-frac", type=float, default=0.1)
    # -- r19 router-tier knobs ---------------------------------------------
    ap.add_argument("--router", action="store_true",
                    help="--serve + --live: the parent routes ONE "
                         "global request stream across the replicas "
                         "(children run externally-fed engines over "
                         "the socket transport); --starve-rank "
                         "becomes a ROUTER-side skew injection (the "
                         "filter withholds traffic from that rank), "
                         "the collector's fleet alert drives "
                         "admission control, and the parent writes "
                         "the schema-8 router record + assertions")
    ap.add_argument("--policy", default="least-queue",
                    help="--router routing policy (least-queue | "
                         "session-affinity | power-of-two-choices)")
    ap.add_argument("--shed", action="store_true",
                    help="--router: arm load-shedding — a tripped "
                         "--fleet-slo budget sheds arrivals with "
                         "rule+replica attribution; without it the "
                         "alert only redirects (zero-drop)")
    ap.add_argument("--shed-window-ms", type=float, default=1000.0,
                    help="--router: how long one alert keeps the "
                         "shed/redirect window open")
    ap.add_argument("--router-endpoint", default=None,
                    help="router server endpoint (internal: parent "
                         "-> child)")
    # -- r22 distributed-trace / flight-recorder knobs ---------------------
    ap.add_argument("--trace", action="store_true",
                    help="--serve: arm per-replica SpanTracers (+ the "
                         "router's, under --router), persist span "
                         "records into every sidecar, and merge them "
                         "into ONE fleet timeline "
                         "(<out root>.trace.json, Perfetto-loadable); "
                         "with --router the parent also ASSERTS the "
                         "trace contract (zero orphan spans, "
                         "span/serving parity, and — under "
                         "--kill-rank — a cross-lane replay hop)")
    ap.add_argument("--flightrec", action="store_true",
                    help="arm flight recorders: each serving child "
                         "buffers its recent records/spans in memory "
                         "and dumps <sidecar root>.flightrec.json on "
                         "any alert; the parent's recorder rides the "
                         "live collector's fleet-scope alerts")
    ap.add_argument("--live-throttle-ms", type=float, default=0.0,
                    help="throttle each child's live SENDER per "
                         "message — the drop-accounting injection "
                         "(drops must be nonzero AND counted)")
    ap.add_argument("--live-queue", type=int, default=2048,
                    help="live emitter queue bound")
    ap.add_argument("--live-endpoint", default=None,
                    help="collector endpoint (internal: parent -> "
                         "child)")
    ap.add_argument("--devices-per-proc", type=int, default=2,
                    help="forced host platform device count per process")
    ap.add_argument("--out", default="TELEM_fleet_smoke.jsonl",
                    help="sidecar path; each process writes "
                         "<out>[.a{attempt}].p{rank}.jsonl")
    ap.add_argument("--log-dir", default=".",
                    help="where non-rank-0 child stdout/stderr lands")
    ap.add_argument("--port", type=int, default=0,
                    help="coordinator port (internal: parent -> child)")
    ap.add_argument("--attempt", type=int, default=0,
                    help="fleet launch attempt (internal: parent -> "
                         "child)")
    return ap.parse_args()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _attempt_out(out: str, attempt: int) -> str:
    root, ext = os.path.splitext(out)
    return f"{root}.a{attempt}{ext}" if attempt else out


def _sidecars(out: str, world: int, attempt: int) -> "list[str]":
    base = _attempt_out(out, attempt)
    if world == 1:
        return [base]           # MetricsLogger suffixes only fleets
    root, ext = os.path.splitext(base)
    return [f"{root}.p{i}{ext}" for i in range(world)]


def _snap_dir(args) -> str:
    return args.snapshot_dir or os.path.splitext(args.out)[0] + "_snaps"


def _read_records(path: str) -> "list[dict]":
    """Plain-JSON sidecar read — the parent deliberately imports no
    jax (and so none of apex_tpu, whose package imports pull it in)."""
    recs = []
    try:
        with open(path) as fh:
            for line in fh:
                line = line.strip()
                if line:
                    recs.append(json.loads(line))
    except FileNotFoundError:
        pass
    return recs


def _live_paths(out: str) -> "dict[str, str]":
    root = os.path.splitext(out)[0]
    return {"sidecar": root + ".live.jsonl",
            "metrics": root + ".metrics.txt",
            "snapshot": root + ".snapshot.json"}


def _assert_live(args, paths: "dict[str, str]",
                 throttled: bool) -> "str | None":
    """The r18 live-plane contract over the written artifacts: an armed
    starvation produced a fleet-scope alert naming a process, every
    per-process monitor stayed SILENT, and the drop accounting matches
    the injection (zero drops in steady state, nonzero counted under a
    throttled sender). Returns an error string, parent-JSON-line
    style."""
    live = _read_records(paths["sidecar"])
    fleet_alerts = [r for r in live if r.get("kind") == "alert"
                    and r.get("scope") == "fleet"]
    if args.starve_rank >= 0 and args.fleet_slo:
        if not fleet_alerts:
            return "starvation armed but no scope=fleet alert was " \
                   "recorded"
        if not any(r.get("process") is not None for r in fleet_alerts):
            return "fleet alert names no culprit process"
        for p in _sidecars(args.out, args.world, 0):
            if any(r.get("kind") == "alert" for r in _read_records(p)):
                return f"per-process monitor fired in {p} — the " \
                       f"degradation was supposed to be invisible " \
                       f"per-process"
    drops = [r for r in live if r.get("kind") == "live_drop"]
    if not drops:
        return "collector flushed no live_drop accounting records"
    total = sum(int(r.get("drops") or 0) for r in drops)
    if throttled and total == 0:
        return "throttled sender armed but zero drops were counted"
    if not throttled and total > 0:
        return f"steady state dropped {total} live sample(s)"
    if not os.path.exists(paths["metrics"]):
        return "no /metrics scrape was written"
    return None


def _assert_router(args, state: dict) -> "str | None":
    """The r19 router contract over the parent's routing ledger:
    nothing LOST (completed + shed == offered - redirected; a replayed
    request counts in ``routed`` once per hop, so the redirected count
    is exactly the double-counting — r22), shed arm sheds with
    every drop attributed to a rule + replica, shed-free arm sheds
    nothing, and the starved rank really was starved by the router."""
    if state.get("error"):
        return f"router driver failed: {state['error']}"
    rsum = state.get("summary")
    if rsum is None:
        return "router driver produced no summary"
    if rsum["completed"] + rsum["shed"] != \
            rsum["offered"] - rsum["redirected"]:
        lost = (rsum["offered"] - rsum["redirected"]
                - rsum["completed"] - rsum["shed"])
        return f"{lost} request(s) LOST (neither completed nor " \
               f"attributed shed)"
    if args.shed:
        if rsum["shed"] == 0:
            return "shed armed but zero requests were shed"
        bad = [r for r in state.get("shed_rows", [])
               if not r.get("rule") or r.get("replica") is None]
        if bad:
            return f"{len(bad)} shed row(s) missing rule/replica " \
                   f"attribution"
    elif rsum["shed"]:
        return f"shed-free arm shed {rsum['shed']} request(s)"
    if args.starve_rank >= 0:
        starved = rsum["per_replica"][args.starve_rank]
        # the filter lets ~starve_frac of requests through; anything
        # near a fair share means the injection never bit
        cap = max(1, int(round(rsum["offered"] * args.starve_frac
                               * 2)))
        if starved["routed"] > cap:
            return f"starved rank {args.starve_rank} was routed " \
                   f"{starved['routed']} request(s) (> {cap}) — the " \
                   f"skew injection did not starve it"
    return None


def _assert_trace(args, merge: dict, lists, names) -> "str | None":
    """The r22 distributed-trace contract over the merged timeline:
    zero orphan request-scope spans; an armed kill produced a trace
    whose life crossed process lanes with a named ``replay_hop`` span;
    and every replica that wrote a ``serving`` record agrees with its
    own span-recomputed percentiles (the r13 span/summary parity
    invariant, held per lane across the process boundary)."""
    if merge["orphans"]:
        sample = merge["orphans"][:3]
        return f"{len(merge['orphans'])} orphan request-scope " \
               f"span(s), e.g. {sample}"
    if args.router and args.kill_rank >= 0:
        crossed = [t for t, s in merge["traces"].items()
                   if s["replay"] and len(s["lanes"]) >= 2]
        if not crossed:
            return "kill armed but no trace crossed lanes with a " \
                   "replay"
        if not any(r.get("name") == "replay_hop"
                   for r in merge["span_records"]):
            return "kill armed but the merged trace has no " \
                   "replay_hop span"
    from apex_tpu.serve.traffic import serving_percentiles_from_spans
    for recs, name in zip(lists, names):
        serving = [r for r in recs if r.get("kind") == "serving"]
        if not serving or not serving[-1].get("completed"):
            continue    # the killed replica never summarized — skip
        spans = [r for r in recs if r.get("kind") == "span"]
        sp = serving_percentiles_from_spans(spans)
        for key in ("ttft_ms", "token_lat_ms"):
            for q in ("p50", "p95"):
                a, b = sp[key][q], serving[-1][key][q]
                if abs(a - b) > 0.051:
                    return f"{name}: span-recomputed {key} {q} = " \
                           f"{a} but serving record says {b}"
    return None


def _router_driver(args, srv, live_col, state: dict) -> None:
    """The parent's routing thread: rendezvous with the replicas,
    arm admission on the collector's fleet alerts, inject the
    starvation skew, route the global stream, drain completions."""
    import random as _random

    from apex_tpu.serve.router import (AdmissionController, Router,
                                       synthetic_requests)
    try:
        srv.wait_ready(180.0)
        adm = None
        if live_col is not None and args.fleet_slo:
            adm = AdmissionController(
                shed=args.shed,
                window_s=args.shed_window_ms * 1e-3).attach(live_col)
        router, _ = srv.make_replicas(
            lambda slots: Router(slots, policy=args.policy,
                                 admission=adm, seed=17,
                                 tracer=state.get("tracer")))
        if args.starve_rank >= 0:
            rng = _random.Random(99)
            R, frac = args.starve_rank, args.starve_frac

            def _filter(req, i, _rng=rng, _R=R, _f=frac):
                return i != _R or _rng.random() < _f
            router.candidate_filter = _filter
        reqs = synthetic_requests(
            args.requests, rate=args.rate, vocab_size=64,
            prompt_lo=3, prompt_hi=10, new_lo=4, new_hi=12, seed=17,
            sessions=(args.world * 4
                      if args.policy == "session-affinity" else 0))
        state["shed_rows"] = router.run(reqs)
        deadline = time.time() + 120.0
        while time.time() < deadline:
            s = router.summary()
            # replays count in routed (so offered) once per hop —
            # back redirects out of the completion target (r22).
            # Close AFTER the target is met: a bye'd replica stops
            # admitting, which would strand a replay routed to it
            # while a killed peer's orphans were still in flight.
            if s["completed"] + s["shed"] >= \
                    s["offered"] - s["redirected"]:
                break
            time.sleep(0.05)
        router.close()
        state["summary"] = router.summary()
    except Exception as e:                # surfaced by _assert_router
        state["error"] = f"{type(e).__name__}: {e}"


def _assert_recovery(args, attempts: int) -> "str | None":
    """The r17 telemetry contract over the written sidecars: the
    incident is named, the restore names its trigger and generation,
    and the final attempt closed cleanly. Returns an error string
    instead of raising so the parent's one JSON line carries it."""
    final = [_read_records(p) for p in
             _sidecars(args.out, args.world, attempts - 1)]
    every = [r for a in range(attempts)
             for p in _sidecars(args.out, args.world, a)
             for r in _read_records(p)]
    for i, recs in enumerate(final):
        if not recs or recs[-1].get("kind") != "close":
            return f"final-attempt sidecar p{i} did not close cleanly"
    restores = [r for r in every if r.get("kind") == "restore"]
    if args.kill_rank >= 0:
        if attempts < 2:
            return "kill armed but the fleet was never relaunched"
        if not any(r.get("name") == "preempt" for r in every) and \
                not any(r.get("rule") == "peer_lost" for r in every):
            return "no preempt event / peer_lost alert names the kill"
        if not any(r.get("reason") == "preemption" for r in restores):
            return "no restore record with reason=preemption"
    if args.desync_rank >= 0:
        if not any(r.get("kind") == "desync" for r in every):
            return "no desync record names the perturbation"
        if not any(r.get("reason") == "desync" for r in restores):
            return "no restore record with reason=desync"
    if (args.kill_rank >= 0 or args.desync_rank >= 0) and not restores:
        return "injection armed but no restore record was written"
    return None


def parent(args) -> int:
    """Spawn the fleet; under --supervise, relaunch dead attempts (the
    process-level supervisor). The parent initializes no jax backend:
    a process that has touched the chip holds it, and the children
    (``launch.multiproc`` pins them to the CPU) are the run."""
    from apex_tpu.parallel import launch
    # children simulate a multi-device host offline (the issue's
    # --xla_force_host_platform_device_count proof)
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count="
            f"{args.devices_per_proc}").strip()
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    extra = os.environ.get("PYTHONPATH", "")
    os.environ["PYTHONPATH"] = repo_root + (
        os.pathsep + extra if extra else "")
    if args.supervise:
        os.environ["APEX_FLEET_GATHER_TIMEOUT_MS"] = \
            str(args.gather_timeout_ms)

    snap_dir = _snap_dir(args)
    if args.snapshot_every or args.supervise:
        # attempt 0 starts from nothing: stale generations of an
        # earlier smoke must not satisfy this run's quorum
        shutil.rmtree(snap_dir, ignore_errors=True)
        os.makedirs(snap_dir, exist_ok=True)

    # r18: the parent hosts the live collector — a package import but
    # never a backend init (prof.live is stdlib at module level); the
    # children stream to it over localhost TCP
    live_col = live_log = None
    live_paths = _live_paths(args.out)
    if args.live:
        from apex_tpu.prof.live import LiveCollector
        from apex_tpu.prof.metrics import MetricsLogger
        live_log = MetricsLogger(
            live_paths["sidecar"], run="live_collector",
            track_compiles=False, process_index=0, process_count=1,
            meta={"world": args.world, "fleet_slo": args.fleet_slo,
                  "starve_rank": args.starve_rank,
                  "throttle_ms": args.live_throttle_ms})
        live_col = LiveCollector(rules=args.fleet_slo, logger=live_log,
                                 min_samples=4).start()
        sys.stderr.write(f"fleet_smoke: live collector {live_col.endpoint}"
                         f", scrape {live_col.metrics_url}\n")

    # r19: the parent IS the router — rendezvous server up before the
    # children spawn, the routing loop on its own thread (multiproc
    # blocks this one until the fleet exits). serve.router is
    # stdlib-only at module level, same deal as prof.live.
    router_srv = router_thread = None
    router_state: dict = {}
    if args.router:
        if not (args.serve and args.live):
            print(json.dumps({"rc": 7, "error":
                              "--router needs --serve --live"}))
            return 7
        import threading

        from apex_tpu.serve.router import RouterServer
        router_srv = RouterServer(args.world)
        if args.trace:
            # the router's own spans (route/admission/shed/redirect/
            # replay_hop) — one lane of the merged fleet timeline
            from apex_tpu.prof.spans import SpanTracer
            router_state["tracer"] = SpanTracer()
        router_thread = threading.Thread(
            target=_router_driver,
            args=(args, router_srv, live_col, router_state),
            name="apex-router-driver", daemon=True)
        router_thread.start()
        sys.stderr.write(f"fleet_smoke: router up at "
                         f"{router_srv.endpoint} "
                         f"(policy {args.policy}, "
                         f"{'SHED' if args.shed else 'redirect'})\n")

    # r22: the parent's flight recorder rides the live plane — fleet-
    # scope alerts (and anything the collector logs) trigger a dump
    flight = None
    if args.flightrec and live_col is not None:
        from apex_tpu.prof.flightrec import FlightRecorder
        flight = FlightRecorder(
            path=os.path.splitext(args.out)[0] + ".flightrec.json",
            window_s=120.0, cooldown_s=0.5)
        flight.attach(telemetry=live_log, live=live_col,
                      tracer=router_state.get("tracer"))

    max_attempts = (args.restarts + 1) if args.supervise else 1
    attempt = rc = 0
    while attempt < max_attempts:
        child_argv = [
            "--world", str(args.world), "--steps", str(args.steps),
            "--probe-every", str(args.probe_every),
            "--desync-every", str(args.desync_every),
            "--sleep-rank", str(args.sleep_rank),
            "--sleep-ms", str(args.sleep_ms),
            "--desync-rank", str(args.desync_rank),
            "--desync-step", str(args.desync_step),
            "--kill-rank", str(args.kill_rank),
            "--kill-at", str(args.kill_at),
            "--preempt", args.preempt,
            "--dim", str(args.dim),
            "--snapshot-every", str(args.snapshot_every),
            "--snapshot-dir", snap_dir,
            "--max-restores", str(args.max_restores),
            "--backoff-ms", str(args.backoff_ms),
            "--out", args.out, "--port", str(_free_port()),
            "--attempt", str(attempt),
        ]
        if args.supervise:
            child_argv.append("--supervise")
        if args.serve:
            child_argv += ["--serve", "--requests", str(args.requests),
                           "--rate", str(args.rate),
                           "--starve-rank", str(args.starve_rank),
                           "--starve-frac", str(args.starve_frac)]
        if router_srv is not None:
            child_argv += ["--router", "--router-endpoint",
                           router_srv.endpoint]
        if args.trace:
            child_argv.append("--trace")
        if args.flightrec:
            child_argv.append("--flightrec")
        if args.slo:
            child_argv += ["--slo", args.slo]
        if live_col is not None:
            child_argv += ["--live-endpoint", live_col.endpoint,
                           "--live-queue", str(args.live_queue),
                           "--live-throttle-ms",
                           str(args.live_throttle_ms)]
        rc = launch.multiproc(os.path.abspath(__file__), args.world,
                              *child_argv, log_dir=args.log_dir)
        attempt += 1
        if rc == 0 or not args.supervise:
            break
        sys.stderr.write(f"fleet_smoke: attempt {attempt - 1} died "
                         f"(rc {rc}) — relaunching with resume\n")

    line = {"rc": rc, "world": args.world, "attempts": attempt,
            "sidecars": _sidecars(args.out, args.world, attempt - 1),
            "all_sidecars": [p for a in range(attempt)
                             for p in _sidecars(args.out, args.world,
                                                a)],
            "sleep_rank": args.sleep_rank,
            "desync_rank": args.desync_rank,
            "kill_rank": args.kill_rank}
    if args.serve:
        line["starve_rank"] = args.starve_rank
    if args.snapshot_every or args.supervise:
        line["snapshot_dir"] = snap_dir
    if router_thread is not None:
        router_thread.join(240.0)
        router_srv.close()
        rsum = router_state.get("summary")
        if rsum is not None:
            line["router"] = {k: rsum[k] for k in
                              ("policy", "offered", "routed",
                               "completed", "shed", "redirected",
                               "shed_by_rule", "routed_balance")}
            if live_log is not None:
                live_log.log_router(**rsum)
        if router_state.get("tracer") is not None \
                and live_log is not None:
            # the router lane's half of the merged timeline — the
            # kind="router" record above is what marks this sidecar
            # as the router lane for merge_process_traces
            live_log.log_spans(router_state["tracer"])
        if rc == 0:
            err = _assert_router(args, router_state)
            if err is not None:
                line["rc"] = rc = 7
                line["error"] = f"router contract violated: {err}"
    if live_col is not None:
        # let the reader threads drain the children's byes (the final
        # drop accounting) — children have exited, so this is bounded
        deadline = time.time() + 3.0
        while time.time() < deadline:
            snap = live_col.snapshot()
            if snap["replicas"] and all(r["closed"]
                                        for r in snap["replicas"]):
                break
            time.sleep(0.05)
        # final scrape + snapshot BEFORE close (close tears the
        # listener down); the sidecar LIVE records land at close —
        # the router summary rides the snapshot so serve_top renders
        # the ROUTER line from the same file
        with open(live_paths["metrics"], "w") as fh:
            fh.write(live_col.prometheus())
        snap = live_col.snapshot()
        if router_state.get("summary") is not None:
            snap["router"] = router_state["summary"]
        with open(live_paths["snapshot"], "w") as fh:
            json.dump(snap, fh)
        live_col.close()
        live_log.close()
        line["live"] = {
            "sidecar": live_paths["sidecar"],
            "metrics": live_paths["metrics"],
            "snapshot": live_paths["snapshot"],
            "fleet_alerts": snap["fleet"]["alerts"],
            "violated": snap["fleet"]["violated"],
            "drops_total": snap["fleet"]["drops_total"]}
        if rc == 0:
            err = _assert_live(args, live_paths,
                               throttled=args.live_throttle_ms > 0)
            if err is not None:
                line["rc"] = rc = 6
                line["error"] = f"live contract violated: {err}"
    if args.trace and args.serve and rc == 0:
        # r22: clock-align every lane's span sidecar into ONE fleet
        # timeline + assert the distributed-trace contract. The live
        # sidecar (closed above) is the router lane; the children's
        # are the replica lanes.
        try:
            from apex_tpu.prof.metrics import read_sidecar
            from apex_tpu.prof.spans import (merge_process_traces,
                                             write_merged_chrome_trace)
            lists, names = [], []
            if args.router and live_col is not None:
                lists.append(read_sidecar(live_paths["sidecar"]))
                names.append("router")
            for i, p in enumerate(_sidecars(args.out, args.world,
                                            attempt - 1)):
                lists.append(read_sidecar(p))
                names.append(f"p{i}")
            merge = merge_process_traces(lists, names=names)
            trace_path = os.path.splitext(args.out)[0] + ".trace.json"
            write_merged_chrome_trace(merge, trace_path)
            line["trace"] = {
                "merged": trace_path,
                "lanes": len(merge["lanes"]),
                "traces": len(merge["traces"]),
                "multi_lane": merge["multi_lane"],
                "replayed": sorted(t for t, s in
                                   merge["traces"].items()
                                   if s["replay"]),
                "orphans": len(merge["orphans"])}
            err = _assert_trace(args, merge, lists, names)
            if err is not None:
                line["rc"] = rc = 8
                line["error"] = f"trace contract violated: {err}"
        except Exception as e:
            line["rc"] = rc = 8
            line["error"] = f"trace merge failed: " \
                            f"{type(e).__name__}: {e}"
    if flight is not None:
        time.sleep(0.3)     # let an in-flight async dump land
        line["flightrec"] = {"path_base": flight.path,
                             "dumps": list(flight.dumps)}
    if rc == 0 and args.supervise and \
            (args.kill_rank >= 0 or args.desync_rank >= 0):
        err = _assert_recovery(args, attempt)
        if err is not None:
            line["rc"] = rc = 5
            line["error"] = f"recovery contract violated: {err}"
    print(json.dumps(line))
    return rc


def _child_emitter(args, logger, rank: int, world: int, run: str):
    """Arm the live stream when the parent gave us a collector: a
    non-blocking emitter tee'd off the child's MetricsLogger (every
    step/serving/alert record streams; direct ``observe`` samples ride
    the same queue)."""
    if not args.live_endpoint:
        return None
    from apex_tpu.prof.live import LiveEmitter
    em = LiveEmitter(args.live_endpoint, process_index=rank,
                     process_count=world, run=run,
                     queue_size=args.live_queue,
                     throttle_ms=args.live_throttle_ms or None)
    return em.attach(logger)


def child_serve(args) -> int:
    """The r18 serving-workload child: a tiny continuous-batching
    engine under Poisson traffic, streaming live. No jax.distributed,
    no collectives — each replica is independent (the live plane is
    out-of-band), exactly the shape the ROADMAP's router tier will
    run N of."""
    rank = int(os.environ["RANK"])
    world = int(os.environ["WORLD_SIZE"])
    import jax
    from apex_tpu import prof
    from apex_tpu.models import TransformerLM
    from apex_tpu.serve import (ContinuousBatchingEngine,
                                poisson_requests, summarize_serving)

    starved = rank == args.starve_rank and not args.router
    frac = args.starve_frac if starved else 1.0
    logger = prof.MetricsLogger(
        _attempt_out(args.out, args.attempt), run="fleet_serve",
        flush_every=8,
        meta={"requests": args.requests, "rate": args.rate,
              "starve_rank": args.starve_rank, "starved": starved,
              "router": bool(args.router), "slo": args.slo})
    emitter = _child_emitter(args, logger, rank, world, "fleet_serve")
    slo_mon = (prof.SLOMonitor(args.slo, logger=logger, min_samples=4)
               if args.slo else None)
    tracer = prof.SpanTracer() if args.trace else None
    flight = None
    if args.flightrec:
        flight = prof.FlightRecorder(
            path=os.path.splitext(logger.path)[0] + ".flightrec.json",
            window_s=120.0, cooldown_s=0.5)

    V = 64
    lm = TransformerLM(vocab_size=V, max_seq_len=32, embed_dim=32,
                       num_heads=4, num_layers=2)
    params = lm.init(jax.random.key(0))
    engine = ContinuousBatchingEngine(lm, params, slots=3, max_len=32,
                                      prefill_chunk=4)
    if args.router:
        # r19: this replica serves whatever the PARENT routes to it —
        # warmup BEFORE the rendezvous so routing starts against a
        # layout-stable fleet, then run on the socket-fed feed (the
        # engine's externally-fed admission hook); every retirement
        # acks back through the client's background sender
        from apex_tpu.serve.router import ReplicaClient
        engine.warmup()
        client = ReplicaClient(args.router_endpoint, rank)
        kill_after = args.kill_at if args.kill_at >= 0 else 2
        retired = [0]

        def _retire(res):
            client.ack(res)
            retired[0] += 1
            if rank == args.kill_rank and retired[0] >= kill_after:
                # r22 kill injection, serve shape: die MID-GENERATION
                # after acking kill_after retirements. Persist the
                # closed spans so far (the dead lane's half of every
                # in-flight request's timeline: queue/prefill/commit;
                # their request spans die open), give the background
                # sender a beat to drain the acks already queued, then
                # exit WITHOUT a bye — the router sees EOF and replays
                # the orphans onto the survivors.
                if tracer is not None:
                    logger.log_spans(tracer.drain_records())
                logger.flush()
                time.sleep(0.25)
                os._exit(0)

        results, stats = engine.run(client.feed, telemetry=logger,
                                    tracer=tracer, slo=slo_mon,
                                    live=emitter, t0=client.t0,
                                    on_retire=_retire,
                                    flightrec=flight)
        client.close()
        rate = args.rate
    else:
        # the starved replica is offered frac of the load over the
        # SAME wall-clock span (rate scaled with the count): it idles
        # between its few arrivals — healthy latencies, collapsed
        # occupancy
        n = max(2, int(round(args.requests * frac)))
        rate = max(args.rate * frac, 0.5)
        reqs = poisson_requests(n, rate=rate,
                                prompt_dist="uniform:3,10",
                                new_dist="uniform:4,12", vocab_size=V,
                                seed=17 + rank, max_len=32,
                                prefill_chunk=4)
        results, stats = engine.run(reqs, telemetry=logger,
                                    tracer=tracer, slo=slo_mon,
                                    live=emitter, flightrec=flight)
    summary = summarize_serving(results, stats, offered_rps=rate)
    logger.log_serving(**summary)
    if tracer is not None:
        logger.log_spans(tracer)
    if emitter is not None:
        emitter.close()
    logger.close()
    if rank == 0:
        sys.stderr.write(f"fleet_smoke serve rank0: "
                         f"{summary['completed']}/{summary['requests']}"
                         f" completed, occupancy "
                         f"{summary['slot_occupancy']}\n")
    return 0


def child(args) -> int:
    rank = int(os.environ["RANK"])
    world = int(os.environ["WORLD_SIZE"])
    import jax
    import jax.numpy as jnp
    from apex_tpu.parallel import launch
    launch.initialize(coordinator_address=f"127.0.0.1:{args.port}",
                      num_processes=world, process_id=rank)
    assert jax.process_count() == world, jax.process_count()

    from apex_tpu import prof, runtime
    from apex_tpu.amp.scaler import LossScaler
    from apex_tpu.prof import fleet as FL

    logger = prof.MetricsLogger(
        _attempt_out(args.out, args.attempt), run="fleet_smoke",
        flush_every=4,
        meta={"steps": args.steps, "attempt": args.attempt,
              "sleep_rank": args.sleep_rank, "sleep_ms": args.sleep_ms,
              "desync_rank": args.desync_rank,
              "desync_step": args.desync_step,
              "kill_rank": args.kill_rank, "kill_at": args.kill_at,
              "snapshot_every": args.snapshot_every,
              "supervise": bool(args.supervise)})
    emitter = _child_emitter(args, logger, rank, world, "fleet_smoke")
    probe = FL.FleetProbe(logger, every=args.probe_every)
    # leaf names chosen so the desync record names a NESTED path
    d = args.dim
    params = {"layers": {"w_perturb": jnp.full((d, d), 0.5),
                         "w_stable": jnp.ones((8,))}}
    dprobe = FL.DesyncProbe(params, logger) if args.desync_every else None
    scaler = LossScaler()
    sstate = scaler.init()

    # -- self-healing runtime (r17) ----------------------------------------
    writer = store = sup = None
    if args.snapshot_every or args.supervise:
        writer = runtime.SnapshotWriter(args.snapshot_dir, logger=logger)
        store = writer.store()

    def apply_payload(payload):
        st = payload["state"]
        return (jax.tree_util.tree_map(jnp.asarray, st["params"]),
                runtime.unpack_scaler_state(st["scaler"]))

    if args.supervise:
        sup = runtime.Supervisor(
            store, apply_payload, logger=logger,
            policy=runtime.RestorePolicy(
                max_restores=args.max_restores,
                backoff_s=args.backoff_ms * 1e-3))

    start_step = 0
    if (args.supervise or args.snapshot_every) and args.attempt > 0:
        res = runtime.resume_from_snapshot(store, logger=logger)
        if res is not None:
            params, sstate = apply_payload(res["payload"])
            start_step = int(res["payload"]["step"])
            sys.stderr.write(
                f"fleet_smoke p{rank}: resumed from generation "
                f"{res['generation']} ({start_step} steps done)\n")

    @jax.jit
    def train(params, sstate, x):
        def loss(p):
            h = x @ p["layers"]["w_perturb"]
            return (jnp.sum(h * h)
                    + jnp.sum(p["layers"]["w_stable"] ** 2)) * 1e-3
        g = jax.grad(loss)(params)
        new = jax.tree_util.tree_map(lambda p, gi: p - 0.01 * gi,
                                     params, g)
        return new, scaler.update(sstate, jnp.asarray(False)), \
            loss(params)

    poll_every = args.desync_every or args.probe_every
    # faults are transient: injected once ever, never on a resume
    killed = perturbed = args.attempt > 0
    x = jnp.ones((d, d))
    step = start_step
    try:
        while step < args.steps:
            t0 = time.perf_counter()
            params, sstate, loss = train(params, sstate, x)
            jax.block_until_ready(loss)
            if rank == args.sleep_rank:
                time.sleep(args.sleep_ms * 1e-3)  # injected straggler
            step_ms = (time.perf_counter() - t0) * 1e3
            logger.log_step(step, step_ms=step_ms, loss=loss)
            if step:   # step 0 carries the jit compile on every rank
                probe.observe(step, step_ms)
            if rank == args.kill_rank and step == args.kill_at \
                    and not killed:
                # injected preemption: name the incident, persist the
                # sidecar so far, then die ungracefully
                killed = True
                logger.event("preempt", step=step,
                             signal=args.preempt)
                logger.flush()
                os.kill(os.getpid(),
                        getattr(signal, args.preempt.upper()))
            if rank == args.desync_rank and step == args.desync_step \
                    and not perturbed:
                # injected replica divergence: one leaf drifts once
                perturbed = True
                params["layers"]["w_perturb"] = (
                    params["layers"]["w_perturb"] + 0.25)
            if dprobe is not None and (step + 1) % args.desync_every \
                    == 0:
                rec = dprobe.check(params, loss_scale=sstate.scale,
                                   step_count=sstate.step_count,
                                   step=step)
                if rec is not None and sup is not None:
                    sup.notify_desync(rec)
            if sup is not None and (step + 1) % poll_every == 0:
                healed = sup.poll(step + 1)
                if healed is not None:
                    params, sstate = healed["result"]
                    step = int(healed["payload"]["step"])
                    continue          # re-run from the restored step
            if writer is not None and args.snapshot_every and \
                    (step + 1) % args.snapshot_every == 0:
                # AFTER the agreement check + poll above: committed
                # generations are certified-good (docs/RUNTIME.md)
                writer.submit(step + 1, step + 1, {
                    "params": params,
                    "scaler": runtime.pack_scaler_state(sstate)})
            step += 1
    except runtime.FleetAbort as e:
        sys.stderr.write(f"fleet_smoke p{rank}: {e}\n")
        logger.close()
        return 5
    except Exception as e:           # a gather died: the peer is gone
        if not args.supervise:
            raise
        logger.log_alert(rule="peer_lost", source="runtime",
                         step=step, error=f"{type(e).__name__}: {e}")
        logger.close()
        sys.stderr.write(f"fleet_smoke p{rank}: peer lost at step "
                         f"{step} ({type(e).__name__}) — exiting for "
                         f"relaunch\n")
        sys.stderr.flush()
        # fast-exit: jax.distributed's atexit shutdown barrier waits
        # out a ~90 s heartbeat timeout on the dead peer — a
        # supervisor-managed worker skips it; the relaunch
        # re-initializes from scratch
        os._exit(4)
    if writer is not None:
        writer.close()
    if emitter is not None:
        emitter.close()
    logger.close()
    if rank == 0:
        sys.stderr.write(f"fleet_smoke rank0: wrote {logger.path} "
                         f"({args.steps} steps, world {world})\n")
    return 0


def main() -> int:
    args = parse_args()
    if os.environ.get("RANK") is not None and args.port:
        return child_serve(args) if args.serve else child(args)
    return parent(args)


if __name__ == "__main__":
    raise SystemExit(main())
