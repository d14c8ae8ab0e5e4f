"""Serving-tier bench: continuous batching under Poisson load (r12).

decode_bench measures the fixed-batch, fixed-length decode ceiling;
this measures what serving actually is — ragged requests arriving at
their own times, admitted into a slot-based KV pool mid-flight and
retired per step (``apex_tpu/serve``) — and reports the latency-bound
numbers: TTFT, per-token latency percentiles (arrival-inclusive),
inter-token latency, tokens/s, slot occupancy, queue depth. The same
seed drives every mode, so ``--mode both`` is a continuous-vs-static
A/B at EQUAL offered load (static = admit only into a fully drained
pool — the decode_bench shape as a serving policy).

r14: the engine defaults to the FUSED hot path — batched multi-slot
prefill (the K requests admitted in one scheduler poll cost one
compiled call chain + one ``prefill_batch`` span) and the fused decode
step (one QKV matmul per layer + the single-query slot-attention
kernel via ``slot_decode_attention``). ``--unfused`` keeps the r13
serialized-prefill / vmapped-reference baseline for A/Bs; greedy
outputs are bit-equal across the two (test-pinned).

r21: ``--spec K`` turns on draft-model speculative decoding (first-N-
layers draft via ``serve.draft_from_prefix``, K proposals per step, one
(K+1)-query target scoring, on-device accept) — with ``--parity`` the
oracle stays the plain dense greedy engine, so the same gate proves the
spec streams lossless bit-for-bit.

One JSON line per mode:
    python tools/serve_bench.py [--requests 64] [--rate 8] [--slots 8]
        [--mode continuous|static|both] [--unfused] [--spec K]
        [--telemetry [PATH]] [--trace [PATH]] [--slo RULES]

The telemetry sidecar carries per-decode-step ``step`` records plus the
schema-4 ``serving`` record; ``tools/telemetry_report.py`` renders both
(and ``--compare`` shows the A/B latency rows).

r13: ``--trace`` arms the request-lifecycle span tracer
(``apex_tpu/prof/spans.py``) — per-request queue → prefill-chunk →
commit → decode → retire spans plus per-step scheduler spans, written
as schema-5 ``span`` records into the sidecar AND as a Chrome
trace-event JSON (Perfetto-loadable; one track per request) at PATH
(auto-named ``SERVE_TRACE_<mode>.json`` when omitted). The report's
**tail-attribution table** decomposes the slowest decile's latency
from those spans. ``--slo`` takes declarative rules
(``apex_tpu/prof/slo.py`` syntax, e.g.
``"ttft_p95_ms<=250,token_lat_p99_ms<=50@100"``) evaluated over
rolling windows DURING the run; violations emit schema-5 ``alert``
records and land in the JSON line's ``slo`` summary.

r22 (schema 11): under ``--router N --trace`` every replica AND the
router itself get their own span tracer; the run writes ONE merged
Perfetto timeline (``SERVE_TRACE_router<N>.json``) with a lane per
replica plus the router lane, tracks grouped by propagated trace id —
a redirected request renders across two lanes with its ``replay_hop``
named, the in-process twin of the fleet_smoke cross-process artifact.
``--flightrec`` arms the alert-triggered flight recorder
(``apex_tpu/prof/flightrec.py``): recent records + spans ride a
bounded in-memory ring at zero steady-state disk cost and dump to
``FLIGHTREC_*.json`` the moment any SLO/fleet alert fires.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import os
# repo root importable without PYTHONPATH
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

_feed = lambda: None  # rebound by arm_watchdog in main()


def _note(m):
    _feed()
    sys.stderr.write(f"serve[{time.strftime('%H:%M:%S')}]: {m}\n")
    sys.stderr.flush()


def main():
    global _feed
    from _perf_common import (arm_watchdog, emit_result, make_decoder_lm,
                              open_telemetry)
    _feed = arm_watchdog("serve_bench")

    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--rate", type=float, default=8.0,
                    help="Poisson arrival rate, req/s (<= 0: everything "
                         "arrives at t=0 — pure drain)")
    ap.add_argument("--slots", type=int, default=8,
                    help="KV-pool slots = max in-flight requests")
    ap.add_argument("--mode", default="continuous",
                    choices=["continuous", "static", "both"],
                    help="admission policy; 'both' runs static then "
                         "continuous over the IDENTICAL request set "
                         "(equal offered load A/B)")
    ap.add_argument("--prompt-dist", default="uniform:16,96",
                    help="prompt-length distribution: fixed:N | "
                         "uniform:LO,HI | geometric:MEAN")
    ap.add_argument("--new-dist", default="uniform:8,48",
                    help="output-length distribution (same specs)")
    ap.add_argument("--max-len", type=int, default=256,
                    help="per-slot arena length (prompt + output cap)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="prompt chunk size of the jitted "
                         "prefill-into-slot program (ONE compile serves "
                         "any prompt length)")
    ap.add_argument("--unfused", action="store_true",
                    help="run the r13 serialized-prefill + vmapped "
                         "reference decode step instead of the fused "
                         "path (batched multi-slot prefill + one-kernel "
                         "slot attention) — the A/B baseline; greedy "
                         "outputs are bit-equal either way")
    ap.add_argument("--paged", action="store_true",
                    help="r20 paged KV arena: global block pool + "
                         "per-slot page tables — admission gated on "
                         "FREE PAGES, so concurrency is bounded by "
                         "aggregate KV bytes, not slots x max_len; "
                         "greedy streams stay bit-equal to the dense "
                         "arena (--parity checks in-run)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="--paged: tokens per KV page (default: the "
                         "prefill chunk; must be a multiple of it and "
                         "divide --max-len)")
    ap.add_argument("--kv-pages", type=int, default=None,
                    help="--paged: total allocatable pages (default: "
                         "slots * max_len/page_size = dense-byte "
                         "parity; set LOWER to cash the reserved-byte "
                         "capacity win)")
    ap.add_argument("--prefix-share", action="store_true",
                    help="--paged: content-hashed shared-prefix cache "
                         "— a common system prompt is prefilled once "
                         "and its pages mapped copy-on-write into "
                         "every matching request (cache-hit TTFT "
                         "collapses to ~one chunk + one commit)")
    ap.add_argument("--system-prompt-len", type=int, default=0,
                    metavar="N",
                    help="prepend the SAME seeded N-token system "
                         "prompt to every request — the shared-prefix "
                         "workload shape (works in every arm, so the "
                         "share/no-share A/B runs at equal offered "
                         "load)")
    ap.add_argument("--parity", action="store_true",
                    help="--paged, temperature 0: after the paged run, "
                         "serve the IDENTICAL request set on a dense-"
                         "arena engine and require bit-equal token "
                         "streams — exit nonzero on any mismatch (the "
                         "CI smoke gate); with --spec the oracle is "
                         "also NON-speculative, so one gate covers "
                         "paged-vs-dense AND spec-vs-plain")
    ap.add_argument("--spec", type=int, default=0, metavar="K",
                    help="r21 speculative decoding: propose K draft "
                         "tokens per step from a first---spec-layers "
                         "draft and score all K+1 rows in one target "
                         "forward (fused engines only; greedy streams "
                         "stay bit-equal to the plain engine)")
    ap.add_argument("--spec-layers", type=int, default=0,
                    help="--spec draft depth (default: half the "
                         "target's layers, min 1)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--eos-id", type=int, default=None,
                    help="arm per-slot EOS retirement on this token id")
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--dim", type=int, default=1024)
    ap.add_argument("--heads", type=int, default=8,
                    help="default 8 -> head_dim 128, the measured TPU "
                         "optimum (docs/PERF.md)")
    ap.add_argument("--vocab", type=int, default=32768)
    ap.add_argument("--dtype", default="bf16", choices=["bf16", "f32"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--telemetry", nargs="?", const="1", default=None,
                    help="write a TELEM_*.jsonl sidecar (per-step "
                         "records + the schema-5 serving record); with "
                         "--mode both the static arm suffixes _static")
    ap.add_argument("--trace", nargs="?", const="1", default=None,
                    help="arm the request-lifecycle span tracer: "
                         "schema-5 span records into the sidecar + a "
                         "Chrome trace-event JSON at PATH (default "
                         "SERVE_TRACE_<mode>.json); with --mode both "
                         "the static arm suffixes _static")
    ap.add_argument("--flightrec", nargs="?", const="1", default=None,
                    help="r22 alert-triggered flight recorder: a "
                         "bounded in-memory ring of recent telemetry "
                         "records + spans at zero steady-state disk "
                         "cost, dumped to PATH (default "
                         "FLIGHTREC_serve_<mode>.json) when any "
                         "--slo/--fleet-slo alert fires")
    ap.add_argument("--slo", default=None,
                    help="in-run SLO rules (prof/slo.py syntax, e.g. "
                         "'ttft_p95_ms<=250,token_lat_p99_ms<=50@100');"
                         " violations emit schema-5 alert records and "
                         "a JSON-line slo summary")
    ap.add_argument("--live", nargs="?", const="1", default=None,
                    help="r18 live telemetry plane: with no argument, "
                         "start an in-process LiveCollector (ephemeral "
                         "TCP + a Prometheus /metrics endpoint "
                         "tools/serve_top.py can watch) and stream the "
                         "run into it; with tcp:HOST:PORT / "
                         "unix:/path.sock, stream to an external "
                         "collector. Emission is non-blocking (drops "
                         "counted, schema-7 live_drop record); the "
                         "collector's final state flushes into the "
                         "telemetry sidecar as the LIVE table")
    ap.add_argument("--fleet-slo", default=None,
                    help="fleet-scope SLO rules for the in-process "
                         "collector (prof/slo.py syntax over fleet "
                         "aggregates: occupancy_min>=0.2@8, "
                         "step_skew_frac<=0.5, merged ttft_p95_ms...); "
                         "alerts carry scope:\"fleet\"")
    ap.add_argument("--router", type=int, default=0, metavar="N",
                    help="r19 router tier: serve the SAME seeded "
                         "request set through N engine replicas "
                         "(--slots each) behind the request router — "
                         "the equal-offered-load A/B axis against a "
                         "saturated single replica is --router 1 vs "
                         "--router N. Implies continuous admission; "
                         "each replica streams to the in-process live "
                         "collector (process label = replica index), "
                         "and the sidecar carries per-replica serving "
                         "records, the aggregate, and the schema-8 "
                         "router record")
    ap.add_argument("--policy", default="least-queue",
                    choices=["least-queue", "session-affinity",
                             "power-of-two-choices",
                             "prefix-affinity"],
                    help="--router routing policy (prefix-affinity "
                         "routes by first-page content hash — hot "
                         "prefixes stay replica-local, the r20 "
                         "shared-prefix cache's fleet shape)")
    ap.add_argument("--shed", action="store_true",
                    help="--router: arm SLO-driven load-shedding — a "
                         "tripped --fleet-slo budget sheds arrivals "
                         "(counted, rule+replica-attributed); without "
                         "this flag alerts only redirect (zero-drop)")
    ap.add_argument("--sessions", type=int, default=0,
                    help="--router: tag requests with this many "
                         "distinct session keys (session-affinity "
                         "pins each to one replica)")
    args = ap.parse_args()

    import jax

    from apex_tpu.serve import (ContinuousBatchingEngine,
                                poisson_requests, summarize_serving)
    from apex_tpu.utils import setup_host_backend

    on_tpu = setup_host_backend() == "tpu"
    if not on_tpu:  # explicit CPU request: shrink the MODEL, keep the load
        args.layers, args.dim, args.heads, args.vocab = 2, 128, 4, 512
        args.max_len = min(args.max_len, 64)
        args.prefill_chunk = min(args.prefill_chunk, 8)
        if args.prompt_dist == "uniform:16,96":
            args.prompt_dist = "uniform:4,24"
        if args.new_dist == "uniform:8,48":
            args.new_dist = "uniform:4,16"
    _note(f"backend={jax.default_backend()} requests={args.requests} "
          f"rate={args.rate}/s slots={args.slots} mode={args.mode} "
          f"decode={'unfused' if args.unfused else 'fused'}")

    if args.prefix_share and not args.paged:
        raise SystemExit("--prefix-share needs --paged")
    if args.parity and not args.paged:
        raise SystemExit("--parity is the paged-vs-dense gate; add "
                         "--paged")
    if args.parity and args.temperature > 0:
        raise SystemExit("--parity needs greedy decoding "
                         "(temperature 0)")
    if args.spec and args.unfused:
        raise SystemExit("--spec rides the fused decode step; drop "
                         "--unfused")
    if args.spec and args.parity and args.dtype == "bf16":
        # the (k+1)-query scoring GEMM accumulates in a different
        # order than the oracle's 1-query step; in bf16 that rounding
        # skew can flip argmax on near-tied logits, which is a
        # precision artifact, not a spec bug — the bitwise gate is
        # defined at f32 scoring precision (docs/SERVING.md)
        args.dtype = "f32"
        _note("spec parity gate: forcing --dtype f32 (bf16 rounding "
              "skew between 1-query and (k+1)-query scoring can flip "
              "near-tied argmax)")

    lm, params, _ = make_decoder_lm(
        vocab=args.vocab, dim=args.dim, heads=args.heads,
        layers=args.layers, max_seq_len=args.max_len, dtype=args.dtype,
        seed=args.seed)
    _note("params shipped")

    draft = None
    if args.spec:
        from apex_tpu.serve import draft_from_prefix
        nl = args.spec_layers or max(1, args.layers // 2)
        draft = draft_from_prefix(lm, params, nl)
        _note(f"spec: k={args.spec} draft={nl}/{args.layers} layers")

    sys_prompt = None
    if args.system_prompt_len:
        import numpy as np
        N = args.system_prompt_len
        if N % args.prefill_chunk != 0:
            raise SystemExit(f"--system-prompt-len must be a multiple "
                             f"of the prefill chunk "
                             f"({args.prefill_chunk}) so prepending "
                             f"keeps chunk/page alignment")
        if N >= args.max_len - args.prefill_chunk:
            raise SystemExit("--system-prompt-len leaves no room for "
                             "per-request prompt + output")
        srng = np.random.RandomState(args.seed + 104729)
        sys_prompt = srng.randint(0, args.vocab, N).astype(np.int32)

    requests = poisson_requests(
        args.requests, rate=args.rate, prompt_dist=args.prompt_dist,
        new_dist=args.new_dist, vocab_size=args.vocab, seed=args.seed,
        max_len=args.max_len - args.system_prompt_len,
        prefill_chunk=args.prefill_chunk)
    if sys_prompt is not None:
        import numpy as np
        for r in requests:
            r.prompt = np.concatenate([sys_prompt, r.prompt])

    if args.router:
        if args.mode != "continuous":
            raise SystemExit("--router implies continuous admission; "
                             "drop --mode")
        if args.shed and not args.fleet_slo:
            raise SystemExit("--shed needs --fleet-slo rules to trip")
        if args.sessions:
            import random as _random
            srng = _random.Random(args.seed)
            for r in requests:
                r.session = srng.randrange(args.sessions)
        _run_router(args, lm, params, requests, _note, _feed,
                    draft=draft)
        return

    def _arm_suffix(path, mode):
        """<path>_static variant for the static arm of --mode both."""
        if path and path != "1" and len(modes) > 1 and mode == "static":
            root, ext = os.path.splitext(path)
            return root + "_static" + ext
        return path

    modes = (["static", "continuous"] if args.mode == "both"
             else [args.mode])
    for mode in modes:
        from apex_tpu import prof
        tracer = prof.SpanTracer() if args.trace else None
        telem, telem_wd, _feed = open_telemetry(
            _arm_suffix(args.telemetry, mode), tag=f"serve_{mode}",
            run="serve_bench", meta={**vars(args), "mode": mode},
            feed=_feed, tracer=tracer)
        if telem is not None:
            _note(f"[{mode}] telemetry sidecar: {telem.path}")
        slo_mon = (prof.SLOMonitor(args.slo, logger=telem,
                                   min_samples=4)
                   if args.slo else None)
        live_col = live_em = None
        if args.live:
            if args.live == "1":
                live_col = prof.LiveCollector(
                    rules=args.fleet_slo, logger=telem,
                    min_samples=4).start()
                endpoint = live_col.endpoint
                _note(f"[{mode}] live collector up: {endpoint}; "
                      f"scrape {live_col.metrics_url} (serve_top "
                      f"watches /snapshot on the same port)")
            else:
                endpoint = args.live
            live_em = prof.LiveEmitter(endpoint, process_index=0,
                                       run="serve_bench")
            if telem is not None:
                live_em.attach(telem)

        flight = None
        if args.flightrec:
            fr_path = _arm_suffix(args.flightrec, mode)
            if fr_path == "1":
                fr_path = os.path.join(
                    os.path.dirname(__file__), "..",
                    f"FLIGHTREC_serve_{mode}.json")
            flight = prof.FlightRecorder(path=fr_path, window_s=120.0,
                                         cooldown_s=0.5)
            if live_col is not None:
                flight.attach(live=live_col)
            _note(f"[{mode}] flight recorder armed -> {fr_path}")

        engine = ContinuousBatchingEngine(
            lm, params, slots=args.slots, max_len=args.max_len,
            prefill_chunk=args.prefill_chunk, eos_id=args.eos_id,
            temperature=args.temperature, seed=args.seed, policy=mode,
            fused=not args.unfused, paged=args.paged,
            page_size=args.page_size if args.paged else None,
            kv_pages=args.kv_pages if args.paged else None,
            prefix_share=args.prefix_share,
            draft=draft, spec_k=args.spec)
        if args.paged:
            _note(f"[{mode}] paged arena: {engine.kv_pages} pages x "
                  f"{engine.page_size} tok "
                  f"(dense would reserve "
                  f"{args.slots * args.max_len} tok)"
                  + (" + prefix cache" if args.prefix_share else ""))
        _note(f"[{mode}] warmup (compiles + layout-stabilizes the "
              f"slot programs)")
        _feed(allow=1200.0)
        engine.warmup()           # untraced: compile noise is not load
        _note(f"[{mode}] serving {args.requests} requests")
        results, stats = engine.run(requests, telemetry=telem,
                                    tracer=tracer, slo=slo_mon,
                                    live=live_em, flightrec=flight)
        summary = summarize_serving(results, stats,
                                    offered_rps=args.rate)
        if summary["dropped"]:
            raise RuntimeError(
                f"[{mode}] {summary['dropped']} requests did not "
                f"complete — the engine contract is zero drops")
        parity = None
        if args.parity:
            # the bit-parity gate: the IDENTICAL request set through a
            # dense-arena oracle engine must emit identical greedy
            # streams (the tentpole invariant, asserted in-run so the
            # CI smoke fails loudly, not quietly)
            _note(f"[{mode}] parity: dense-arena oracle run")
            _feed(allow=1200.0)
            oracle = ContinuousBatchingEngine(
                lm, params, slots=args.slots, max_len=args.max_len,
                prefill_chunk=args.prefill_chunk, eos_id=args.eos_id,
                temperature=0.0, seed=args.seed, policy=mode,
                fused=not args.unfused)
            oracle.warmup()
            ores, _ = oracle.run(requests)
            bad = [r.id for r, o in zip(results, ores)
                   if r.tokens != o.tokens]
            if bad:
                raise RuntimeError(
                    f"[{mode}] PARITY VIOLATION: "
                    + ("speculative " if args.spec else "")
                    + f"paged streams differ from the plain dense "
                    f"arena on request(s) {bad[:8]}"
                    + ("..." if len(bad) > 8 else ""))
            parity = ("spec-bit-equal" if args.spec else "bit-equal")
            _note(f"[{mode}] parity: {len(results)} "
                  + ("speculative " if args.spec else "")
                  + "paged streams bit-equal to the plain dense arena")
        out = {
            "metric": (f"serve_{mode}"
                       + ("_paged" if args.paged else "")
                       + ("_share" if args.prefix_share else "")
                       + (f"_spec{args.spec}" if args.spec else "")
                       + f"_p95_token_lat_ms"
                       f"_r{args.requests}_s{args.slots}"),
            "value": summary["token_lat_ms"]["p95"],
            "unit": "ms/token(p95, arrival-inclusive)",
            **summary,
        }
        if parity is not None:
            out["parity"] = parity
        if tracer is not None:
            trace_path = _arm_suffix(args.trace, mode)
            if trace_path == "1":
                trace_path = os.path.join(
                    os.path.dirname(__file__), "..",
                    f"SERVE_TRACE_{mode}.json")
            tracer.write_chrome_trace(trace_path)
            out["trace"] = trace_path
            out["spans"] = tracer.completed_count
            if tracer.dropped:
                out["spans_dropped"] = tracer.dropped
            if telem is not None:
                telem.log_spans(tracer)
            _note(f"[{mode}] {tracer.completed_count} spans -> "
                  f"{trace_path}")
        if slo_mon is not None:
            out["slo"] = slo_mon.summary()
            if slo_mon.alerts:
                _note(f"[{mode}] SLO ALERTS: "
                      f"{out['slo']['violated']}")
        if live_em is not None:
            ls = live_em.close()
            out["live"] = {"endpoint": ls["endpoint"],
                           "drops": ls["drops"], "sent": ls["sent"]}
            if live_col is not None:
                out["live"]["metrics_url"] = live_col.metrics_url
                out["live"]["fleet_alerts"] = len(live_col.alerts)
                if live_col.alerts:
                    _note(f"[{mode}] FLEET-SCOPE ALERTS: "
                          f"{sorted({a['rule'] for a in live_col.alerts})}")
                live_col.close()   # LIVE table records -> the sidecar
            _note(f"[{mode}] live stream: {ls['sent']} sent, "
                  f"{ls['drops']} dropped")
        if flight is not None:
            time.sleep(0.3)        # background dump threads settle
            if flight.dumps:
                out["flightrec"] = {"dumps": list(flight.dumps),
                                    "observed": flight.observed}
                _note(f"[{mode}] flight recorder dumped: "
                      f"{flight.dumps}")
        if telem is not None:
            telem.log_serving(**summary)
            telem_wd.stop()
            telem.close()
            out["telemetry"] = telem.path
            from apex_tpu.prof.metrics import SCHEMA_VERSION
            out["telemetry_schema"] = SCHEMA_VERSION
        # r16: run_meta/format stamp + the trajectory hook in one funnel
        emit_result(out, "serve_bench")


def _run_router(args, lm, params, requests, _note, _feed, draft=None):
    """The r19 router arm: N in-process engine replicas (threads on
    the engine's externally-fed admission hook) behind the request
    router, streaming to an in-process live collector whose
    fleet-scope alerts drive admission control. One JSON line with
    the aggregate serving summary + the router ledger."""
    import time

    from _perf_common import emit_result, open_telemetry
    from apex_tpu import prof
    from apex_tpu.serve import (AdmissionController,
                                ContinuousBatchingEngine,
                                EngineReplica, Router,
                                merge_router_run, summarize_serving)

    N = args.router
    # r22: one SpanTracer per replica + one for the router itself —
    # the in-process analogue of the fleet's per-process sidecars.
    # Each tracer becomes one LANE in the merged timeline, so a
    # redirected request renders exactly like the cross-process case.
    tracers = ([prof.SpanTracer() for _ in range(N)]
               if args.trace else None)
    router_tracer = prof.SpanTracer() if args.trace else None
    telem, telem_wd, _feed = open_telemetry(
        args.telemetry, tag=f"serve_router{N}", run="serve_bench",
        meta={**vars(args), "mode": "router"}, feed=_feed,
        tracer=router_tracer)
    if telem is not None:
        _note(f"[router] telemetry sidecar: {telem.path}")

    live_col = None
    emitters = []
    if args.live or args.fleet_slo:
        live_col = prof.LiveCollector(rules=args.fleet_slo,
                                      logger=telem,
                                      min_samples=4).start()
        _note(f"[router] live collector up: {live_col.endpoint}; "
              f"scrape {live_col.metrics_url}")
    admission = None
    if live_col is not None and args.fleet_slo:
        admission = AdmissionController(shed=args.shed).attach(
            live_col)
        _note(f"[router] admission control armed "
              f"({'SHED' if args.shed else 'redirect-only'}) on: "
              f"{args.fleet_slo}")

    flight = None
    if args.flightrec:
        fr_path = args.flightrec
        if fr_path == "1":
            fr_path = os.path.join(os.path.dirname(__file__), "..",
                                   f"FLIGHTREC_router{N}.json")
        flight = prof.FlightRecorder(path=fr_path, window_s=120.0,
                                     cooldown_s=0.5)
        flight.attach(telemetry=telem, tracer=router_tracer,
                      live=live_col)
        _note(f"[router] flight recorder armed -> {fr_path}")

    replicas = []
    for i in range(N):
        engine = ContinuousBatchingEngine(
            lm, params, slots=args.slots, max_len=args.max_len,
            prefill_chunk=args.prefill_chunk, eos_id=args.eos_id,
            temperature=args.temperature, seed=args.seed,
            policy="continuous", fused=not args.unfused,
            paged=args.paged,
            page_size=args.page_size if args.paged else None,
            kv_pages=args.kv_pages if args.paged else None,
            prefix_share=args.prefix_share,
            draft=draft, spec_k=args.spec)
        em = (prof.LiveEmitter(live_col.endpoint, process_index=i,
                               process_count=N, run="serve_router")
              if live_col is not None else None)
        replicas.append(EngineReplica(
            engine, i, emitter=em,
            tracer=tracers[i] if tracers else None, flightrec=flight))
        emitters.append(em)
    _note(f"[router] warmup x{N} (compiles + layout-stabilizes each "
          f"replica's slot programs)")
    _feed(allow=1200.0 * N)
    for rep in replicas:
        rep.engine.warmup()

    # prefix-affinity keys at the fleet's page granularity so routing
    # and the engines' prefix caches agree on what "same prefix" means
    router = Router(replicas, policy=args.policy,
                    admission=admission, seed=args.seed,
                    tracer=router_tracer,
                    prefix_page=(replicas[0].engine.page_size
                                 if args.paged else 32))
    _note(f"[router] serving {args.requests} requests across {N} "
          f"replica(s), policy {args.policy}")
    t0 = time.perf_counter()
    for rep in replicas:
        rep.start(t0, on_retire=lambda res, i=rep.index:
                  router.on_complete(i, res.id))
    shed_rows = router.run(requests, t0=t0)
    router.close()
    for rep in replicas:
        rep.join(600.0)
    for em in emitters:
        if em is not None:
            em.close()

    results, merged = merge_router_run(
        replicas, shed_rows,
        duration_s=max([router.duration_s]
                       + [r.stats["duration_s"] for r in replicas
                          if r.stats]))
    summary = summarize_serving(results, merged,
                                offered_rps=args.rate,
                                shed=shed_rows)
    if summary["dropped"]:
        raise RuntimeError(
            f"[router] {summary['dropped']} request(s) LOST — shed "
            f"mode may drop with attribution, but a lost request is "
            f"a contract violation")
    rsum = router.summary()
    out = {
        "metric": (f"serve_router{N}_p95_token_lat_ms"
                   f"_r{args.requests}_s{args.slots}"),
        "value": summary["token_lat_ms"]["p95"],
        "unit": "ms/token(p95, arrival-inclusive)",
        **summary,
        "router": {k: rsum[k] for k in
                   ("policy", "replicas", "offered", "routed",
                    "completed", "shed", "redirected", "shed_rate",
                    "routed_balance", "shed_by_rule",
                    "alerts_consumed")},
    }
    if tracers is not None:
        # one merged timeline per run: fabricate the per-process
        # record lists the fleet merge consumes (header + span rows)
        # from the in-process tracers — router lane first, one lane
        # per replica, redirected requests render across lanes exactly
        # like the cross-process fleet_smoke artifact
        from apex_tpu.prof.spans import (merge_process_traces,
                                         write_merged_chrome_trace)
        lists = [[{"kind": "header", "run": "serve_router",
                   "meta": {"role": "router"}}]
                 + [dict(r, kind="span")
                    for r in router_tracer.records()]]
        names = ["router"]
        for i, tr in enumerate(tracers):
            lists.append([{"kind": "header", "run": "serve_router",
                           "process_index": i, "process_count": N}]
                         + [dict(r, kind="span")
                            for r in tr.records()])
            names.append(f"replica{i}")
        merge = merge_process_traces(lists, names=names)
        trace_path = args.trace
        if trace_path == "1":
            trace_path = os.path.join(
                os.path.dirname(__file__), "..",
                f"SERVE_TRACE_router{N}.json")
        write_merged_chrome_trace(merge, trace_path)
        out["trace"] = trace_path
        out["trace_lanes"] = len(merge["lanes"])
        out["trace_multi_lane"] = len(merge["multi_lane"])
        out["spans"] = len(merge["span_records"])
        if merge["orphans"]:
            out["orphan_spans"] = len(merge["orphans"])
        _note(f"[router] merged trace: {len(merge['span_records'])} "
              f"spans across {len(merge['lanes'])} lanes "
              f"({len(merge['multi_lane'])} cross-lane trace(s)) -> "
              f"{trace_path}")
    if flight is not None:
        time.sleep(0.3)            # background dump threads settle
        if flight.dumps:
            out["flightrec"] = {"dumps": list(flight.dumps),
                                "observed": flight.observed}
            _note(f"[router] flight recorder dumped: {flight.dumps}")
    if live_col is not None:
        out["live"] = {"metrics_url": live_col.metrics_url,
                       "fleet_alerts": len(live_col.alerts),
                       "violated": sorted({a["rule"] for a in
                                           live_col.alerts})}
        if live_col.alerts:
            _note(f"[router] FLEET-SCOPE ALERTS: "
                  f"{out['live']['violated']}")
    if telem is not None:
        if tracers is not None:
            telem.log_spans(router_tracer)
            for tr in tracers:
                telem.log_spans(tr)
        for rep in replicas:
            if rep.results is not None and rep.stats is not None:
                rs = summarize_serving(rep.results, rep.stats,
                                       offered_rps=args.rate / N)
                telem.log_serving(**{**rs, "replica": rep.index})
        if live_col is not None:
            live_col.close()        # LIVE table -> the sidecar
        telem.log_serving(**summary)       # the aggregate rides LAST
        router.log_router(telem)
        telem_wd.stop()
        telem.close()
        out["telemetry"] = telem.path
        from apex_tpu.prof.metrics import SCHEMA_VERSION
        out["telemetry_schema"] = SCHEMA_VERSION
    elif live_col is not None:
        live_col.close()
    _note(f"[router] {rsum['completed']} completed, "
          f"{rsum['shed']} shed, balance {rsum['routed_balance']}")
    emit_result(out, "serve_bench")


if __name__ == "__main__":
    main()
