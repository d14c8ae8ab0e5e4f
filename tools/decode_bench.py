"""KV-cache decode throughput bench (the inference-side headline).

lm_bench covers training; this measures ``TransformerLM.generate`` —
the beyond-parity inference path (the reference has no inference story,
SURVEY.md §2.3 "absent") — as decoded tokens/s with per-layer K/V
caches at a prompt length long enough that full-prefix recompute would
dominate.

``--fused`` (r14) swaps the measurement for a fused-vs-reference
decode-step A/B over the SAME seeded prompts: the serving engine's
fused path (batched multi-slot prefill + one-kernel slot attention,
``apex_tpu/serve``) against its r13 reference path (serialized prefill
+ vmapped ``_decode_one``), one static-drain run each, ONE JSON line
carrying both decode-step medians + the greedy parity verdict — the
kernel win measurable outside the serving harness.

``--spec`` (r21) A/Bs speculative decoding on the fused PAGED engine:
a first-``--spec-layers`` draft proposes ``--spec-k`` tokens per step,
the target scores all k+1 rows in one forward, and the emitted greedy
streams are asserted BIT-equal to the plain fused arm — the JSON line
carries tokens/s for both arms plus the accepted-length histogram.

One JSON line per run:
    python tools/decode_bench.py [--prompt 512] [--new 128] [--batch 8]
        [--fused | --spec [--spec-k 4] [--spec-layers 1]]
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
    sys.stderr.write(f"decode[{time.strftime('%H:%M:%S')}]: {m}\n")
    sys.stderr.flush()


def main():
    global _feed
    from _perf_common import arm_watchdog
    _feed = arm_watchdog("decode_bench")
    def _new_tokens(v: str) -> int:
        n = int(v)
        if n < 4:
            raise argparse.ArgumentTypeError(
                f"--new must be >= 4 (got {n}): decode-only throughput "
                f"is differenced between an N-token and an N//4-token "
                f"variant, which needs at least a 4-token spread to be "
                f"meaningful")
        return n

    ap = argparse.ArgumentParser()
    ap.add_argument("--prompt", type=int, default=512)
    ap.add_argument("--new", type=_new_tokens, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--dim", type=int, default=1024)
    ap.add_argument("--heads", type=int, default=8,
                    help="default 8 -> head_dim 128, the measured TPU "
                         "optimum (docs/PERF.md)")
    ap.add_argument("--vocab", type=int, default=32768)
    ap.add_argument("--dtype", default="bf16", choices=["bf16", "f32"])
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--fused", action="store_true",
                    help="A/B the serve decode step instead: fused "
                         "(batched prefill + slot-attention kernel) vs "
                         "reference (r13 path) over the same seeded "
                         "prompts; one JSON line with both medians")
    ap.add_argument("--spec", action="store_true",
                    help="A/B speculative decoding (r21) on the fused "
                         "paged engine: draft-k proposals + one "
                         "(k+1)-query target scoring vs the plain "
                         "fused step, same seeded prompts, greedy "
                         "streams asserted bit-equal")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens proposed per spec step")
    ap.add_argument("--spec-layers", type=int, default=1,
                    help="draft = the target's first N layers "
                         "(serve.draft_from_prefix)")
    ap.add_argument("--spec-damp", type=float, default=0.0,
                    help="scale every layer's output projections by "
                         "this factor (0 = off): random-init weights "
                         "make a truncated-prefix draft agree with "
                         "the target ~never, so the CPU A/B damps the "
                         "per-layer residual writes to emulate the "
                         "trained-model regime where draft and target "
                         "share the dominant embedding pathway")
    ap.add_argument("--telemetry", nargs="?", const="1", default=None,
                    help="write a TELEM_*.jsonl runtime-telemetry "
                         "sidecar (prof.metrics; pass a path or let it "
                         "auto-name)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from _perf_common import emit_result, make_decoder_lm, open_telemetry
    from apex_tpu.utils import setup_host_backend

    # CPU configs below apply only under an explicit CPU request; with
    # nothing pinned and no chip the gate raises
    on_tpu = setup_host_backend() == "tpu"
    if not on_tpu and args.spec:
        # CPU spec A/B regime: decode must be weight-streaming-bound
        # for the draft's cheapness to show (tiny dims are
        # op-overhead-bound and spec can only lose there), and damped
        # residual writes stand in for trained-model draft agreement
        args.prompt, args.new, args.batch, args.layers = 16, 64, 2, 8
        args.dim, args.heads, args.vocab = 512, 8, 512
        args.iters = 2
        args.dtype = "f32"
        if args.spec_damp == 0.0:
            args.spec_damp = 0.1
    elif not on_tpu:  # CPU smoke config
        args.prompt, args.new, args.batch, args.layers = 16, 8, 2, 2
        args.dim, args.heads, args.vocab = 128, 4, 512
        args.iters = 2
    _note(f"backend={jax.default_backend()} P={args.prompt} "
          f"new={args.new} B={args.batch} h{args.heads}"
          f"d{args.dim // args.heads}")

    # runtime telemetry sidecar (r07): compile counts + decode-step
    # timings + stall records, logged outside the timed calls
    telem, telem_wd, _feed = open_telemetry(
        args.telemetry, tag=f"decode_P{args.prompt}", run="decode_bench",
        meta=vars(args), feed=_feed)
    if telem is not None:
        _note(f"telemetry sidecar: {telem.path}")

    half = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
    lm, params, prompt = make_decoder_lm(
        vocab=args.vocab, dim=args.dim, heads=args.heads,
        layers=args.layers, max_seq_len=args.prompt + args.new,
        dtype=args.dtype,
        host_extras=lambda: jax.random.randint(
            jax.random.key(1), (args.batch, args.prompt), 0, args.vocab))
    _note("params + prompt shipped")

    if args.fused:
        # fused-vs-reference decode-step A/B (r14): both arms drain the
        # SAME seeded prompt batch through the serving engine under the
        # static policy (every slot seated, then pure decode), so the
        # per-step medians isolate the decode program — and greedy
        # parity is asserted on the emitted streams, not assumed.
        import numpy as np

        from apex_tpu.serve import ContinuousBatchingEngine, Request
        chunk = min(args.prompt, 32)
        reqs = [Request(id=i, prompt=np.asarray(prompt[i], np.int32),
                        max_new=args.new)
                for i in range(args.batch)]
        arms = {}
        for name, fused in (("reference", False), ("fused", True)):
            _note(f"[{name}] building engine "
                  f"(slots={args.batch}, chunk={chunk})")
            eng = ContinuousBatchingEngine(
                lm, params, slots=args.batch,
                max_len=args.prompt + args.new, prefill_chunk=chunk,
                policy="static", fused=fused)
            _feed(allow=1200.0)
            eng.warmup()         # compile + layout-stabilize
            eng.run(reqs)        # warm the exact workload untimed
            _note(f"[{name}] timed drain")
            results, stats = eng.run(reqs)
            arms[name] = (results, stats)
        ref_res, ref_stats = arms["reference"]
        fus_res, fus_stats = arms["fused"]
        streams_equal = ([r.tokens for r in ref_res]
                         == [r.tokens for r in fus_res])
        if not streams_equal:
            raise RuntimeError(
                "fused decode step diverged from the reference on "
                "greedy streams — the parity contract is bit-equality")
        fused_p50 = float(np.median(fus_stats["step_ms"]))
        ref_p50 = float(np.median(ref_stats["step_ms"]))
        out = {
            "metric": (f"lm_fused_decode_ab_P{args.prompt}"
                       f"_N{args.new}_b{args.batch}"
                       f"_h{args.heads}d{args.dim // args.heads}"
                       + ("_bf16" if half == jnp.bfloat16 else "")),
            "value": round(fused_p50, 3),
            "unit": "ms/decode_step(p50)",
            "fused_ms_p50": round(fused_p50, 3),
            "reference_ms_p50": round(ref_p50, 3),
            "speedup": round(ref_p50 / max(fused_p50, 1e-9), 3),
            "fused_prefill_calls": fus_stats["prefill_chunks"],
            "reference_prefill_calls": ref_stats["prefill_chunks"],
            "prefill_batch_mean": round(
                float(np.mean(fus_stats["prefill_batch_sizes"])), 2),
            "decode_steps": fus_stats["decode_steps"],
            "parity": "greedy-bit-equal",
            "batch": args.batch,
            "prompt": args.prompt,
            "new_tokens": args.new,
            "dtype": "bfloat16" if half == jnp.bfloat16 else "float32",
            "heads": args.heads,
            "head_dim": args.dim // args.heads,
        }
        if telem is not None:
            telem.log_step(1, step_ms=fused_p50, phase="decode_fused",
                           reference_ms_p50=ref_p50)
            telem_wd.stop()
            telem.close()
            out["telemetry"] = telem.path
            from apex_tpu.prof.metrics import SCHEMA_VERSION
            out["telemetry_schema"] = SCHEMA_VERSION
        emit_result(out, "decode_bench")
        return

    if args.spec:
        # spec-vs-plain fused A/B (r21): both arms drain the SAME
        # seeded prompts through the fused PAGED engine; the spec arm
        # adds a first-N-layers draft + (k+1)-query target scoring.
        # Greedy bit-equality is asserted, not assumed — losslessness
        # is part of the measurement.
        import numpy as np

        from apex_tpu.serve import (ContinuousBatchingEngine, Request,
                                    draft_from_prefix)
        if args.spec_damp > 0.0:
            params = dict(params)
            for i in range(args.layers):
                lay = dict(params[f"layer_{i}"])
                attn, mlp = dict(lay["attn"]), dict(lay["mlp"])
                for kk in ("out_proj", "out_proj_bias"):
                    attn[kk] = attn[kk] * args.spec_damp
                for kk in ("w2", "b2"):
                    mlp[kk] = mlp[kk] * args.spec_damp
                lay["attn"], lay["mlp"] = attn, mlp
                params[f"layer_{i}"] = lay
        chunk = min(args.prompt, 32)
        max_len = args.prompt + args.new
        page = 16
        reqs = [Request(id=i, prompt=np.asarray(prompt[i], np.int32),
                        max_new=args.new)
                for i in range(args.batch)]
        arms = {}
        for name in ("baseline", "spec"):
            _note(f"[{name}] building engine (slots={args.batch}, "
                  f"k={args.spec_k}, draft_layers={args.spec_layers})")
            kw = dict(slots=args.batch, max_len=max_len,
                      prefill_chunk=chunk, policy="static", fused=True,
                      paged=True, page_size=page,
                      kv_pages=args.batch * (-(-max_len // page)) + 8)
            if name == "spec":
                kw.update(draft=draft_from_prefix(lm, params,
                                                  args.spec_layers),
                          spec_k=args.spec_k)
            eng = ContinuousBatchingEngine(lm, params, **kw)
            _feed(allow=1200.0)
            eng.warmup()         # compile + layout-stabilize
            eng.run(reqs)        # warm the exact workload untimed
            _note(f"[{name}] timed drain")
            t0 = time.perf_counter()
            results, stats = eng.run(reqs)
            arms[name] = (results, stats,
                          time.perf_counter() - t0)
        base_res, base_stats, base_dt = arms["baseline"]
        spec_res, spec_stats, spec_dt = arms["spec"]
        streams_equal = ([r.tokens for r in base_res]
                         == [r.tokens for r in spec_res])
        if not streams_equal:
            raise RuntimeError(
                "speculative greedy streams diverged from the plain "
                "fused engine — the r21 contract is bit-equality")
        ntok = sum(len(r.tokens) for r in base_res)
        base_tps = ntok / base_dt
        spec_tps = ntok / spec_dt
        out = {
            "metric": (f"lm_spec_decode_ab_P{args.prompt}"
                       f"_N{args.new}_b{args.batch}"
                       f"_k{args.spec_k}dl{args.spec_layers}"
                       f"_h{args.heads}d{args.dim // args.heads}"
                       + ("_bf16" if half == jnp.bfloat16 else "")),
            "value": round(spec_tps, 1),
            "unit": "decoded_tokens/s",
            "baseline_tok_s": round(base_tps, 1),
            "speedup": round(spec_tps / max(base_tps, 1e-9), 3),
            "spec_k": args.spec_k,
            "spec_layers": args.spec_layers,
            "spec_damp": args.spec_damp,
            "spec_accept_mean": round(
                spec_stats["spec_accept_mean"], 3),
            "spec_accept_hist": spec_stats["spec_accept_hist"],
            "spec_draft_tokens": spec_stats["spec_draft_tokens"],
            "spec_steps": spec_stats["spec_steps"],
            "baseline_steps": base_stats["decode_steps"],
            "parity": "greedy-bit-equal",
            "batch": args.batch,
            "prompt": args.prompt,
            "new_tokens": args.new,
            "layers": args.layers,
            "dtype": "bfloat16" if half == jnp.bfloat16 else "float32",
            "heads": args.heads,
            "head_dim": args.dim // args.heads,
        }
        if telem is not None:
            telem.log_step(1, step_ms=float(np.median(
                spec_stats["step_ms"])), phase="decode_spec",
                spec_accept_mean=out["spec_accept_mean"])
            telem_wd.stop()
            telem.close()
            out["telemetry"] = telem.path
            from apex_tpu.prof.metrics import SCHEMA_VERSION
            out["telemetry_schema"] = SCHEMA_VERSION
        emit_result(out, "decode_bench")
        return

    # Every generate() call includes the PROMPT PREFILL, so timing one
    # program and dividing by new tokens would conflate prefill compute
    # with decode throughput. Difference two compiled variants that
    # differ only in max_new_tokens: the per-decode-step cost is
    # (dt_long - dt_short)/(N_long - N_short), prefill cancels.
    n_short = max(2, args.new // 4)
    if n_short >= args.new:
        n_short = args.new // 2
    def make(nn):
        return jax.jit(lambda p, t: lm.generate(p, t, max_new_tokens=nn))

    gens = {n: make(n) for n in (n_short, args.new)}
    _note(f"compiling both variants (N={n_short}, {args.new})")
    _feed(allow=1200.0)
    t0 = time.perf_counter()
    for n, g in gens.items():
        # apex-lint: disable=host-sync-in-hot-loop -- warm-up: each variant compiles and finishes before the timed calls
        jax.block_until_ready(g(params, prompt))
    _note(f"compiled+first calls in {time.perf_counter() - t0:.0f}s")

    # On the CPU smoke config a generate() takes ~3 ms and the variants
    # differ by six decode steps of ~0.05 ms: one stall of a loaded host
    # times the short variant as the slower one and the step clamps to
    # 0. A stall only ever adds time, so there each variant keeps the
    # best of many rounds; the chip's timings need one.
    rounds = 1 if on_tpu else 50

    def timed(g):
        best = float("inf")
        for _ in range(rounds):
            t0 = time.perf_counter()
            for _ in range(args.iters):
                out = g(params, prompt)
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / args.iters)
        return best, out

    dt_short, _ = timed(gens[n_short])
    dt_long, out = timed(gens[args.new])
    assert out.shape == (args.batch, args.prompt + args.new)
    step_s = max(dt_long - dt_short, 1e-9) / (args.new - n_short)
    decode_tok_s = args.batch / step_s
    prefill_ms = max(dt_long - args.new * step_s, 0.0) * 1e3
    out = {
        "metric": (f"lm_decode_tok_s_P{args.prompt}_N{args.new}"
                   f"_b{args.batch}"
                   f"_h{args.heads}d{args.dim // args.heads}"
                   + ("_bf16" if half == jnp.bfloat16 else "")),
        # decode-ONLY throughput (prefill differenced out)
        "value": round(decode_tok_s, 1),
        "unit": "decoded_tokens/s",
        "decode_ms_per_step": round(step_s * 1e3, 3),
        "prefill_ms": round(prefill_ms, 1),
        "e2e_tok_s": round(args.batch * args.new / dt_long, 1),
        "batch": args.batch,
        "prompt": args.prompt,
        "new_tokens": args.new,
        "dtype": "bfloat16" if half == jnp.bfloat16 else "float32",
        "heads": args.heads,
        "head_dim": args.dim // args.heads,
    }
    if telem is not None:
        telem.log_step(args.new, steps=args.new, step_ms=step_s * 1e3,
                       throughput=decode_tok_s, unit="decoded_tokens/s",
                       phase="decode", prefill_ms=round(prefill_ms, 1))
        telem_wd.stop()
        telem.close()
        out["telemetry"] = telem.path
        from apex_tpu.prof.metrics import SCHEMA_VERSION
        out["telemetry_schema"] = SCHEMA_VERSION
    emit_result(out, "decode_bench")


if __name__ == "__main__":
    main()
