"""TransformerLM training throughput bench (the long-context headline).

The RN50 bench (bench.py) covers the reference's own L1 vehicle; this
covers the beyond-parity surface — flash attention + fused xentropy +
FusedAdam on a decoder LM — at sequence lengths where the attention
implementation decides feasibility (docs/PERF.md "Long context": at
S=16384 the unfused path OOMs on a v5e while the flash kernel runs).

fori_loop timing, one JSON line per config:
    python tools/lm_bench.py [--seq 4096] [--attn fast|default]
        [--layers 8] [--dim 1024] [--heads 16] [--batch 8]

MFU numerator: 6 * P * tokens (dense param flops, fwd+bwd) +
6 * L * d * S^2 * B (attention scores+values fwd+bwd, causal halved) —
the standard decoder-LM accounting (12*L*d*S^2 per batch elem full,
halved for causal).
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
    sys.stderr.write(f"lmbench[{time.strftime('%H:%M:%S')}]: {m}\n")
    sys.stderr.flush()


def build_train_step(lm, params, mesh, *, half, zero=False, lr=1e-4):
    """The dense-LM train step every arm here — and ``chip_smoke.py`` —
    compiles: FusedAdam over flat fp32 masters (the O2 master-weight
    pattern: differentiate wrt the FLAT master, ``unflatten``'s dtype
    arg fuses the ``half`` cast and its transpose returns ONE flat fp32
    grad), replicated + DDP over a >1-device ``mesh`` (the flat master
    then in DDP's buckets, each bucket's flat grad reduced where its
    backward ends, the sums joined into the optimizer's one buffer), or
    with ``zero`` the DistributedFusedAdam 1/n shards. Call under
    ``host_init()``: the optimizers flatten real arrays.

    Returns ``(opt, state, step, plan)``; ``step(state, toks) ->
    (state, loss)`` (``(state, (loss, counters))`` for a model that has
    ``loss_with_counters``) is the body ``compile_step_with_plan(body, plan)``
    lowers (a 1-device plan is plain jit — the single-chip program),
    and :func:`place_for_plan` puts ``(state, toks)`` where it wants
    them."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from apex_tpu.contrib.optimizers import DistributedFusedAdam
    from apex_tpu.ops import flat as F
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import DistributedDataParallel, Plan

    n_dev = mesh.size
    # a model with counters of its own (HybridLM: pairs past the dispatch
    # bound, expert load) hands them out beside the loss: the step then
    # returns (state, (loss, counters)). On one chip only: across chips
    # each counter would need its own reduction (a sum, a maximum)
    counted = getattr(lm, "loss_with_counters", None)
    if counted is not None and (zero or n_dev > 1):
        raise NotImplementedError(
            f"{type(lm).__name__} hands counters out of its step, which "
            "only the one-chip FusedAdam step carries")
    if zero:
        opt = DistributedFusedAdam(
            params, lr=lr, axis_name="data", num_shards=n_dev,
            model_dtype=half or jnp.float32)
        table = opt.table
        state_spec = opt.state_pspec()

        def step(state, toks):
            # ZeRO weight-update sharding: full params exist only
            # transiently (compressed all_gather at gather_dtype); the
            # flat grad psum_scatters back to the 1/n shard inside
            # shard_step
            with jax.named_scope("collective"):     # prof.SCOPES
                gathered = lax.all_gather(
                    state.master.astype(opt.gather_dtype), "data",
                    tiled=True)
            loss, fg = jax.value_and_grad(
                lambda g: lm.loss(F.unflatten(g, table, dtype=half),
                                  toks))(gathered)
            new_state, _ = opt.shard_step(state,
                                          fg.astype(jnp.float32))
            return new_state, lax.pmean(loss, "data")
    else:
        opt = FusedAdam(params, lr=lr)
        table = opt._tables[0]
        state_spec = P()
        # DDP sums; the division by the world rides the join below
        ddp = DistributedDataParallel(axis_name="data",
                                      gradient_average=False) \
            if n_dev > 1 else None
        # the flat master in buckets, runs of leaves (the DDP policy's;
        # one chip: one bucket, the buffer itself). Differentiated with
        # respect to the buckets, each bucket's flat gradient is whole
        # where the backward of ITS leaves ends, so its psum runs under
        # the backward of the layers before them
        buckets = F.split_table(
            table, ddp.buckets(table.padded_sizes) if ddp is not None
            else (table.num_segments,))

        def step(state, toks):
            out, fgs = jax.value_and_grad(
                lambda ms: (counted or lm.loss)(
                    F.unflatten_split(ms, buckets, table.treedef,
                                      dtype=half), toks),
                has_aux=counted is not None)(
                    F.split(state[0].master, buckets))
            loss, counters = out if counted else (out, None)
            if ddp is not None:
                # one psum a bucket; the pass that joins the sums into
                # the optimizer's one buffer makes them the average, and
                # is DDP's cost like them (prof.SCOPES)
                fgs = ddp.average_gradients(fgs)
                with jax.named_scope("collective"):
                    fg = F.join(fgs, divisor=n_dev)
                loss = lax.pmean(loss, "data")
            else:
                fg = F.join(fgs)
            return opt.apply_update(state, [fg]), \
                (loss, counters) if counted else loss

    if zero or n_dev > 1:
        plan = Plan(mesh=mesh, in_specs=(state_spec, P("data")),
                    out_specs=(state_spec, P()), donate_argnums=(0,),
                    # all_gather outputs aren't vma-provable replicated;
                    # flash attention's pallas_call skips vma checks too
                    check_vma=False)
    else:
        plan = Plan(mesh=mesh, donate_argnums=(0,))
    return opt, opt.init_state(), step, plan


def place_for_plan(state, toks, plan):
    """Place ``(state, toks)`` as ``plan`` declares them (ZeRO state in
    its 1/n shards, DDP state replicated, tokens split over ``data``),
    so the first call times no reshard and donation holds; a 1-device
    plan gets one bulk transfer to its device."""
    from apex_tpu.parallel import place_with_specs
    from apex_tpu.utils import ship
    if plan.in_specs is None:
        return ship((state, toks), plan.mesh.devices.flat[0])
    return place_with_specs((state, toks), plan.mesh, plan.in_specs)


def main():
    # Stall watchdog, fed by every _note: a hung device call costs
    # PROBE_DEADMAN seconds, not the caller's whole time limit.
    global _feed
    from _perf_common import arm_watchdog
    _feed = arm_watchdog("lm_bench")
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--dim", type=int, default=1024)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--vocab", type=int, default=32768)
    ap.add_argument("--attn", default="fast",
                choices=["fast", "default", "auto"])
    ap.add_argument("--remat-policy", default=None,
                    help="jax.checkpoint_policies name (e.g. "
                         "dots_saveable) for --remat")
    ap.add_argument("--remat", action="store_true",
                    help="rematerialize each block (activation memory "
                         "O(boundaries); enables long-S configs)")
    ap.add_argument("--head-chunk", type=int, default=8192,
                    help="vocab chunk for the fused LM-head loss "
                         "(linear_cross_entropy); 0 materializes full "
                         "[N, V] fp32 logits — the allocation that OOMed "
                         "the r4 --seq 4096 run on a 16 GB chip")
    ap.add_argument("--moe-experts", type=int, default=0,
                    help="replace every --moe-every'th MLP with a "
                         "Switch-MoE of this many experts (0 = dense)")
    ap.add_argument("--moe-every", type=int, default=2)
    ap.add_argument("--moe-top-k", type=int, default=1)
    ap.add_argument("--dtype", default="bf16", choices=["bf16", "f32"],
                    help="model compute dtype. bf16 = the O2 "
                         "master-weight pattern (bench.py train_step): "
                         "fp32 flat masters, ONE fused convert to bf16 "
                         "params inside the loss — the reference's own "
                         "AMP training methodology. f32 reproduces the "
                         "pre-r5 full-precision rows (which understated "
                         "tok/s ~2x vs the bf16-peak MFU denominator "
                         "and OOM'd s4096 on f32 attention temps)")
    # 50 timed iterations: short windows carry the warmup ramp and
    # understate steady state — s2048 h8d128 measured 95,530 tok/s at
    # 50 iters vs 90,047 at 10 on the same chip (r05, docs/PERF.md;
    # the CPU smoke keeps 2)
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--telemetry", nargs="?", const="1", default=None,
                    help="write a TELEM_*.jsonl runtime-telemetry "
                         "sidecar (prof.metrics; pass a path or let it "
                         "auto-name next to this tool's artifacts)")
    ap.add_argument("--fleet-probe", action="store_true",
                    default=os.environ.get("BENCH_FLEET", "")
                    not in ("", "0"),
                    help="r10 fleet: after the timed window, run one "
                         "FleetProbe gather (per-process step-EMA "
                         "all_gather under the apex_fleet_probe scope) "
                         "so the sidecar carries a fleet_skew record; "
                         "needs --telemetry")
    ap.add_argument("--zero", action="store_true",
                    default=os.environ.get("BENCH_ZERO", "")
                    not in ("", "0", "ddp"),
                    help="r11 optimizer arm: DistributedFusedAdam — the "
                         "fp32 (master, m, v) flat buffers shard 1/n "
                         "over the data mesh (psum_scatter grads -> "
                         "sharded update -> compressed all_gather). "
                         "Without it, >1 device runs replicated "
                         "FusedAdam + DDP grad averaging on the same "
                         "mesh. Both compile through "
                         "compile_step_with_plan; the telemetry sidecar "
                         "records params+opt_state bytes/device")
    ap.add_argument("--snapshot", default=os.environ.get(
                    "BENCH_SNAPSHOT") or None, metavar="DIR",
                    help="r17 runtime: arm the async SnapshotWriter — "
                         "one generation after warmup (its host fetch "
                         "+ write overlap the timed window: the async "
                         "contract under measurement) and one of the "
                         "end state; schema-6 snapshot records land in "
                         "the --telemetry sidecar")
    ap.add_argument("--numerics", action="store_true",
                    default=os.environ.get("BENCH_NUMERICS", "")
                    not in ("", "0"),
                    help="r09 numerics: audit the step's precision "
                         "coverage (bf16 share of ops/FLOPs per module, "
                         "fp32-only control-flow bodies) + one sampled "
                         "underflow census of the grads — summary in "
                         "the JSON line, records in the sidecar")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from apex_tpu.models import TransformerLM
    from apex_tpu.ops import flat as F
    from apex_tpu.utils import setup_host_backend

    # the strict device gate: the chip, or the CPU because the caller
    # asked for it (JAX_PLATFORMS=cpu) — never a silent fall-back
    on_tpu = setup_host_backend() == "tpu"
    if not on_tpu:  # CPU smoke config (explicit CPU request only)
        args.seq, args.batch, args.layers = 128, 2, 2
        args.dim, args.heads, args.vocab = 128, 4, 512
        args.iters = 2
    _note(f"backend={jax.default_backend()} S={args.seq} "
          f"L={args.layers} d={args.dim} attn={args.attn}")

    # runtime telemetry sidecar (r07): armed before model build so the
    # compile tracker counts the step's compiles; logging stays outside
    # the timed fori dispatch. The watchdog records stalls into the
    # sidecar; arm_watchdog above still owns the hard exit.
    telem = None
    if args.telemetry:
        from apex_tpu import prof
        path = (args.telemetry if args.telemetry != "1" else
                prof.metrics.default_sidecar_path(
                    f"lmbench_S{args.seq}",
                    os.path.join(os.path.dirname(__file__), "..")))
        telem = prof.MetricsLogger(path, run="lm_bench", meta=vars(args))
        telem_wd = prof.Watchdog(telem, min_interval_s=600.0,
                                 label="lm_bench").start()
        _prev_feed = _feed

        def _feed_and_beat(allow=None):   # noqa: E306
            telem_wd.heartbeat()
            _prev_feed(allow)
        _feed = _feed_and_beat
        _note(f"telemetry sidecar: {path}")

    if args.head_chunk and args.vocab % min(args.head_chunk, args.vocab):
        ap.error(f"--head-chunk must divide --vocab ({args.vocab})")
    lm = TransformerLM(vocab_size=args.vocab, max_seq_len=args.seq,
                      embed_dim=args.dim, num_heads=args.heads,
                      num_layers=args.layers, attn_impl=args.attn,
                      remat=args.remat,
                      remat_policy=args.remat_policy,
                      head_chunk=min(args.head_chunk, args.vocab),
                      moe_experts=args.moe_experts,
                      moe_every=args.moe_every,
                      moe_top_k=args.moe_top_k)
    half = jnp.bfloat16 if args.dtype == "bf16" else None
    # the data mesh every arm compiles over (1-device meshes plan down
    # to plain jit — the single-chip program is unchanged); device
    # count read BEFORE host_init so the mesh sees the real backend
    n_dev = len(jax.devices())
    if args.batch % n_dev:
        args.batch += -args.batch % n_dev   # global batch must shard

    from apex_tpu.parallel import compile_step_with_plan, make_mesh
    from apex_tpu.utils import host_init
    mesh = make_mesh({"data": n_dev})
    with host_init():
        opt, state, step, plan = build_train_step(
            lm, lm.init(jax.random.key(0)), mesh, half=half,
            zero=args.zero)
        table = opt.table if args.zero else opt._tables[0]
        n_params = int(table.total)
        toks = jax.random.randint(jax.random.key(1),
                                  (args.batch, args.seq), 0, args.vocab)
    _note("host-side init done; placing state on the mesh")
    state, toks = place_for_plan(state, toks, plan)
    _note("state on device")
    # NB: past ~237M params XLA's remat-compression pass OOMs the chip
    # on a pathologically tiled copy of the fp32 master (docs/PERF.md
    # "Platform finding", r05); neither per-leaf casts nor a
    # lane-aligned pre-reshape dissuade it, so there is no code-side
    # workaround — keep single-device configs under ~150M params.

    def run_n_body(state, toks):
        def body(i, carry):
            st, _ = carry
            return step(st, toks)
        return jax.lax.fori_loop(
            0, args.iters, body, (state, jnp.asarray(0.0, jnp.float32)))

    run_n = compile_step_with_plan(run_n_body, plan)

    def _master0(state):
        return state.master if args.zero else state[0].master

    _note(f"compiling (plan lowering={plan.lowering()}, "
          f"{n_dev} device(s))")
    _feed(allow=2400.0)  # a long-S remat compile may exceed the default
    t0 = time.perf_counter()
    compiled = run_n.lower(state, toks).compile()
    _note(f"compiled in {time.perf_counter()-t0:.0f}s")  # tight again
    state, loss = compiled(state, toks)
    float(loss), float(_master0(state)[0])
    snap_writer = None
    if args.snapshot:
        # r17: generation 0 = the post-warmup state; staged device
        # copies now (the state is donated into the timed dispatch),
        # host fetch + sharded write on the writer thread UNDER the
        # timed window — the async contract, measured
        from apex_tpu import runtime as _rt

        def _snap_payload(state):
            return {"opt": (opt.state_dict_arrays(state) if args.zero
                            else {"master": state[0].master})}
        snap_writer = _rt.SnapshotWriter(args.snapshot, logger=telem)
        snap_writer.submit(0, 0, _snap_payload(state))
    t0 = time.perf_counter()
    state, loss = compiled(state, toks)
    float(loss), float(_master0(state)[0])
    dt = (time.perf_counter() - t0) / args.iters
    if snap_writer is not None:
        snap_writer.submit(args.iters, args.iters, _snap_payload(state))
        snap_writer.close()   # drains both generations

    tokens = args.batch * args.seq
    tok_s = tokens / dt
    # dense fwd+bwd ~ 6 flops/param/token; attention fwd+bwd =
    # 12*L*d*S^2*B (qk^T + av, with backward = 2x forward), /2 causal
    attn_flops = (12 * args.layers * args.dim * args.seq * args.seq
                  * args.batch) / 2
    step_flops = 6.0 * n_params * tokens + attn_flops
    from apex_tpu.prof import chip_peak
    peak = chip_peak().bf16_flops_per_s if on_tpu else None
    out = {
        "metric": (f"lm_train_tok_s_S{args.seq}_attn_{args.attn}"
                   + ("_remat" if args.remat else "")
                   + ("_fusedhead" if args.head_chunk else "")
                   + ("_bf16" if half is not None else "")
                   # head shape is a ~45% lever (see the "heads" field
                   # note): rows differing only in --heads must not
                   # collide under one metric key
                   + f"_h{args.heads}d{args.dim // args.heads}"
                   + (f"_moe{args.moe_experts}top{args.moe_top_k}"
                      f"every{args.moe_every}"
                      if args.moe_experts else "")
                   # distributed arms must not collide with the
                   # single-device rows under one metric key
                   + (f"_zero{n_dev}dev" if args.zero else
                      (f"_ddp{n_dev}dev" if n_dev > 1 else ""))),
        "value": round(tok_s, 1),
        "unit": "tokens/s",
        "ms_per_step": round(dt * 1e3, 2),
        "params_m": round(n_params / 1e6, 2),
        "loss": round(float(loss), 4),
        "batch": args.batch,
        "iters": args.iters,
        "dtype": "bfloat16" if half is not None else "float32",
        # head_dim decides flash-kernel efficiency on TPU (64 pads to
        # 128 lanes and doubles the per-head softmax count): measured
        # +30-76% tok/s at head_dim 128 vs 64, same analytic FLOPs
        "heads": args.heads,
        "head_dim": args.dim // args.heads,
    }
    if args.moe_experts:
        out["moe_experts"] = args.moe_experts
        out["moe_top_k"] = args.moe_top_k
        out["moe_every"] = args.moe_every
    if args.zero or n_dev > 1:
        from apex_tpu.prof.metrics import tracked_bytes_per_device
        out["devices"] = n_dev
        out["zero"] = bool(args.zero)
        out["opt_state_bytes_per_device"] = \
            tracked_bytes_per_device(state)
    if peak:
        if args.moe_experts:
            # the 6*P*tokens flop model counts EVERY expert's params
            # but only top-k experts run per token — an MFU from it
            # would overstate; report throughput only
            out["mfu_note"] = ("omitted: dense param-count flop model "
                               "overcounts inactive experts")
        else:
            out["mfu"] = round(step_flops / dt / peak, 4)
    if args.numerics:
        # r09 numerics (untimed, after the measurement): precision
        # coverage of the step (abstract trace — free at any size; the
        # bf16 share per module + any fp32-only scan bodies the remat
        # path hides) and one underflow census of the current grads
        # (fraction that would sit subnormal / flush to zero in fp16 —
        # bf16 keeps the fp32 exponent range, so this measures fp16
        # headroom, not bf16 loss).
        try:
            from apex_tpu.prof import coverage as COV
            from apex_tpu.prof import numerics as NU
            cov = COV.audit_fn(step, state, toks)
            meta = NU.tree_meta(table)

            @jax.jit
            def _grad_probe(state, toks):
                # GSPMD view: works for the ZeRO arm too — the sharded
                # master reads as one global array outside shard_map
                fg = jax.grad(lambda m: lm.loss(
                    F.unflatten(m, table, dtype=half), toks))(
                    _master0(state))
                return NU.underflow_census(fg, table=table)

            ucensus = _grad_probe(state, toks)
            usum = NU.underflow_summary(meta, ucensus)
            out["numerics"] = {
                "half_op_share": round(cov.half_op_share, 4),
                "half_flop_share": round(cov.half_flop_share, 4),
                "cf_fp32_only": list(cov.cf_fp32_only),
                "tiny_frac": usum["tiny_frac"],
                "ftz_frac": usum["ftz_frac"],
            }
            if telem is not None:
                telem.log_coverage(cov, label="lm_step")
                telem.log_numerics(meta, ucensus, step=args.iters)
            _note(f"numerics: half_op_share {out['numerics']['half_op_share']}"
                  f" cf_fp32_only={len(cov.cf_fp32_only)}")
        except Exception as e:  # never lose the tok/s line to numerics
            _note(f"numerics pass failed: {type(e).__name__}: {e}")
            out["numerics"] = {"error": f"{type(e).__name__}: {e}"}
    if snap_writer is not None:
        out["snapshots"] = snap_writer.written
        out["snapshot_dir"] = args.snapshot
    if telem is not None:
        telem.log_step(args.iters, steps=args.iters, step_ms=dt * 1e3,
                       throughput=tok_s, unit="tokens/s", loss=loss,
                       phase="fori")
        # sharding-derived per-device state footprint (r11): the row
        # telemetry_report --compare turns into the ZeRO HBM delta
        telem.log_state_bytes(
            opt_state=state,
            label="zero" if args.zero else
            ("ddp" if n_dev > 1 else "replicated"))
        if args.fleet_probe:
            try:  # one untimed gather; never lose the tok/s line to it
                from apex_tpu.prof import fleet as FL
                FL.FleetProbe(telem, every=1).observe(args.iters,
                                                      dt * 1e3)
            except Exception as e:
                _note(f"fleet probe failed: {type(e).__name__}: {e}")
        telem_wd.stop()
        telem.close()
        out["telemetry"] = telem.path
        from apex_tpu.prof.metrics import SCHEMA_VERSION
        out["telemetry_schema"] = SCHEMA_VERSION
    from _perf_common import emit_result
    emit_result(out, "lm_bench")


if __name__ == "__main__":
    main()
