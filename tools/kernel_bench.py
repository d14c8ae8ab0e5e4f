"""Kernel-vs-XLA microbenchmarks (VERDICT r2 task #7).

Times each Pallas kernel against the XLA/jnp implementation of the same
op, on-chip, with fori_loop timing (one dispatch per measurement, warmup
call first). Prints one JSON line per benchmark and a markdown table at
the end (docs/PERF.md "Optimizer / BN kernels" keeps the r03-r05 rows).

Benchmarks:
  flash    : flash attention fwd+bwd vs jnp reference_attention, causal,
             S in {1k, 4k, 16k} (16k jnp fwd+bwd materializes S^2 — may OOM;
             recorded as such), then the kernels alone at the benchmark
             cells' shapes; each row with its block_census (grid steps
             that are dead / interior / edge, forward and backward)
  flash_crossover : the impl='auto' dispatch sweep, S in {512..8192};
             prints the measured flash_min_s (the committed constant is
             flash_attention.DEFAULT_FLASH_MIN_S)
  flash_verify / flash_blocks : anomaly recheck / block-size sweep
  mlp      : the reference's MLP microbenchmark, bf16 against fp32
  linear_xent : the fused LM head, per-row (vocabulary chunks) and reduced
             (row blocks), against materialized logits

LayerNorm, xentropy, the BatchNorm moments and LAMB have no kernel to time:
XLA's side won each on the chip and is the only one (docs/PERF.md
"Optimizer / BN kernels" keeps the r03-r05 rows that decided it).

Usage: python tools/kernel_bench.py [--only flash,mlp,...] [--steps N]
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
import os
# repo root importable without PYTHONPATH
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

results = []
# per-benchmark pass times from time_fn's two timed passes (ms) — rows
# that care about pass-to-pass drift (flash_verify) surface them in
# their JSON instead of letting min-of-two hide an anomaly recurrence
PASS_TIMES = {}


_feed = lambda: None  # rebound by arm_watchdog in main()


def _note(m):
    _feed()
    sys.stderr.write(f"kbench[{time.strftime('%H:%M:%S')}]: {m}\n")
    sys.stderr.flush()


def time_fn(name, fn, *args, steps=20):
    """jit(fori_loop(steps)) timing with a warmup call then one timed
    call. The first (float array) argument is perturbed by the carry and
    the carry folds in the output, creating a genuine loop-carried
    dependency — otherwise XLA hoists a loop-invariant pure-HLO body out
    of the while loop and the measurement times it once, not N times."""
    import jax
    import jax.numpy as jnp

    # args are passed through jit as real arguments — closing over them
    # would embed multi-MB constants in the program.
    @functools.partial(jax.jit, static_argnums=(1,))
    def run(c0, n, a0, *rest):
        def body(i, c):
            out = fn(a0 + (c * 1e-30).astype(a0.dtype), *rest)
            # anchor EVERY output leaf so XLA cannot DCE part of the
            # computation (a multi-output Pallas call is opaque, but the
            # jnp twin's unused outputs would be eliminated, biasing the
            # comparison); *0.0 is not foldable (NaN semantics)
            probe = sum(jnp.sum(l.ravel()[:1]).astype(jnp.float32)
                        for l in jax.tree.leaves(out))
            return c + probe * 0.0 + 1.0
        return jax.lax.fori_loop(0, n, body, c0)

    try:
        _feed(allow=2400.0)  # one compile may legitimately run long
        t0 = time.perf_counter()
        compiled = run.lower(jnp.asarray(0.0, jnp.float32), steps,
                             *args).compile()
        compile_s = time.perf_counter() - t0
        _note(f"{name}: compiled in {compile_s:.0f}s")  # tight window again
        c = compiled(jnp.asarray(0.0, jnp.float32), *args)
        float(c)
        # two timed passes, report the min: r04 produced two
        # contradictory flash rows whose common trait was being the
        # FIRST timed kernel in their process (s1024 default 26.9 ms vs
        # r3's 4.4; explicit f512b512 162.8 vs the identical default
        # config's 17.1) — a one-time warm-path cost poisons
        # single-pass timing; min-of-two bounds it
        dts = []
        for _ in range(2):
            t0 = time.perf_counter()
            c = compiled(c * 0.0, *args)
            float(c)
            dts.append((time.perf_counter() - t0) / steps)
            _feed()  # each pass is progress — don't let two slow-but-
            # legitimate passes accumulate into a watchdog hard-exit
        dt = min(dts)
        PASS_TIMES[name] = [round(d * 1e3, 3) for d in dts]
        _note(f"{name}: {dt*1e3:.3f} ms/iter (passes "
              f"{', '.join(f'{d*1e3:.3f}' for d in dts)}; "
              f"compile {compile_s:.0f}s)")
        return dt
    except Exception as e:
        _note(f"{name}: FAILED {type(e).__name__}: {str(e)[:200]}")
        return None


def _stamp(row):
    """run_meta/format tag (r16) on every row line — KBENCH
    captures stay self-describing without a separate header."""
    from _perf_common import stamp_result
    return stamp_result(row, "kernel_bench")


def record(bench, config, pallas_s, xla_s, **more):
    row = {"bench": bench, "config": config,
           "pallas_ms": None if pallas_s is None else round(pallas_s * 1e3, 3),
           "xla_ms": None if xla_s is None else round(xla_s * 1e3, 3), **more}
    if pallas_s and xla_s:
        row["speedup_vs_xla"] = round(xla_s / pallas_s, 2)
    results.append(row)
    print(json.dumps(_stamp(row)), flush=True)


FLASH_CELL_SHAPES = ((160, 8192, 256), (64, 8192, 128), (32, 8192, 256),
                     (384, 2048, 128))


def _flash_census(s):
    """Grid steps by kind, ``dead/interior/edge``, of causal self-attention
    over ``s`` tokens at the kernels' default blocks."""
    from apex_tpu.contrib.multihead_attn.flash_attention import (
        block_census, block_sizes)
    fq, fk, bq, bk = block_sizes(s, s)
    return {name: "/".join(str(n) for n in block_census(
        s, s, *blocks, causal=True).values())
        for name, blocks in (("fwd", (fq, fk)), ("bwd", (bq, bk)))}


def bench_flash(steps):
    import jax
    import jax.numpy as jnp
    from apex_tpu.contrib.multihead_attn import (flash_attention,
                                                 reference_attention)
    bh, d = 16, 64
    for s in (1024, 4096, 16384):
        ks = jax.random.split(jax.random.key(0), 3)
        q, k, v = (jax.random.normal(kk, (bh, s, d), jnp.bfloat16)
                   for kk in ks)

        def f_pallas(q, k, v):
            return jax.grad(lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, causal=True).astype(jnp.float32)),
                argnums=(0, 1, 2))(q, k, v)

        def f_xla(q, k, v):
            return jax.grad(lambda q, k, v: jnp.sum(
                reference_attention(q, k, v, causal=True)
                .astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)

        n = max(2, steps // max(1, s // 1024))
        tp = time_fn(f"flash_s{s}_pallas", f_pallas, q, k, v, steps=n)
        tx = time_fn(f"flash_s{s}_xla", f_xla, q, k, v, steps=n)
        record("flash_fwd_bwd", f"bh{bh} s{s} d{d} causal bf16", tp, tx,
               block_census=_flash_census(s))

    # the benchmark's training cells (kvl, lfm2, qnext, cgpt): the kernels
    # alone at the shapes each gives them. S^2 scores do not fit: no XLA.
    for bh, s, d in FLASH_CELL_SHAPES:
        ks = jax.random.split(jax.random.key(0), 3)
        q, k, v = (jax.random.normal(kk, (bh, s, d), jnp.bfloat16)
                   for kk in ks)
        tp = time_fn(f"flash_bh{bh}_s{s}_d{d}_pallas", f_pallas, q, k, v,
                     steps=max(2, steps // 4))
        record("flash_fwd_bwd", f"bh{bh} s{s} d{d} causal bf16", tp, None,
               block_census=_flash_census(s))


def bench_flash_blocks(steps):
    """Sweep (block_q, block_k) x (bwd_block_q, bwd_block_k) for the flash
    kernel at a long sequence — the tuning run behind VERDICT r4 task #3.
    Env: KBENCH_FLASH_S (default 4096)."""
    import os

    import jax
    import jax.numpy as jnp
    from apex_tpu.contrib.multihead_attn import flash_attention
    bh = int(os.environ.get("KBENCH_FLASH_BH", 16))
    d = 64
    s = int(os.environ.get("KBENCH_FLASH_S", 4096))
    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (bh, s, d), jnp.bfloat16)
               for kk in ks)
    n = max(2, steps // max(1, s // 1024))
    # blocks must tile the 128-rounded (padded) length, not raw s —
    # flash_attention's own validation uses the padded length
    sp = ((s + 127) // 128) * 128
    combos = [(512, 512, 512, 512), (512, 512, 256, 256),
              (512, 512, 128, 128), (512, 512, 256, 512),
              (512, 512, 512, 256), (256, 256, 256, 256),
              (512, 512, 128, 512), (128, 128, 128, 128)]
    base = None
    ran = 0
    for fq, fk, bq, bk in combos:
        if any(sp % b for b in (fq, fk, bq, bk)):
            continue

        def f(q, k, v, _fq=fq, _fk=fk, _bq=bq, _bk=bk):
            return jax.grad(lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, causal=True, block_q=_fq,
                                block_k=_fk, bwd_block_q=_bq,
                                bwd_block_k=_bk).astype(jnp.float32)),
                argnums=(0, 1, 2))(q, k, v)

        t = time_fn(f"flash_s{s}_f{fq}x{fk}_b{bq}x{bk}", f, q, k, v,
                    steps=n)
        ran += 1
        # NOT a pallas-vs-xla comparison (record()'s schema): every row
        # here is the Pallas kernel at a different block config, compared
        # against the first SUCCESSFUL combo
        if base is None and t is not None:
            base = (f"f{fq}x{fk} b{bq}x{bk}", t)
        row = {"bench": "flash_blocks",
               "config": f"s{s} fwd {fq}x{fk} bwd {bq}x{bk}",
               "ms": None if t is None else round(t * 1e3, 3),
               "baseline": base[0] if base else None,
               "vs_baseline_config": (None if (t is None or not base)
                                      else round(t / base[1], 3))}
        results.append(row)
        print(json.dumps(_stamp(row)), flush=True)
    if not ran:
        _note(f"flash_blocks: no block combo tiles padded S={sp}; "
              f"nothing measured")


def bench_flash_verify(steps):
    """Anomaly recheck for the r4 window's contradictory flash rows:
    (a) s1024 default blocks measured 26.9 ms vs round-3's 4.4 ms with
    128s; (b) s4096 default (= f512 b512 by _pick_block) measured
    17.1 ms while the EXPLICIT f512x512 b512x512 sweep row measured
    162.8 ms — identical configs, 10x apart. Measures each config TWICE
    in interleaved order within ONE process so drift shows up as
    pass-to-pass disagreement instead of silently poisoning one row."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.contrib.multihead_attn import flash_attention
    bh, d = 16, 64
    # KBENCH_VERIFY_S trims the list (CPU smoke: interpret-mode flash at
    # s4096 runs minutes/iter; use e.g. "256")
    seqs = [int(s) for s in
            os.environ.get("KBENCH_VERIFY_S", "1024,4096").split(",")]
    block_sets = {1024: [None, (128, 128, 128, 128),
                         (512, 512, 256, 512)],
                  4096: [None, (512, 512, 512, 512),
                         (512, 512, 256, 512), (128, 128, 128, 128)]}
    configs = [(s, b) for s in seqs
               for b in block_sets.get(s, [None, (128, 128, 128, 128)])
               if b is None or all(((s + 127) // 128 * 128) % x == 0
                                   for x in b)]
    for rep in (1, 2):
        for s, blocks in configs:
            ks = jax.random.split(jax.random.key(0), 3)
            q, k, v = (jax.random.normal(kk, (bh, s, d), jnp.bfloat16)
                       for kk in ks)
            kw = {} if blocks is None else dict(
                block_q=blocks[0], block_k=blocks[1],
                bwd_block_q=blocks[2], bwd_block_k=blocks[3])

            def f(q, k, v, _kw=kw):
                return jax.grad(lambda q, k, v: jnp.sum(
                    flash_attention(q, k, v, causal=True, **_kw)
                    .astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)

            n = max(2, steps // max(1, s // 1024))
            name = "default" if blocks is None else \
                "f{}x{}_b{}x{}".format(*blocks)
            t = time_fn(f"flash_s{s}_{name}_rep{rep}", f, q, k, v, steps=n)
            row = {"bench": "flash_verify",
                   "config": f"s{s} {name} rep{rep}",
                   "ms": None if t is None else round(t * 1e3, 3),
                   "passes_ms": PASS_TIMES.get(
                       f"flash_s{s}_{name}_rep{rep}"),
                   "baseline": "self", "vs_baseline_config": None}
            results.append(row)
            print(json.dumps(_stamp(row)), flush=True)


def bench_mlp(steps):
    """The reference's own MLP microbenchmark config (tests/L0/run_mlp/
    test_mlp.py:11-13: mlp_sizes [480,1024,1024,512,256,1], batch 1024,
    timed fwd+bwd) — on TPU the MLP is a whole-block XLA callable by
    design (SURVEY §2.2), so both columns time the same path in fp32 vs
    bf16-input O2 style (the interesting TPU axis)."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.mlp import MLP
    sizes = [480, 1024, 1024, 512, 256, 1]
    m = MLP(sizes)
    params = m.init(jax.random.key(0))
    x32 = jax.random.normal(jax.random.key(1), (1024, sizes[0]),
                            jnp.float32)
    pb = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)

    # params ride time_fn's *args (real jit arguments — closures would
    # embed ~9 MB of HLO constants);
    # grads are wrt x AND the weights, so the timed backward includes
    # every layer's dW GEMM like the reference's training backward.
    def f32(x, p):
        return jax.grad(lambda x, p: jnp.sum(m.apply(p, x) ** 2),
                        argnums=(0, 1))(x, p)

    def fbf16(x, p):
        return jax.grad(lambda x, p: jnp.sum(
            m.apply(p, x.astype(jnp.bfloat16)).astype(jnp.float32) ** 2
        ), argnums=(0, 1))(x, p)

    t32 = time_fn("mlp_fp32", f32, x32, params, steps=steps)
    tbf = time_fn("mlp_bf16", fbf16, x32, pb, steps=steps)
    # record() schema: "pallas" column = bf16 path, "xla" = fp32 path
    record("mlp_fwd_bwd", "480-1024-1024-512-256-1 b1024 (bf16 vs fp32)",
           tbf, t32)


def bench_linear_xent(steps):
    """Fused LM-head loss vs materialized logits + fused xent, fwd+bwd
    at a long-context-feasible size (N=8192 tokens, D=1024, V=32768 —
    the lm_bench S=4096 head shape at batch 2): the per-row op chunked
    over the vocabulary (four vocabulary-wide matmuls) and the reduced
    op over blocks of rows (three), each against the materialized path.
    The fused paths' pitch is the memory bound; these rows answer
    whether they also cost or save TIME where both fit."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.contrib.xentropy import (linear_cross_entropy,
                                           softmax_cross_entropy_loss,
                                           weighted_linear_cross_entropy)
    n, d, v = 8192, 1024, 32768
    h = jax.random.normal(jax.random.key(0), (n, d), jnp.bfloat16)
    w = jax.random.normal(jax.random.key(1), (v, d), jnp.bfloat16) * 0.02
    labels = jax.random.randint(jax.random.key(2), (n,), 0, v)

    def fused(h, w):
        return jax.grad(lambda h, w: jnp.mean(linear_cross_entropy(
            h, w, labels, chunk=8192)), argnums=(0, 1))(h, w)

    def reduced(h, w):
        rw = jnp.full((n,), 1.0 / n, jnp.float32)
        return jax.grad(lambda h, w: weighted_linear_cross_entropy(
            h, w, labels, rw), argnums=(0, 1))(h, w)

    def materialized(h, w):
        def loss(h, w):
            logits = jax.lax.dot_general(
                h, w, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            return jnp.mean(softmax_cross_entropy_loss(
                logits, labels, padding_idx=None))
        return jax.grad(loss, argnums=(0, 1))(h, w)

    tf = time_fn("linear_xent_fused", fused, h, w, steps=steps)
    tr = time_fn("linear_xent_reduced", reduced, h, w, steps=steps)
    tm = time_fn("linear_xent_materialized", materialized, h, w,
                 steps=steps)
    # record() schema: "pallas" column = fused, "xla" = materialized
    record("linear_xent_fwd_bwd", f"n{n} d{d} v{v} chunk8192 bf16",
           tf, tm)
    record("linear_xent_reduced_fwd_bwd", f"n{n} d{d} v{v} row-blocks bf16",
           tr, tm)


def bench_flash_crossover(steps):
    """Measure the flash-vs-composed crossover (VERDICT r4 #2): fwd+bwd
    at S from 512 to 8192 on the perf-test shape the reference's own
    crossover evidence uses (bh16 d64 causal — apex/contrib/examples/
    multihead_attn/perf_test_multihead_attn.py). Emits one row per S;
    main() reduces the rows to the measured ``flash_min_s`` threshold
    (:func:`crossover_threshold`) and prints it."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.contrib.multihead_attn import (flash_attention,
                                                 reference_attention)
    bh, d = 16, 64
    seqs = [int(s) for s in os.environ.get(
        "KBENCH_CROSSOVER_S", "512,1024,2048,4096,8192").split(",")]
    for s in seqs:
        ks = jax.random.split(jax.random.key(0), 3)
        q, k, v = (jax.random.normal(kk, (bh, s, d), jnp.bfloat16)
                   for kk in ks)

        def f_pallas(q, k, v):
            return jax.grad(lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, causal=True).astype(jnp.float32)),
                argnums=(0, 1, 2))(q, k, v)

        def f_xla(q, k, v):
            return jax.grad(lambda q, k, v: jnp.sum(
                reference_attention(q, k, v, causal=True)
                .astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)

        n = max(2, steps // max(1, s // 1024))
        tp = time_fn(f"xover_s{s}_pallas", f_pallas, q, k, v, steps=n)
        tx = time_fn(f"xover_s{s}_xla", f_xla, q, k, v, steps=n)
        record("flash_crossover", f"bh{bh} s{s} d{d} causal bf16", tp, tx)


def crossover_threshold(rows):
    """Smallest measured S such that the kernel is <= 1.05x XLA at that
    S and every larger measured S (monotone suffix rule — a single noisy
    mid-table win must not drag the threshold down past a loss). Returns
    None when the kernel never qualifies."""
    xs = sorted((r for r in rows if r["bench"] == "flash_crossover"
                 and r.get("pallas_ms") and r.get("xla_ms")),
                key=lambda r: int(r["config"].split(" s")[1].split()[0]))
    thr = None
    for r in reversed(xs):
        s = int(r["config"].split(" s")[1].split()[0])
        if r["pallas_ms"] <= 1.05 * r["xla_ms"]:
            thr = s
        else:
            break
    return thr


BENCHES = {"flash": bench_flash, "flash_blocks": bench_flash_blocks,
           "flash_verify": bench_flash_verify,
           "flash_crossover": bench_flash_crossover,
           "mlp": bench_mlp, "linear_xent": bench_linear_xent}


def main():
    # Stall watchdog, fed by every _note: a hung device call costs
    # PROBE_DEADMAN seconds, not the caller's whole time limit.
    global _feed
    from _perf_common import arm_watchdog
    _feed = arm_watchdog("kernel_bench")
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()

    from apex_tpu.utils import setup_host_backend
    _note(f"backend={setup_host_backend()}")
    names = args.only.split(",") if args.only else list(BENCHES)
    for name in names:
        _note(f"=== {name} ===")
        BENCHES[name](args.steps)

    if "flash_crossover" in names:
        _note(f"measured flash_min_s: {crossover_threshold(results)} "
              f"(None = the kernel never reached 1.05x of XLA)")

    print("\n| bench | config | pallas ms | xla ms | speedup |")
    print("|---|---|---|---|---|")
    for r in results:
        if "ms" in r:  # flash_blocks rows: config-vs-config, not vs-XLA
            vs = r["vs_baseline_config"]
            print(f"| {r['bench']} | {r['config']} | {r['ms'] or '-'} | "
                  f"(baseline {r['baseline'] or '-'}) | "
                  f"{f'{vs}x' if vs is not None else '-'} |")
        else:
            census = r.get("block_census")
            config = r["config"] + (
                f" (dead/interior/edge: fwd {census['fwd']}, "
                f"bwd {census['bwd']})" if census else "")
            print(f"| {r['bench']} | {config} | {r['pallas_ms']} | "
                  f"{r['xla_ms'] or '-'} | {r.get('speedup_vs_xla', '-')} |")


if __name__ == "__main__":
    main()
