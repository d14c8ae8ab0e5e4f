"""The quickest proof that apex_tpu still starts on the chip.

One process drives the train and serve main paths once, through the
entry points a user or ``tools/*_bench.py`` calls, at the full width of
the dense LM the repo benchmarks (depth cut, weights random from
``--seed``), and checks each result by the repo's own means:

    python chip_smoke.py             # one chip: device, kernels,
                                     #   train_lm, train_rn50, serve
    python chip_smoke.py --chips 4   # four chips: the dense-LM step on
                                     #   one device vs Plan DDP vs Plan
                                     #   ZeRO, and no other phase

One JSON line per phase (seconds, compile seconds, what was checked,
peak device bytes), then, as the last line and nothing after it,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed phase — above all a device that is not a TPU — makes ``ok``
false and the exit code 1; nothing is swallowed into a 0 exit.

``--rehearse`` shrinks every size so the whole script can be walked on
the CPU (``JAX_PLATFORMS=cpu``, Pallas kernels interpreted; with
``--chips 4`` on ``--xla_force_host_platform_device_count=4``). A
rehearsal finds wrong paths, not results: off the chip the ``device``
phase still fails, so the run still ends ``"ok": false`` with exit 1.

The chip belongs to one process: this script starts no child.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
import traceback

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_ROOT, "tools"))   # lm_bench, _perf_common
sys.path.insert(0, _ROOT)                          # apex_tpu, bench

# The dense LM of tools/serve_bench.py at its published widths
# (docs/PERF.md): d1024, 8 heads x 128, V 32768, S 4096.
FULL = dict(
    vocab=32768, dim=1024, heads=8, layers=8, seq=4096, batch=8,
    head_chunk=8192, steps=3,
    # --chips 4: same widths, a quarter of the depth — three step
    # programs compile in one four-chip call, charged four times a
    # second, and the ZeRO program's compile time is far from linear in
    # depth (17 s at 2 layers, 188 s at 4, compiled for a described
    # v5e:2x2)
    multichip_layers=2,
    rn50=dict(batch=384, image=224, steps=3),
    # weights (0.27 GB bf16) + KV arena (32 slots x 2048 x 8 layers x
    # K,V x 8 x 128 bf16 = 2.1 GB) stay resident
    serve=dict(slots=32, max_len=2048, page=32, chunk=32, requests=24,
               rate=16.0, system_prompt=256, prompt_dist="uniform:16,96",
               new_dist="uniform:32,64", compare=4, compare_tokens=32),
    # kernel-family shapes: what the phases above trace
    kernels=dict(flat=128 * 1024 * 1024, flash=(1, 8, 4096, 128),
                 head_rows=4096, decode_slots=32, decode_len=2048, page=32),
)
TINY = dict(
    vocab=512, dim=128, heads=4, layers=1, seq=128, batch=4,
    head_chunk=256, steps=3, multichip_layers=1,
    rn50=dict(batch=8, image=32, steps=3),
    serve=dict(slots=2, max_len=64, page=8, chunk=8, requests=16,
               rate=64.0, system_prompt=16, prompt_dist="uniform:4,12",
               new_dist="uniform:8,16", compare=2, compare_tokens=8),
    kernels=dict(flat=128 * 64, flash=(1, 2, 256, 64), head_rows=16,
                 decode_slots=2, decode_len=256, page=32),
)


class Smoke:
    """The run: sizes, the compile tracker, and the phase lines."""

    def __init__(self, args):
        self.args = args
        self.cfg = TINY if args.rehearse else FULL
        self.ok = True
        self.tracker = None     # prof.metrics.CompileTracker, set by device
        self.on_tpu = False
        self.device_line = {"platform": None, "kind": None, "count": 0}
        self.partial = {}       # what a phase learned before it failed

    # -- bookkeeping -------------------------------------------------------
    def compiles(self):
        """(backend compiles so far, seconds spent in them, how many
        the persistent cache served) — zeros before the backend is up."""
        if self.tracker is None:
            return 0, 0.0, 0
        snap = self.tracker.snapshot()
        return (snap["backend_compiles"], snap["durations_s"].get(
            "/jax/core/compile/backend_compile_duration", 0.0),
            snap["counts"].get("/jax/compilation_cache/cache_hits", 0))

    def phase(self, name, fn):
        """Run one phase and print its line; an exception fails the
        phase (and the run) with its traceback on stderr."""
        import jax
        t0 = time.perf_counter()
        c0 = self.compiles()
        self.partial = {}
        try:
            info, ok = fn(), True
        except Exception as e:          # the phase boundary: report + fail
            traceback.print_exc()
            info, ok = {**self.partial,
                        "error": f"{type(e).__name__}: {e}"[:500]}, False
        c1 = self.compiles()
        line = {"phase": name, "ok": ok,
                "seconds": round(time.perf_counter() - t0, 2),
                "compile_seconds": round(c1[1] - c0[1], 2),
                "compiles": c1[0] - c0[0],
                "cache_hits": c1[2] - c0[2], **info}
        if self.tracker:                # backend is up
            stats = [d.memory_stats() or {} for d in jax.local_devices()]
            if any("peak_bytes_in_use" in s for s in stats):
                line["peak_bytes"] = [s.get("peak_bytes_in_use")
                                      for s in stats]
        print(json.dumps(line), flush=True)
        self.ok = self.ok and ok
        # drop the phase's executables and buffers before the next one
        jax.clear_caches()
        gc.collect()
        return ok

    # -- device ------------------------------------------------------------
    def device(self):
        import importlib.metadata as md

        import jax
        import jaxlib

        from apex_tpu.prof.metrics import CompileTracker
        from apex_tpu.utils import setup_host_backend

        try:
            platform = setup_host_backend()   # raises on a silent CPU
        finally:                              # ... which is still named
            dev = jax.devices()[0]
            self.device_line = {"platform": dev.platform,
                                "kind": dev.device_kind,
                                "count": len(jax.devices())}
        self.tracker = CompileTracker.install()
        self.on_tpu = platform == "tpu"
        if not self.on_tpu:
            raise RuntimeError(f"platform is {platform!r}, not 'tpu'")
        if len(jax.devices()) < self.args.chips:
            raise RuntimeError(f"--chips {self.args.chips} needs that "
                               f"many devices, jax reports "
                               f"{len(jax.devices())}")
        return {**self.device_line, "jax": jax.__version__,
                "jaxlib": jaxlib.__version__,
                "libtpu": md.version("libtpu"),
                "compile_cache": jax.config.jax_compilation_cache_dir}

    # -- kernels -----------------------------------------------------------
    def kernels(self):
        """The multi-tensor, flash and decode kernels, COMPILED (interpret
        off, ``tpu_custom_call`` in the lowered text) and compared with
        their jnp reference at the widths the later phases trace. On the
        chip the kernel side runs under "auto": the platform and the
        shapes pick the kernel, as in every later phase. A rehearsal
        forces it (``backend("pallas")``, interpreted)."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from apex_tpu.contrib.multihead_attn import (flash_attention,
                                                     reference_attention)
        from apex_tpu.contrib.multihead_attn.decode_attention import (
            reference_slot_decode_attention, slot_decode_attention)
        from apex_tpu.contrib.xentropy import (linear_cross_entropy,
                                               softmax_cross_entropy_loss)
        from apex_tpu.ops import dispatch, kernels as K
        from apex_tpu.ops.pallas._common import interpret_mode

        k = self.cfg["kernels"]
        if self.on_tpu and (interpret_mode() or not dispatch.use_pallas()):
            raise AssertionError("on the chip but the dispatch chose "
                                 "interpret mode / the jnp reference")
        kernel_side = "auto" if self.on_tpu else "pallas"
        key = iter(jax.random.split(jax.random.key(self.args.seed), 64))
        checked = []

        def under(backend, fn):
            def traced(*a):
                with dispatch.backend(backend):
                    return fn(*a)
            return traced

        def case(name, kernel_fn, ref_fn, args, tol):
            """tol: max |kernel - reference| per output, relative to
            the reference's own max magnitude (floor 1)."""
            lowered = jax.jit(under(kernel_side, kernel_fn)).lower(*args)
            compiled_kernel = "tpu_custom_call" in lowered.as_text()
            if self.on_tpu and not compiled_kernel:
                raise AssertionError(f"{name}: no tpu_custom_call in the "
                                     f"lowered program")
            outs = jax.tree.leaves(lowered.compile()(*args))
            refs = jax.tree.leaves(
                jax.jit(under("reference", ref_fn))(*args))
            assert len(outs) == len(refs), name
            worst = 0.0
            for o, r in zip(outs, refs):
                assert o.shape == r.shape, (name, o.shape, r.shape)
                o32, r32 = o.astype(jnp.float32), r.astype(jnp.float32)
                err = float(jnp.max(jnp.abs(o32 - r32)) / jnp.maximum(
                    jnp.max(jnp.abs(r32)), 1.0)) if o.size else 0.0
                if not np.isfinite(err) or err > tol:
                    raise AssertionError(
                        f"{name}: |kernel - reference| = {err} > {tol}")
                worst = max(worst, err)
            checked.append({"name": name, "compiled": compiled_kernel,
                            "err": float(f"{worst:.3g}")})

        def same(fn):   # one function, the dispatch picks the side
            return fn, fn

        # multi_tensor over a flat buffer the size of the LM's masters
        n = k["flat"]
        x = jax.random.normal(next(key), (n,), jnp.float32)
        g = jax.random.normal(next(key), (n,), jnp.float32) * 0.01
        case("scale", *same(lambda x: K.scale(x, 0.37)[0]), (x,), 1e-6)
        case("axpby", *same(lambda x, y: K.axpby(1.3, x, -0.7, y)[0]),
             (x, g), 1e-6)
        case("l2norm", *same(K.l2norm), (x,), 1e-4)
        case("adam_step", *same(lambda g, p: K.adam_step(
            g, p, jnp.zeros_like(p), jnp.zeros_like(p), lr=1e-3,
            beta1=0.9, beta2=0.999, eps=1e-8, step=1)), (g, x), 1e-5)
        del x, g

        # flash attention fwd + bwd at the LM's head shape, then the
        # kv_bias and in-kernel dropout variants at short S
        bq, hq, sq, dq = k["flash"]
        q, kk, v = (jax.random.normal(next(key), (bq * hq, sq, dq),
                                      jnp.bfloat16) for _ in range(3))

        def sq_loss(attn):
            return lambda q, k, v: jnp.sum(attn(
                q, k, v, causal=True).astype(jnp.float32) ** 2)
        case(f"flash_fwd_S{sq}_D{dq}",
             lambda q, k, v: flash_attention(q, k, v, causal=True),
             lambda q, k, v: reference_attention(q, k, v, causal=True),
             (q, kk, v), 0.05)
        case(f"flash_bwd_S{sq}_D{dq}",
             jax.grad(sq_loss(flash_attention), argnums=(0, 1, 2)),
             jax.grad(sq_loss(reference_attention), argnums=(0, 1, 2)),
             (q, kk, v), 0.05)
        q, kk, v = (a[:4, :256, :64] for a in (q, kk, v))
        kvb = jnp.where(jnp.arange(256) >= 250, -1e30, 0.0)[None, :]
        case("flash_kv_bias",
             lambda q, k, v: flash_attention(q, k, v, kv_bias=kvb,
                                             causal=True),
             lambda q, k, v: reference_attention(q, k, v, kv_bias=kvb,
                                                 causal=True),
             (q, kk, v), 0.05)
        drop = dict(dropout_rate=0.3, dropout_seed=42)
        qf, kf, vf = (a[:, :128].astype(jnp.float32) for a in (q, kk, v))
        case("flash_dropout_fwd",
             lambda q, k, v: flash_attention(q, k, v, **drop),
             lambda q, k, v: reference_attention(q, k, v, **drop),
             (qf, kf, vf), 0.02)
        case("flash_dropout_bwd",
             jax.grad(lambda q, k, v: jnp.sum(
                 flash_attention(q, k, v, **drop) ** 2)),
             jax.grad(lambda q, k, v: jnp.sum(
                 reference_attention(q, k, v, **drop) ** 2)),
             (qf, kf, vf), 0.05)
        del q, kk, v, qf, kf, vf

        # the chunked fused LM head against materialized logits
        vocab = self.cfg["vocab"]
        rows = k["head_rows"]
        labels = jax.random.randint(next(key), (rows,), 0, vocab)
        dim = self.cfg["dim"]
        hid = jax.random.normal(next(key), (rows, dim), jnp.bfloat16)
        wte = jax.random.normal(next(key), (vocab, dim),
                                jnp.bfloat16) * 0.05

        def head_fused(h, w):
            return jnp.mean(linear_cross_entropy(
                h, w, labels, chunk=self.cfg["head_chunk"]))

        def head_plain(h, w):
            return jnp.mean(softmax_cross_entropy_loss(
                h.astype(jnp.float32) @ w.astype(jnp.float32).T, labels,
                padding_idx=None))
        # a jnp scan on both sides (the head matmul rides the MXU, no
        # Pallas kernel) — compared, not asserted compiled
        o = jax.jit(under(kernel_side, jax.value_and_grad(
            head_fused, argnums=(0, 1))))(hid, wte)
        r = jax.jit(under("reference", jax.value_and_grad(
            head_plain, argnums=(0, 1))))(hid, wte)
        for a, b in zip(jax.tree.leaves(o), jax.tree.leaves(r)):
            err = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                        - b.astype(jnp.float32))))
            if not err <= 0.05:
                raise AssertionError(f"linear_cross_entropy: {err}")
        checked.append({"name": "linear_cross_entropy", "compiled": None})
        del hid, wte

        # single-query slot attention, dense arena and paged pool
        s, ln, page = k["decode_slots"], k["decode_len"], k["page"]
        h, hd = self.cfg["heads"], self.cfg["dim"] // self.cfg["heads"]
        if not self.on_tpu:
            h, hd = 2, 128      # the kernels need a lane-wide head_dim
        qd = jax.random.normal(next(key), (s, h, hd), jnp.bfloat16)
        kd, vd = (jax.random.normal(next(key), (s, h, ln, hd),
                                    jnp.bfloat16) for _ in range(2))
        lens = jax.random.randint(next(key), (s,), 1, ln + 1)
        case(f"slot_decode_L{ln}",
             lambda q, k, v, n: slot_decode_attention(q, k, v, n,
                                                      impl="pallas"),
             reference_slot_decode_attention, (qd, kd, vd, lens), 0.02)
        n_pages = ln // page
        perm = jax.random.permutation(next(key), s * n_pages) + 1
        table = perm.reshape(s, n_pages).astype(jnp.int32)
        kp, vp = (jax.random.normal(next(key),
                                    (s * n_pages + 1, h, page, hd),
                                    jnp.bfloat16) for _ in range(2))
        case(f"paged_decode_L{ln}_page{page}",
             lambda q, k, v, n, t: slot_decode_attention(
                 q, k, v, n, impl="pallas", page_table=t),
             lambda q, k, v, n, t: reference_slot_decode_attention(
                 q, k, v, n, page_table=t),
             (qd, kp, vp, lens, table), 0.02)
        return {"checked": checked,
                "against": "ops.reference / reference_attention / "
                           "reference_slot_decode_attention"}

    # -- the dense-LM train step -------------------------------------------
    def _lm(self, layers, **kw):
        from apex_tpu.models import TransformerLM
        c = self.cfg
        return TransformerLM(
            vocab_size=c["vocab"], max_seq_len=c["seq"],
            embed_dim=c["dim"], num_heads=c["heads"], num_layers=layers,
            attn_impl="fast", head_chunk=c["head_chunk"], **kw)

    def _lm_arm(self, lm, params, devices, *, zero=False):
        """Build, place and compile the dense-LM step (tools/lm_bench) over
        ``devices`` from host-side ``params``; returns what the checks
        read."""
        import jax
        import jax.numpy as jnp
        from lm_bench import build_train_step, place_for_plan

        from apex_tpu.parallel import compile_step_with_plan, make_mesh
        from apex_tpu.utils import host_init

        c = self.cfg
        mesh = make_mesh({"data": len(devices)}, devices=devices)
        with host_init():
            opt, state, step, plan = build_train_step(
                lm, params, mesh, half=jnp.bfloat16, zero=zero)
            toks = jax.random.randint(
                jax.random.key(self.args.seed + 1),
                (c["batch"], c["seq"]), 0, c["vocab"])
        state, toks = place_for_plan(state, toks, plan)
        compiled = compile_step_with_plan(step, plan) \
            .lower(state, toks).compile()
        return opt, state, toks, compiled, plan

    def _run_steps(self, compiled, state, toks):
        import numpy as np
        losses = []
        for _ in range(self.cfg["steps"]):
            state, loss = compiled(state, toks)
            losses.append(float(loss))
        if not np.isfinite(losses).all():
            raise AssertionError(f"non-finite loss: {losses}")
        return state, losses

    def train_lm(self):
        import jax
        import jax.numpy as jnp

        from apex_tpu.ops import dispatch, flat as F
        from apex_tpu.utils import host_init

        c = self.cfg
        lm = self._lm(c["layers"])
        with host_init():
            params = lm.init(jax.random.key(self.args.seed))
        opt, state, toks, compiled, plan = self._lm_arm(
            lm, params, jax.devices()[:1])
        del params
        table = opt._tables[0]

        # the same parameters through the unfused reference path, one
        # row at a time (the [B, H, S, S] fp32 scores of the whole batch
        # do not fit beside the train state); equal row lengths, so the
        # mean of row losses is the batch loss
        lm_ref = dataclasses.replace(lm, attn_impl="default")

        @jax.jit
        def ref_loss(master, toks, i):
            with dispatch.backend("reference"):
                return lm_ref.loss(
                    F.unflatten(master, table, dtype=jnp.bfloat16),
                    jax.lax.dynamic_slice_in_dim(toks, i, 1))
        ref = sum(float(ref_loss(state[0].master, toks, i))
                  for i in range(c["batch"])) / c["batch"]

        state, losses = self._run_steps(compiled, state, toks)
        tol = 0.05   # bf16: 2^-8 relative on a loss of ~ln(V) = 10.4
        if abs(losses[0] - ref) > tol:
            raise AssertionError(f"step-0 loss {losses[0]} vs reference "
                                 f"{ref}: beyond {tol}")
        if not losses[-1] < losses[0]:
            raise AssertionError(f"loss did not fall: {losses}")
        mem = compiled.memory_analysis()
        kernels = compiled.as_text().count("tpu_custom_call")
        if self.on_tpu and not kernels:
            raise AssertionError("no tpu_custom_call in the train step: "
                                 "the dispatch took the jnp reference")
        return {"config": {k: c[k] for k in ("layers", "dim", "heads",
                                             "vocab", "seq", "batch")},
                "params_m": round(int(table.total) / 1e6, 1),
                "lowering": plan.lowering(), "losses": losses,
                "reference_loss": round(ref, 4), "tolerance": tol,
                "custom_calls": kernels,
                "program_bytes": {
                    "arguments": mem.argument_size_in_bytes,
                    "temporaries": mem.temp_size_in_bytes,
                    "generated_code": mem.generated_code_size_in_bytes}
                if mem is not None else None}

    # -- the RN50 O2 + FusedLAMB step --------------------------------------
    def train_rn50(self):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from bench import build_train_step

        from apex_tpu import amp
        from apex_tpu.models import ResNet, resnet50
        from apex_tpu.utils import host_init, ship

        r = self.cfg["rn50"]
        # the measured-best stem, where the benchmark's configuration
        # took it from
        with open(os.path.join(_ROOT, "BENCH_DEFAULTS.json")) as f:
            stem = json.load(f)["stem"]
        model = (ResNet(block_sizes=(1,), bottleneck=True,
                        num_classes=10, width=8, stem=stem)
                 if self.args.rehearse else resnet50(stem=stem))
        with host_init():
            params, bn_state = model.init(jax.random.key(self.args.seed))
            _, handle = amp.initialize(opt_level="O2", verbosity=0)
            amp_state = handle.init_state()
            half = handle.policy.cast_model_dtype
            opt, _, train_step = build_train_step(model, params, handle)
            opt_state = opt.init_state()
            rs = np.random.RandomState(self.args.seed)
            x = jnp.asarray(rs.randn(r["batch"], r["image"], r["image"],
                                     3), half)
            y = jnp.asarray(rs.randint(0, model.num_classes, r["batch"]),
                            jnp.int32)
        opt_state, bn_state, amp_state, x, y = ship(
            (opt_state, bn_state, amp_state, x, y))
        step = jax.jit(train_step, donate_argnums=(0, 1, 2))
        losses = []
        for _ in range(r["steps"]):
            opt_state, bn_state, amp_state, loss = step(
                opt_state, bn_state, amp_state, x, y)
            losses.append(float(loss))
        if not np.isfinite(losses).all():
            raise AssertionError(f"non-finite loss: {losses}")
        scaler = handle.scalers[0].state_dict(amp_state[0])
        if scaler["step_count"] != r["steps"]:
            raise AssertionError(f"loss-scale state did not advance: "
                                 f"{scaler}")
        if scaler["overflow_count"]:
            raise AssertionError(f"overflow skip on clean data: {scaler}")
        return {"config": {"model": "resnet50" if not self.args.rehearse
                           else "tiny-resnet", "opt_level": "O2",
                           "optimizer": "FusedLAMB", "stem": stem,
                           "layout": "NHWC", **r},
                "losses": losses, "scaler": scaler}

    # -- the paged serving engine ------------------------------------------
    def serve(self):
        import jax
        import numpy as np
        from _perf_common import make_decoder_lm

        from apex_tpu.ops import dispatch
        from apex_tpu.serve import (ContinuousBatchingEngine,
                                    poisson_requests)

        c, s = self.cfg, self.cfg["serve"]
        lm, params, _ = make_decoder_lm(
            vocab=c["vocab"], dim=c["dim"], heads=c["heads"],
            layers=c["layers"], max_seq_len=s["max_len"], dtype="bf16",
            seed=self.args.seed)

        def build():
            return ContinuousBatchingEngine(
                lm, params, slots=s["slots"], max_len=s["max_len"],
                prefill_chunk=s["chunk"], fused=True, paged=True,
                page_size=s["page"], prefix_share=True,
                seed=self.args.seed)

        # serve_bench's shared-prefix workload: one seeded system prompt
        # in front of every request's own prompt
        system = np.random.RandomState(self.args.seed + 104729).randint(
            0, c["vocab"], s["system_prompt"]).astype(np.int32)
        requests = poisson_requests(
            s["requests"], rate=s["rate"], prompt_dist=s["prompt_dist"],
            new_dist=s["new_dist"], vocab_size=c["vocab"],
            seed=self.args.seed,
            max_len=s["max_len"] - s["system_prompt"],
            prefill_chunk=s["chunk"])
        for r in requests:
            r.prompt = np.concatenate([system, r.prompt])

        engine = build()
        decode, = [p for p in engine.lint_programs()
                   if p["name"].endswith(".decode")]
        if self.on_tpu and "tpu_custom_call" not in decode["fn"].lower(
                *decode["args"]).as_text():
            raise AssertionError("no tpu_custom_call in the decode step: "
                                 "the dispatch took the jnp reference")
        del decode
        engine.warmup()
        warm = self.compiles()[0]
        results, stats = engine.run(requests)
        compiled_in_run = self.compiles()[0] - warm
        done = [r for r in results if r.finish_s is not None]
        if len(done) != len(requests):
            raise AssertionError(f"{len(requests) - len(done)} of "
                                 f"{len(requests)} requests did not finish")
        hits = sum(1 for r in results if r.prefix_tokens > 0)
        if not hits:
            raise AssertionError("no prefix-cache hit on a shared prefix")
        if compiled_in_run:
            raise AssertionError(f"{compiled_in_run} compilations after "
                                 f"warmup()")
        for r, q in zip(results, requests):
            if len(r.tokens) != q.max_new or not all(
                    0 <= t < c["vocab"] for t in r.tokens):
                raise AssertionError(f"request {r.id}: bad stream")

        # the same engine built and traced under the jnp reference:
        # greedy streams must agree token for token
        n_cmp, n_tok = s["compare"], s["compare_tokens"]
        subset = [dataclasses.replace(q, arrival_s=0.0)
                  for q in requests[:n_cmp]]
        with dispatch.backend("reference"):
            ref_results, _ = build().run(subset)
        equal, first_diff = 0, {}
        for r, ref in zip(results[:n_cmp], ref_results):
            a, b = r.tokens[:n_tok], ref.tokens[:n_tok]
            if len(a) < n_tok:
                raise AssertionError(f"request {r.id}: {len(a)} tokens, "
                                     f"need {n_tok} to compare")
            if a == b:
                equal += 1
            else:
                first_diff[r.id] = next(
                    i for i, (x, y) in enumerate(zip(a, b)) if x != y)
        out = {"config": {k: c[k] for k in ("layers", "dim", "heads",
                                            "vocab")} | s,
               "completed": len(done), "prefix_hits": hits,
               "compilations_after_warmup": compiled_in_run,
               "decode_steps": stats["decode_steps"],
               "kv_reserved_bytes": stats.get("kv_reserved_bytes"),
               "param_bytes": sum(p.nbytes
                                  for p in jax.tree.leaves(params)),
               "compared": n_cmp, "compare_tokens": n_tok,
               "streams_equal": equal}
        if equal == n_cmp:
            out["agreement"] = "greedy streams token-for-token equal"
            return out
        # bf16 near-ties can flip an argmax between the kernel and the
        # reference path; a divergence is accepted only as such a tie:
        # under the reference forward of the common prefix, the kernel
        # path's token is within `tie` of the best logit
        tie = 0.05
        gaps = {}
        for r in results[:n_cmp]:
            if r.id not in first_diff:
                continue
            i = first_diff[r.id]
            prefix = np.concatenate([requests[r.id].prompt,
                                     np.asarray(r.tokens[:i], np.int32)])
            with dispatch.backend("reference"):
                logits = np.asarray(dataclasses.replace(
                    lm, attn_impl="default").apply(
                        params, prefix[None])[0, -1])
            gaps[r.id] = float(logits.max() - logits[r.tokens[i]])
            if not gaps[r.id] <= tie:
                raise AssertionError(
                    f"request {r.id} token {i}: kernel-path token is "
                    f"{gaps[r.id]} below the reference's best logit "
                    f"(> {tie}): not a near-tie")
        out["agreement"] = (f"streams equal up to a first divergence that "
                            f"is a logit near-tie (<= {tie}) under the "
                            f"reference")
        out["first_divergence"] = first_diff
        out["tie_gaps"] = gaps
        return out

    # -- four chips: one device vs Plan DDP vs Plan ZeRO -------------------
    def multichip(self):
        import jax
        import numpy as np

        from apex_tpu.ops import flat as F
        from apex_tpu.utils import host_init

        c = self.cfg
        n = self.args.chips
        devices = jax.devices()[:n]
        lm = self._lm(c["multichip_layers"])
        arms, params = {}, {}
        self.partial = {"arms": arms}
        with host_init():   # every arm starts from these
            init = lm.init(jax.random.key(self.args.seed))
        start = [np.asarray(x) for x in jax.tree.leaves(init)]
        for name, devs, zero in (("one_device", devices[:1], False),
                                 ("ddp", devices, False),
                                 ("zero", devices, True)):
            opt, state, toks, compiled, plan = self._lm_arm(
                lm, init, devs, zero=zero)
            text = compiled.as_text()
            found = [op for op in ("all-reduce", "reduce-scatter",
                                   "all-gather", "all-to-all",
                                   "collective-permute") if op in text]
            info = {"lowering": plan.lowering(), "collectives": found}
            if zero:
                # 1/n of the master on each of n DISTINCT devices
                shards = state.master.addressable_shards
                info["shard_devices"] = sorted(
                    sh.device.id for sh in shards)
                info["shard_bytes"] = [sh.data.nbytes for sh in shards]
                if len(set(info["shard_devices"])) != n or any(
                        b * n != state.master.nbytes
                        for b in info["shard_bytes"]):
                    raise AssertionError(f"ZeRO state is not 1/{n} on "
                                         f"{n} devices: {info}")
            state, losses = self._run_steps(compiled, state, toks)
            table = opt.table if zero else opt._tables[0]
            master = state.master if zero else state[0].master
            # the tables pad differently: compare as parameter trees
            leaves = jax.tree.leaves(F.unflatten(
                jax.device_put(master, devices[0]), table))
            arms[name] = {**info, "losses": losses}
            params[name] = [np.asarray(x) for x in leaves]
            del state, compiled, master, leaves
            jax.clear_caches()
        # the compiler may lower one collective to another; which it
        # chose is on the phase line — but SOME collective must be there
        for name, asked in (("ddp", {"all-reduce"}),
                            ("zero", {"reduce-scatter", "all-gather"})):
            arms[name]["as_asked"] = asked <= set(arms[name]["collectives"])
            if not arms[name]["collectives"]:
                raise AssertionError(f"{name}: no collective in the "
                                     f"compiled program")
        l0 = [arms[a]["losses"][0] for a in arms]
        # bf16 forward, per-device row sums in another order
        if max(l0) - min(l0) > 0.02:
            raise AssertionError(f"step-0 losses disagree: {l0}")

        def norm(tree):
            return sum(float(np.sum(x.astype(np.float64) ** 2))
                       for x in tree) ** 0.5

        def compare(a, b):
            """How far apart two arms' masters ended, against how far
            the optimizer moved them from the common start."""
            diff = [x - y for x, y in zip(params[a], params[b])]
            moved = norm([x - s for x, s in zip(params[a], start)])
            worst = max(float(np.max(np.abs(d))) for d in diff)
            return {"update_rel_l2": norm(diff) / moved,
                    "master_rel_l2": norm(diff) / norm(params[a]),
                    "max_abs": worst}
        out = {"config": {"layers": c["multichip_layers"],
                          **{k: c[k] for k in ("dim", "heads", "vocab",
                                               "seq", "batch")}},
               "devices": n, "arms": arms,
               "ddp_vs_zero": compare("ddp", "zero"),
               "one_device_vs_ddp": compare("one_device", "ddp")}
        self.partial = out
        # Same math, separately compiled programs: the bf16 backward
        # rounds differently in each, and Adam's m/sqrt(v) turns a
        # gradient near zero into a step of +-lr whichever way the
        # rounding fell — so masters agree to the step size on a few
        # elements (max_abs <= 2 lr per step), not to fp32 epsilon.
        # What must hold is that both took the same trajectory: the
        # masters differ by a small part of the distance they moved.
        tol = 0.02
        lr_bound = 2 * 1e-4 * c["steps"] * 1.01
        got = out["ddp_vs_zero"]
        if got["update_rel_l2"] > tol or got["max_abs"] > lr_bound:
            raise AssertionError(f"DDP and ZeRO masters differ: {got} "
                                 f"(tolerance {tol}, {lr_bound})")
        out["tolerance"] = {"update_rel_l2": tol, "max_abs": lr_bound}
        return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the multi-chip phase (one device "
                         "vs Plan DDP vs Plan ZeRO over a 4-device mesh)")
    ap.add_argument("--seed", type=int, default=0,
                    help="weights, tokens and traffic are made from it")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, for walking every phase on the CPU; "
                         "off the chip the run still ends ok=false")
    smoke = Smoke(ap.parse_args())
    if smoke.phase("device", smoke.device) or (
            smoke.args.rehearse and smoke.tracker is not None):
        phases = (("multichip",) if smoke.args.chips == 4 else
                  ("kernels", "train_lm", "train_rn50", "serve"))
        for name in phases:
            smoke.phase(name, getattr(smoke, name))
    print(json.dumps({"ok": smoke.ok, "device": smoke.device_line}),
          flush=True)
    return 0 if smoke.ok else 1


if __name__ == "__main__":
    sys.exit(main())
