"""Perf probe for the headline RN50 O2+FusedLAMB train step.

Answers the round-3 questions from VERDICT.md Weak #1/#7:
  1. How much of the measured step time is per-call dispatch overhead?
     (times the same compiled step per-call vs. inside one lax.fori_loop)
  2. What do the step's Pallas kernels (the flat-buffer optimizer ops)
     buy over their jnp sides? (--backend auto|reference ablation; the
     welford BN kernels this was first asked of lost, 150 ms to 16 a
     step, and are gone)
  3. What are the true analytic FLOPs per image (vs. XLA cost_analysis)?

Usage (on the TPU host):
    python tools/perf_probe.py --backend auto --iters 50
    python tools/perf_probe.py --backend reference --iters 50
    python tools/perf_probe.py --trace /tmp/trace   # adds profiler capture

Prints one JSON line per timing mode.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
# repo root importable without PYTHONPATH
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from functools import partial


_feed = lambda: None  # rebound by arm_watchdog in main()


def _note(msg):
    _feed()
    sys.stderr.write(f"probe[{time.strftime('%H:%M:%S')}]: {msg}\n")
    sys.stderr.flush()


def analytic_resnet_flops(model, image: int) -> float:
    """Analytic fwd FLOPs/img — canonical impl lives with the model."""
    from apex_tpu.models.resnet import analytic_flops
    return analytic_flops(model, image)


def main():
    # Stall watchdog, fed by every _note: a hung device call costs
    # PROBE_DEADMAN seconds, not the caller's whole time limit.
    global _feed
    from _perf_common import arm_watchdog
    _feed = arm_watchdog("perf_probe")
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "reference", "pallas"])
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--modes", default="foriloop,percall")
    ap.add_argument("--trace", default=None,
                    help="directory for a jax.profiler trace of 3 steps")
    ap.add_argument("--no-running-stats", action="store_true")
    ap.add_argument("--no-bn", action="store_true")
    ap.add_argument("--avg-pool", action="store_true",
                    help="replace the stem maxpool with avgpool (isolates "
                         "the select_and_scatter maxpool-backward cost)")
    ap.add_argument("--s2d", action="store_true",
                    help="space-to-depth stem rewrite (exact; MXU-denser "
                         "12-channel 4x4/s1 conv instead of 3-channel "
                         "7x7/s2)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu import amp
    from apex_tpu.models import resnet50
    from apex_tpu.optimizers import FusedLAMB
    from apex_tpu.ops import dispatch
    from apex_tpu.ops import flat as F

    # the strict device gate: the chip, or the CPU that was asked for
    from apex_tpu.utils import setup_host_backend
    platform = setup_host_backend()
    dispatch.set_backend(args.backend)
    _note(f"backend={platform} dispatch={args.backend}")

    if args.s2d and args.image % 2:
        ap.error("--s2d requires an even --image size (odd sizes silently "
                 "fall back to the plain conv stem)")
    model = resnet50(stem_pool="avg" if args.avg_pool else "max",
                     stem="space_to_depth" if args.s2d else "conv")
    # init on the host cpu backend + ONE bulk transfer (utils.host_init)
    from apex_tpu.utils import host_init, ship
    with host_init():
        params, bn_state = model.init(jax.random.key(0))
        _, handle = amp.initialize(opt_level="O2", verbosity=0)
        amp_state = handle.init_state()
        half = handle.policy.cast_model_dtype
        opt = FusedLAMB(params, lr=1e-3)
        table = opt._tables[0]
        opt_state = opt.init_state()

        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randn(args.batch, args.image, args.image, 3),
                        half)
        y = jnp.asarray(rs.randint(0, model.num_classes, args.batch),
                        jnp.int32)
    _note("host-side init done; shipping state to the default device")
    opt_state, bn_state, amp_state, x, y = ship(
        (opt_state, bn_state, amp_state, x, y))
    _note("state on device")

    # The timed modes donate their state args, which DELETES the donated
    # buffers — rebuilding state through accessor methods after a donating
    # call handed back deleted arrays when init_state() aliased self.state
    # (this killed the r4 trace step; init_state now copies).
    # Belt and braces here: keep the originals pristine; donate copies.
    pristine = (opt_state, bn_state, amp_state)

    def fresh_states():
        return jax.tree.map(jnp.copy, pristine)

    # One compiled donated-step executable shared by percall and --trace
    # (separate jax.jit wrappers would each pay the multi-minute compile).
    jstep_compiled = None

    def get_compiled_step():
        nonlocal jstep_compiled
        if jstep_compiled is None:
            jstep = jax.jit(step, donate_argnums=(0, 1, 2))
            _note("compiling per-call step")
            _feed(allow=2400.0)  # one long compile is legitimate
            o0, b0, a0 = fresh_states()
            t0 = time.perf_counter()
            jstep_compiled = jstep.lower(o0, b0, a0, x, y).compile()
            _note(f"compiled in {time.perf_counter()-t0:.1f}s")
        return jstep_compiled

    def step(opt_state, bn_state, amp_state, x, y):
        # flat-master differentiation: one fused bf16 cast, flat fp32
        # grads straight from autodiff (see bench.py train_step)
        def loss_fn(master):
            p_half = F.unflatten(master, table, dtype=half)
            logits, new_st = model.apply(p_half, bn_state, x, training=True)
            logits = logits.astype(jnp.float32)
            logp = jax.nn.log_softmax(logits)
            from apex_tpu.contrib.xentropy import select_label_logits
            loss = -jnp.mean(select_label_logits(logp, y))
            return handle.scale_loss(loss, amp_state), (loss, new_st)

        fg, (loss, new_bn) = jax.grad(loss_fn, has_aux=True)(
            opt_state[0].master)
        fg, found_inf = handle.unscale(fg, amp_state)
        new_opt = opt.apply_update(opt_state, [fg], found_inf=found_inf)
        new_amp = handle.update(amp_state, found_inf)
        return new_opt, new_bn, new_amp, loss

    if args.no_bn and args.no_running_stats:
        ap.error("--no-bn and --no-running-stats are mutually exclusive "
                 "(--no-bn removes the stats entirely)")
    if args.no_bn:
        # Replace every BN with a per-channel affine (no stats, no
        # normalization): isolates the total cost of BN in the step.
        from apex_tpu.parallel import sync_batchnorm as SBN

        def apply_affine(self, params, state, x, z=None, training=True):
            w = params.get("weight") if self.affine else None
            b = params.get("bias") if self.affine else None
            out = x.astype(jnp.float32)
            if w is not None:
                out = out * w.reshape((1,) * (x.ndim - 1) + (-1,))
            if b is not None:
                out = out + b.reshape((1,) * (x.ndim - 1) + (-1,))
            if z is not None:
                out = out + z.astype(jnp.float32)
            if self.fuse_relu:
                out = jnp.maximum(out, 0.0)
            return out.astype(x.dtype), state
        SBN.SyncBatchNorm.apply = apply_affine
        _note("BN replaced with per-channel affine (--no-bn)")

    if args.no_running_stats:
        # Skip the running-stat EMA update entirely. NOTE: since the
        # round-3 SyncBN change, mean/var come from the SAME moments pass
        # as the normalize, so this now elides only the [C]-sized EMA
        # arithmetic — expect a near-zero delta (kept as a sanity probe).
        from apex_tpu.parallel import sync_batchnorm as SBN
        orig_apply = SBN.SyncBatchNorm.apply

        def apply_no_stats(self, params, state, x, z=None, training=True):
            if not training:
                return orig_apply(self, params, state, x, z=z,
                                  training=training)
            w = params.get("weight") if self.affine else None
            bias = params.get("bias") if self.affine else None
            out, _, _, _ = SBN._bn_train(x, z, w, bias, self.eps,
                                         self.axis_name,
                                         self.axis_index_groups,
                                         self.fuse_relu, self.channel_axis)
            return out, state
        SBN.SyncBatchNorm.apply = apply_no_stats
        _note("running-stat recompute DISABLED")

    fwd_flops = analytic_resnet_flops(model, args.image)
    train_flops_img = 3.0 * fwd_flops
    _note(f"analytic fwd GFLOP/img = {fwd_flops/1e9:.3f}; "
          f"train (3x) = {train_flops_img/1e9:.3f}")

    results = {}
    modes = args.modes.split(",")

    if "percall" in modes:
        compiled = get_compiled_step()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        xla_flops = float((ca or {}).get("flops", 0.0))
        _note(f"XLA cost_analysis flops/step = {xla_flops/1e12:.3f} TF "
              f"(analytic {train_flops_img*args.batch/1e12:.3f} TF)")
        o0, b0, a0 = fresh_states()
        o, b, a, loss = compiled(o0, b0, a0, x, y)
        float(loss), float(o[0].master[0])
        t0 = time.perf_counter()
        n = args.iters
        for _ in range(n):
            o, b, a, loss = compiled(o, b, a, x, y)
        float(loss), float(o[0].master[0])
        dt = time.perf_counter() - t0
        results["percall"] = dt / n
        _note(f"percall: {dt/n*1e3:.1f} ms/step = "
              f"{args.batch*n/dt:.0f} img/s")

    if "foriloop" in modes:
        n = args.iters

        @partial(jax.jit, donate_argnums=(0, 1, 2), static_argnums=(5,))
        def run_n(opt_state, bn_state, amp_state, x, y, n):
            def body(i, carry):
                o, b, a, _ = carry
                return step(o, b, a, x, y)
            loss0 = jnp.asarray(0.0, jnp.float32)
            return jax.lax.fori_loop(
                0, n, body, (opt_state, bn_state, amp_state, loss0))

        _note("compiling fori_loop step")
        _feed(allow=2400.0)  # one long compile is legitimate
        o0, b0, a0 = fresh_states()
        t0 = time.perf_counter()
        lowered = run_n.lower(o0, b0, a0, x, y, n)
        compiled = lowered.compile()
        _note(f"compiled in {time.perf_counter()-t0:.1f}s")
        # warmup call (first dispatch pays setup costs), then time
        # the second call of the same compiled n-step loop.
        t0 = time.perf_counter()
        o, b, a, loss = compiled(o0, b0, a0, x, y)
        float(loss), float(o[0].master[0])
        _note(f"warmup call: {(time.perf_counter()-t0)/n*1e3:.1f} ms/step")
        t0 = time.perf_counter()
        o, b, a, loss = compiled(o, b, a, x, y)
        float(loss), float(o[0].master[0])
        dt = time.perf_counter() - t0
        results["foriloop"] = dt / n
        _note(f"foriloop: {dt/n*1e3:.1f} ms/step = "
              f"{args.batch*n/dt:.0f} img/s")

    def time_scalar_loop(name, body):
        """Time n iterations of `body(carry_scalar) -> scalar` on device."""
        n = args.iters

        @partial(jax.jit, static_argnums=(1,))
        def run(c0, n):
            return jax.lax.fori_loop(0, n, lambda i, c: body(c), c0)

        _note(f"compiling {name}")
        _feed(allow=2400.0)  # one long compile is legitimate
        t0 = time.perf_counter()
        compiled = run.lower(jnp.asarray(0.0, jnp.float32), n).compile()
        _note(f"compiled in {time.perf_counter()-t0:.1f}s")
        c = compiled(jnp.asarray(0.0, jnp.float32))
        float(c)
        t0 = time.perf_counter()
        c = compiled(c * 0.0)
        float(c)
        dt = time.perf_counter() - t0
        results[name] = dt / n
        _note(f"{name}: {dt/n*1e3:.1f} ms/step = {args.batch*n/dt:.0f} img/s")

    master_fwd = pristine[0][0].master

    if "fwd_eval" in modes:
        def body_fwd_eval(c):
            p_half = F.unflatten(master_fwd, table, dtype=half)
            logits, _ = model.apply(p_half, bn_state, x, training=False)
            return c + jnp.sum(logits) * 0.0 + 1.0
        time_scalar_loop("fwd_eval", body_fwd_eval)

    if "fwd_train" in modes:
        def body_fwd_train(c):
            p_half = F.unflatten(master_fwd, table, dtype=half)
            logits, new_st = model.apply(p_half, bn_state, x, training=True)
            probe = sum(jnp.sum(v) for v in jax.tree.leaves(new_st))
            return c + jnp.sum(logits) * 0.0 + probe * 0.0 + 1.0
        time_scalar_loop("fwd_train", body_fwd_train)

    if "grads" in modes:
        def body_grads(c):
            def loss_fn(master):
                p_half = F.unflatten(master, table, dtype=half)
                logits, new_st = model.apply(p_half, bn_state, x,
                                             training=True)
                logits = logits.astype(jnp.float32)
                logp = jax.nn.log_softmax(logits)
                from apex_tpu.contrib.xentropy import select_label_logits
                loss = -jnp.mean(select_label_logits(logp, y))
                return handle.scale_loss(loss, amp_state), (loss, new_st)
            fg, (loss, _) = jax.grad(loss_fn, has_aux=True)(master_fwd)
            # anchor the WHOLE grad buffer: anchoring one element lets
            # XLA slice-of-concat + DCE drop every other param's weight
            # grad and under-measure the backward
            return c + loss * 0.0 + jnp.sum(fg) * 0.0 + 1.0
        time_scalar_loop("grads", body_grads)

    if args.trace:
        import jax.profiler
        compiled = get_compiled_step()
        o0, b0, a0 = fresh_states()
        o, b, a, loss = compiled(o0, b0, a0, x, y)
        float(loss)
        with jax.profiler.trace(args.trace):
            for _ in range(3):
                o, b, a, loss = compiled(o, b, a, x, y)
            float(loss), float(o[0].master[0])
        _note(f"trace written to {args.trace}")

    # MFU is a device metric: only against the attached chip's peak
    from apex_tpu.prof import chip_peak
    peak = chip_peak().bf16_flops_per_s if platform == "tpu" else None
    out = {
        "backend": args.backend,
        "batch": args.batch,
        "analytic_train_gflop_per_img": round(train_flops_img / 1e9, 2),
    }
    # FLOPs actually executed per mode: fwd-only modes run 1x fwd
    mode_flops = {"percall": train_flops_img, "foriloop": train_flops_img,
                  "grads": train_flops_img, "fwd_eval": fwd_flops,
                  "fwd_train": fwd_flops}
    for mode, spp in results.items():
        out[f"{mode}_ms_per_step"] = round(spp * 1e3, 2)
        out[f"{mode}_img_s"] = round(args.batch / spp, 1)
        if peak:
            out[f"{mode}_mfu"] = round(
                mode_flops[mode] * args.batch / spp / peak, 4)
    from _perf_common import stamp_result
    print(json.dumps(stamp_result(out, "perf_probe")))


if __name__ == "__main__":
    main()
