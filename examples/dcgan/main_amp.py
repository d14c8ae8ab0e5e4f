"""DCGAN mixed-precision example (the apex examples/dcgan/main_amp.py
equivalent).

The reference DCGAN driver demonstrates the multi-loss AMP API: TWO models
(G, D), TWO optimizers, THREE scaled losses via ``amp.initialize(...,
num_losses=3)`` and per-loss ``scale_loss(loss, opt, loss_id=i)``. This
driver shows the same shape functionally: one AmpHandle with three
LossScalers, each loss scaled/unscaled with its own scaler state.

Synthetic 32x32 data (no dataset download in this environment):

    python examples/dcgan/main_amp.py --steps 20 --platform cpu
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--nz", type=int, default=64, help="latent dim")
    p.add_argument("--ngf", type=int, default=32)
    p.add_argument("--ndf", type=int, default=32)
    p.add_argument("--lr", type=float, default=2e-4)
    p.add_argument("--opt-level", default="O1")
    p.add_argument("--platform", default=None)
    p.add_argument("--telemetry", nargs="?", const="1", default=None,
                   help="write a TELEM_*.jsonl runtime-telemetry sidecar "
                        "(per-interval step records + the THREE loss "
                        "scalers' event counters) + stall watchdog")
    p.add_argument("--numerics", action="store_true",
                   default=os.environ.get("BENCH_NUMERICS", "")
                   not in ("", "0"),
                   help="r09 numerics: carry a per-parameter overflow "
                        "census per loss scaler (the multi-loss "
                        "provenance case: a skip names WHICH model's "
                        "WHICH parameter overflowed, per loss_id) + a "
                        "final underflow census of the G grads")
    p.add_argument("--slo", default=os.environ.get("BENCH_SLO") or None,
                   help="r13 in-run SLO rules (prof/slo.py syntax, "
                        "e.g. 'step_p95_ms<=40,skip_rate<=0.3') checked"
                        " at the print cadence — needs --telemetry")
    return p.parse_args()


def main():
    args = parse_args()
    import jax
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu import amp
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.ops import flat as F
    # BEFORE any other jax op: the strict device gate (the chip, or the
    # CPU that was asked for — never a silent fall-back)
    from apex_tpu.utils import setup_host_backend, host_init, ship
    setup_host_backend()

    # -- models (simple conv G/D over NHWC 32x32) ------------------------
    def g_init(key):
        ks = jax.random.split(key, 4)
        s = lambda k, sh: jax.random.normal(k, sh) * 0.02
        return {
            "fc": s(ks[0], (args.nz, 4 * 4 * args.ngf * 4)),
            "c1": s(ks[1], (4, 4, args.ngf * 4, args.ngf * 2)),
            "c2": s(ks[2], (4, 4, args.ngf * 2, args.ngf)),
            "c3": s(ks[3], (4, 4, args.ngf, 3)),
        }

    def d_init(key):
        ks = jax.random.split(key, 4)
        s = lambda k, sh: jax.random.normal(k, sh) * 0.02
        return {
            "c1": s(ks[0], (4, 4, 3, args.ndf)),
            "c2": s(ks[1], (4, 4, args.ndf, args.ndf * 2)),
            "c3": s(ks[2], (4, 4, args.ndf * 2, args.ndf * 4)),
            "fc": s(ks[3], (4 * 4 * args.ndf * 4, 1)),
        }

    def upconv(x, w, out_hw):
        b, h, _, _ = x.shape
        y = jax.image.resize(x, (b, out_hw, out_hw, x.shape[-1]), "nearest")
        return jax.lax.conv_general_dilated(
            y, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def downconv(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (2, 2), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def generator(p, z):
        h = (z @ p["fc"]).reshape(-1, 4, 4, args.ngf * 4)
        h = jax.nn.relu(h)
        h = jax.nn.relu(upconv(h, p["c1"], 8))
        h = jax.nn.relu(upconv(h, p["c2"], 16))
        return jnp.tanh(upconv(h, p["c3"], 32))

    def discriminator(p, x):
        h = jax.nn.leaky_relu(downconv(x, p["c1"]), 0.2)
        h = jax.nn.leaky_relu(downconv(h, p["c2"]), 0.2)
        h = jax.nn.leaky_relu(downconv(h, p["c3"]), 0.2)
        return (h.reshape(h.shape[0], -1) @ p["fc"])[:, 0]

    # -- AMP with three scaled losses (reference: num_losses=3) ----------
    # host-side init + one bulk transfer (the bench.py move: per-leaf
    # init on the chip is one small compile per leaf)
    with host_init():
        _, handle = amp.initialize(opt_level=args.opt_level, num_losses=3,
                                   verbosity=1)
        amp_state = handle.init_state()
        gp, dp = g_init(jax.random.key(1)), d_init(jax.random.key(2))
        g_opt = FusedAdam(gp, lr=args.lr, betas=(0.5, 0.999))
        d_opt = FusedAdam(dp, lr=args.lr, betas=(0.5, 0.999))
        g_table, d_table = g_opt._tables[0], d_opt._tables[0]
        g_state, d_state = g_opt.init_state(), d_opt.init_state()
    g_state, d_state, amp_state = ship((g_state, d_state, amp_state))
    autocast = amp.autocast if handle.policy.autocast else None

    g_fwd = amp.autocast(generator) if autocast else generator
    d_fwd = amp.autocast(discriminator) if autocast else discriminator

    def bce_logits(logits, target):
        return jnp.mean(jnp.maximum(logits, 0) - logits * target +
                        jnp.log1p(jnp.exp(-jnp.abs(logits))))

    # r09 numerics: one provenance census per loss scaler — the
    # multi-loss case: a skip is attributable to (loss_id, parameter)
    censuses = None
    if args.numerics:
        from apex_tpu.prof import numerics as NU
        d_meta, g_meta = NU.tree_meta(d_table), NU.tree_meta(g_table)
        censuses = (NU.empty_census(d_meta.n), NU.empty_census(d_meta.n),
                    NU.empty_census(g_meta.n))

    # donate both optimizers' flat state + the scaler state (r06
    # donation audit): in-place update, no per-step state copy; the
    # train loop rebinds all three before any reuse
    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_step(g_state, d_state, amp_state, real, z, key,
                   censuses=None):
        gp = F.unflatten(g_state[0].master, g_table)
        dp = F.unflatten(d_state[0].master, d_table)
        fake = g_fwd(gp, z)

        # D: real loss (scaler 0) + fake loss (scaler 1)
        def d_loss_real(dp):
            return handle.scale_loss(
                bce_logits(d_fwd(dp, real), 1.0), amp_state, loss_id=0)

        def d_loss_fake(dp):
            return handle.scale_loss(
                bce_logits(d_fwd(dp, jax.lax.stop_gradient(fake)), 0.0),
                amp_state, loss_id=1)

        dg_r = jax.grad(d_loss_real)(dp)
        dg_f = jax.grad(d_loss_fake)(dp)
        fg_r = F.flatten(dg_r, table=d_table, dtype=jnp.float32)[0]
        fg_f = F.flatten(dg_f, table=d_table, dtype=jnp.float32)[0]
        fg_r, inf0 = handle.unscale(fg_r, amp_state, loss_id=0)
        fg_f, inf1 = handle.unscale(fg_f, amp_state, loss_id=1)
        d_new = d_opt.apply_update(d_state, [fg_r + fg_f],
                                   found_inf=inf0 | inf1)

        # G: fool D (scaler 2)
        def g_loss(gp):
            return handle.scale_loss(
                bce_logits(d_fwd(dp, g_fwd(gp, z)), 1.0), amp_state,
                loss_id=2)

        gg = jax.grad(g_loss)(gp)
        fgg = F.flatten(gg, table=g_table, dtype=jnp.float32)[0]
        fgg, inf2 = handle.unscale(fgg, amp_state, loss_id=2)
        g_new = g_opt.apply_update(g_state, [fgg], found_inf=inf2)

        # each scaler backs off / grows on ITS OWN loss's overflow (the
        # joint inf0|inf1 flag only gates the shared optimizer step-skip);
        # reference num_losses semantics: scaler.py per-loss update_scale.
        if censuses is not None:
            c0, c1, c2 = censuses
            new_amp, c0 = handle.update_with_census(
                amp_state, inf0, fg_r, c0, loss_id=0, table=d_table)
            new_amp, c1 = handle.update_with_census(
                new_amp, inf1, fg_f, c1, loss_id=1, table=d_table)
            new_amp, c2 = handle.update_with_census(
                new_amp, inf2, fgg, c2, loss_id=2, table=g_table)
            new_censuses = (c0, c1, c2)
        else:
            new_amp = handle.update(amp_state, inf0, loss_id=0)
            new_amp = handle.update(new_amp, inf1, loss_id=1)
            new_amp = handle.update(new_amp, inf2, loss_id=2)
            new_censuses = None
        d_loss = bce_logits(d_fwd(dp, real), 1.0) + \
            bce_logits(d_fwd(dp, fake), 0.0)
        g_l = bce_logits(d_fwd(dp, fake), 1.0)
        return g_new, d_new, new_amp, new_censuses, d_loss, g_l

    # runtime telemetry (r07): the multi-loss case — one amp record per
    # scaler at close, interval step records at the print cadence
    telem = telem_wd = tracer = slo_mon = None
    if args.telemetry:
        from apex_tpu import prof
        path = (args.telemetry if args.telemetry != "1" else
                prof.metrics.default_sidecar_path("dcgan"))
        telem = prof.MetricsLogger(
            path, run="dcgan", meta={"opt_level": args.opt_level,
                                     "batch": args.batch_size,
                                     "num_losses": 3})
        train_step = telem.track_recompiles(train_step, "train_step")
        tracer = prof.SpanTracer()
        telem_wd = prof.Watchdog(telem, min_interval_s=120.0,
                                 label="dcgan", tracer=tracer).start()
        if args.slo:
            slo_mon = prof.SLOMonitor(args.slo, logger=telem,
                                      min_samples=1)
        print(f"=> telemetry sidecar: {path}")

    rs = np.random.RandomState(0)
    t0 = time.perf_counter()
    t_int = t0
    for it in range(args.steps):
        real = jnp.asarray(rs.randn(args.batch_size, 32, 32, 3) * 0.5,
                           jnp.float32)
        z = jnp.asarray(rs.randn(args.batch_size, args.nz), jnp.float32)
        g_state, d_state, amp_state, censuses, d_l, g_l = train_step(
            g_state, d_state, amp_state, real, z, jax.random.key(it),
            censuses)
        if telem_wd is not None:
            telem_wd.heartbeat()
        if (it + 1) % 10 == 0:
            # apex-lint: disable=host-sync-in-hot-loop -- print-cadence fetch: losses leave the device every 10 steps
            d_f, g_f = float(d_l), float(g_l)
            print(f"it {it + 1}/{args.steps} loss_D {d_f:.4f} "
                  f"loss_G {g_f:.4f} "
                  f"scales {[float(s.scale) for s in amp_state]}")
            if telem is not None:
                now = time.perf_counter()
                int_ms = (now - t_int) / 10 * 1e3
                telem.log_step(it + 1, steps=10, step_ms=int_ms,
                               loss=d_l, loss_g=g_l,
                               loss_scale=amp_state[0].scale)
                if tracer is not None:
                    tn = tracer.now()
                    iv = tracer.begin("train_interval",
                                      t0=tn - (now - t_int),
                                      step=it + 1, steps=10)
                    tracer.end(iv, t1=tn)
                if slo_mon is not None:
                    slo_mon.observe("step_ms", int_ms,
                                    context={"step": it + 1})
                t_int = now
    print(f"done in {time.perf_counter() - t0:.1f}s")
    if telem is not None:
        for i in range(3):   # one amp record per loss scaler
            telem.log_amp(handle.scalers[i], amp_state[i], loss_id=i)
        if censuses is not None:
            # per-loss provenance: any scaler that skipped names its
            # culprit parameters (d params for losses 0/1, g for 2)
            metas = (d_meta, d_meta, g_meta)
            for i in range(3):
                if int(amp_state[i].overflow_count) > 0 and \
                        int(censuses[i].step) >= 0:
                    telem.log_overflow(metas[i], censuses[i], loss_id=i,
                                       loss_scale=amp_state[i].scale)
            # one underflow sample of the final G grads
            from apex_tpu.prof import numerics as NU
            gp_f = F.unflatten(g_state[0].master, g_table)
            dp_f = F.unflatten(d_state[0].master, d_table)
            gg = jax.grad(lambda p: bce_logits(
                d_fwd(dp_f, g_fwd(p, z)), 1.0))(gp_f)
            fgg = F.flatten(gg, table=g_table, dtype=jnp.float32)[0]
            telem.log_numerics(g_meta, NU.underflow_census(
                fgg, table=g_table), step=args.steps, loss_id=2)
        if slo_mon is not None:
            # the multi-loss skip budget: worst scaler's rate decides
            rates = [int(s.overflow_count) / max(int(s.step_count), 1)
                     for s in amp_state]
            slo_mon.observe("skip_rate", max(rates))
        if tracer is not None:
            telem.log_spans(tracer)
        telem_wd.stop()
        telem.close()
        print(f"=> telemetry written: {telem.path}")


if __name__ == "__main__":
    main()
