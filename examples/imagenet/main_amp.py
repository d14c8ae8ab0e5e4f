"""ImageNet-style mixed-precision training driver (the apex
examples/imagenet/main_amp.py equivalent, TPU-native).

The reference script wires argparse -> amp.initialize -> DDP -> epochs of
train/validate with img/s reporting (examples/imagenet/main_amp.py:
opt_level/loss-scale/keep-batchnorm flags, AverageMeter throughput
:320,390-398, checkpoint resume :178-192). This driver reproduces that
surface on the flat-buffer stack: one jitted train step carrying
(opt_state, bn_state, amp_state), data parallel over a mesh axis, dynamic
loss scaling on device, checkpoint/resume via apex_tpu.utils.

Run (synthetic data; no dataset download in this environment):

    python examples/imagenet/main_amp.py --arch resnet50 --batch-size 64 \
        --opt-level O2 --epochs 1 --steps-per-epoch 20
    python examples/imagenet/main_amp.py --data-parallel 8 --platform cpu \
        --arch tiny --image-size 32     # 8-device CPU mesh smoke run
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def parse_args():
    p = argparse.ArgumentParser(description="TPU AMP ImageNet training")
    p.add_argument("--arch", default="resnet50",
                   choices=["resnet18", "resnet34", "resnet50", "tiny",
                            "vit_tiny", "vit_small", "vit_b16"])
    p.add_argument("--data", default=None, metavar="DIR",
                   help="train from an on-disk image-folder dataset "
                        "(root/<class>/*.ppm|*.npy, or root/train + "
                        "root/val splits) through the sharded loader + "
                        "native decode pipeline + device prefetcher; "
                        "default stays the synthetic pool")
    p.add_argument("--data-workers", type=int, default=2,
                   help="host worker threads assembling --data batches")
    p.add_argument("--prefetch-depth", type=int, default=2,
                   help="device batches kept in flight by the prefetcher")
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--steps-per-epoch", type=int, default=30,
                   help="steps per epoch (0 with --data = one full "
                        "pass over the shard)")
    p.add_argument("--batch-size", type=int, default=64,
                   help="GLOBAL batch size")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight-decay", type=float, default=1e-4)
    p.add_argument("--opt-level", default="O2",
                   choices=["O0", "O1", "O2", "O3"])
    p.add_argument("--loss-scale", default=None,
                   help="'dynamic' (default for O2) or a number")
    p.add_argument("--keep-batchnorm-fp32", default=None)
    p.add_argument("--optimizer", default="sgd",
                   choices=["sgd", "adam", "lamb"])
    p.add_argument("--dropout", type=float, default=0.0,
                   help="attention dropout (ViT archs only)")
    p.add_argument("--sync_bn", action="store_true",
                   help="convert BatchNorms to cross-replica "
                        "SyncBatchNorm under --data-parallel (the "
                        "reference's --sync_bn, main_amp.py:85-86)")
    p.add_argument("--data-parallel", type=int, default=1,
                   help="mesh size for DDP (1 = single device)")
    p.add_argument("--platform", default=None,
                   help="force a JAX platform (e.g. cpu for mesh smoke)")
    p.add_argument("--resume", default=None)
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--print-freq", type=int, default=10)
    p.add_argument("--telemetry", nargs="?", const="1", default=None,
                   help="write a TELEM_*.jsonl runtime-telemetry sidecar "
                        "(apex_tpu.prof.metrics: per-interval step time/"
                        "img/s, loss-scale events, compile counts, memory"
                        " watermarks) + arm the stall watchdog; pass a "
                        "path or let it auto-name in the cwd")
    p.add_argument("--fleet-probe", action="store_true",
                   default=os.environ.get("BENCH_FLEET", "")
                   not in ("", "0"),
                   help="r10 fleet observability: at every print "
                        "interval, all-gather the per-process step-EMA "
                        "(fleet_skew record naming the slowest process) "
                        "and — when this is one process of a "
                        "multi-process run — check cross-process "
                        "replica agreement (desync record naming the "
                        "first divergent parameter). Needs --telemetry; "
                        "all processes must share the print cadence")
    p.add_argument("--numerics", action="store_true",
                   default=os.environ.get("BENCH_NUMERICS", "")
                   not in ("", "0"),
                   help="r09 numerics observability: carry the "
                        "per-parameter overflow-provenance census "
                        "through the train step (skip steps emit an "
                        "amp_overflow record naming the culprit "
                        "parameters), sample an underflow census every "
                        "print interval, and audit the step's precision "
                        "coverage — needs --telemetry for the records")
    p.add_argument("--slo", default=os.environ.get("BENCH_SLO") or None,
                   help="r13 in-run SLO rules (apex_tpu/prof/slo.py "
                        "syntax, e.g. 'step_p95_ms<=40,skip_rate<=0.2,"
                        "input_wait_share<=0.1') evaluated at every "
                        "print interval; violations emit schema-5 "
                        "alert records — needs --telemetry")
    return p.parse_args()


def main():
    args = parse_args()
    import jax
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    # the strict device gate: the chip, or the CPU that was asked for —
    # never a silent fall-back
    from apex_tpu.utils import setup_host_backend, host_init, ship
    setup_host_backend()

    from apex_tpu import amp
    from apex_tpu.models import resnet18, resnet34, resnet50, ResNet
    from apex_tpu.optimizers import FusedSGD, FusedAdam, FusedLAMB
    from apex_tpu.parallel import (DistributedDataParallel,
                                   convert_syncbn_model, make_mesh)
    from apex_tpu.ops import flat as F
    from apex_tpu.utils import save_checkpoint, load_checkpoint

    # real-data path: class count comes from the dataset scan (the
    # reference's ImageFolder contract), not the arch default
    train_ds = val_ds = None
    if args.data:
        from apex_tpu.data import ImageFolder
        troot = os.path.join(args.data, "train")
        vroot = os.path.join(args.data, "val")
        if os.path.isdir(troot):
            train_ds = ImageFolder(troot)
            val_ds = ImageFolder(vroot) if os.path.isdir(vroot) \
                else train_ds
        else:  # unsplit mini datasets: train and eval share the folder
            train_ds = val_ds = ImageFolder(args.data)
        num_classes = len(train_ds.classes)
        print(f"=> dataset {args.data}: {len(train_ds)} train / "
              f"{len(val_ds)} val samples, {num_classes} classes")
    else:
        num_classes = 10 if args.arch in ("tiny", "vit_tiny") else 1000
    is_vit = args.arch.startswith("vit")
    if args.arch == "tiny":
        model = ResNet(block_sizes=(1, 1), bottleneck=True, width=8,
                       num_classes=num_classes)
    elif args.arch == "vit_tiny":
        from apex_tpu.models import vit_tiny
        model = vit_tiny(num_classes=num_classes,
                         image_size=args.image_size, patch_size=4,
                         dropout=args.dropout)
    elif is_vit:
        from apex_tpu.models import vit_small, vit_b16
        model = {"vit_small": vit_small, "vit_b16": vit_b16}[args.arch](
            num_classes=num_classes, image_size=args.image_size,
            dropout=args.dropout)
    else:
        if args.dropout:
            raise SystemExit("--dropout only applies to ViT archs")
        model = {"resnet18": resnet18, "resnet34": resnet34,
                 "resnet50": resnet50}[args.arch](
                     num_classes=num_classes)
    if args.sync_bn:
        if is_vit:
            raise SystemExit("--sync_bn applies to BN archs, not ViT")
        if args.data_parallel <= 1:
            raise SystemExit("--sync_bn needs --data-parallel > 1 "
                             "(single-device BN is already exact)")
        model = convert_syncbn_model(model, axis_name="data")
        print("=> BatchNorms converted to SyncBatchNorm over the "
              "data axis")
    def apply_model(p, bn, x, training, key=None):
        """(logits, new_bn) for either family — ViT has no BN state."""
        if is_vit:
            return model.apply(p, x, is_training=training,
                               dropout_key=key), bn
        return model.apply(p, bn, x, training=training)

    # build all init-time state on the host cpu backend, then ship it
    # once (per-leaf init on the chip is one small compile per leaf —
    # the same move bench.py makes)
    with host_init():
        if is_vit:  # no batch-stats state; keep one step signature
            params, bn_state = model.init(jax.random.key(0)), {}
        else:
            params, bn_state = model.init(jax.random.key(0))

        overrides = {}
        if args.loss_scale is not None:
            overrides["loss_scale"] = args.loss_scale
        if args.keep_batchnorm_fp32 is not None:
            overrides["keep_batchnorm_fp32"] = args.keep_batchnorm_fp32
        _, handle = amp.initialize(opt_level=args.opt_level, verbosity=1,
                                   **overrides)
        amp_state = handle.init_state()
        half = handle.policy.cast_model_dtype or jnp.float32

        opt_cls = {"sgd": partial(FusedSGD, momentum=args.momentum),
                   "adam": FusedAdam, "lamb": FusedLAMB}[args.optimizer]
        opt = opt_cls(params, lr=args.lr, weight_decay=args.weight_decay)
        table = opt._tables[0]
        opt_state = opt.init_state()

    start_epoch = 0
    if args.resume:
        with host_init():  # array reconstruction stays host-side too
            out = load_checkpoint(args.resume, optimizer=opt,
                                  amp_handle=handle)
            opt_state = opt.init_state()
            amp_state = out.get("amp_state", amp_state)
        start_epoch = out["step"]
        print(f"=> resumed from {args.resume} (epoch {start_epoch})")

    n_dev = args.data_parallel
    mesh = make_mesh({"data": n_dev}) if n_dev > 1 else None
    ddp = DistributedDataParallel(axis_name="data")

    # one bulk transfer to where training runs: replicated on the mesh
    # under dp, else the default device (a no-op alias on pure-cpu runs)
    if mesh is not None:
        target = NamedSharding(mesh, P())
    else:
        target = jax.devices()[0]
    opt_state, bn_state, amp_state = ship(
        (opt_state, bn_state, amp_state), target)

    from apex_tpu.data import normalize_imagenet

    def loss_and_state(master, bn, x, y, amp_st, step_key):
        # uint8 batch in; normalization INSIDE the jitted step so XLA
        # fuses the subtract/divide into the first conv's input (no
        # separate fp32 batch materialized in HBM)
        x = normalize_imagenet(x, dtype=half if
                               handle.policy.cast_model_dtype is not None
                               else jnp.float32)
        # flat-master differentiation: the half cast is ONE fused convert
        # on the flat buffer and the grad arrives as one flat fp32 buffer
        # (161 per-leaf casts/flattens cost ~15 ms/step of per-op
        # overhead on a v5e — docs/PERF.md r03)
        if handle.policy.cast_model_dtype is not None:
            p = F.unflatten(master, table, dtype=half)
        else:
            p = F.unflatten(master, table)
        logits, new_bn = apply_model(p, bn, x, training=True, key=step_key)
        logits = logits.astype(jnp.float32)
        logp = jax.nn.log_softmax(logits)
        from apex_tpu.contrib.xentropy import select_label_logits
        loss = -jnp.mean(select_label_logits(logp, y))
        acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
        return handle.scale_loss(loss, amp_st), (loss, acc, new_bn)

    def step_body(opt_state, bn_state, amp_state, x, y, step_key,
                  census=None, *, distributed):
        if distributed:
            # decorrelate dropout across data-parallel shards
            step_key = jax.random.fold_in(
                step_key, jax.lax.axis_index("data"))
        fg, (loss, acc, new_bn) = jax.grad(
            lambda m: loss_and_state(m, bn_state, x, y, amp_state,
                                     step_key),
            has_aux=True)(opt_state[0].master)
        if distributed:
            # one flat buffer = one psum (the ideal "bucket": the whole
            # gradient in a single allreduce)
            fg = ddp.average_gradients(fg)
            loss = jax.lax.pmean(loss, "data")
            acc = jax.lax.pmean(acc, "data")
        fg, found_inf = handle.unscale(fg, amp_state)
        new_opt = opt.apply_update(opt_state, [fg], found_inf=found_inf)
        if census is not None:
            # r09 numerics: branchless per-parameter census carry — the
            # host resolves it into culprit paths only when a skip
            # actually happened (prof/numerics.py)
            new_amp, new_census = handle.update_with_census(
                amp_state, found_inf, fg, census, table=table)
            return new_opt, new_bn, new_amp, new_census, loss, acc
        new_amp = handle.update(amp_state, found_inf)
        return new_opt, new_bn, new_amp, loss, acc

    # donate the flat opt/bn/amp state (r06 donation audit): the step
    # updates ~3x-model-size buffers in place instead of allocating a
    # fresh copy each call; every caller rebinds before any reuse.
    # (x/y stay undonated: the uint8 batch feeds a convert, so its
    # buffer can never alias an output — donating it only warns.)
    if mesh is None:
        train_step = jax.jit(partial(step_body, distributed=False),
                             donate_argnums=(0, 1, 2))
    else:
        train_step = jax.jit(jax.shard_map(
            partial(step_body, distributed=True),
            mesh=mesh,
            in_specs=(P(), P(), P(), P("data"), P("data"), P()),
            out_specs=(P(), P(), P(), P(), P()),
            check_vma=False),  # check_vma: pallas_call inside does not support vma checking
            donate_argnums=(0, 1, 2))

    rs = np.random.RandomState(0)
    sz = args.image_size

    # place batches in their training sharding AHEAD of consumption —
    # otherwise the whole batch lands on device 0 and is resliced on the
    # critical path every step
    batch_sharding = None
    if mesh is not None:
        batch_sharding = NamedSharding(mesh, P("data"))

    from apex_tpu.data import DevicePrefetcher, HostImageLoader
    # the ACTIVE prefetcher (telemetry reads its input-wait accounting)
    pf_ref: list = [None]

    def _wrap(src, background):
        pf = DevicePrefetcher(src, depth=args.prefetch_depth,
                              sharding=batch_sharding,
                              background=background)
        pf_ref[0] = pf
        return pf

    def _cycle(loader, n):
        it = iter(loader)
        for _ in range(n):
            try:
                yield next(it)
            except StopIteration:  # next epoch (fresh shuffle/crops)
                it = iter(loader)
                yield next(it)

    if args.data:
        # On-disk path: sharded folder scan -> host worker pool reading
        # + native decode/crop/flip (csrc image_pipeline) -> background
        # device prefetch. Shard = this process's rows of the (seed,
        # epoch) global permutation; single-process here, but the same
        # loader serves multi-host via process_index/process_count.
        from apex_tpu.data import ShardedImageFolderLoader
        loader = ShardedImageFolderLoader(
            train_ds, batch_size=args.batch_size, crop=(sz, sz), seed=0,
            workers=args.data_workers)
        val_loader = ShardedImageFolderLoader(
            val_ds, batch_size=args.batch_size, crop=(sz, sz),
            train=False, workers=args.data_workers)
        if args.steps_per_epoch <= 0:
            args.steps_per_epoch = len(loader)

        def prefetcher(n):
            # background=True: batch assembly overlaps the compiled
            # step instead of riding its critical path
            return _wrap(_cycle(loader, n), background=True)

        def val_batches():
            return _wrap(iter(val_loader.set_epoch(0)), background=True)
    else:
        # Host batch assembly: a synthetic uint8 image POOL fed through
        # the real augmentation loader — shuffle + random crop + random
        # flip run in the native threaded runtime
        # (csrc/image_pipeline.cpp), exactly the reference example's
        # transforms+DataLoader role (main_amp.py:229-246);
        # normalization runs inside the jitted step.
        pool_n = max(4 * args.batch_size, 512)
        pool = rs.randint(0, 256, (pool_n, sz + 8, sz + 8, 3),
                          dtype=np.uint8)
        pool_labels = rs.randint(0, num_classes, pool_n).astype(np.int32)

        # last n_val_imgs rows are the validation hold-out — train only
        # on the rest (a batch_size multiple so eval compiles exactly
        # once)
        n_val_imgs = max(args.batch_size,
                         (min(2 * args.batch_size, pool_n // 4)
                          // args.batch_size) * args.batch_size)
        loader = HostImageLoader(pool[:-n_val_imgs],
                                 pool_labels[:-n_val_imgs],
                                 batch_size=args.batch_size,
                                 crop=(sz, sz), seed=0)

        def prefetcher(n):
            return _wrap(_cycle(loader, n), background=False)

        # the validation hold-out (excluded from the loader above):
        # center crops, no augmentation
        off = (pool.shape[1] - sz) // 2
        val_x = pool[-n_val_imgs:, off:off + sz, off:off + sz]
        val_y = pool_labels[-n_val_imgs:]

        def val_batches():
            return _wrap(
                ((val_x[i:i + args.batch_size],
                  val_y[i:i + args.batch_size])
                 for i in range(0, n_val_imgs, args.batch_size)),
                background=False)

    kk = min(5, num_classes)

    @jax.jit
    def eval_step(opt_state, bn_state, x, y):
        xn = normalize_imagenet(x, dtype=half if
                                handle.policy.cast_model_dtype is not None
                                else jnp.float32)
        p = (F.unflatten(opt_state[0].master, table, dtype=half)
             if handle.policy.cast_model_dtype is not None
             else F.unflatten(opt_state[0].master, table))
        logits, _ = apply_model(p, bn_state, xn, training=False)
        logits = logits.astype(jnp.float32)
        _, topk = jax.lax.top_k(logits, kk)   # descending
        hit = topk == y[:, None]
        return (jnp.mean(hit[:, 0].astype(jnp.float32)),
                jnp.mean(jnp.any(hit, -1).astype(jnp.float32)))

    # r09 numerics: provenance census carried through the jitted step
    # (single-device path; the shard_map step is not instrumented — its
    # census would need replicated-spec plumbing for no extra signal,
    # since grads are identical across data-parallel replicas anyway)
    use_numerics = args.numerics and mesh is None
    if args.numerics and mesh is not None:
        print("=> --numerics: data-parallel step not instrumented; "
              "running without the census")
    num_meta = census = None
    if use_numerics:
        from apex_tpu.prof import numerics as NU
        num_meta = NU.tree_meta(table)
        census = NU.empty_census(num_meta.n)

        @jax.jit
        def underflow_probe(opt_state, bn_state, amp_state, x, y,
                            step_key):
            # the sampled underflow census: one extra (untimed) grad
            # computation at the print cadence, never in the step path
            fg, _ = jax.grad(
                lambda m: loss_and_state(m, bn_state, x, y, amp_state,
                                         step_key),
                has_aux=True)(opt_state[0].master)
            fg, _ = handle.unscale(fg, amp_state)
            return NU.underflow_census(fg, table=table)

    # runtime telemetry (r07): per-interval step records + AMP counters
    # + compile tracking + stall watchdog. Per-step cost is one buffered
    # append and a heartbeat clock read; device scalars (loss, scale)
    # are held by reference and fetched only at flush boundaries.
    telem = telem_wd = tracer = slo_mon = None
    if args.telemetry:
        from apex_tpu import prof
        path = (args.telemetry if args.telemetry != "1" else
                prof.metrics.default_sidecar_path(f"imagenet_{args.arch}"))
        telem = prof.MetricsLogger(
            path, run=f"imagenet_{args.arch}_{args.opt_level}",
            meta={"arch": args.arch, "opt_level": args.opt_level,
                  "batch": args.batch_size, "devices": n_dev})
        # the wrapper flags avals changes of the train step — the silent
        # recompile that turns a tuned run into a compile loop
        train_step = telem.track_recompiles(train_step, "train_step")
        # r13 phase spans: train intervals, census/fleet probes,
        # validation — logged at close; the watchdog names the open
        # span when a stall fires
        tracer = prof.SpanTracer()
        telem_wd = prof.Watchdog(telem, min_interval_s=120.0,
                                 label="imagenet",
                                 tracer=tracer).start()
        if args.slo:
            # interval-cadence observations: one bad interval is a
            # violation, don't wait for 8 of them
            slo_mon = prof.SLOMonitor(args.slo, logger=telem,
                                      min_samples=1)
            print("=> SLO rules armed: " + ", ".join(
                r.name for r in slo_mon.rules))
        print(f"=> telemetry sidecar: {telem.path}")

    # r10 fleet probes: per-interval skew gather; the desync check only
    # when there genuinely is a fleet to disagree with (pc > 1). Both
    # run at the print cadence — identical across processes — never in
    # the step path.
    fleet_probe = desync_probe = None
    if args.fleet_probe and telem is not None:
        from apex_tpu.prof import fleet as FL
        fleet_probe = FL.FleetProbe(telem, every=1)
        if fleet_probe.pc > 1:
            desync_probe = FL.DesyncProbe(table, telem)
        print(f"=> fleet probe armed (process "
              f"{fleet_probe.pi}/{fleet_probe.pc}"
              + (", desync check on)" if desync_probe else ")"))

    print(f"training {args.arch} opt_level={args.opt_level} "
          f"devices={n_dev} global_batch={args.batch_size}")
    dropout_base = jax.random.key(17)
    overflows_seen = 0   # host-side watermark for provenance emission
    for epoch in range(start_epoch, args.epochs):
        t0, seen = time.perf_counter(), 0
        t_int, seen_int = t0, 0
        for it, (x, y) in enumerate(prefetcher(args.steps_per_epoch)):
            step_key = jax.random.fold_in(
                dropout_base, epoch * args.steps_per_epoch + it)
            if census is not None:
                (opt_state, bn_state, amp_state, census, loss,
                 acc) = train_step(opt_state, bn_state, amp_state, x, y,
                                   step_key, census)
            else:
                opt_state, bn_state, amp_state, loss, acc = train_step(
                    opt_state, bn_state, amp_state, x, y, step_key)
            seen += args.batch_size
            seen_int += args.batch_size
            if telem_wd is not None:
                telem_wd.heartbeat()
            if (it + 1) % args.print_freq == 0:
                # apex-lint: disable=host-sync-in-hot-loop -- interval boundary: the img/s window closes on device-complete work
                jax.block_until_ready(loss)
                dt = time.perf_counter() - t0
                # host-pipeline stalls this interval (per-step mean, the
                # same basis as step_ms — prefetcher accounting)
                waits = pf_ref[0].pop_input_waits()
                in_wait = sum(waits) / max(len(waits), 1)
                # apex-lint: disable=host-sync-in-hot-loop -- print-cadence fetch: loss/acc leave the device every print_freq steps
                loss_f, acc_f = float(loss), float(acc)
                # reference metric: world*batch/batch_time (main_amp.py:390)
                print(f"epoch {epoch} it {it + 1}/{args.steps_per_epoch} "
                      f"loss {loss_f:.4f} acc {acc_f:.3f} "
                      f"scale {float(amp_state[0].scale):.0f} "
                      f"img/s {seen / dt:.1f}"
                      + (f" in_wait {in_wait:.1f}ms" if args.data else ""))
                if telem is not None:
                    now = time.perf_counter()
                    gstep = epoch * args.steps_per_epoch + it + 1
                    int_ms = (now - t_int) / args.print_freq * 1e3
                    telem.log_step(
                        gstep,
                        steps=args.print_freq,
                        step_ms=int_ms,
                        throughput=seen_int / (now - t_int),
                        unit="img/s", loss=loss,
                        input_wait_ms=round(in_wait, 3),
                        loss_scale=amp_state[0].scale, epoch=epoch)
                    if tracer is not None:
                        # the interval as one backdated span — the
                        # train-phase timeline in the sidecar
                        tn = tracer.now()
                        iv = tracer.begin("train_interval",
                                          t0=tn - (now - t_int),
                                          epoch=epoch, step=gstep,
                                          steps=args.print_freq)
                        tracer.end(iv, t1=tn)
                    t_int, seen_int = now, 0
                    if slo_mon is not None:
                        slo_mon.observe("step_ms", int_ms,
                                        context={"step": gstep})
                        if args.data:
                            slo_mon.observe(
                                "input_wait_share",
                                in_wait / max(int_ms, 1e-9),
                                context={"step": gstep})
                    probe_sp = (tracer.begin("fleet_probe", step=gstep)
                                if tracer is not None
                                and fleet_probe is not None else None)
                    if fleet_probe is not None:
                        # per-interval mean = same basis as step_ms
                        fleet_probe.observe(gstep, int_ms)
                    if desync_probe is not None:
                        rec = desync_probe.check(
                            opt_state[0].master,
                            loss_scale=float(amp_state[0].scale),
                            step_count=gstep, step=gstep)
                        if rec:
                            print(f"=> DESYNC at step {gstep}: "
                                  f"processes {rec['processes']}, "
                                  f"first path "
                                  f"{rec.get('path', '<scalars>')}")
                    if probe_sp is not None:
                        tracer.end(probe_sp)
                if use_numerics:
                    # provenance: the scale already synced for the print
                    # above, so one more tiny fetch per interval is free
                    oc = int(amp_state[0].overflow_count)
                    if oc > overflows_seen and telem is not None \
                            and int(census.step) >= 0:
                        telem.log_overflow(
                            num_meta, census,
                            loss_scale=amp_state[0].scale)
                        print(f"=> amp_overflow recorded "
                              f"({oc - overflows_seen} skip(s) this "
                              f"interval)")
                    overflows_seen = oc
                    if telem is not None:
                        cs = (tracer.begin("numerics_census")
                              if tracer is not None else None)
                        telem.log_numerics(
                            num_meta,
                            underflow_probe(opt_state, bn_state,
                                            amp_state, x, y, step_key),
                            step=epoch * args.steps_per_epoch + it + 1)
                        if cs is not None:
                            tracer.end(cs)
        # validation each epoch: Prec@1/Prec@5 on center crops, eval-mode
        # BN (reference validate(), main_amp.py:390-398)
        vs = (tracer.begin("validate", epoch=epoch)
              if tracer is not None else None)
        top1, top5, n_val = 0.0, 0.0, 0
        for x, y in val_batches():
            t1, t5 = eval_step(opt_state, bn_state, x, y)
            # apex-lint: disable=host-sync-in-hot-loop -- validation accumulates per-batch scalars; the val pass is outside the timed window
            t1_f, t5_f = float(t1), float(t5)
            top1 += t1_f * y.size
            top5 += t5_f * y.size
            n_val += y.size
        if vs is not None:
            tracer.end(vs, batches=n_val)
        print(f"epoch {epoch} * Prec@1 {100 * top1 / n_val:.3f} "
              f"Prec@5 {100 * top5 / n_val:.3f} (n={n_val})")
        if telem is not None:
            # flush-boundary samples: scaler counters (device refs,
            # fetched in flush), HBM watermarks, compile totals
            telem.log_amp(handle.scalers[0], amp_state[0])
            telem.log_compiles()
            telem.log_memory()
            telem.event("epoch_done", epoch=epoch,
                        prec1=round(100 * top1 / n_val, 3),
                        prec5=round(100 * top5 / n_val, 3))
            telem.flush()
            if slo_mon is not None:
                # epoch-boundary skip-rate check (one tiny host fetch)
                sc = int(amp_state[0].step_count)
                if sc:
                    slo_mon.observe(
                        "skip_rate",
                        int(amp_state[0].overflow_count) / sc,
                        context={"epoch": epoch})
        if args.checkpoint:
            opt.state = opt_state
            save_checkpoint(args.checkpoint, step=epoch + 1, optimizer=opt,
                            amp_state=amp_state, amp_handle=handle)
            print(f"=> saved {args.checkpoint}")
    if use_numerics and telem is not None:
        try:   # precision coverage of the step actually trained with
            from apex_tpu.prof import coverage as COV
            rep = COV.audit_fn(
                partial(step_body, distributed=False), opt_state,
                bn_state, amp_state, x, y, step_key, census)
            telem.log_coverage(
                rep, label=f"imagenet_{args.arch}_{args.opt_level}")
            print(f"=> precision coverage: "
                  f"{100 * rep.half_op_share:.1f}% of float ops in half"
                  + (f"; fp32-only control flow: "
                     f"{', '.join(rep.cf_fp32_only)}"
                     if rep.cf_fp32_only else ""))
        except Exception as e:
            print(f"=> coverage audit failed: {type(e).__name__}: {e}")
    if telem is not None:
        if tracer is not None:
            telem.log_spans(tracer)
        if slo_mon is not None and slo_mon.alerts:
            print(f"=> SLO ALERTS: "
                  f"{sorted({a['rule'] for a in slo_mon.alerts})}")
        telem_wd.stop()
        telem.close()
        print(f"=> telemetry written: {telem.path}")


if __name__ == "__main__":
    main()
