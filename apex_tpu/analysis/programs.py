"""The canonical program registry ``tools/apex_lint.py`` audits.

One builder per program the repo actually ships: the two benchmark
training steps at tiny sizes, built through ``apex_tpu.train_step`` as
the cells build theirs (tiny-ResNet O2 + FusedLAMB — the same builder
``tools/precision_audit.py`` delegates to — and the dense LM,
plan-compiled; DDP shard_map body when >1 device is visible),
the serve engine's prefill/commit/decode trio (fused, serialized
AND paged — r20,
described by the engine itself via
``ContinuousBatchingEngine.lint_programs``), and tiny replicas of
both examples' train steps (mirroring their donation contract and AMP
opt levels — the examples build their steps inside ``main()``, so the
replicas restate the step shape).

Everything here only *builds and traces* — ``jax.jit`` is lazy and
``make_jaxpr`` is abstract, so registering the full canonical set
compiles nothing and runs in seconds on any host.

``rnn_o1`` (the O1 control-flow-gap vehicle, ROADMAP) is exposed for
``precision_audit`` and the fixture tests but is NOT canonical: it
carries the repo's one known-open precision gap by construction.
"""

from __future__ import annotations

from typing import Optional

from apex_tpu.analysis.core import ProgramView

__all__ = ["CANONICAL", "build_programs", "bench_step_program",
           "rnn_step_program", "lm_step_program", "serve_programs",
           "imagenet_step_program", "dcgan_step_program"]

CANONICAL = ("bench_o2", "lm", "serve_fused", "serve_serial",
             "serve_paged", "imagenet", "dcgan")


def _bench_step(opt_level: str, batch: int, image: int, half_dtype):
    """The ResNet benchmark step at tiny sizes, through the package's
    builder (``apex_tpu.train_step.build_step``) as ``bench.py`` makes it:
    tiny-ResNet, FusedLAMB over the flat fp32 master, the softmax head,
    dynamic scaler — O2 casts the master via unflatten's fused convert,
    O1 wraps the apply in autocast, O0 stays fp32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu import amp
    from apex_tpu.contrib.xentropy import select_label_logits
    from apex_tpu.models import ResNet
    from apex_tpu.optimizers import FusedLAMB
    from apex_tpu.train_step import build_step

    model = ResNet(block_sizes=(1, 1), bottleneck=True, num_classes=10,
                   width=8)
    params, bn_state = model.init(jax.random.key(0))
    _, handle = amp.initialize(opt_level=opt_level, verbosity=0,
                               half_dtype=half_dtype)
    amp_state = handle.init_state()
    half = handle.policy.cast_model_dtype
    opt = FusedLAMB(params, lr=1e-3)
    opt_state = opt.init_state()
    apply_fn = (amp.autocast(model.apply, handle.policy.compute_dtype)
                if handle.policy.autocast else model.apply)

    rs = np.random.RandomState(0)
    # the batch rides in the model compute dtype under O2/O3, exactly as
    # the benchmark feeds it (model convs follow x.dtype); fp32 under
    # O0/O1
    x = jnp.asarray(rs.randn(batch, image, image, 3),
                    half if half is not None else jnp.float32)
    y = jnp.asarray(rs.randint(0, 10, batch), jnp.int32)

    def loss_fn(p, bn_state, x, y):
        logits, new_st = apply_fn(p, bn_state, x, training=True)
        with jax.named_scope("head"):
            logits = logits.astype(jnp.float32)
            logp = jax.nn.log_softmax(logits)
            loss = -jnp.mean(select_label_logits(logp, y))
        return loss, new_st

    body = build_step(opt, loss_fn, half=half, handle=handle)

    def train_step(opt_state, bn_state, amp_state, x, y):
        opt_state, amp_state, loss, bn_state = body(
            opt_state, amp_state, bn_state, x, y)
        return opt_state, bn_state, amp_state, loss

    return train_step, (opt_state, bn_state, amp_state, x, y)


def bench_step_program(opt_level: str = "O2", batch: int = 8,
                       image: int = 32,
                       half_dtype: str = "bfloat16") -> ProgramView:
    import jax
    step, ex = _bench_step(opt_level, batch, image, half_dtype)
    # the benchmark's driver donates the flat opt/bn/amp state
    jstep = jax.jit(step, donate_argnums=(0, 1, 2))
    return ProgramView(
        name=f"bench.train_step@{opt_level}", fn=jstep,
        example_args=ex, expect_half=opt_level != "O0",
        consumed_outputs=frozenset({"0", "1", "2", "3"}))


def _rnn_step(opt_level: str, batch: int, half_dtype):
    """A scanned model (RNN.LSTM over lax.scan): the O1 gap vehicle —
    autocast executes the scan body at traced dtypes, so under O1 the
    whole recurrence audits fp32-only."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu import amp
    from apex_tpu.RNN import LSTM

    model = LSTM(input_size=32, hidden_size=64, num_layers=1)
    params = model.init(jax.random.key(0))
    _, handle = amp.initialize(opt_level=opt_level, verbosity=0,
                               half_dtype=half_dtype)
    amp_state = handle.init_state()
    fwd = (amp.autocast(model.apply, handle.policy.compute_dtype)
           if handle.policy.autocast else model.apply)

    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(16, batch, 32), jnp.float32)  # (T, B, F)

    def train_step(params, amp_state, x):
        def loss_fn(p):
            out, _ = fwd(p, x)
            loss = jnp.mean(jnp.square(out.astype(jnp.float32)))
            return handle.scale_loss(loss, amp_state)

        g = jax.grad(loss_fn)(params)
        return g, amp_state

    return train_step, (params, amp_state, x)


def rnn_step_program(opt_level: str = "O1", batch: int = 2,
                     half_dtype: str = "float16") -> ProgramView:
    """The known-open O1 control-flow gap, as a program (NOT
    canonical): the precision-gap rule must fire on it, consistent
    with the strict xfail in tests/test_numerics.py."""
    import jax
    step, ex = _rnn_step(opt_level, batch, half_dtype)
    return ProgramView(
        name=f"rnn.train_step@{opt_level}", fn=jax.jit(step),
        example_args=ex, expect_half=opt_level != "O0",
        consumed_outputs=frozenset({"0", "1"}))


def lm_step_program() -> ProgramView:
    """The dense-LM benchmark step at tiny sizes, through the package's
    builder (``apex_tpu.train_step``) as ``tools/lm_bench.py`` makes it
    and plan-compiled the way the drivers compile it: plain-jit plan on
    one device, DDP's buckets (shard_map + a psum a bucket over 'data')
    when more devices are visible."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu import train_step as T
    from apex_tpu.models import TransformerLM
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import (DistributedDataParallel,
                                   compile_step_with_plan, make_mesh)

    seq, batch, layers, dim, heads, vocab = 128, 2, 2, 128, 4, 512
    lm = TransformerLM(vocab_size=vocab, max_seq_len=seq,
                       embed_dim=dim, num_heads=heads,
                       num_layers=layers, head_chunk=vocab)
    n_dev = len(jax.devices())
    if batch % n_dev:
        batch += -batch % n_dev
    opt = FusedAdam(lm.init(jax.random.key(0)), lr=1e-4)
    state = opt.init_state()
    toks = jax.random.randint(jax.random.key(1), (batch, seq), 0, vocab)
    body = T.build_step(
        opt, lm.loss, half=jnp.bfloat16,
        ddp=DistributedDataParallel(axis_name="data") if n_dev > 1
        else None)

    def step(state, toks):
        state, _, loss, _ = body(state, None, toks)
        return state, loss

    plan = T.step_plan(make_mesh({"data": n_dev}),
                       P() if n_dev > 1 else None)
    return ProgramView(
        name=f"lm.train_step@{plan.lowering()}x{n_dev}",
        fn=compile_step_with_plan(step, plan),
        example_args=(state, toks), plan=plan, expect_half=True,
        consumed_outputs=frozenset({"0", "1"}))


def serve_programs(fused: bool = True,
                   paged: bool = False) -> list[ProgramView]:
    """The serve engine's donated program trio at the test-tier model
    size (tests/test_serve.py's fixture shape) — described by the
    engine itself, lineage metadata included. ``paged=True`` (r20)
    audits the page-pool variant: same trio, prefill/decode gathering
    K/V through the host page table."""
    import jax

    from apex_tpu.models import TransformerLM
    from apex_tpu.serve import ContinuousBatchingEngine

    m = TransformerLM(vocab_size=50, max_seq_len=64, embed_dim=32,
                      num_heads=4, num_layers=2)
    kw = dict(page_size=8, kv_pages=8,
              prefix_share=True) if paged else {}
    eng = ContinuousBatchingEngine(m, m.init(jax.random.key(0)),
                                   slots=3, max_len=32,
                                   prefill_chunk=4, fused=fused,
                                   paged=paged, **kw)
    return [ProgramView(name=d["name"], fn=d["fn"],
                        example_args=d["args"],
                        lineages=d["lineages"],
                        warmup_lineages=d["warmup_lineages"],
                        consumed_outputs=d["consumed_outputs"])
            for d in eng.lint_programs()]


def imagenet_step_program(opt_level: str = "O2") -> ProgramView:
    """Tiny replica of examples/imagenet/main_amp.py's train step
    contract: uint8 batch normalized INSIDE the step, flat-master
    differentiation, FusedSGD+momentum, donate (opt, bn, amp)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu import amp
    from apex_tpu.contrib.xentropy import select_label_logits
    from apex_tpu.data import normalize_imagenet
    from apex_tpu.models import ResNet
    from apex_tpu.optimizers import FusedSGD
    from apex_tpu.ops import flat as F

    model = ResNet(block_sizes=(1, 1), bottleneck=False, num_classes=10,
                   width=8)
    params, bn_state = model.init(jax.random.key(0))
    _, handle = amp.initialize(opt_level=opt_level, verbosity=0)
    amp_state = handle.init_state()
    half = handle.policy.cast_model_dtype
    opt = FusedSGD(params, lr=0.1, momentum=0.9)
    table = opt._tables[0]
    opt_state = opt.init_state()

    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randint(0, 256, (4, 32, 32, 3)), jnp.uint8)
    y = jnp.asarray(rs.randint(0, 10, 4), jnp.int32)

    def loss_and_state(master, bn, x, y, amp_st):
        x = normalize_imagenet(
            x, dtype=half if half is not None else jnp.float32)
        p = F.unflatten(master, table,
                        dtype=half if half is not None else None)
        logits, new_bn = model.apply(p, bn, x, training=True)
        logits = logits.astype(jnp.float32)
        logp = jax.nn.log_softmax(logits)
        loss = -jnp.mean(select_label_logits(logp, y))
        acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
        return handle.scale_loss(loss, amp_st), (loss, acc, new_bn)

    def step_body(opt_state, bn_state, amp_state, x, y):
        fg, (loss, acc, new_bn) = jax.grad(
            lambda m: loss_and_state(m, bn_state, x, y, amp_state),
            has_aux=True)(opt_state[0].master)
        fg, found_inf = handle.unscale(fg, amp_state)
        new_opt = opt.apply_update(opt_state, [fg], found_inf=found_inf)
        new_amp = handle.update(amp_state, found_inf)
        return new_opt, new_bn, new_amp, loss, acc

    jstep = jax.jit(step_body, donate_argnums=(0, 1, 2))
    return ProgramView(
        name=f"examples.imagenet.train_step@{opt_level}", fn=jstep,
        example_args=(opt_state, bn_state, amp_state, x, y),
        expect_half=opt_level != "O0",
        consumed_outputs=frozenset({"0", "1", "2", "3", "4"}))


def dcgan_step_program(opt_level: str = "O1") -> ProgramView:
    """Tiny replica of examples/dcgan/main_amp.py's train step
    contract: conv G/D over NHWC 32x32, three scaled losses on one amp
    state, both optimizers' flat state + the scaler state donated."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu import amp
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.ops import flat as F

    nz, ngf, ndf, batch = 8, 4, 4, 2
    ks = jax.random.split(jax.random.key(1), 8)
    s = lambda k, sh: jax.random.normal(k, sh) * 0.02
    gp = {"fc": s(ks[0], (nz, 4 * 4 * ngf * 4)),
          "c1": s(ks[1], (4, 4, ngf * 4, ngf * 2)),
          "c2": s(ks[2], (4, 4, ngf * 2, ngf)),
          "c3": s(ks[3], (4, 4, ngf, 3))}
    dp = {"c1": s(ks[4], (4, 4, 3, ndf)),
          "c2": s(ks[5], (4, 4, ndf, ndf * 2)),
          "c3": s(ks[6], (4, 4, ndf * 2, ndf * 4)),
          "fc": s(ks[7], (4 * 4 * ndf * 4, 1))}

    def upconv(x, w, out_hw):
        b = x.shape[0]
        y = jax.image.resize(x, (b, out_hw, out_hw, x.shape[-1]),
                             "nearest")
        return jax.lax.conv_general_dilated(
            y, w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def downconv(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (2, 2), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def generator(p, z):
        h = jax.nn.relu((z @ p["fc"]).reshape(-1, 4, 4, ngf * 4))
        h = jax.nn.relu(upconv(h, p["c1"], 8))
        h = jax.nn.relu(upconv(h, p["c2"], 16))
        return jnp.tanh(upconv(h, p["c3"], 32))

    def discriminator(p, x):
        h = jax.nn.leaky_relu(downconv(x, p["c1"]), 0.2)
        h = jax.nn.leaky_relu(downconv(h, p["c2"]), 0.2)
        h = jax.nn.leaky_relu(downconv(h, p["c3"]), 0.2)
        return (h.reshape(h.shape[0], -1) @ p["fc"])[:, 0]

    _, handle = amp.initialize(opt_level=opt_level, num_losses=3,
                               verbosity=0)
    amp_state = handle.init_state()
    g_opt = FusedAdam(gp, lr=2e-4, betas=(0.5, 0.999))
    d_opt = FusedAdam(dp, lr=2e-4, betas=(0.5, 0.999))
    g_table, d_table = g_opt._tables[0], d_opt._tables[0]
    g_state, d_state = g_opt.init_state(), d_opt.init_state()
    g_fwd = amp.autocast(generator) if handle.policy.autocast \
        else generator
    d_fwd = amp.autocast(discriminator) if handle.policy.autocast \
        else discriminator

    def bce_logits(logits, target):
        return jnp.mean(jnp.maximum(logits, 0) - logits * target +
                        jnp.log1p(jnp.exp(-jnp.abs(logits))))

    rs = np.random.RandomState(0)
    real = jnp.asarray(rs.randn(batch, 32, 32, 3), jnp.float32)
    z = jnp.asarray(rs.randn(batch, nz), jnp.float32)

    def train_step(g_state, d_state, amp_state, real, z):
        gp = F.unflatten(g_state[0].master, g_table)
        dpp = F.unflatten(d_state[0].master, d_table)
        fake = g_fwd(gp, z)

        def d_loss_real(p):
            return handle.scale_loss(
                bce_logits(d_fwd(p, real), 1.0), amp_state, loss_id=0)

        def d_loss_fake(p):
            return handle.scale_loss(
                bce_logits(d_fwd(p, jax.lax.stop_gradient(fake)), 0.0),
                amp_state, loss_id=1)

        fg_r = F.flatten(jax.grad(d_loss_real)(dpp), table=d_table,
                         dtype=jnp.float32)[0]
        fg_f = F.flatten(jax.grad(d_loss_fake)(dpp), table=d_table,
                         dtype=jnp.float32)[0]
        fg_r, inf0 = handle.unscale(fg_r, amp_state, loss_id=0)
        fg_f, inf1 = handle.unscale(fg_f, amp_state, loss_id=1)
        d_new = d_opt.apply_update(d_state, [fg_r + fg_f],
                                   found_inf=inf0 | inf1)

        def g_loss(p):
            return handle.scale_loss(
                bce_logits(d_fwd(dpp, g_fwd(p, z)), 1.0), amp_state,
                loss_id=2)

        fgg = F.flatten(jax.grad(g_loss)(gp), table=g_table,
                        dtype=jnp.float32)[0]
        fgg, inf2 = handle.unscale(fgg, amp_state, loss_id=2)
        g_new = g_opt.apply_update(g_state, [fgg], found_inf=inf2)
        new_amp = handle.update(amp_state, inf0, loss_id=0)
        new_amp = handle.update(new_amp, inf1, loss_id=1)
        new_amp = handle.update(new_amp, inf2, loss_id=2)
        d_l = bce_logits(d_fwd(dpp, real), 1.0)
        g_l = bce_logits(d_fwd(dpp, fake), 1.0)
        return g_new, d_new, new_amp, d_l, g_l

    jstep = jax.jit(train_step, donate_argnums=(0, 1, 2))
    return ProgramView(
        name=f"examples.dcgan.train_step@{opt_level}", fn=jstep,
        example_args=(g_state, d_state, amp_state, real, z),
        expect_half=opt_level != "O0",
        consumed_outputs=frozenset({"0", "1", "2", "3", "4"}))


_BUILDERS = {
    "bench_o2": lambda: [bench_step_program("O2")],
    "lm": lambda: [lm_step_program()],
    "serve_fused": lambda: serve_programs(fused=True),
    "serve_serial": lambda: serve_programs(fused=False),
    "serve_paged": lambda: serve_programs(fused=True, paged=True),
    "imagenet": lambda: [imagenet_step_program("O2")],
    "dcgan": lambda: [dcgan_step_program("O1")],
    # the gap vehicle — opt-in only (carries the known O1 finding)
    "rnn_o1": lambda: [rnn_step_program("O1")],
}


def build_programs(names: Optional[list] = None) -> list[ProgramView]:
    names = list(CANONICAL) if names is None else list(names)
    missing = [n for n in names if n not in _BUILDERS]
    if missing:
        raise KeyError(f"unknown program(s): {missing}; known: "
                       f"{sorted(_BUILDERS)}")
    out: list[ProgramView] = []
    for n in names:
        out.extend(_BUILDERS[n]())
    return out
