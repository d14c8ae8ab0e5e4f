"""Long-context LM training with ring-attention sequence parallelism.

No reference equivalent exists (apex has no sequence parallelism,
SURVEY.md §5): this example shows the beyond-parity path — a TransformerLM
whose TIME axis is sharded over a ``seq`` mesh axis, attention running as a
ring over ICI (K/V ppermute + online-softmax merge), composed with a
data-parallel axis and a fused optimizer on the flat parameter store.

    python examples/lm/train_ring.py --seq-parallel 4 --seq-len 512
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
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--head-chunk", type=int, default=0,
                   help="vocab chunk for the fused LM-head loss "
                        "(contrib.xentropy.linear_cross_entropy); 0 "
                        "materializes full logits — set e.g. 8192 at "
                        "large vocab/seq to avoid the O(N*V) fp32 temp")
    p.add_argument("--seq-len", type=int, default=512,
                   help="GLOBAL sequence length")
    p.add_argument("--batch-size", type=int, default=4)
    p.add_argument("--embed-dim", type=int, default=128)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--seq-parallel", type=int, default=4)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--grad-accum", type=int, default=1,
                   help="microbatches per step (amp.accumulate_grads)")
    p.add_argument("--loss-scale", default=None,
                   help='e.g. "dynamic" for fp16-style scaling')
    p.add_argument("--resume", default=None)
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--platform", default=None)
    return p.parse_args()


def main():
    args = parse_args()
    n = args.seq_parallel
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
    import jax
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    else:
        # default to an n-device CPU mesh WITHOUT probing jax.devices()
        # first — initializing a broken TPU plugin can hang. Pass
        # --platform to run on real hardware.
        from apex_tpu.parallel import pin_cpu_devices
        pin_cpu_devices(n)
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from apex_tpu import amp
    from apex_tpu.models import TransformerLM
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.ops import flat as F
    from apex_tpu.parallel import make_mesh
    from apex_tpu.utils import load_checkpoint, save_checkpoint

    # the strict device gate, then host-side init + one replicated
    # placement (the bench.py move)
    from apex_tpu.utils import setup_host_backend, host_init, ship
    setup_host_backend()

    mesh = make_mesh({"seq": n}, devices=jax.devices()[:n])
    model = TransformerLM(
        vocab_size=args.vocab, max_seq_len=args.seq_len,
        embed_dim=args.embed_dim, num_heads=args.heads,
        num_layers=args.layers, seq_axis="seq", seq_axis_size=n,
        head_chunk=min(args.head_chunk, args.vocab))
    with host_init():
        params = model.init(jax.random.key(0))
        opt = FusedAdam(params, lr=args.lr)
        table = opt._tables[0]
        opt_state = opt.init_state()
        overrides = ({"loss_scale": args.loss_scale}
                     if args.loss_scale is not None else {})
        _, handle = amp.initialize(opt_level="O2", verbosity=0, **overrides)
        amp_state = handle.init_state()

    start_step = 0
    if args.resume:
        with host_init():
            out = load_checkpoint(args.resume, optimizer=opt,
                                  amp_handle=handle)
            opt_state = opt.state
            if out.get("amp_state") is not None:
                amp_state = out["amp_state"]
        start_step = out["step"]
        print(f"=> resumed from {args.resume} (step {start_step})")

    from jax.sharding import NamedSharding
    opt_state, amp_state = ship((opt_state, amp_state),
                                NamedSharding(mesh, P()))

    acc = max(1, args.grad_accum)
    if args.batch_size % acc:
        raise SystemExit(f"--batch-size {args.batch_size} must divide by "
                         f"--grad-accum {acc}")
    half = handle.policy.cast_model_dtype

    # donate the flat opt + scaler state (r06 donation audit): in-place
    # update; the train loop rebinds both before eval_loss reads them
    @partial(jax.jit, donate_argnums=(0, 1))
    @partial(jax.shard_map, mesh=mesh, in_specs=(P(), P(), P(None, None, "seq")),
             out_specs=(P(), P(), P()), check_vma=False)  # check_vma: pallas_call inside does not support vma checking
    def train_step(opt_state, amp_state, micro_tokens):
        # micro_tokens is the LOCAL [acc, B/acc, T/n] shard stack;
        # model.loss handles the cross-shard target shift (ppermute) and
        # global masking/mean. Differentiating wrt the FLAT master buffer
        # makes the cross-shard reduction ONE pmean of ONE buffer, and
        # accumulate_grads folds the microbatch loop + per-microbatch
        # overflow checks into one scan (amp.frontend.accumulate_grads).
        def loss_fn(m, mb):
            # O2: the half cast is ONE fused convert on the flat buffer
            p = F.unflatten(m, table, dtype=half) if half is not None \
                else F.unflatten(m, table)
            return model.loss(p, mb, is_training=False)

        fg, found_inf, loss = handle.accumulate_grads(
            loss_fn, opt_state[0].master, micro_tokens, amp_state)
        # LOAD-BEARING: under shard_map, psum's transpose is psum, so each
        # shard's raw grad is n x (its own partial contribution) to the
        # psum/count loss; pmean (= sum/n) reassembles the exact global
        # gradient (pinned by test_transformer.py
        # test_sequence_parallel_grads_inside_shard_map).
        fg = jax.lax.pmean(fg, "seq")
        found_inf = jax.lax.pmax(found_inf, "seq")
        new_opt = opt.apply_update(opt_state, [fg], found_inf=found_inf)
        return new_opt, handle.update(amp_state, found_inf), loss

    @jax.jit
    @partial(jax.shard_map, mesh=mesh, in_specs=(P(), P(None, "seq")),
             out_specs=P(), check_vma=False)  # check_vma: see above
    def eval_loss(opt_state, tokens):
        m = opt_state[0].master
        p = F.unflatten(m, table, dtype=half) if half is not None \
            else F.unflatten(m, table)
        return model.loss(p, tokens, is_training=False)

    # synthetic "copy the previous token" data — learnable quickly
    rs = np.random.RandomState(0)
    base = rs.randint(0, args.vocab, (args.batch_size, args.seq_len // 8))
    tokens = jnp.asarray(np.repeat(base, 8, axis=1), jnp.int32)
    micro = tokens.reshape(acc, args.batch_size // acc, args.seq_len)
    val_base = rs.randint(0, args.vocab,
                          (args.batch_size, args.seq_len // 8))
    val_tokens = jnp.asarray(np.repeat(val_base, 8, axis=1), jnp.int32)

    t0 = time.perf_counter()
    for i in range(start_step, start_step + args.steps):
        opt_state, amp_state, loss = train_step(opt_state, amp_state,
                                                micro)
        if (i + 1) % 5 == 0:
            # apex-lint: disable=host-sync-in-hot-loop -- print-cadence fetch: one scalar every 5 steps
            print(f"step {i + 1} loss {float(loss):.4f} "
                  f"scale {float(handle.loss_scale(amp_state)):.0f}")
    dt = time.perf_counter() - t0
    tok_s = args.steps * args.batch_size * args.seq_len / dt
    # held-out perplexity: same copy-structure distribution, unseen draws
    vl = float(eval_loss(opt_state, val_tokens))
    print(f"val loss {vl:.4f} ppl {np.exp(min(vl, 30.0)):.2f}")
    # sample a continuation with the KV-cache decoder — generation runs
    # single-device, so decode through a non-sequence-parallel twin of
    # the model over the SAME trained params
    import dataclasses as _dc
    lm_decode = _dc.replace(model, seq_axis=None, seq_axis_size=0)
    p_final = F.unflatten(opt_state[0].master, table)
    plen = min(8, args.seq_len // 2)
    prompt = val_tokens[:1, :plen]
    sample = lm_decode.generate(
        p_final, prompt,
        max_new_tokens=min(16, args.seq_len - plen))  # fits max_seq_len
    print(f"sample continuation of {np.asarray(prompt[0]).tolist()}: "
          f"{np.asarray(sample[0, plen:]).tolist()}")
    print(f"done: {tok_s:.0f} tok/s over {n} sequence shards "
          f"({jax.default_backend()})")
    if args.checkpoint:
        opt.state = opt_state
        save_checkpoint(args.checkpoint, step=start_step + args.steps,
                        optimizer=opt, amp_state=amp_state,
                        amp_handle=handle)
        print(f"=> saved {args.checkpoint}")


if __name__ == "__main__":
    main()
