"""The ResNet-50 O2 + FusedLAMB training step the benchmark's driver and
``chip_smoke.py`` build: the choice of optimizer and loss around the
package's step builder (``apex_tpu.train_step``).
``benchmarks/drivers/train_rn50.py`` imports :func:`build_train_step` from
here (ROADMAP D1b moves the choice into the driver); measure with
``python3 benchmarks/run.py --workload rn50_train_b384``.
"""

from __future__ import annotations


def build_train_step(model, params, handle, *, lr=1e-3):
    """FusedLAMB over flat fp32 masters on the softmax cross-entropy of
    ``model``'s logits, under ``handle`` (AMP O2: the parameters in its
    half dtype, dynamic loss scale). Call under ``host_init()`` (the
    optimizer flattens real arrays). Returns ``(opt, loss_fn,
    train_step)``; ``loss_fn(params, bn_state, x, y) -> (loss,
    bn_state)`` and ``train_step(opt_state, bn_state, amp_state, x, y)
    -> (opt_state, bn_state, amp_state, loss)``."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.contrib.xentropy import select_label_logits
    from apex_tpu.optimizers import FusedLAMB
    from apex_tpu.train_step import build_step

    opt = FusedLAMB(params, lr=lr)

    def loss_fn(p_half, bn_state, x, y):
        logits, new_bn = model.apply(p_half, bn_state, x, training=True)
        with jax.named_scope("head"):   # the model's own scope: prof.SCOPES
            logits = logits.astype(jnp.float32)
            logp = jax.nn.log_softmax(logits)
            loss = -jnp.mean(select_label_logits(logp, y))
        return loss, new_bn

    body = build_step(opt, loss_fn, half=handle.policy.cast_model_dtype,
                      handle=handle)

    def train_step(opt_state, bn_state, amp_state, x, y):
        opt_state, amp_state, loss, bn_state = body(
            opt_state, amp_state, bn_state, x, y)
        return opt_state, bn_state, amp_state, loss

    return opt, loss_fn, train_step
