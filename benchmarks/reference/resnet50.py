"""ResNet v1.5 (He et al., arXiv:1512.03385; bottleneck, stride on the
3x3) and LAMB in plain ``jax.numpy``: float32, ``lax.conv``, BatchNorm on
the batch's own statistics, no kernel.

Departures, both the program's: convolutions pad as XLA's ``SAME`` does
(the 7x7/2 stem pads 2 before and 3 after where torchvision pads 3 and 3;
a 3x3/2 pads 0 and 1 where torchvision pads 1 and 1), and the classifier
starts normal with the variance of torch's uniform. The configuration
file lists them. Imports nothing of ``apex_tpu``.

The whole batch goes through at once, because BatchNorm needs it; stages
and blocks are rematerialized (nested), so the float32 activations of
384 images fit beside nothing else on the chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import precision as P

LAMB = dict(beta1=0.9, beta2=0.999, eps=1e-6, weight_decay=0.01,
            max_grad_norm=1.0)       # FusedLAMB's defaults
BN_EPS = 1e-5


def batch_norm(x, p, tap=None):
    """Normalize by the batch's own mean and (biased) variance; ``tap``,
    a list, is given the variance."""
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(jnp.square(x), (0, 1, 2)) - jnp.square(mean)
    if tap is not None:
        tap.append(var)
    return (x - mean) * jax.lax.rsqrt(var + BN_EPS) * p["weight"] + p["bias"]


def block(x, p, stride: int, prec: str, tap=None):
    short = x
    if "conv_proj" in p:
        short = batch_norm(P.conv(x, p["conv_proj"], (stride, stride),
                                  "SAME", prec), p["bn_proj"], tap)
    h = jax.nn.relu(batch_norm(P.conv(x, p["conv1"], (1, 1), "SAME", prec),
                               p["bn1"], tap))
    h = jax.nn.relu(batch_norm(P.conv(h, p["conv2"], (stride, stride),
                                      "SAME", prec), p["bn2"], tap))
    h = batch_norm(P.conv(h, p["conv3"], (1, 1), "SAME", prec), p["bn3"],
                   tap)
    return jax.nn.relu(h + short)


def stem(x, params, prec: str, tap=None):
    h = jax.nn.relu(batch_norm(P.conv(x, params["conv_stem"], (2, 2), "SAME",
                                      prec), params["bn_stem"], tap))
    return jax.lax.reduce_window(
        h, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        ((0, 0), (1, 1), (1, 1), (0, 0)))


def _stages(params):
    s = 0
    while f"stage{s}_block0" in params:
        blocks, b = [], 0
        while f"stage{s}_block{b}" in params:
            blocks.append(params[f"stage{s}_block{b}"])
            b += 1
        yield s, blocks
        s += 1


def logits(params, x, prec: str = "float32"):
    h = jax.checkpoint(stem, static_argnums=(2,))(x, params, prec)
    for s, blocks in _stages(params):
        def stage(h, blocks, s=s):
            for b, p in enumerate(blocks):
                h = jax.checkpoint(block, static_argnums=(2, 3))(
                    h, p, 2 if (s > 0 and b == 0) else 1, prec)
            return h
        h = jax.checkpoint(stage)(h, blocks)
    h = jnp.mean(h, (1, 2))
    return P.matmul(h, params["fc_w"], prec) + params["fc_b"]


def batch_variances(params, x, prec: str = "float32") -> list:
    """Every BatchNorm's batch variance (one vector a layer, in the order
    the layers run), from a forward pass with nothing kept: what the first
    step leaves in the program's running statistics."""
    tap = []
    h = stem(x, params, prec, tap)
    for s, blocks in _stages(params):
        for b, p in enumerate(blocks):
            h = block(h, p, 2 if (s > 0 and b == 0) else 1, prec, tap)
    return tap


def loss(params, x, y, prec: str = "float32"):
    logp = jax.nn.log_softmax(logits(params, x, prec), -1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], -1))


def leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), tree)


def lamb(params, grad, m, v, step, *, lr, beta1, beta2, eps, weight_decay,
         max_grad_norm):
    """LAMB as NVIDIA's FusedLAMB states it: gradients divided by
    (global norm / max_grad_norm) where that is above 1, Adam moments,
    decoupled decay in the update, and a trust ratio |p| / |update| per
    tensor. Returns the clipped gradient too: it is what the moments see."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grad)))
    clip = jnp.where(gnorm > max_grad_norm, gnorm / max_grad_norm, 1.0)
    grad = jax.tree.map(lambda g: g / clip, grad)
    m = jax.tree.map(lambda m, g: beta1 * m + (1 - beta1) * g, m, grad)
    v = jax.tree.map(lambda v, g: beta2 * v + (1 - beta2) * g * g, v, grad)
    bc1, bc2 = 1 - beta1 ** step, 1 - beta2 ** step

    def one(p, m, v):
        u = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + weight_decay * p
        pn, un = jnp.sqrt(jnp.sum(p * p)), jnp.sqrt(jnp.sum(u * u))
        ratio = jnp.where((pn != 0) & (un != 0), lr * pn / un, lr)
        return p - ratio * u
    return jax.tree.map(one, params, m, v), m, v, grad


def train_steps(params, batches, prec: str = "float32", *, lr: float):
    """Follow the first ``len(batches)`` steps on ``(x, y)`` batches:
    each step's loss, the per-leaf norm of the first gradient as LAMB's
    moments get it (clipped), the parameters' change at the end, and the
    first batch's BatchNorm variances (``vectors``)."""
    @jax.jit
    def step(params, m, v, t, x, y):
        l, grad = jax.value_and_grad(loss)(params, x, y, prec)
        params, m, v, grad = lamb(params, grad, m, v, t, lr=lr, **LAMB)
        return params, m, v, l, leaf_norms(grad)

    start = params
    variances = jax.jit(batch_variances, static_argnums=(2,))(
        params, batches[0][0], prec)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for i, (x, y) in enumerate(batches):
        params, m, v, l, gn = step(params, m, v, jnp.float32(i + 1), x, y)
        losses.append(float(l))
        if i == 0:
            grad_norms = jax.tree.map(float, gn)
    delta = jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))(params, start)
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": jax.tree.map(float, delta),
            "vectors": [np.asarray(v) for v in variances]}
