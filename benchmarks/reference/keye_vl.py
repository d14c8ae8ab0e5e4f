"""Keye-VL-2.0-30B-A3B's language block in plain ``jax.numpy``: float32,
every product at the highest precision (``reference/precision.py``), no
kernel, no scan over layers, no batch.

From the model's public ``config.json``
(https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B, ``model_type:
KeyeVL2``): a decoder whose every layer is grouped-query attention **over
a learned per-query set of keys** (``sa_config``: DeepSeek-V3.2's lightning
indexer, 16 heads of 64 over one shared indexer key head, ``topk`` 2048)
followed by a mixture of experts (``d`` = ``hidden_size``, ``eps`` =
``rms_norm_eps``; no bias but the indexer key norm's, an untied head):

    rms(x; w) = x * rsqrt(mean(x^2) + eps) * w
    ln(x; w, b) = (x - mean(x)) * rsqrt(var(x) + eps) * w + b

    mixer:   h = rms(x; w_in);  hbar = stop_gradient(h)
      indexer  qI = rope(hbar W_qI) -> [T, HI, DI]     the whole head turned
               kI = rope(ln(hbar W_kI; w_kn, b_kn))    one head, [T, DI]
                    (the leaves ``w_k`` and ``w_w`` hold W_kI and W_w
                    transposed, ``[out, in]``)
               w  = hbar W_w * HI^-1/2 * DI^-1/2                  [T, HI]
               I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])     s <= t
      the set  S_t = the min(t + 1, topk) keys s <= t of largest I[t, s],
               exactly that many, among equals the lower s
               (jax.lax.top_k's order); one set for all heads; no
               gradient passes through the choice
      main     q = h W_q -> [T, H, hd];  k, v = h W_k, h W_v -> [T, G, hd]
               q, k <- rope(rms(q; w_qn)), rope(rms(k; w_kn))
               A[n, t, :] = softmax over s in S_t of q[n, t] . k[n // (H /
               G), s] * hd^-0.5
               x += (sum_{s in S_t} A[n, t, s] v[., s]) W_o
      its loss p[t, s] = stop_gradient(mean_n A[n, t, s]), s in S_t
               qi[t, :] = softmax over S_t of I[t, :]
               L_I = mean_t sum_{s in S_t} p (log p - log qi)

    rope: inv_freq_m = rope_theta^(-2m / D), m < D / 2, half-split pairing,
    positions 0..T-1 (text tokens: the three multimodal position ids are
    equal, the sectioned table is the plain one), D = hd for the main
    heads and DI for the indexer's

    FFN:     g = rms(x; w_post);  p = softmax(g W_r)               [T, E]
             sel = top_k(p);  w = p[sel] / sum(p[sel])
             x += sum_{e in sel, e held} w_e swiglu_e(g)  no shared expert

The loss of a batch is the mean next-token cross-entropy plus
``router_aux_loss_coef`` times, a layer, ``E sum_e f_e P_e`` over the
batch, plus ``indexer_loss_coef`` times, a layer, the mean over the rows
of ``L_I``. With the two ``stop_gradient``s the indexer's leaves learn
from ``L_I`` alone and every other leaf from the rest alone. The expert
layer is ``reference/mellum2.py``'s (the held share, the weights held
constant in the backward). Attention, the index scores and the choice run
a block of queries at a time, the head a block of tokens at a time, a row
goes through the layers one program at a time.

Departures, each in the configuration's ``assumed``: the norm a head; the
indexer reads the layer's normed input (the source projects its queries
from a query latent this model does not have); the key's LayerNorm; the
scale on ``w``; no Hadamard rotation and no fp8 in the indexer (inference
devices: an orthogonal map changes no dot product); ``q_chunk_size`` /
``kv_chunk_size`` tile the score computation and change no result.

Imports nothing of ``apex_tpu``. One sequence at a time: ``tokens`` is
``[T]``. Parameters are the tree ``benchmarks.weights_keye_vl.specs``
describes, as float32, on the device or on the host. ``cfg`` is the
configuration file.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import precision as P
from benchmarks.reference.kimi_vl import (  # noqa: F401
    ADAM, QUERY_BLOCK, _adam, _divisor, _norms, head_logits, head_loss, rms)
from benchmarks.reference.mellum2 import moe, width  # noqa: F401
from benchmarks.reference.qwen3_next import held  # noqa: F401


def layer_kinds(cfg: dict) -> list:
    return ["sparse"] * cfg["num_hidden_layers"]


def selected_pairs(t: int, topk: int) -> int:
    """The pairs a row of ``t`` tokens selects: ``sum_t min(t + 1, topk)``."""
    k = min(t, topk)
    return t * k - k * (k - 1) // 2


def rotary(x, theta: float):
    """``x [T, H, D]`` turned whole, half-split pairing, plain table."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w + b


def indexer(hbar, p, cfg: dict, prec: str):
    """``(qI [T, HI, DI], kI [T, DI], w [T, HI])``."""
    sa, theta = cfg["sa_config"], float(cfg["rope_theta"])
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    assert sa["indexer_num_kv_heads"] == 1, sa
    t = hbar.shape[0]
    qi = rotary(P.matmul(hbar, p["w_q"], prec).reshape(t, hi, di), theta)
    ki = layer_norm(P.matmul(hbar, p["w_k"].T, prec), p["k_norm"]["w"],
                    p["k_norm"]["b"], cfg["rms_norm_eps"])
    ki = rotary(ki[:, None], theta)[:, 0]
    return qi, ki, P.matmul(hbar, p["w_w"].T, prec) * (hi * di) ** -0.5


def index_scores(qi_b, ki, w_b, start, prec: str):
    """``I [blk, T]`` of a block of queries that starts at ``start``,
    ``-inf`` above the diagonal; one zero (a top-k tells ``-0.0`` from
    ``0.0``)."""
    r = jax.nn.relu(P.einsum("tjd,sd->tjs", qi_b, ki, prec))
    i = jnp.sum(w_b[..., None] * r, 1)
    i = jnp.where(i == 0.0, 0.0, i)
    seen = (start + jnp.arange(qi_b.shape[0]))[:, None] \
        >= jnp.arange(ki.shape[0])[None, :]
    return jnp.where(seen, i, -jnp.inf)


def chosen(i, topk: int):
    """bool like ``i [blk, T]``: each row's ``topk`` largest entries by
    ``jax.lax.top_k`` (equal scores: the lower key first), of the keys at
    or below the diagonal: exactly ``min(t + 1, topk)`` a row."""
    _, idx = jax.lax.top_k(i, min(topk, i.shape[-1]))
    keep = jnp.zeros(i.shape, bool).at[
        jnp.arange(i.shape[0])[:, None], idx].set(True)
    return keep & (i > -jnp.inf)


def sparse_mixer(h, p, ip, cfg: dict, prec: str):
    """``(the mixer's output [T, d], L_I, the chosen keys bool [T, T])``."""
    t = h.shape[0]
    nh, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    topk = cfg["sa_config"]["topk"]
    q = P.matmul(h, p["w_q"], prec).reshape(t, nh, hd)
    k = P.matmul(h, p["w_k"], prec).reshape(t, kv, hd)
    v = P.matmul(h, p["w_v"], prec).reshape(t, kv, hd)
    q = rotary(rms(q, p["q_norm"], eps), theta)
    k = rotary(rms(k, p["k_norm"], eps), theta)
    qi, ki, w = indexer(jax.lax.stop_gradient(h), ip, cfg, prec)
    blk = _divisor(t, QUERY_BLOCK // 4)

    @jax.checkpoint
    def block(args):
        q_b, qi_b, w_b, start = args        # [blk, G, H / G, hd]
        i = index_scores(qi_b, ki, w_b, start, prec)
        keep = chosen(jax.lax.stop_gradient(i), topk)
        s = P.einsum("tgqd,sgd->gqts", q_b, k, prec) * hd ** -0.5
        a = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
        target = jax.lax.stop_gradient(jnp.mean(a, (0, 1)))     # [blk, T]
        log_qi = jax.nn.log_softmax(jnp.where(keep, i, -jnp.inf), -1)
        kl = jnp.sum(jnp.where(keep, jax.scipy.special.xlogy(target, target)
                               - target * jnp.where(keep, log_qi, 0.0), 0.0))
        return P.einsum("gqts,sgd->tgqd", a, v, prec), kl, keep
    a, kl, keep = jax.lax.map(block, (
        q.reshape(t // blk, blk, kv, nh // kv, hd),
        qi.reshape(t // blk, blk, *qi.shape[1:]),
        w.reshape(t // blk, blk, -1), jnp.arange(0, t, blk)))
    return P.matmul(a.reshape(t, nh * hd), p["w_o"], prec), \
        jnp.sum(kl) / t, keep.reshape(t, t)


# -- the model, a layer at a time ----------------------------------------------

def block(x, lp, kind: str, cfg: dict, prec: str):
    """One layer: ``(x out, experts chosen [T, K], mean router
    probabilities [E], L_I, the chosen keys [T, T])``."""
    eps = cfg["rms_norm_eps"]
    a, index_loss, keep = sparse_mixer(rms(x, lp["norm1"], eps), lp["attn"],
                                       lp["index"], cfg, prec)
    x = x + a
    y, idx, probs = moe(rms(x, lp["norm2"], eps), lp["moe"], cfg, prec)
    return x + y, idx, jnp.mean(probs, 0), index_loss, keep


def logits(params, tokens, cfg: dict, prec: str = "float32"):
    """``[T, vocab]`` for one sequence ``tokens [T]``."""
    x = params["embed"][tokens]
    for i, kind in enumerate(layer_kinds(cfg)):
        x = block(x, params[f"layer_{i}"], kind, cfg, prec)[0]
    return head_logits(x, params["norm_f"], params["head"], cfg, prec)


@functools.lru_cache(maxsize=None)
def _programs(cfg_json: str, prec: str) -> dict:
    """The jitted pieces for one configuration and precision, made once
    a process."""
    cfg = json.loads(cfg_json)

    def fwd(lp, x):
        """``(x out, pairs an expert [E], the chosen keys, a bit a pair
        (numpy's packbits), and their count)``."""
        x, idx, probs, _, keep = block(x, lp, "sparse", cfg, prec)
        return x, jnp.sum(idx[..., None] == jnp.arange(probs.shape[0]),
                          (0, 1)), jnp.packbits(keep, axis=-1), jnp.sum(keep)

    def bwd(lp, x, share, dx, daux, dindex):
        """The layer's backward from its input: its output's cotangent
        ``dx``, the cotangent ``daux`` of its load-balancing term ``E sum_e
        share_e P_e`` and ``dindex`` of its ``L_I``."""
        def f(lp, x):
            y, _, probs, index_loss, _ = block(x, lp, "sparse", cfg, prec)
            return y, probs.shape[0] * jnp.sum(share * probs), index_loss
        (_, aux, index_loss), vjp = jax.vjp(f, lp, x)
        return vjp((dx, daux, dindex)) + (aux, index_loss)
    return {
        "sparse": (jax.jit(fwd), jax.jit(bwd)),
        "head": jax.jit(jax.value_and_grad(
            lambda x, norm_f, head, targets: head_loss(
                x, norm_f, head, targets, cfg, prec), argnums=(0, 1, 2))),
        "embed": jax.jit(lambda table, tokens, dx:
                         jnp.zeros_like(table).at[tokens].add(dx)),
        "add": jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                       donate_argnums=0),
        "scale": jax.jit(lambda a, s: jax.tree.map(lambda x: x * s, a),
                         donate_argnums=0)}


def batch_loss_and_grad(params, batch, cfg: dict, prec: str = "float32"):
    """``(loss, gradient, pairs an expert [layers, E], facts)`` of ``batch
    [B, T + 1]``: the mean next-token cross-entropy plus
    ``router_aux_loss_coef`` times the layers' load-balancing terms, each
    over the **batch**, plus ``indexer_loss_coef`` times the layers'
    ``L_I``, each the mean over the rows. ``facts``: ``index_loss`` (the
    layers' summed, before its coefficient), ``select_pairs`` (all layers
    and rows) and ``select_bits`` (row 0's chosen keys of layer 0, a bit a
    pair, ``uint8 [T, T / 8]``). Every row goes forward first, keeping each
    layer's input, and then back."""
    prog = _programs(json.dumps(cfg, sort_keys=True), prec)
    fwd, bwd = prog["sparse"]
    layers = [params[f"layer_{i}"] for i in range(cfg["num_hidden_layers"])]
    coef, icoef = cfg["router_aux_loss_coef"], cfg["indexer_loss_coef"]
    n, t = batch.shape[0], batch.shape[1] - 1
    layer_inputs, counts = [], [0] * len(layers)
    facts = {"index_loss": 0.0, "select_pairs": 0}
    for r, row in enumerate(batch):
        xs = [params["embed"][row[:-1]]]
        for i, lp in enumerate(layers):
            x, c, bits, pairs = fwd(lp, xs[-1])
            xs.append(x)
            counts[i] = counts[i] + c
            facts["select_pairs"] += int(pairs)
            if r == 0 and i == 0:
                facts["select_bits"] = bits
        layer_inputs.append(xs)
    share = [c / (n * t) for c in counts]
    loss, grad = 0.0, {}

    def add(name, g):       # a leaf group at a time: no second whole tree
        grad[name] = prog["add"](grad[name], g) if name in grad else g
    for row, xs in zip(batch, layer_inputs):
        xent, (dx, d_norm, d_head) = prog["head"](
            xs.pop(), params["norm_f"], params["head"], row[1:])
        add("norm_f", d_norm)
        add("head", d_head)
        for i in reversed(range(len(layers))):
            g, dx, aux, index_loss = bwd(layers[i], xs.pop(), share[i], dx,
                                         jnp.float32(coef),
                                         jnp.float32(icoef))
            add(f"layer_{i}", g)
            xent = xent + coef * aux + icoef * index_loss
            facts["index_loss"] += float(index_loss) / n
        add("embed", prog["embed"](params["embed"], row[:-1], dx))
        loss = loss + xent
    return loss / n, prog["scale"](grad, jnp.float32(1.0 / n)), \
        jnp.stack(counts), facts


def train_steps(params, batches, cfg: dict, prec: str = "float32", *,
                lr: float):
    """Follow the first ``len(batches)`` optimizer steps, as
    ``reference/mellum2.py`` does (``params`` may lie on the host): each
    step's loss, the per-leaf norm of the first gradient, the per-leaf
    norm of the parameters' change; and of this model each step's
    ``index_losses`` and ``select_pairs`` and the first step's
    ``select_bits``."""
    update = _adam(lr)
    start = params
    params = jax.tree.map(jnp.array, params)    # a copy: the steps donate
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    out = {"losses": [], "index_losses": [], "select_pairs": []}
    for i, batch in enumerate(batches):
        loss, grad, _, facts = batch_loss_and_grad(params, batch, cfg, prec)
        if i == 0:
            out["grad_norms"] = jax.tree.map(float, _norms(grad))
            out["select_bits"] = np.asarray(facts["select_bits"])
        params, m, v = update(params, grad, m, v, jnp.float32(i + 1))
        out["losses"].append(float(loss))
        out["index_losses"].append(facts["index_loss"])
        out["select_pairs"].append(facts["select_pairs"])
    delta = _norms(jax.tree.map(jnp.subtract, params, start))
    out["delta_norms"] = jax.tree.map(float, delta)
    return out
