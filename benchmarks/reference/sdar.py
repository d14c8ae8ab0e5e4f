"""SDAR-30B-A3B-Chat's block-diffusion training step in plain
``jax.numpy``: float32, every product at the highest precision
(``reference/precision.py``), no kernel, no scan over layers, no batch.

From the model's public ``config.json``
(https://huggingface.co/JetLM/SDAR-30B-A3B-Chat, ``model_type: sdar_moe``;
arXiv:2510.06303, whose training form is block diffusion,
arXiv:2503.09573): every layer grouped-query softmax attention above a
mixture of experts, the keys of the Qwen3-MoE convention (``d`` =
``hidden_size``, ``eps`` = ``rms_norm_eps``; no bias anywhere, an untied
head). What makes it this model is the step. A sequence ``x[0..L-1]`` of
data ids comes with a mask ``m[0..L-1]`` drawn at the probability ``p``;
``V`` = ``vocab_size`` rows are held here and **id ``V - 1`` stands for the
mask token**; ``B`` = ``block_length``, ``blk(i) = floor(i / B)``:

    rows:    x_t[i] = V - 1 if m[i] else x[i];   u = [x_t ; x]   (2L rows:
             the noised copy, then the clean one)
             pos = [0..L-1, 0..L-1];   h = E[u]

    rms(x; w) = x * rsqrt(mean(x^2) + eps) * w

    mixer:   g = rms(h; w_in)
             q = g W_q -> [2L, H, hd];  k, v = g W_k, g W_v -> [2L, G, hd]
             q, k <- rope(rms(q; w_qn), pos), rope(rms(k; w_kn), pos)
                                 a norm a head, the whole head turned at
                                 theta, half-split pairing, plain table
             s_ab = q_a . k_b * hd^-0.5      query head n reads key/value
                                             head n // (H / G)
             row a sees row b iff
               a <  L, b <  L:  blk(a) == blk(b)
               a <  L, b >= L:  blk(b - L) <  blk(a)
               a >= L, b >= L:  blk(b - L) <= blk(a - L)
               a >= L, b <  L:  never
             h += softmax_b(s_ab over the visible b) v_b  W_o

    FFN:     g = rms(h; w_post);  p = softmax(g W_r)               [2L, E]
             sel = top_k(p);  w = p[sel] / sum(p[sel])
             h += sum_{e in sel, e held} w_e swiglu_e(g)  no shared expert

    loss:    z_i = rms(h_i; w_f) Head^T  for the noised rows i < L alone
             (1 / L) sum_{i: m[i] = 1} xent(z_i, x[i]) / p
             + router_aux_loss_coef * sum over layers of E sum_e f_e P_e

A masked position predicts its own token: no shift. The clean rows of the
last layer feed nothing. The balance term is the Switch form over all
``E`` experts and all ``2L`` rows of the batch (``f_e`` the share of the
rows that chose expert ``e``, ``P_e`` their mean router probability). The
expert layer is ``reference/mellum2.py``'s share, letter for letter: the
part the experts held here give, the tokens' weights constant in the
backward. The mask is a boolean array built from the four cases a block
of queries at a time; the head runs a block of rows at a time; a sequence
goes through the layers one program at a time.

Imports nothing of ``apex_tpu``. One sequence at a time: ``tokens`` and
``masked`` are ``[L]``, ``p`` a scalar. Parameters are the tree
``benchmarks.weights_sdar.specs`` describes, as float32, on the device or
on the host. ``cfg`` is the configuration file.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import precision as P
from benchmarks.reference.kimi_vl import (  # noqa: F401
    ADAM, QUERY_BLOCK, TOKEN_BLOCK, _adam, _divisor, _norms, head_logits,
    rms)
from benchmarks.reference.mellum2 import moe, width  # noqa: F401
from benchmarks.reference.qwen3_next import held  # noqa: F401


PROBE_ROWS = 32     # the first noised rows whose layer-0 attention is compared


def layer_kinds(cfg: dict) -> list:
    return ["full"] * cfg["num_hidden_layers"]


def rows_of(tokens, masked, cfg: dict):
    """``u [2L]``: the noised copy (the mask token where ``masked``), then
    the clean one."""
    return jnp.concatenate([jnp.where(masked, cfg["vocab_size"] - 1, tokens),
                            tokens])


def visible(a, b, block: int, length: int):
    """Whether row ``a`` sees row ``b`` (integer arrays that broadcast),
    the four cases as the docstring has them."""
    blk_a = jnp.where(a < length, a, a - length) // block
    blk_b = jnp.where(b < length, b, b - length) // block
    return jnp.where(
        a < length,
        jnp.where(b < length, blk_a == blk_b, blk_b < blk_a),
        jnp.where(b < length, False, blk_b <= blk_a))


def visible_pairs(block: int, length: int) -> int:
    """Pairs the mask shows a head: ``L^2 + B L`` (``B L`` noised by
    noised, ``(L^2 - B L) / 2`` noised by clean, ``(L^2 + B L) / 2`` clean
    by clean)."""
    return length * length + block * length


def rotary(x, theta: float, positions):
    """``x [T, H, D]`` turned whole by ``positions [T]``, half-split
    pairing, plain table."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention_mixer(h, p, cfg: dict, prec: str):
    """``(the mixer's output [2L, d], what the heads made for the first
    ``PROBE_ROWS`` noised rows, before ``W_o`` [rows, H x hd])``."""
    t = h.shape[0]                                  # 2L
    length, bl = t // 2, cfg["block_length"]
    nh, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    pos = jnp.tile(jnp.arange(length), 2)
    q = P.matmul(h, p["w_q"], prec).reshape(t, nh, hd)
    k = P.matmul(h, p["w_k"], prec).reshape(t, kv, hd)
    v = P.matmul(h, p["w_v"], prec).reshape(t, kv, hd)
    q = rotary(rms(q, p["q_norm"], eps), theta, pos)
    k = rotary(rms(k, p["k_norm"], eps), theta, pos)
    blk = _divisor(t, QUERY_BLOCK // 4)

    @jax.checkpoint
    def block(args):
        q_b, start = args                   # [blk, G, H / G, hd]
        s = P.einsum("tgqd,sgd->gqts", q_b, k, prec) * hd ** -0.5
        ok = visible((start + jnp.arange(blk))[:, None],
                     jnp.arange(t)[None, :], bl, length)
        return P.einsum("gqts,sgd->tgqd", jax.nn.softmax(
            jnp.where(ok, s, -jnp.inf), -1), v, prec)
    a = jax.lax.map(block, (q.reshape(t // blk, blk, kv, nh // kv, hd),
                            jnp.arange(0, t, blk)))
    a = a.reshape(t, nh * hd)
    return P.matmul(a, p["w_o"], prec), a[:PROBE_ROWS]


def block(x, lp, cfg: dict, prec: str):
    """One layer: ``(x out, experts chosen [2L, K], mean router
    probabilities [E], the attention's probe rows)``."""
    eps = cfg["rms_norm_eps"]
    a, probe = attention_mixer(rms(x, lp["norm1"], eps), lp["attn"], cfg,
                               prec)
    x = x + a
    y, idx, probs = moe(rms(x, lp["norm2"], eps), lp["moe"], cfg, prec)
    return x + y, idx, jnp.mean(probs, 0), probe


def logits(params, tokens, masked, cfg: dict, prec: str = "float32"):
    """``[L, vocab]`` for the noised copy of one sequence."""
    x = params["embed"][rows_of(tokens, masked, cfg)]
    for i in range(cfg["num_hidden_layers"]):
        x = block(x, params[f"layer_{i}"], cfg, prec)[0]
    return head_logits(x[:tokens.shape[0]], params["norm_f"],
                       params["head"], cfg, prec)


def head_loss(x, norm_f, head, targets, weight, cfg: dict, prec: str):
    """``(1 / L) sum_i weight_i xent(z_i, targets_i)`` over the noised
    rows ``x [L, d]``, a block of rows at a time."""
    def picked(xtw):
        x, t, w = xtw
        logp = jax.nn.log_softmax(head_logits(x, norm_f, head, cfg, prec))
        return w * jnp.take_along_axis(logp, t[:, None], -1)[:, 0]
    n = x.shape[0]
    blk = _divisor(n, TOKEN_BLOCK)
    return -jnp.sum(jax.lax.map(jax.checkpoint(picked), (
        x.reshape(n // blk, blk, -1), targets.reshape(n // blk, blk),
        weight.reshape(n // blk, blk)))) / n


@functools.lru_cache(maxsize=None)
def _programs(cfg_json: str, prec: str) -> dict:
    """The jitted pieces for one configuration and precision, made once
    a process (a closure made anew would compile anew)."""
    cfg = json.loads(cfg_json)

    def fwd(lp, x):
        x, idx, probs, probe = block(x, lp, cfg, prec)
        return x, jnp.sum(idx[..., None] == jnp.arange(probs.shape[0]),
                          (0, 1)), probe

    def bwd(lp, x, share, dx, daux):
        """The layer's backward from its input: its output's cotangent
        ``dx`` and the cotangent ``daux`` of its load-balancing term ``E
        sum_e share_e P_e``."""
        def f(lp, x):
            y, _, probs, _ = block(x, lp, cfg, prec)
            return y, probs.shape[0] * jnp.sum(share * probs)
        (_, aux), vjp = jax.vjp(f, lp, x)
        return vjp((dx, daux)) + (aux,)

    def head(x, norm_f, head, targets, weight):
        """The head over the noised half of ``x [2L, d]``; the clean
        half's cotangent is zero."""
        return head_loss(x[:targets.shape[0]], norm_f, head, targets, weight,
                         cfg, prec)
    return {
        "layer": (jax.jit(fwd), jax.jit(bwd)),
        "head": jax.jit(jax.value_and_grad(head, argnums=(0, 1, 2))),
        "embed": jax.jit(lambda table, rows, dx:
                         jnp.zeros_like(table).at[rows].add(dx)),
        "add": jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                       donate_argnums=0),
        "scale": jax.jit(lambda a, s: jax.tree.map(lambda x: x * s, a),
                         donate_argnums=0)}


def batch_loss_and_grad(params, batch, cfg: dict, prec: str = "float32"):
    """``(loss, gradient, pairs an expert [layers, E], layer 0's probe rows
    [R, PROBE_ROWS, H x hd])`` of ``batch =
    (tokens [R, L], masked [R, L], p [R])``: the mean over the sequences
    of ``(1 / L) sum_masked xent / p`` plus ``router_aux_loss_coef`` times
    the layers' load-balancing terms, each over the **batch**'s ``R x 2L``
    rows. So every sequence goes forward first, keeping each layer's
    input, and then back."""
    prog = _programs(json.dumps(cfg, sort_keys=True), prec)
    fwd, bwd = prog["layer"]
    tokens, masked, p = batch
    layers = [params[f"layer_{i}"] for i in range(cfg["num_hidden_layers"])]
    coef = cfg["router_aux_loss_coef"]
    n, length = tokens.shape
    rows = [rows_of(t, m, cfg) for t, m in zip(tokens, masked)]
    layer_inputs, counts, probes = [], [0] * len(layers), []
    for u in rows:
        xs = [params["embed"][u]]
        for i, lp in enumerate(layers):
            x, c, probe = fwd(lp, xs[-1])
            xs.append(x)
            counts[i] = counts[i] + c
            probes += [probe] if i == 0 else []
        layer_inputs.append(xs)
    share = [c / (n * 2 * length) for c in counts]
    loss, grad = 0.0, {}

    def add(name, g):       # a leaf group at a time: no second whole tree
        grad[name] = prog["add"](grad[name], g) if name in grad else g
    for u, t, m, p_r, xs in zip(rows, tokens, masked, p, layer_inputs):
        xent, (dx, d_norm, d_head) = prog["head"](
            xs.pop(), params["norm_f"], params["head"], t,
            m.astype(jnp.float32) / p_r)
        add("norm_f", d_norm)
        add("head", d_head)
        for i in reversed(range(len(layers))):
            g, dx, aux = bwd(layers[i], xs.pop(), share[i], dx,
                             jnp.float32(coef))
            add(f"layer_{i}", g)
            xent = xent + coef * aux
        add("embed", prog["embed"](params["embed"], u, dx))
        loss = loss + xent
    return loss / n, prog["scale"](grad, jnp.float32(1.0 / n)), \
        jnp.stack(counts), jnp.stack(probes)


def train_steps(params, batches, cfg: dict, prec: str = "float32", *,
                lr: float):
    """Follow the first ``len(batches)`` optimizer steps, as
    ``reference/mellum2.py`` does (``params`` may lie on the host: the
    steps run on a copy on the device, 551M parameters with ``m``, ``v``
    and the gradient 8.8 GB of the chip's 16.9): each step's loss, the
    per-leaf norm of the first gradient, the per-leaf norm of the
    parameters' change; and as ``vectors`` the first step's probe: what
    layer 0's heads made for the first ``PROBE_ROWS`` noised rows of every
    sequence, a forward reading that no router's choice has touched. A
    batch is ``(tokens [R, L], masked [R, L], p [R])``."""
    update = _adam(lr)
    start = params
    params = jax.tree.map(jnp.array, params)    # a copy: the steps donate
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norms, vectors = [], None, None
    for i, batch in enumerate(batches):
        loss, grad, _, probe = batch_loss_and_grad(params, batch, cfg, prec)
        if i == 0:
            grad_norms = jax.tree.map(float, _norms(grad))
            vectors = [np.asarray(probe, np.float64).ravel()]
        params, m, v = update(params, grad, m, v, jnp.float32(i + 1))
        losses.append(float(loss))
    delta = _norms(jax.tree.map(jnp.subtract, params, start))
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": jax.tree.map(float, delta), "vectors": vectors}
