"""LFM2-24B-A2B's block in plain ``jax.numpy``: float32, every product at
the highest precision (``reference/precision.py``), no kernel, no scan
over layers, no batch.

From the model's public ``config.json``
(https://huggingface.co/LiquidAI/LFM2-24B-A2B, ``model_type: lfm2_moe``):
a decoder whose layers mix with a **double-gated short convolution** or
with **grouped-query softmax attention**, by ``layer_types`` (``d`` =
``hidden_size``, ``eps`` = ``norm_eps``; no bias anywhere, the head is the
embedding):

    rms(x; w) = x * rsqrt(mean(x^2) + eps) * w

    conv:      h = rms(x; w_op)
               [B | C | u] = h W_in                 three streams of d
               z_t = sum_{j < L} k_j * (B * u)_{t-(L-1)+j}
                                    depthwise, causal, L = conv_L_cache
                                    taps a channel, zeros before t = 0
               x += (C * z) W_out

    attention: h = rms(x; w_op)
               q = h W_q -> [T, H, hd];  k, v = h W_k, h W_v -> [T, G, hd]
               q, k <- rope(rms(q; w_qn)), rope(rms(k; w_kn))
                                    a norm a head, the whole head turned
               a = softmax(causal(q k^T hd^-0.5)) v
                                    query head i reads key/value head
                                    i // (H / G)
               x += a W_o           no output gate

The first ``num_dense_layers`` layers follow the mixer with a dense
SwiGLU of ``intermediate_size``, the others with the expert layer:

    g = rms(x; w_ffn);  s = sigmoid(g W_r)                       [T, E]
    sel = top_k(s + b);  w = routed_scaling_factor * s[sel] / sum(s[sel])
    x += sum_{e in sel, e held} w_e swiglu_e(g)      no shared expert

``b`` is the router's selection bias, ``E`` floats a layer that no
gradient reaches; after a step ``b_e += u * sign(mean(c) - c_e)`` with
``c_e`` the step's pairs on expert ``e``. The loss of a batch is the mean
next-token cross-entropy of its rows, with **no auxiliary term**: the
bias alone balances the load. The expert layer computes the part that the
experts held here give (``[expert_chip x num_experts, (expert_chip + 1) x
num_experts)`` of the router's width), each of them densely over every
token; what absent experts would add is left out, as in the program, and
a share holds the tokens' weights constant in the backward (the
configuration's ``assumed``): ``W_r`` then has no gradient at all, and its
leaf reads zero. Attention runs a block of queries at a time, the dense
SwiGLU and the head a block of tokens at a time, a row goes through the
layers one program at a time.

Imports nothing of ``apex_tpu``. One sequence at a time: ``tokens`` is
``[T]``. Parameters are the tree ``benchmarks.weights_lfm2.specs``
describes, as float32; ``biases`` is ``[expert layers, E]``. ``cfg`` is
the configuration file.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import precision as P
# what the two bias-balanced references share, letter for letter: the norm,
# the whole-head rotary, the SwiGLU, the bias's move, the blocking, Adam
from benchmarks.reference.kimi_vl import (  # noqa: F401
    ADAM, QUERY_BLOCK, TOKEN_BLOCK, _adam, _divisor, _in_blocks, _norms,
    moved_biases, rms, rotary, swiglu)


def width(cfg: dict) -> int:
    """The router's width: every expert, wherever it lies."""
    return cfg["num_experts"] * cfg.get("expert_chips", 1)


def held(cfg: dict) -> tuple:
    lo = cfg.get("expert_chip", 0) * cfg["num_experts"]
    return lo, lo + cfg["num_experts"]


def layer_kinds(cfg: dict) -> list:
    """``(mixer, ffn)`` a layer: "conv" | "full", "dense" | "experts"."""
    return [("conv" if kind == "conv" else "full",
             "dense" if i < cfg["num_dense_layers"] else "experts")
            for i, kind in enumerate(cfg["layer_types"])]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def zero_biases(cfg: dict):
    layers = sum(ffn == "experts" for _, ffn in layer_kinds(cfg))
    return jnp.zeros((layers, width(cfg)))


# -- the mixers --------------------------------------------------------------

def conv_mixer(h, p, cfg: dict, prec: str):
    """The double-gated short convolution, the convolution written as the
    sum of its shifted products."""
    d, taps = cfg["hidden_size"], cfg["conv_L_cache"]
    bcu = P.matmul(h, p["w_in"], prec)
    b, c, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
    bu = jnp.pad(b * u, ((taps - 1, 0), (0, 0)))
    z = sum(p["taps"][j] * bu[j:j + h.shape[0]] for j in range(taps))
    return P.matmul(c * z, p["w_out"], prec)


def attention_mixer(h, p, cfg: dict, prec: str):
    t = h.shape[0]
    nh, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    eps, theta = cfg["norm_eps"], cfg["rope_parameters"]["rope_theta"]
    q = P.matmul(h, p["w_q"], prec).reshape(t, nh, hd)
    k = P.matmul(h, p["w_k"], prec).reshape(t, kv, hd)
    v = P.matmul(h, p["w_v"], prec).reshape(t, kv, hd)
    q = rotary(rms(q, p["q_norm"], eps), theta)
    k = rotary(rms(k, p["k_norm"], eps), theta)
    blk = _divisor(t, QUERY_BLOCK)

    @jax.checkpoint
    def block(args):
        q_b, start = args                   # [blk, G, H / G, hd]
        s = P.einsum("tgqd,sgd->gqts", q_b, k, prec) * hd ** -0.5
        ok = (start + jnp.arange(blk))[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(ok, s, -jnp.inf)
        return P.einsum("gqts,sgd->tgqd", jax.nn.softmax(s, -1), v, prec)
    a = jax.lax.map(block, (q.reshape(t // blk, blk, kv, nh // kv, hd),
                            jnp.arange(0, t, blk)))
    return P.matmul(a.reshape(t, nh * hd), p["w_o"], prec)


# -- the feed-forward halves -------------------------------------------------

def route(h, p, bias, cfg: dict, prec: str):
    """``(weights [T, K], experts [T, K])`` over the router's whole
    width: chosen on ``scores + bias``, weighted by the scores
    themselves."""
    s = jax.nn.sigmoid(P.matmul(h, p["router"], prec))
    _, idx = jax.lax.top_k(s + bias, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, -1)
    return cfg["routed_scaling_factor"] * w / jnp.sum(w, -1, keepdims=True), \
        idx


def moe(h, p, bias, cfg: dict, prec: str):
    """``(the held experts' part, pairs an expert [E])``. Every held
    expert is computed over every token and weighted by the token's weight
    for it, zero where it was not among the token's
    ``num_experts_per_tok``. A share (``expert_chips`` > 1) holds the
    tokens' weights constant in the backward (``assumed``,
    ``router_gradient``)."""
    w, idx = route(h, p, bias, cfg, prec)
    if cfg.get("expert_chips", 1) > 1:
        w = jax.lax.stop_gradient(w)
    lo, _ = held(cfg)

    @jax.checkpoint
    def expert(y, x):
        e, w_gate, w_up, w_down = x
        w_e = jnp.sum(jnp.where(idx == lo + e, w, 0.0), -1, keepdims=True)
        return y + w_e * swiglu(h, {"w_gate": w_gate, "w_up": w_up,
                                    "w_down": w_down}, prec), None
    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), (
        jnp.arange(p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
        p["w_down"]))
    return y, jnp.sum(idx[..., None] == jnp.arange(width(cfg)), (0, 1))


# -- the model, a layer at a time --------------------------------------------
#
# A row goes through the layers one program at a time, and back through
# them the same way (each layer's backward recomputes its forward from
# the layer's input): layers of one kind share one compiled program, and
# nothing larger than a layer is ever compiled or resident.

def block(x, lp, bias, kind: tuple, cfg: dict, prec: str):
    """One layer: ``(x out, pairs an expert [E])``; a dense layer has no
    pairs."""
    mixer, ffn = kind
    eps = cfg["norm_eps"]
    h = rms(x, lp["norm1"], eps)
    x = x + (conv_mixer(h, lp["conv"], cfg, prec) if mixer == "conv"
             else attention_mixer(h, lp["attn"], cfg, prec))
    g = rms(x, lp["norm2"], eps)
    if ffn == "dense":
        return x + _in_blocks(lambda g: swiglu(g, lp["mlp"], prec), g,
                              TOKEN_BLOCK), None
    y, pairs = moe(g, lp["moe"], bias, cfg, prec)
    return x + y, pairs


def head_logits(x, norm_f, embed, cfg: dict, prec: str):
    return P.matmul(rms(x, norm_f, cfg["norm_eps"]), embed.T, prec)


def head_loss(x, norm_f, embed, targets, cfg: dict, prec: str):
    """Mean next-token cross-entropy of one row from its last hidden
    states, a block of tokens at a time; the head is the embedding."""
    def picked(xt):
        x, t = xt
        logp = jax.nn.log_softmax(head_logits(x, norm_f, embed, cfg, prec))
        return jnp.take_along_axis(logp, t[:, None], -1)[:, 0]
    n = x.shape[0]
    blk = _divisor(n, TOKEN_BLOCK)
    return -jnp.mean(jax.lax.map(jax.checkpoint(picked), (
        x.reshape(n // blk, blk, -1), targets.reshape(n // blk, blk))))


def _bias_rows(cfg: dict, biases):
    """A layer's bias (``None`` for a dense layer), in the layers' order."""
    rows = iter(zero_biases(cfg) if biases is None else biases)
    return [next(rows) if ffn == "experts" else None
            for _, ffn in layer_kinds(cfg)]


def logits(params, tokens, cfg: dict, prec: str = "float32", biases=None):
    """``[T, vocab]`` for one sequence ``tokens [T]``."""
    x = params["embed"][tokens]
    for i, (kind, bias) in enumerate(zip(layer_kinds(cfg),
                                         _bias_rows(cfg, biases))):
        x, _ = block(x, params[f"layer_{i}"], bias, kind, cfg, prec)
    return head_logits(x, params["norm_f"], params["embed"], cfg, prec)


@functools.lru_cache(maxsize=None)
def _programs(cfg_json: str, prec: str) -> dict:
    """The jitted pieces for one configuration and precision, made once
    a process (a closure made anew would compile anew)."""
    cfg = json.loads(cfg_json)
    out = {}
    for kind in set(layer_kinds(cfg)):
        def fwd(lp, bias, x, _kind=kind):
            return block(x, lp, bias, _kind, cfg, prec)

        def bwd(lp, bias, x, dx, _kind=kind):
            """The layer's backward from its input and its output's
            cotangent ``dx``: (the layer's gradient, the input's)."""
            _, vjp = jax.vjp(
                lambda lp, x: block(x, lp, bias, _kind, cfg, prec)[0], lp, x)
            return vjp(dx)
        out[kind] = jax.jit(fwd), jax.jit(bwd)
    out["head"] = jax.jit(jax.value_and_grad(
        lambda x, norm_f, embed, targets: head_loss(x, norm_f, embed,
                                                    targets, cfg, prec),
        argnums=(0, 1, 2)))
    out["embed"] = jax.jit(lambda d_table, tokens, dx:
                           d_table.at[tokens].add(dx), donate_argnums=0)
    out["add"] = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                         donate_argnums=0)
    out["scale"] = jax.jit(lambda a, s: jax.tree.map(lambda x: x * s, a),
                           donate_argnums=0)
    return out


def batch_loss_and_grad(params, batch, cfg: dict, prec: str = "float32",
                        biases=None):
    """``(loss, gradient, pairs an expert [expert layers, E])`` of
    ``batch [B, T + 1]``: the mean over the rows of a row's mean
    next-token cross-entropy. A row goes forward, keeping each layer's
    input, and back, alone. The embedding's gradient is the head's plus
    the gather's scatter-add."""
    prog = _programs(json.dumps(cfg, sort_keys=True), prec)
    kinds, bias = layer_kinds(cfg), _bias_rows(cfg, biases)
    layers = [params[f"layer_{i}"] for i in range(len(kinds))]
    n, loss, grad, pairs = batch.shape[0], 0.0, {}, 0

    def add(name, g):       # a leaf group at a time: no second whole tree
        grad[name] = prog["add"](grad[name], g) if name in grad else g
    for row in batch:
        xs, counts = [params["embed"][row[:-1]]], []
        for lp, b, kind in zip(layers, bias, kinds):
            x, c = prog[kind][0](lp, b, xs[-1])
            xs.append(x)
            counts += [c] if kind[1] == "experts" else []
        pairs = pairs + jnp.stack(counts)
        xent, (dx, d_norm, d_embed) = prog["head"](
            xs.pop(), params["norm_f"], params["embed"], row[1:])
        add("norm_f", d_norm)
        for i in reversed(range(len(kinds))):
            g, dx = prog[kinds[i]][1](layers[i], bias[i], xs.pop(), dx)
            add(f"layer_{i}", g)
        add("embed", prog["embed"](d_embed, row[:-1], dx))
        loss = loss + xent
    return loss / n, prog["scale"](grad, jnp.float32(1.0 / n)), pairs


def train_steps(params, batches, cfg: dict, prec: str = "float32", *,
                lr: float):
    """Follow the first ``len(batches)`` optimizer steps, as
    ``reference/gpt2.py`` does, the routers' biases moving after each:
    each step's loss, the per-leaf norm of the first gradient, the
    per-leaf norm of the parameters' change, as ``vectors`` the first
    step's pairs an expert, a vector an expert layer, and the biases
    after the last step. ``params`` may lie on the host (numpy): the steps
    then run on a copy on the device and the start is brought there only
    for the change's norms."""
    update = _adam(lr)
    start = params
    params = jax.tree.map(jnp.array, params)    # a copy: the steps donate
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    biases = zero_biases(cfg)
    losses, grad_norms, vectors = [], None, None
    for i, batch in enumerate(batches):
        loss, grad, pairs = batch_loss_and_grad(params, batch, cfg, prec,
                                                biases)
        if i == 0:
            grad_norms = jax.tree.map(float, _norms(grad))
            vectors = list(np.asarray(pairs, np.float64))
        params, m, v = update(params, grad, m, v, jnp.float32(i + 1))
        biases = moved_biases(biases, pairs, cfg["bias_update_speed"])
        losses.append(float(loss))
    delta = _norms(jax.tree.map(jnp.subtract, params, start))
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": jax.tree.map(float, delta), "vectors": vectors,
            "router_biases": np.asarray(biases)}
