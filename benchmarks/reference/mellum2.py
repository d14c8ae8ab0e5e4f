"""Mellum2-12B-A2.5B's block in plain ``jax.numpy``: float32, every
product at the highest precision (``reference/precision.py``), no kernel,
no scan over layers, no batch.

From the model's public ``config.json``
(https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct, ``model_type:
mellum``): a decoder whose layers attend over a **sliding window** or over
the **whole causal past**, by ``layer_types``, each with a rotary table of
its own (``rope_parameters`` by layer type), every layer followed by a
mixture of experts (``d`` = ``hidden_size``, ``eps`` = ``rms_norm_eps``;
no bias anywhere, an untied head):

    rms(x; w) = x * rsqrt(mean(x^2) + eps) * w

    mixer:   h = rms(x; w_in)
             q = h W_q -> [T, H, hd];  k, v = h W_k, h W_v -> [T, G, hd]
             q, k <- rope_kind(rms(q; w_qn)), rope_kind(rms(k; w_kn))
                                 a norm a head, the whole head turned
             s_ij = q_i . k_j * hd^-0.5      query head n reads key/value
                                             head n // (H / G)
      full_attention:     j visible to i  iff  j <= i
      sliding_attention:  j visible to i  iff  0 <= i - j < sliding_window
             x += softmax_j(s_ij over the visible j) v_j  W_o

    rope, sliding_attention: inv_freq_m = theta^(-2m / hd), m < hd / 2
    rope, full_attention (YaRN: ``factor`` f over ``original_max_position_
    embeddings`` L, ``beta_fast`` b+, ``beta_slow`` b-):
             extra_m = theta^(-2m / hd);  inter_m = extra_m / f
             c(n) = hd ln(L / (2 pi n)) / (2 ln theta)
             low = floor(c(b+)), high = ceil(c(b-))      (18, 35 here)
             ramp_m = clip((m - low) / (high - low), 0, 1)
             inv_freq_m = inter_m ramp_m + extra_m (1 - ramp_m)
             cos, sin <- attention_factor * cos, sin  (on q and on k)

    FFN:     g = rms(x; w_post);  p = softmax(g W_r)               [T, E]
             sel = top_k(p);  w = p[sel] / sum(p[sel])
             x += sum_{e in sel, e held} w_e swiglu_e(g)  no shared expert

The loss of a batch is the mean next-token cross-entropy plus
``router_aux_loss_coef`` times, a layer, ``E sum_e f_e P_e`` over the
batch (``f_e`` the share of the batch's tokens that chose expert ``e``,
a count; ``P_e`` the batch's mean router probability). The expert layer
computes the part that the experts held here give (``[expert_chip x
num_experts, (expert_chip + 1) x num_experts)`` of the router's width),
each of them densely over every token; what absent experts would add is
left out, as in the program, and a share holds the tokens' weights
constant in the backward (the configuration's ``assumed``). Attention
runs a block of queries at a time with the window as a mask on the
block's scores, the head a block of tokens at a time, a row goes through
the layers one program at a time.

Imports nothing of ``apex_tpu``. One sequence at a time: ``tokens`` is
``[T]``. Parameters are the tree ``benchmarks.weights_mellum2.specs``
describes, as float32, on the device or on the host. ``cfg`` is the
configuration file.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmarks.reference import precision as P
# what the references share, letter for letter: the plain norm, the
# blocking, the blocked head, Adam (kimi_vl); the softmax router, the
# SwiGLU, the held range (qwen3_next)
from benchmarks.reference.kimi_vl import (  # noqa: F401
    ADAM, QUERY_BLOCK, _adam, _divisor, _norms, head_logits, head_loss, rms)
from benchmarks.reference.qwen3_next import held, route, swiglu

KINDS = {"sliding_attention": "window", "full_attention": "full"}


def width(cfg: dict) -> int:
    """The router's width: every expert, wherever it lies."""
    return cfg["num_experts"] * cfg.get("expert_chips", 1)


def layer_kinds(cfg: dict) -> list:
    """A mixer a layer: "window" | "full"."""
    return [KINDS[kind] for kind in cfg["layer_types"]]


# -- rotary tables by layer kind ---------------------------------------------

def inv_freq(cfg: dict, kind: str):
    """``(inv_freq [hd / 2], the factor on cos and sin)`` of a layer
    kind, from its section of ``rope_parameters``."""
    rope = cfg["rope_parameters"][
        "sliding_attention" if kind == "window" else "full_attention"]
    hd, theta = cfg["head_dim"], rope["rope_theta"]
    m = jnp.arange(hd // 2, dtype=jnp.float32)
    extra = theta ** (-2.0 * m / hd)
    if rope["rope_type"] == "default":
        return extra, 1.0
    assert rope["rope_type"] == "yarn", rope

    def c(turns):
        return hd * math.log(rope["original_max_position_embeddings"]
                             / (2 * math.pi * turns)) / (2 * math.log(theta))
    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), hd - 1)
    ramp = jnp.clip((m - low) / max(high - low, 0.001), 0.0, 1.0)
    return extra / rope["factor"] * ramp + extra * (1.0 - ramp), \
        rope["attention_factor"]


def rotary(x, cfg: dict, kind: str):
    """``x [T, H, hd]`` turned whole, half-split pairing, on the layer
    kind's table."""
    freq, factor = inv_freq(cfg, kind)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq
    cos = factor * jnp.cos(ang)[:, None, :]
    sin = factor * jnp.sin(ang)[:, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# -- the mixer ---------------------------------------------------------------

def attention_mixer(h, p, kind: str, cfg: dict, prec: str):
    t = h.shape[0]
    nh, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, window = cfg["rms_norm_eps"], cfg["sliding_window"]
    q = P.matmul(h, p["w_q"], prec).reshape(t, nh, hd)
    k = P.matmul(h, p["w_k"], prec).reshape(t, kv, hd)
    v = P.matmul(h, p["w_v"], prec).reshape(t, kv, hd)
    q = rotary(rms(q, p["q_norm"], eps), cfg, kind)
    k = rotary(rms(k, p["k_norm"], eps), cfg, kind)
    blk = _divisor(t, QUERY_BLOCK)

    @jax.checkpoint
    def block(args):
        q_b, start = args                   # [blk, G, H / G, hd]
        s = P.einsum("tgqd,sgd->gqts", q_b, k, prec) * hd ** -0.5
        ahead = (start + jnp.arange(blk))[:, None] - jnp.arange(t)[None, :]
        ok = ahead >= 0
        if kind == "window":
            ok = ok & (ahead < window)
        s = jnp.where(ok, s, -jnp.inf)
        return P.einsum("gqts,sgd->tgqd", jax.nn.softmax(s, -1), v, prec)
    a = jax.lax.map(block, (q.reshape(t // blk, blk, kv, nh // kv, hd),
                            jnp.arange(0, t, blk)))
    return P.matmul(a.reshape(t, nh * hd), p["w_o"], prec)


# -- the expert layer's share ------------------------------------------------

def moe(h, p, cfg: dict, prec: str):
    """``(the held experts' part, experts chosen [T, K], probabilities
    [T, E])``. Every held expert is computed over every token and weighted
    by the token's weight for it, zero where it was not among the token's
    ``num_experts_per_tok``; no shared expert. A share (``expert_chips`` >
    1) holds the tokens' weights constant in the backward (``assumed``,
    ``router_gradient``): the router learns from the load-balancing
    term."""
    w, idx, probs = route(h, p, cfg, prec)
    if cfg.get("expert_chips", 1) > 1:
        w = jax.lax.stop_gradient(w)
    lo, _ = held(cfg)

    @jax.checkpoint
    def expert(y, x):
        e, w_gate, w_up, w_down = x
        w_e = jnp.sum(jnp.where(idx == lo + e, w, 0.0), -1, keepdims=True)
        return y + w_e * swiglu(h, w_gate, w_up, w_down, prec), None
    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), (
        jnp.arange(p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
        p["w_down"]))
    return y, idx, probs


# -- the model, a layer at a time --------------------------------------------
#
# A row goes through the layers one program at a time, and back through
# them the same way (each layer's backward recomputes its forward from
# the layer's input): the three window layers share one compiled program,
# and nothing larger than a layer is ever compiled or resident.

def block(x, lp, kind: str, cfg: dict, prec: str):
    """One layer: ``(x out, experts chosen [T, K], mean router
    probabilities [E])``."""
    eps = cfg["rms_norm_eps"]
    x = x + attention_mixer(rms(x, lp["norm1"], eps), lp["attn"], kind, cfg,
                            prec)
    y, idx, probs = moe(rms(x, lp["norm2"], eps), lp["moe"], cfg, prec)
    return x + y, idx, jnp.mean(probs, 0)


def logits(params, tokens, cfg: dict, prec: str = "float32"):
    """``[T, vocab]`` for one sequence ``tokens [T]``."""
    x = params["embed"][tokens]
    for i, kind in enumerate(layer_kinds(cfg)):
        x, _, _ = block(x, params[f"layer_{i}"], kind, cfg, prec)
    return head_logits(x, params["norm_f"], params["head"], cfg, prec)


@functools.lru_cache(maxsize=None)
def _programs(cfg_json: str, prec: str) -> dict:
    """The jitted pieces for one configuration and precision, made once
    a process (a closure made anew would compile anew)."""
    cfg = json.loads(cfg_json)
    out = {}
    for kind in set(layer_kinds(cfg)):
        def fwd(lp, x, _kind=kind):
            x, idx, probs = block(x, lp, _kind, cfg, prec)
            return x, jnp.sum(idx[..., None] == jnp.arange(probs.shape[0]),
                              (0, 1))

        def bwd(lp, x, share, dx, daux, _kind=kind):
            """The layer's backward from its input: its output's
            cotangent ``dx`` and the cotangent ``daux`` of its
            load-balancing term ``E sum_e share_e P_e``."""
            def f(lp, x):
                y, _, probs = block(x, lp, _kind, cfg, prec)
                return y, probs.shape[0] * jnp.sum(share * probs)
            (_, aux), vjp = jax.vjp(f, lp, x)
            return vjp((dx, daux)) + (aux,)
        out[kind] = jax.jit(fwd), jax.jit(bwd)
    out["head"] = jax.jit(jax.value_and_grad(
        lambda x, norm_f, head, targets: head_loss(x, norm_f, head, targets,
                                                   cfg, prec),
        argnums=(0, 1, 2)))
    out["embed"] = jax.jit(lambda table, tokens, dx:
                           jnp.zeros_like(table).at[tokens].add(dx))
    out["add"] = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                         donate_argnums=0)
    out["scale"] = jax.jit(lambda a, s: jax.tree.map(lambda x: x * s, a),
                           donate_argnums=0)
    return out


def batch_loss_and_grad(params, batch, cfg: dict, prec: str = "float32"):
    """``(loss, gradient, pairs an expert [layers, E])`` of ``batch [B, T
    + 1]``: the mean next-token cross-entropy plus ``router_aux_loss_coef``
    times the layers' load-balancing terms, each over the **batch**. So
    every row goes forward first, keeping each layer's input, and then
    back; rows are equally long, so the mean of the rows' parts is the
    batch's."""
    prog = _programs(json.dumps(cfg, sort_keys=True), prec)
    kinds, coef = layer_kinds(cfg), cfg["router_aux_loss_coef"]
    layers = [params[f"layer_{i}"] for i in range(len(kinds))]
    n, t = batch.shape[0], batch.shape[1] - 1
    layer_inputs, counts = [], [0] * len(kinds)
    for row in batch:
        xs = [params["embed"][row[:-1]]]
        for i, kind in enumerate(kinds):
            x, c = prog[kind][0](layers[i], xs[-1])
            xs.append(x)
            counts[i] = counts[i] + c
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
        for i in reversed(range(len(kinds))):
            g, dx, aux = prog[kinds[i]][1](layers[i], xs.pop(), share[i], dx,
                                           jnp.float32(coef))
            add(f"layer_{i}", g)
            xent = xent + coef * aux
        add("embed", prog["embed"](params["embed"], row[:-1], dx))
        loss = loss + xent
    return loss / n, prog["scale"](grad, jnp.float32(1.0 / n)), \
        jnp.stack(counts)


def train_steps(params, batches, cfg: dict, prec: str = "float32", *,
                lr: float):
    """Follow the first ``len(batches)`` optimizer steps, as
    ``reference/gpt2.py`` does: each step's loss, the per-leaf norm of the
    first gradient, the per-leaf norm of the parameters' change.
    ``params`` may lie on the host (numpy): the steps then run on a copy
    on the device and the start is brought there only for the change's
    norms, so that the gradient has parameters, ``m`` and ``v`` beside it
    and nothing else (595M parameters: 9.5 GB of the chip's 16.9)."""
    update = _adam(lr)
    start = params
    params = jax.tree.map(jnp.array, params)    # a copy: the steps donate
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for i, batch in enumerate(batches):
        loss, grad, _ = batch_loss_and_grad(params, batch, cfg, prec)
        if i == 0:
            grad_norms = jax.tree.map(float, _norms(grad))
        params, m, v = update(params, grad, m, v, jnp.float32(i + 1))
        losses.append(float(loss))
    delta = _norms(jax.tree.map(jnp.subtract, params, start))
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": jax.tree.map(float, delta)}
