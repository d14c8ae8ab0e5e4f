"""Kimi-VL-A3B's language model in plain ``jax.numpy``: float32, every
product at the highest precision (``reference/precision.py``), no kernel,
no scan over layers, no batch.

From the model's public ``config.json``
(https://huggingface.co/moonshotai/Kimi-VL-A3B-Instruct, ``text_config``):
a DeepSeek-V3-shaped decoder. Every layer mixes with **latent attention**
(``d`` = ``hidden_size``, ``H`` heads, ``eps`` = ``rms_norm_eps``; no bias
anywhere, an untied head):

    rms(x; w) = x * rsqrt(mean(x^2) + eps) * w
    h  = rms(x; w_in)
    q  = h W_q                    -> [T, H, qk_nope | qk_rope]
    [c | kr] = h W_kva            -> c [T, kv_lora_rank], kr [T, qk_rope]
    [kn | v] = rms(c; w_c) W_kvb  -> [T, H, qk_nope | v_head_dim]
    q_r, kr <- rope(theta)        kr is ONE head, used by all H
    a  = softmax(causal([q_n | q_r] [kn | kr]^T (qk_nope + qk_rope)^-0.5)) v
    x += a W_o

The first ``first_k_dense_replace`` layers follow it with a dense SwiGLU
of ``intermediate_size``, the others with the expert layer:

    g = rms(x; w_post);  s = sigmoid(g W_r)                      [T, E]
    sel = top_k(s + b);  w = routed_scaling_factor * s[sel] / sum(s[sel])
    x += sum_{e in sel, e held} w_e swiglu_e(g) + swiglu_shared(g)
    L_bal = sum_e f_e P_e,  f_e = E / (k T) * #{t : e in sel_t} (a count),
                            P_e = mean_t s_te / sum_j s_tj

``b`` is the router's selection bias, ``E`` floats a layer that no
gradient reaches; after a step ``b_e += u * sign(mean(c) - c_e)`` with
``c_e`` the step's pairs on expert ``e``. The loss of a batch is the mean
over its rows of ``xent + aux_loss_alpha * sum_layers L_bal``, both of the
row (``seq_aux``). The expert layer computes the part that the experts
held here give (``[expert_chip x n_routed_experts, (expert_chip + 1) x
n_routed_experts)`` of the router's width), each of them densely over
every token, plus the shared experts; what absent experts would add is
left out, as in the program, and a share holds the tokens' weights
constant in the backward (the configuration's ``assumed``). Attention runs
a block of queries at a time, the dense SwiGLU and the head a block of
tokens at a time, a row goes through the layers one program at a time.

Imports nothing of ``apex_tpu``. One sequence at a time: ``tokens`` is
``[T]``. Parameters are the tree ``benchmarks.weights_kimi_vl.specs``
describes, as float32; ``biases`` is ``[expert layers, E]``. ``cfg`` is
the configuration file.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import gpt2, precision as P

ADAM = gpt2.ADAM                        # FusedAdam's defaults
QUERY_BLOCK = 512
TOKEN_BLOCK = 2048


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def width(cfg: dict) -> int:
    """The router's width: every expert, wherever it lies."""
    return cfg["n_routed_experts"] * cfg.get("expert_chips", 1)


def held(cfg: dict) -> tuple:
    lo = cfg.get("expert_chip", 0) * cfg["n_routed_experts"]
    return lo, lo + cfg["n_routed_experts"]


def ffn_kinds(cfg: dict) -> list:
    return ["dense" if i < cfg["first_k_dense_replace"] else "experts"
            for i in range(cfg["num_hidden_layers"])]


def zero_biases(cfg: dict):
    return jnp.zeros((ffn_kinds(cfg).count("experts"), width(cfg)))


def _divisor(n: int, most: int) -> int:
    return next(s for s in range(min(most, n), 0, -1) if n % s == 0)


def _in_blocks(fn, x, most: int):
    """``fn`` over ``x [T, ...]`` a block of rows at a time, each block
    recomputed in the backward."""
    t = x.shape[0]
    blk = _divisor(t, most)
    y = jax.lax.map(jax.checkpoint(fn), x.reshape(t // blk, blk,
                                                  *x.shape[1:]))
    return y.reshape(t, *y.shape[2:])


# -- latent attention --------------------------------------------------------

def rotary(x, theta: float):
    """``x [T, H, D]`` turned whole, half-split pairing."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def latent_mixer(h, p, cfg: dict, prec: str):
    t = h.shape[0]
    nh, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    q = P.matmul(h, p["w_q"], prec).reshape(t, nh, dn + dr)
    kva = P.matmul(h, p["w_kva"], prec)
    kv = P.matmul(rms(kva[:, :r], p["kv_norm"], cfg["rms_norm_eps"]),
                  p["w_kvb"], prec).reshape(t, nh, dn + dv)
    q_n, q_r = q[..., :dn], rotary(q[..., dn:], cfg["rope_theta"])
    k_n, v = kv[..., :dn], kv[..., dn:]
    k_r = rotary(kva[:, None, r:], cfg["rope_theta"])[:, 0]     # one head
    blk = _divisor(t, QUERY_BLOCK)

    @jax.checkpoint
    def block(args):
        qn_b, qr_b, start = args                # [blk, H, dn], [blk, H, dr]
        s = (P.einsum("thd,shd->hts", qn_b, k_n, prec)
             + P.einsum("thd,sd->hts", qr_b, k_r, prec)) * (dn + dr) ** -0.5
        ok = (start + jnp.arange(blk))[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(ok, s, -jnp.inf)
        return P.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v, prec)
    a = jax.lax.map(block, (q_n.reshape(t // blk, blk, nh, dn),
                            q_r.reshape(t // blk, blk, nh, dr),
                            jnp.arange(0, t, blk)))
    return P.matmul(a.reshape(t, nh * dv), p["w_o"], prec)


# -- the feed-forward halves -------------------------------------------------

def swiglu(h, p, prec: str):
    return P.matmul(jax.nn.silu(P.matmul(h, p["w_gate"], prec))
                    * P.matmul(h, p["w_up"], prec), p["w_down"], prec)


def route(h, p, bias, cfg: dict, prec: str):
    """``(weights [T, K], experts [T, K], scores [T, E])`` over the
    router's whole width: chosen on ``scores + bias``, weighted by the
    scores themselves."""
    s = jax.nn.sigmoid(P.matmul(h, p["router"], prec))
    _, idx = jax.lax.top_k(s + bias, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, -1)
    return cfg["routed_scaling_factor"] * w / jnp.sum(w, -1, keepdims=True), \
        idx, s


def moe(h, p, bias, cfg: dict, prec: str):
    """``(the held experts' part plus the shared experts, pairs an expert
    [E], the sequence's load-balancing term)``. Every held expert is
    computed over every token and weighted by the token's weight for it,
    zero where it was not among the token's ``num_experts_per_tok``. A
    share (``expert_chips`` > 1) holds the tokens' weights constant in the
    backward (``assumed``, ``router_gradient``)."""
    w, idx, s = route(h, p, bias, cfg, prec)
    if cfg.get("expert_chips", 1) > 1:
        w = jax.lax.stop_gradient(w)
    lo, _ = held(cfg)
    e_all, k, t = s.shape[-1], cfg["num_experts_per_tok"], h.shape[0]

    @jax.checkpoint
    def expert(y, x):
        e, w_gate, w_up, w_down = x
        w_e = jnp.sum(jnp.where(idx == lo + e, w, 0.0), -1, keepdims=True)
        return y + w_e * swiglu(h, {"w_gate": w_gate, "w_up": w_up,
                                    "w_down": w_down}, prec), None
    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), (
        jnp.arange(p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
        p["w_down"]))
    y = y + swiglu(h, p["shared"], prec)
    pairs = jnp.sum(idx[..., None] == jnp.arange(e_all), (0, 1))
    f = jax.lax.stop_gradient(pairs * (e_all / (k * t)))
    share = jnp.mean(s / jnp.sum(s, -1, keepdims=True), 0)
    return y, pairs, jnp.sum(f * share)


# -- the model, a layer at a time --------------------------------------------
#
# A row goes through the layers one program at a time, and back through
# them the same way (each layer's backward recomputes its forward from
# the layer's input): the four expert layers share one compiled program,
# and nothing larger than a layer is ever compiled or resident.

def block(x, lp, bias, kind: str, cfg: dict, prec: str):
    """One layer: ``(x out, pairs an expert [E], the load-balancing
    term)``; a dense layer has no pairs and no term."""
    eps = cfg["rms_norm_eps"]
    x = x + latent_mixer(rms(x, lp["norm1"], eps), lp["latent"], cfg, prec)
    g = rms(x, lp["norm2"], eps)
    if kind == "dense":
        return x + _in_blocks(lambda g: swiglu(g, lp["mlp"], prec), g,
                              TOKEN_BLOCK), None, 0.0
    y, pairs, balance = moe(g, lp["moe"], bias, cfg, prec)
    return x + y, pairs, balance


def head_logits(x, norm_f, head, cfg: dict, prec: str):
    return P.matmul(rms(x, norm_f, cfg["rms_norm_eps"]), head.T, prec)


def head_loss(x, norm_f, head, targets, cfg: dict, prec: str):
    """Mean next-token cross-entropy of one row from its last hidden
    states, a block of tokens at a time."""
    def picked(xt):
        x, t = xt
        logp = jax.nn.log_softmax(head_logits(x, norm_f, head, cfg, prec))
        return jnp.take_along_axis(logp, t[:, None], -1)[:, 0]
    n = x.shape[0]
    blk = _divisor(n, TOKEN_BLOCK)
    return -jnp.mean(jax.lax.map(jax.checkpoint(picked), (
        x.reshape(n // blk, blk, -1), targets.reshape(n // blk, blk))))


def _bias_rows(cfg: dict, biases):
    """A layer's bias (``None`` for a dense layer), in the layers' order."""
    rows = iter(zero_biases(cfg) if biases is None else biases)
    return [next(rows) if kind == "experts" else None
            for kind in ffn_kinds(cfg)]


def logits(params, tokens, cfg: dict, prec: str = "float32", biases=None):
    """``[T, vocab]`` for one sequence ``tokens [T]``."""
    x = params["embed"][tokens]
    for i, (kind, bias) in enumerate(zip(ffn_kinds(cfg),
                                         _bias_rows(cfg, biases))):
        x, _, _ = block(x, params[f"layer_{i}"], bias, kind, cfg, prec)
    return head_logits(x, params["norm_f"], params["head"], cfg, prec)


@functools.lru_cache(maxsize=None)
def _programs(cfg_json: str, prec: str) -> dict:
    """The jitted pieces for one configuration and precision, made once
    a process (a closure made anew would compile anew)."""
    cfg = json.loads(cfg_json)
    out = {}
    for kind in set(ffn_kinds(cfg)):
        def fwd(lp, bias, x, _kind=kind):
            return block(x, lp, bias, _kind, cfg, prec)[:2]

        def bwd(lp, bias, x, dx, daux, _kind=kind):
            """The layer's backward from its input: its output's
            cotangent ``dx`` and the cotangent ``daux`` of its
            load-balancing term."""
            def f(lp, x):
                y, _, aux = block(x, lp, bias, _kind, cfg, prec)
                return y, jnp.float32(aux)
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


def batch_loss_and_grad(params, batch, cfg: dict, prec: str = "float32",
                        biases=None):
    """``(loss, gradient, pairs an expert [expert layers, E])`` of
    ``batch [B, T + 1]``: the mean over the rows of a row's mean
    next-token cross-entropy plus ``aux_loss_alpha`` times its layers'
    load-balancing terms. A row's terms are its own (``seq_aux``), so a
    row goes forward, keeping each layer's input, and back, alone."""
    prog = _programs(json.dumps(cfg, sort_keys=True), prec)
    kinds, coef = ffn_kinds(cfg), jnp.float32(cfg["aux_loss_alpha"])
    bias = _bias_rows(cfg, biases)
    layers = [params[f"layer_{i}"] for i in range(len(kinds))]
    n, loss, grad, pairs = batch.shape[0], 0.0, {}, 0

    def add(name, g):       # a leaf group at a time: no second whole tree
        grad[name] = prog["add"](grad[name], g) if name in grad else g
    for row in batch:
        xs, counts = [params["embed"][row[:-1]]], []
        for lp, b, kind in zip(layers, bias, kinds):
            x, c = prog[kind][0](lp, b, xs[-1])
            xs.append(x)
            counts += [c] if kind == "experts" else []
        pairs = pairs + jnp.stack(counts)
        xent, (dx, d_norm, d_head) = prog["head"](
            xs.pop(), params["norm_f"], params["head"], row[1:])
        add("norm_f", d_norm)
        add("head", d_head)
        for i in reversed(range(len(kinds))):
            g, dx, aux = prog[kinds[i]][1](layers[i], bias[i], xs.pop(), dx,
                                           coef)
            add(f"layer_{i}", g)
            xent = xent + coef * aux
        add("embed", prog["embed"](params["embed"], row[:-1], dx))
        loss = loss + xent
    return loss / n, prog["scale"](grad, jnp.float32(1.0 / n)), pairs


def moved_biases(biases, pairs, rate: float):
    """``b_e += rate * sign(mean(c) - c_e)``, a layer at a time."""
    return biases + rate * jnp.sign(
        jnp.mean(pairs.astype(jnp.float32), -1, keepdims=True) - pairs)


def train_steps(params, batches, cfg: dict, prec: str = "float32", *,
                lr: float):
    """Follow the first ``len(batches)`` optimizer steps, as
    ``reference/gpt2.py`` does, the routers' biases moving after each:
    each step's loss, the per-leaf norm of the first gradient, the
    per-leaf norm of the parameters' change, and as ``vectors`` the first
    step's pairs an expert, a vector an expert layer. ``params`` may
    lie on the host (numpy): the steps then run on a copy on the device
    and the start is brought there only for the change's norms, so that
    the gradient has parameters, ``m`` and ``v`` beside it and nothing
    else (568M parameters: 9.1 GB of the chip's 16.9 and not 11.4)."""
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


_norms = jax.jit(gpt2.leaf_norms)


@functools.lru_cache(maxsize=None)
def _adam(lr: float):
    return jax.jit(functools.partial(gpt2.adam, lr=lr, **ADAM),
                   donate_argnums=(0, 2, 3))
