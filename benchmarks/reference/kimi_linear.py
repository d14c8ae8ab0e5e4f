"""Kimi-Linear-48B-A3B's block in plain ``jax.numpy``: float32, every
product at the highest precision (``reference/precision.py``), no kernel,
no chunking of the delta rule, no scan over layers, no batch.

From the model's public ``config.json`` (``model_type`` ``kimi_linear``;
https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct; the
paper is arXiv:2510.26692): layers ``kda_layers`` (1-indexed) mix with
**Kimi Delta Attention**, layers ``full_attn_layers`` with **latent
attention without positions** (``mla_use_nope``: no rotary anywhere in
the model); a leading dense SwiGLU layer, then layers of
``num_experts x expert_chips`` SwiGLU experts, ``num_experts_per_token`` a
token, chosen by a sigmoid router on score + bias, one shared expert;
plain RMSNorm, no bias but the output gate's, an untied head. With ``H``
heads of ``d`` (``linear_attn_config``), for ``h = rms(x)``:

    q~ = silu(conv(h W_q)), k~ = silu(conv(h W_k)), v = silu(conv(h W_v))
    q  = unit(q~) d^-1/2,   k = unit(k~)           (L2 a head)
    g  = -exp(A_log[head]) softplus((h W_f1) W_f2 + dt_bias)   [T, H, d]
    beta = sigmoid(h W_b^T)                                    [T, H]
    for each token in order, a head's S [d, d] from zero:
        S = Diag(exp(g_t)) S;  u = beta_t (v_t - S^T k_t)
        S = S + k_t u^T;       o_t = S^T q_t
    x += [rms_head(o) * sigmoid((h W_g1) W_g2 + b_g)] W_o

The delta rule runs **token by token** (a ``lax.scan``, checkpointed in
segments so that its gradient at 8192 tokens fits). Latent attention is
``reference/kimi_vl.py``'s with its two rotations left out: the 64-wide
``kr`` stays, one unrotated key head that the heads share. The expert
layer, the dense layer, the head, the balance term, the biases' move and
the steps are that reference's own functions (under its names for this
config's keys: ``view``); the departures from the source are the
configuration file's ``assumed``.

Imports nothing of ``apex_tpu``. One sequence at a time: ``tokens`` is
``[T]``. Parameters are the tree ``benchmarks.weights_kimi_linear.specs``
describes, as float32; ``biases`` is ``[expert layers, E]``. ``cfg`` is
the configuration file.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import kimi_vl as V, precision as P, qwen3_next as Q
from benchmarks.weights_kimi_linear import layer_kinds

ADAM = V.ADAM                           # FusedAdam's defaults
SEGMENT = Q.SEGMENT                     # tokens a checkpointed scan segment

rms, swiglu, moved_biases, ffn_kinds = (V.rms, V.swiglu, V.moved_biases,
                                        V.ffn_kinds)


def view(cfg: dict) -> dict:
    """``cfg`` under the names ``reference/kimi_vl.py`` reads: the same
    numbers, this source's keys for them."""
    return {**cfg, "n_routed_experts": cfg["num_experts"],
            "num_experts_per_tok": cfg["num_experts_per_token"],
            "n_shared_experts": cfg["num_shared_experts"]}


def width(cfg: dict) -> int:
    return V.width(view(cfg))


def held(cfg: dict) -> tuple:
    return V.held(view(cfg))


def zero_biases(cfg: dict):
    return V.zero_biases(view(cfg))


# -- Kimi Delta Attention ----------------------------------------------------

def delta_rule(q, k, v, g, beta, prec: str):
    """``q, k, g [T, H, d]``, ``v [T, H, dv]``, ``beta [T, H]`` ->
    ``o [T, H, dv]``: for each token in order row ``c`` of ``S`` times
    ``exp(g[c])``; ``u = beta (v - S^T k)``; ``S = S + k u^T``; ``o = S^T
    q``."""
    t, h, dk = q.shape

    def token(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[:, :, None]
        u = b_t[:, None] * (v_t - P.einsum("hkv,hk->hv", s, k_t, prec))
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, P.einsum("hkv,hk->hv", s, q_t, prec)

    seg = Q._divisor(t, SEGMENT)

    @jax.checkpoint
    def segment(s, xs):
        return jax.lax.scan(token, s, xs)
    xs = tuple(a.reshape(t // seg, seg, *a.shape[1:])
               for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(segment, jnp.zeros((h, dk, v.shape[-1])), xs)
    return o.reshape(t, h, v.shape[-1])


def gate(h, p, cfg: dict, prec: str):
    """The log-decay a token, head and key channel ``[T, H, d]``."""
    lin = cfg["linear_attn_config"]
    a = P.matmul(P.matmul(h, p["w_f1"], prec), p["w_f2"], prec) + p["dt_bias"]
    return -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(a).reshape(
        h.shape[0], lin["num_heads"], lin["head_dim"])


def kda_mixer(h, p, cfg: dict, prec: str):
    t = h.shape[0]
    lin = cfg["linear_attn_config"]
    hh, d = lin["num_heads"], lin["head_dim"]
    q, k, v = (jax.nn.silu(Q.causal_conv(P.matmul(h, p["w_" + n], prec),
                                         p["conv_" + n])).reshape(t, hh, d)
               for n in "qkv")

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(P.matmul(h, p["w_b"].T, prec))
    o = delta_rule(unit(q) * d ** -0.5, unit(k), v, gate(h, p, cfg, prec),
                   beta, prec)
    o = p["norm"] * o * jax.lax.rsqrt(
        jnp.mean(o * o, -1, keepdims=True) + cfg["rms_norm_eps"])
    out = jax.nn.sigmoid(P.matmul(P.matmul(h, p["w_g1"], prec), p["w_g2"],
                                  prec) + p["b_g"])
    return P.matmul(o.reshape(t, hh * d) * out, p["w_out"], prec)


# -- latent attention without positions --------------------------------------

def latent_mixer(h, p, cfg: dict, prec: str):
    """``kimi_vl.latent_mixer`` less its two ``rotary`` calls."""
    t = h.shape[0]
    nh, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    q = P.matmul(h, p["w_q"], prec).reshape(t, nh, dn + dr)
    kva = P.matmul(h, p["w_kva"], prec)
    kv = P.matmul(rms(kva[:, :r], p["kv_norm"], cfg["rms_norm_eps"]),
                  p["w_kvb"], prec).reshape(t, nh, dn + dv)
    k_n, v, k_r = kv[..., :dn], kv[..., dn:], kva[:, r:]        # one head
    blk = Q._divisor(t, V.QUERY_BLOCK)

    @jax.checkpoint
    def block(args):
        q_b, start = args                       # [blk, H, dn + dr]
        s = (P.einsum("thd,shd->hts", q_b[..., :dn], k_n, prec)
             + P.einsum("thd,sd->hts", q_b[..., dn:], k_r, prec)) \
            * (dn + dr) ** -0.5
        ok = (start + jnp.arange(blk))[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(ok, s, -jnp.inf)
        return P.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v, prec)
    a = jax.lax.map(block, (q.reshape(t // blk, blk, nh, dn + dr),
                            jnp.arange(0, t, blk)))
    return P.matmul(a.reshape(t, nh * dv), p["w_o"], prec)


# -- the model, a layer at a time --------------------------------------------

def block(x, lp, bias, kind: tuple, cfg: dict, prec: str):
    """One layer of ``kind`` (mixer, FFN): ``(x out, pairs an expert [E],
    the load-balancing term)``; a dense layer has no pairs and no term."""
    eps = cfg["rms_norm_eps"]
    mixer, ffn = kind
    h = rms(x, lp["norm1"], eps)
    x = x + (kda_mixer(h, lp["kda"], cfg, prec) if mixer == "kda"
             else latent_mixer(h, lp["latent"], cfg, prec))
    g = rms(x, lp["norm2"], eps)
    if ffn == "dense":
        return x + V._in_blocks(lambda g: swiglu(g, lp["mlp"], prec), g,
                                V.TOKEN_BLOCK), None, 0.0
    y, pairs, balance = V.moe(g, lp["moe"], bias, view(cfg), prec)
    return x + y, pairs, balance


def kinds(cfg: dict) -> list:
    return list(zip(layer_kinds(cfg), ffn_kinds(cfg)))


def _bias_rows(cfg: dict, biases):
    return V._bias_rows(view(cfg), biases)


def logits(params, tokens, cfg: dict, prec: str = "float32", biases=None):
    """``[T, vocab]`` for one sequence ``tokens [T]``."""
    x = params["embed"][tokens]
    for i, (kind, bias) in enumerate(zip(kinds(cfg),
                                         _bias_rows(cfg, biases))):
        x, _, _ = block(x, params[f"layer_{i}"], bias, kind, cfg, prec)
    return V.head_logits(x, params["norm_f"], params["head"], cfg, prec)


@functools.lru_cache(maxsize=None)
def _programs(cfg_json: str, prec: str) -> dict:
    """The jitted pieces for one configuration and precision, made once
    a process: a forward and a backward for each kind of layer (mixer,
    FFN), and ``kimi_vl``'s own head, embedding and sums."""
    cfg = json.loads(cfg_json)
    out = dict(V._programs(json.dumps(view(cfg), sort_keys=True), prec))
    for kind in set(kinds(cfg)):
        def fwd(lp, bias, x, _kind=kind):
            return block(x, lp, bias, _kind, cfg, prec)[:2]

        def bwd(lp, bias, x, dx, daux, _kind=kind):
            def f(lp, x):
                y, _, aux = block(x, lp, bias, _kind, cfg, prec)
                return y, jnp.float32(aux)
            (_, aux), vjp = jax.vjp(f, lp, x)
            return vjp((dx, daux)) + (aux,)
        out[kind] = jax.jit(fwd), jax.jit(bwd)
    return out


def batch_loss_and_grad(params, batch, cfg: dict, prec: str = "float32",
                        biases=None):
    """``(loss, gradient, pairs an expert [expert layers, E])`` of
    ``batch [B, T + 1]``, as ``kimi_vl.batch_loss_and_grad``: a row goes
    forward, keeping each layer's input, and back, alone."""
    prog = _programs(json.dumps(cfg, sort_keys=True), prec)
    layer, coef = kinds(cfg), jnp.float32(cfg["aux_loss_alpha"])
    bias = _bias_rows(cfg, biases)
    layers = [params[f"layer_{i}"] for i in range(len(layer))]
    n, loss, grad, pairs = batch.shape[0], 0.0, {}, 0

    def add(name, g):       # a leaf group at a time: no second whole tree
        grad[name] = prog["add"](grad[name], g) if name in grad else g
    for row in batch:
        xs, counts = [params["embed"][row[:-1]]], []
        for lp, b, kind in zip(layers, bias, layer):
            x, c = prog[kind][0](lp, b, xs[-1])
            xs.append(x)
            counts += [c] if kind[1] == "experts" else []
        pairs = pairs + jnp.stack(counts)
        xent, (dx, d_norm, d_head) = prog["head"](
            xs.pop(), params["norm_f"], params["head"], row[1:])
        add("norm_f", d_norm)
        add("head", d_head)
        for i in reversed(range(len(layer))):
            g, dx, aux = prog[layer[i]][1](layers[i], bias[i], xs.pop(), dx,
                                           coef)
            add(f"layer_{i}", g)
            xent = xent + coef * aux
        add("embed", prog["embed"](params["embed"], row[:-1], dx))
        loss = loss + xent
    return loss / n, prog["scale"](grad, jnp.float32(1.0 / n)), pairs


def decay_nats(params, tokens, cfg: dict, chunk: int) -> float:
    """The first Kimi Delta Attention layer's largest decay of a channel
    inside a chunk of ``chunk`` tokens, for one row ``tokens [T]`` whose
    first layer it is: ``-min`` over chunks, heads and channels of the
    chunk's summed ``g``. What the program's counter reads there."""
    lp = params["layer_0"]
    h = rms(params["embed"][tokens], lp["norm1"], cfg["rms_norm_eps"])
    g = gate(h, lp["kda"], cfg, "float32")
    t = g.shape[0] - g.shape[0] % chunk
    return float(-jnp.min(jnp.sum(
        g[:t].reshape(t // chunk, chunk, *g.shape[1:]), 1)))


def train_steps(params, batches, cfg: dict, prec: str = "float32", *,
                lr: float):
    """Follow the first ``len(batches)`` optimizer steps as
    ``kimi_vl.train_steps`` does (weights may lie on the host), the
    routers' biases moving after each. Adam's two moments rest on the
    host between updates: at 602M parameters they are 4.8 GB, and with
    them beside the parameters and the gradient a Kimi Delta Attention
    layer's backward (3.5 GB of its own) did not load on the chip."""
    update = V._adam(lr)
    start = params
    params = jax.tree.map(jnp.array, params)    # a copy: the steps donate
    m = v = None
    biases = zero_biases(cfg)
    losses, grad_norms, vectors = [], None, None
    for i, batch in enumerate(batches):
        loss, grad, pairs = batch_loss_and_grad(params, batch, cfg, prec,
                                                biases)
        if i == 0:
            grad_norms = jax.tree.map(float, V._norms(grad))
            vectors = list(np.asarray(pairs, np.float64))
        if m is None:
            m, v = (jax.tree.map(jnp.zeros_like, params) for _ in range(2))
        params, m, v = update(params, grad, *jax.device_put((m, v)),
                              jnp.float32(i + 1))
        del grad
        m, v = jax.device_get((m, v))
        biases = moved_biases(biases, pairs, cfg["bias_update_speed"])
        losses.append(float(loss))
    delta = V._norms(jax.tree.map(jnp.subtract, params, start))
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": jax.tree.map(float, delta), "vectors": vectors,
            "router_biases": np.asarray(biases)}
