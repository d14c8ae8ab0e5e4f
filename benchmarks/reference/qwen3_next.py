"""Qwen3-Next's block in plain ``jax.numpy``: float32, no kernel, no
chunking of the recurrence, no batch.

From the model's public ``config.json`` (``model_type`` ``qwen3_next``;
https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct): three Gated
DeltaNet linear-attention layers to one gated softmax-attention layer,
every layer's FFN a mixture of ``num_experts x expert_chips`` experts,
``num_experts_per_tok`` a token, with one gated shared expert;
zero-centred RMSNorm, no bias anywhere, partial rotary positions, an
untied head. The delta rule runs **token by token** (a ``lax.scan``,
checkpointed in segments so that its gradient at 8192 tokens fits);
attention a block of queries at a time; the expert layer computes the
part of the result that the experts held here give
(``[expert_chip x num_experts, (expert_chip + 1) x num_experts)`` of the
router's width), each of them densely over every token, plus the shared
expert; what absent experts would add is left out, as in the program.
The departures from the source are the configuration file's ``assumed``.

Imports nothing of ``apex_tpu``. One sequence at a time: ``tokens`` is
``[T]``. Parameters are the tree ``benchmarks.weights_qwen3_next.specs``
describes, as float32. ``cfg`` is the configuration file.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from benchmarks.reference import gpt2, precision as P

ADAM = gpt2.ADAM                        # FusedAdam's defaults
SEGMENT = 128                           # tokens a checkpointed scan segment
QUERY_BLOCK = 512


def norm0(x, w, eps):
    """The model's RMSNorm: zero-centred weight."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def held(cfg: dict) -> tuple:
    lo = cfg.get("expert_chip", 0) * cfg["num_experts"]
    return lo, lo + cfg["num_experts"]


def layer_kinds(cfg: dict) -> list:
    every = cfg["full_attention_interval"]
    return ["full" if (i + 1) % every == 0 else "linear"
            for i in range(cfg["num_hidden_layers"])]


def _divisor(n: int, most: int) -> int:
    return next(s for s in range(min(most, n), 0, -1) if n % s == 0)


# -- Gated DeltaNet ----------------------------------------------------------

def delta_rule(q, k, v, g, beta, prec: str):
    """``q, k [T, H, dk]``, ``v [T, H, dv]``, ``g, beta [T, H]`` ->
    ``o [T, H, dv]``: for each token in order ``S = exp(g) S``; ``d =
    beta (v - S^T k)``; ``S = S + k d^T``; ``o = S^T q``."""
    t, h, dk = q.shape

    def token(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[:, None, None]
        d = b_t[:, None] * (v_t - P.einsum("hkv,hk->hv", s, k_t, prec))
        s = s + k_t[:, :, None] * d[:, None, :]
        return s, P.einsum("hkv,hk->hv", s, q_t, prec)

    seg = _divisor(t, SEGMENT)

    @jax.checkpoint
    def segment(s, xs):
        return jax.lax.scan(token, s, xs)
    xs = tuple(a.reshape(t // seg, seg, *a.shape[1:])
               for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(segment, jnp.zeros((h, dk, v.shape[-1])), xs)
    return o.reshape(t, h, v.shape[-1])


def causal_conv(x, w):
    """Depthwise: ``y[t, c] = sum_j w[j, c] x[t - (K - 1) + j, c]``."""
    taps, t = w.shape[0], x.shape[0]
    xp = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return sum(xp[j:j + t] * w[j] for j in range(taps))


def linear_mixer(h, p, cfg: dict, prec: str):
    t = h.shape[0]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    kd, vd = hk * dk, hv * dv
    qkvz = P.matmul(h, p["w_qkvz"], prec)
    ba = P.matmul(h, p["w_ba"], prec)
    z = qkvz[:, 2 * kd + vd:].reshape(t, hv, dv)
    qkv = jax.nn.silu(causal_conv(qkvz[:, :2 * kd + vd], p["conv"]))
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, hv:] + p["dt_bias"])

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    q = unit(qkv[:, :kd].reshape(t, hk, dk)) * dk ** -0.5
    k = unit(qkv[:, kd:2 * kd].reshape(t, hk, dk))
    q, k = (jnp.repeat(a, hv // hk, axis=1) for a in (q, k))
    v = qkv[:, 2 * kd:].reshape(t, hv, dv)
    o = delta_rule(q, k, v, g, beta, prec)
    o = p["norm"] * o * jax.lax.rsqrt(
        jnp.mean(o * o, -1, keepdims=True) + cfg["rms_norm_eps"])
    return P.matmul((o * jax.nn.silu(z)).reshape(t, vd), p["w_out"], prec)


# -- gated attention ---------------------------------------------------------

def rotary(x, cfg: dict):
    """``x [T, H, D]``: the first ``partial_rotary_factor`` of ``D``
    turned, half-split pairing."""
    rot = int(cfg["head_dim"] * cfg["partial_rotary_factor"])
    half = rot // 2
    freq = cfg["rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], -1)


def full_mixer(h, p, cfg: dict, prec: str):
    t = h.shape[0]
    nh, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    qg = P.matmul(h, p["w_q"], prec).reshape(t, nh, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = P.matmul(h, p["w_k"], prec).reshape(t, kv, hd)
    v = P.matmul(h, p["w_v"], prec).reshape(t, kv, hd)
    q = rotary(norm0(q, p["q_norm"], eps), cfg).reshape(t, kv, nh // kv, hd)
    k = rotary(norm0(k, p["k_norm"], eps), cfg)
    blk = _divisor(t, QUERY_BLOCK)

    @jax.checkpoint
    def block(args):
        q_b, start = args                       # [blk, kv, grp, hd]
        s = P.einsum("tkgd,skd->kgts", q_b, k, prec) * hd ** -0.5
        ok = (start + jnp.arange(blk))[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(ok, s, -jnp.inf)
        return P.einsum("kgts,skd->tkgd", jax.nn.softmax(s, -1), v, prec)
    a = jax.lax.map(block, (q.reshape(t // blk, blk, kv, nh // kv, hd),
                            jnp.arange(0, t, blk)))
    a = a.reshape(t, nh, hd) * jax.nn.sigmoid(gate)
    return P.matmul(a.reshape(t, nh * hd), p["w_o"], prec)


# -- the expert layer's share ------------------------------------------------

def swiglu(h, w_gate, w_up, w_down, prec: str):
    return P.matmul(jax.nn.silu(P.matmul(h, w_gate, prec))
                    * P.matmul(h, w_up, prec), w_down, prec)


def route(h, p, cfg: dict, prec: str):
    """``(weights [T, K], experts [T, K], probs [T, E])`` over the
    router's whole width."""
    probs = jax.nn.softmax(P.matmul(h, p["router"], prec), -1)
    w, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    return w / jnp.sum(w, -1, keepdims=True), idx, probs


def moe(h, p, cfg: dict, prec: str):
    """The held experts' part plus the gated shared expert; also the
    router's probabilities and choices, for the load-balancing term.
    Every held expert is computed over every token and weighted by the
    token's weight for it, zero where it was not among the token's
    ``num_experts_per_tok``. A share (``expert_chips`` > 1) holds the
    tokens' weights constant in the backward (``assumed``,
    ``router_gradient``): the router learns from the load-balancing term."""
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
    sp = p["shared"]
    y = y + jax.nn.sigmoid(P.matmul(h, sp["gate"], prec)) * swiglu(
        h, sp["w_gate"], sp["w_up"], sp["w_down"], prec)
    return y, idx, probs


# -- the model, a layer at a time --------------------------------------------
#
# A row goes through the layers one program at a time, and back through
# them the same way (each layer's backward recomputes its forward from
# the layer's input): the three linear layers share one compiled program,
# and nothing larger than a layer is ever compiled or resident.

def block(x, lp, kind: str, cfg: dict, prec: str):
    """One layer: ``(x out, experts chosen [T, K], mean router
    probabilities [E])``."""
    h = norm0(x, lp["norm1"], cfg["rms_norm_eps"])
    x = x + (linear_mixer(h, lp["linear"], cfg, prec) if kind == "linear"
             else full_mixer(h, lp["attn"], cfg, prec))
    y, idx, probs = moe(norm0(x, lp["norm2"], cfg["rms_norm_eps"]),
                        lp["moe"], cfg, prec)
    return x + y, idx, jnp.mean(probs, 0)


def head_logits(x, norm_f, head, cfg: dict, prec: str):
    return P.matmul(norm0(x, norm_f, cfg["rms_norm_eps"]), head.T, prec)


def head_loss(x, norm_f, head, targets, cfg: dict, prec: str):
    """Mean next-token cross-entropy of one row from its last hidden
    states."""
    logp = jax.nn.log_softmax(head_logits(x, norm_f, head, cfg, prec), -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], -1))


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
            width = probs.shape[0]
            return x, jnp.sum(idx[..., None] == jnp.arange(width), (0, 1))

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
    return out


def batch_loss_and_grad(params, batch, cfg: dict, prec: str = "float32"):
    """The loss of ``batch [B, T + 1]`` and its gradient: mean next-token
    cross-entropy plus ``router_aux_loss_coef`` times the load-balancing terms, ``E
    sum_e f_e P_e`` a layer with ``f_e`` the share of the **batch's**
    tokens that chose expert ``e`` (a count: no gradient) and ``P_e`` the
    batch's mean router probability. So every row goes forward first,
    keeping each layer's input, and then back; rows are equally long, so
    the mean of the rows' parts is the batch's."""
    prog = _programs(json.dumps(cfg, sort_keys=True), prec)
    kinds, coef = layer_kinds(cfg), cfg["router_aux_loss_coef"]
    n, t = batch.shape[0], batch.shape[1] - 1
    layer_inputs, counts = [], [0] * len(kinds)
    for row in batch:
        xs = [params["embed"][row[:-1]]]
        for i, kind in enumerate(kinds):
            x, c = prog[kind][0](params[f"layer_{i}"], xs[-1])
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
            g, dx, aux = prog[kinds[i]][1](
                params[f"layer_{i}"], xs.pop(), share[i], dx,
                jnp.float32(coef))
            add(f"layer_{i}", g)
            xent = xent + coef * aux
        add("embed", prog["embed"](params["embed"], row[:-1], dx))
        loss = loss + xent
    return loss / n, jax.tree.map(lambda x: x / n, grad)


def train_steps(params, batches, cfg: dict, prec: str = "float32", *,
                lr: float):
    """Follow the first ``len(batches)`` optimizer steps, as
    ``reference/gpt2.py`` does: each step's loss, the per-leaf norm of
    the first gradient, the per-leaf norm of the parameters' change."""
    update = _adam(lr)
    start = params
    params = jax.tree.map(jnp.copy, params)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for i, batch in enumerate(batches):
        loss, grad = batch_loss_and_grad(params, batch, cfg, prec)
        if i == 0:
            grad_norms = jax.tree.map(float, _norms(grad))
        params, m, v = update(params, grad, m, v, jnp.float32(i + 1))
        losses.append(float(loss))
    delta = _norms(jax.tree.map(jnp.subtract, params, start))
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": jax.tree.map(float, delta)}


_norms = jax.jit(gpt2.leaf_norms)


@functools.lru_cache(maxsize=None)
def _adam(lr: float):
    return jax.jit(functools.partial(gpt2.adam, lr=lr, **ADAM),
                   donate_argnums=(0, 2, 3))
