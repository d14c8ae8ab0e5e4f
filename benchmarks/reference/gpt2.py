"""GPT-2 in plain ``jax.numpy``: float32, no kernel, no cache, no batch.

Radford et al. 2019 as Cerebras-GPT (arXiv:2304.03208) uses it: learned
positions, pre-LayerNorm blocks with biases, a GELU MLP, causal softmax
attention, the output head tied to the token embedding. One departure
from the source is possible and follows the program: the MLP's GELU is
the tanh form (GPT-2's ``gelu_new``); see the configuration file.

Imports nothing of ``apex_tpu``. One sequence at a time: ``tokens`` is
``[T]``. Parameters are the tree ``benchmarks.weights.gpt2_specs``
describes, as float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import precision as P

ADAM = dict(beta1=0.9, beta2=0.999, eps=1e-8)     # FusedAdam's defaults


def layer_norm(x, p, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["g"] + p["b"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def embed(tok_emb, pos_emb, tokens):
    return tok_emb[tokens] + pos_emb[:tokens.shape[0]]


def block(x, lp, n_head: int, prec: str):
    t, e = x.shape
    hd = e // n_head
    h = layer_norm(x, lp["ln1"])
    qkv = P.matmul(h, lp["attn"]["in_proj"], prec) + lp["attn"]["in_proj_bias"]
    q, k, v = (a.reshape(t, n_head, hd) for a in jnp.split(qkv, 3, -1))
    s = P.einsum("thd,shd->hts", q, k, prec) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    a = P.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v, prec)
    x = x + P.matmul(a.reshape(t, e), lp["attn"]["out_proj"], prec) \
        + lp["attn"]["out_proj_bias"]
    h = layer_norm(x, lp["ln2"])
    h = gelu_tanh(P.matmul(h, lp["mlp"]["w1"], prec) + lp["mlp"]["b1"])
    return x + P.matmul(h, lp["mlp"]["w2"], prec) + lp["mlp"]["b2"]


def head(x, ln_f, tok_emb, prec: str):
    return P.matmul(layer_norm(x, ln_f), tok_emb.T, prec)


def logits(params, tokens, n_head: int, prec: str = "float32",
           remat: bool = False):
    """``[T, vocab]`` for one sequence ``tokens [T]``."""
    x = embed(params["tok_emb"], params["pos_emb"], tokens)
    blk = jax.checkpoint(block, static_argnums=(2, 3)) if remat else block
    i = 0
    while f"layer_{i}" in params:
        x = blk(x, params[f"layer_{i}"], n_head, prec)
        i += 1
    return head(x, params["ln_f"], params["tok_emb"], prec)


def row_loss(params, row, n_head: int, prec: str = "float32"):
    """Mean next-token cross-entropy of one row of ``T + 1`` tokens."""
    lg = logits(params, row[:-1], n_head, prec, remat=True)
    logp = jax.nn.log_softmax(lg, -1)
    return -jnp.mean(jnp.take_along_axis(logp, row[1:, None], -1))


def batch_loss_and_grad(params, batch, n_head: int, prec: str = "float32"):
    """Mean loss over the rows of ``batch [B, T + 1]`` and its gradient,
    one row at a time (rows are equally long, so the mean of row losses
    is the batch loss)."""
    def one(carry, row):
        loss, grad = jax.value_and_grad(row_loss)(params, row, n_head, prec)
        return (carry[0] + loss,
                jax.tree.map(jnp.add, carry[1], grad)), None
    zero = (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, params))
    (loss, grad), _ = jax.lax.scan(one, zero, batch)
    n = batch.shape[0]
    return loss / n, jax.tree.map(lambda g: g / n, grad)


def adam(params, grad, m, v, step, *, lr, beta1, beta2, eps):
    """AdamW with no decay, bias-corrected; ``step`` counts from 1."""
    m = jax.tree.map(lambda m, g: beta1 * m + (1 - beta1) * g, m, grad)
    v = jax.tree.map(lambda v, g: beta2 * v + (1 - beta2) * g * g, v, grad)
    bc1, bc2 = 1 - beta1 ** step, 1 - beta2 ** step
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps),
        params, m, v)
    return params, m, v


def leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(x.astype(jnp.float32) ** 2)),
                        tree)


def train_steps(params, batches, n_head: int, prec: str = "float32", *,
                lr: float):
    """Follow the first ``len(batches)`` optimizer steps. Returns each
    step's loss, the per-leaf norm of the first gradient and the per-leaf
    norm of the parameters' change after the last step."""
    @jax.jit
    def step(params, m, v, t, batch):
        loss, grad = batch_loss_and_grad(params, batch, n_head, prec)
        params, m, v = adam(params, grad, m, v, t, lr=lr, **ADAM)
        return params, m, v, loss, leaf_norms(grad)

    start = params
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for i, batch in enumerate(batches):
        params, m, v, loss, gn = step(params, m, v, jnp.float32(i + 1),
                                      batch)
        losses.append(float(loss))
        if i == 0:
            grad_norms = jax.tree.map(float, gn)
    delta = jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))(params, start)
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": jax.tree.map(float, delta)}


# -- a served model ---------------------------------------------------------

def served_gaps(cfg: dict, specs: dict, key, served: list, *, pad_to: int,
                control: str | None = None) -> dict:
    """For each ``(prompt, tokens)`` of ``served``, one forward pass over
    the prompt and the served tokens; returns the widest gap by which a
    served token's logit lies below the best logit at its position.

    The weights are made a layer at a time from ``key`` (the bfloat16
    values the program serves, upcast), so the whole model is never
    resident in float32. Sequences are padded to ``pad_to`` at the end,
    which a causal model does not see. With ``control`` (a lower
    precision), the pass is made in it first, and the gap of the token
    *it* puts first at each position is read from the float32 pass.
    """
    from benchmarks import weights as W
    n_head = cfg["n_head"]
    max_new = max(len(t) for _, t in served)
    seqs, lens = [], []
    for prompt, toks in served:
        seq = np.concatenate([prompt, toks[:-1]])
        lens.append((len(prompt), len(toks)))
        seqs.append(np.pad(seq, (0, pad_to - len(seq))))
    seqs = jnp.asarray(np.stack(seqs), jnp.int32)

    def layer(name, layer_specs):
        # the key is an argument: closed over, it would be a constant of
        # the executable and every seed would compile every layer anew
        return jax.jit(lambda k: jax.tree.map(
            lambda x: x.astype(jnp.float32),
            W.build(layer_specs, k, jnp.bfloat16, prefix=name)))(key)

    top = {k: specs[k] for k in ("tok_emb", "pos_emb", "ln_f")}
    top = {k: layer(f"/{k}", v) for k, v in top.items()}

    def logits_at(prec):
        """[n_seq, max_new, vocab] at the positions that predict the
        served tokens."""
        # the tables are arguments: a closed-over 400 MB array would be
        # a constant of the executable, folded at compile time and too
        # large for the compilation cache
        x = jax.jit(lambda te, pe, seqs: jax.vmap(
            lambda s: embed(te, pe, s))(seqs))(
                top["tok_emb"], top["pos_emb"], seqs)
        blk = jax.jit(lambda x, lp: jax.lax.map(
            lambda row: block(row, lp, n_head, prec), x))
        for i in range(cfg["n_layer"]):
            x = blk(x, layer(f"/layer_{i}", specs[f"layer_{i}"]))
        starts = jnp.asarray([p - 1 for p, _ in lens], jnp.int32)
        take = jax.jit(jax.vmap(lambda row, s: jax.lax.dynamic_slice_in_dim(
            row, s, max_new, 0)))
        hid = take(jnp.pad(x, ((0, 0), (0, max_new), (0, 0))), starts)
        return jax.jit(lambda h, ln, te: jax.lax.map(
            lambda r: head(r, ln, te, prec), h))(
                hid, top["ln_f"], top["tok_emb"])

    first = None
    if control:
        first = np.asarray(jnp.argmax(logits_at(control), -1))
    lg = logits_at("float32")
    best = np.asarray(jnp.max(lg, -1))
    out = {"served": 0.0, "tokens": 0}
    if control:
        out["control"] = 0.0
    for i, ((_, n), (_, toks)) in enumerate(zip(lens, served)):
        got = np.asarray(lg[i, jnp.arange(n), jnp.asarray(toks)])
        out["served"] = max(out["served"], float((best[i, :n] - got).max()))
        out["tokens"] += n
        if control:
            c = np.asarray(lg[i, jnp.arange(n), jnp.asarray(first[i, :n])])
            out["control"] = max(out["control"],
                                 float((best[i, :n] - c).max()))
    return out
