"""A mixture-of-experts decoder whose layers differ: the block of today's
Gated DeltaNet hybrids, of the latent-attention expert models and of the
short-convolution hybrids, with the layer pattern as data.

    x  = embed[tokens]
    x += mixer_i(norm(x))         mixer_i by ``layer_types[i]``:
                                  "linear" (Gated DeltaNet), "full"
                                  (grouped-query softmax attention, gated
                                  or not), "window" (the same over a
                                  sliding window), "sparse" (the same
                                  over a learned per-query key set),
                                  "latent" (latent attention, MLA),
                                  "conv" (the double-gated short
                                  convolution) or "kda" (Kimi Delta
                                  Attention)
    x += ffn_i(norm(x))           ffn_i by ``ffn_types[i]``: "experts" (a
                                  chip's share of a many-expert layer) or
                                  "dense" (one SwiGLU of ``dense_ffn``)
    loss = xent(norm(x) @ head^T) + aux_coef * sum of the routers'
           load-balancing terms + index_coef * sum of the "sparse"
           layers' indexer losses
           next-token (row t predicts token t + 1, every row weighs 1)
           or, with ``block_diffusion``, block diffusion's (below)

``norm(x; w) = x * rsqrt(mean(x^2) + eps) * (1 + w)`` is the zero-centred
RMSNorm (weight zero at init) or, with ``zero_centred_norm=False``, the
plain one (``* w``, one at init); no matrix has a bias; the head is a
matrix of its own or, with ``tied_head``, the embedding (its gradient is
then the gather's scatter-add plus the fused head's). Where
:class:`~apex_tpu.models.TransformerLM` is one GPT-2 block repeated, this
model's layers differ, so it is a class of its own and shares with the
dense LM what lies under it: the flash-attention kernels, the fused head
(``weighted_linear_cross_entropy``), recomputation (``jax.checkpoint`` a block) and
the step builder (``apex_tpu.train_step.build_step``).

The **Gated DeltaNet mixer** (``linear_k_heads`` key heads and
``linear_v_heads`` value heads of ``linear_k_dim`` / ``linear_v_dim``):
``[q, k, v, z] = h W_qkvz``, ``[b, a] = h W_ba``; ``q, k, v`` go through
a causal depthwise convolution (``conv_kernel`` taps, no bias) and SiLU;
``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)`` in
float32; ``q`` and ``k`` are L2-normalised a head (``q`` scaled by
``dk^-0.5``) and repeated to the value heads; the recurrence is
``ops.gated_delta_rule``; its output is RMS-normalised a head, gated by
``silu(z)`` and projected out.

The **attention mixer** (``num_heads`` query heads over
``num_kv_heads`` key/value heads of ``head_dim``): ``W_q`` gives each
head its query and, with ``attn_gate``, an output gate; ``q`` and ``k``
are ``norm0``-ed a head; rotary positions turn the first ``rotary_dim``
of a head (half-split pairing; ``rotary_dim = head_dim`` turns it whole);
causal attention runs through ``flash_attention`` with K and V
**broadcast to the query heads in front of the kernel** (the kernels,
which other programs share, stay as they are, and pad a head narrower
than a lane tile to 128; the broadcast's transpose sums a group's dK and
dV); the result, times ``sigmoid(gate)`` where there is a gate, is
projected out. The **window mixer** (``"window"``) is that mixer, leaf for
leaf, with query ``i`` seeing key ``j`` iff ``0 <= i - j < window``:
``flash_attention(window=)``, whose grids are cut to the band, under a
scope of its own. **Rotary tables go by layer kind**: a window layer turns
its heads with the plain table at ``rope_theta``, a full layer with
``rope_yarn``'s where that is given: YaRN's frequencies (interpolated by
``factor`` below the ramp over ``original positions``, extrapolated above
it, blended between ``beta_fast`` and ``beta_slow`` rotations) and its
``attention_factor`` on ``cos`` and ``sin``.

**Block diffusion** (``block_diffusion = B`` > 0, over "full" layers
without a gate; SDAR's training form): a sequence of ``L`` tokens runs as
``2 L`` rows, its noised copy (a masked position holds the mask token, id
``vocab_size - 1``) followed by its clean one, both at rotary positions
``0 .. L - 1``; the attention mixer's mask is
``flash_attention(block_diffusion=(B, L))``'s under ``causal=False`` (a
noised block sees itself and the clean blocks before it, a clean row the
clean blocks up to its own, no clean row a noised one), under a scope of
its own; the head runs over the noised half alone and a masked position
predicts its own token (no shift) with weight ``1 / p``, ``p`` the
probability its sequence was masked with
(:meth:`HybridLM.diffusion_loss_with_counters`; ``loss_with_counters``
takes the triple ``(tokens, masked, p)`` in the tokens' place). Such a
mixer also hands out what layer 0's heads made for the first
``PROBE_ROWS`` noised rows (the counter ``diffusion_probe``): every masked
row enters layer 0 as one embedding row and the routers read them alike,
so a forward reading made before any router is what a comparison with a
reference can rest on.

The **sparse mixer** (``"sparse"``: DeepSeek-V3.2's lightning indexer
over the ungated attention mixer, leaf for leaf, as Keye-VL-2.0 has it):
beside ``attn`` a layer holds ``index = {w_q [d, index_heads x index_dim],
w_k [index_dim, d], w_w [index_heads, d], k_norm {w, b}}`` (the two narrow
projections lie ``[out, in]``: under XLA ``ops.flat.unflatten``'s slice
and reshape of a ``[d, 16]`` leaf becomes a view of the whole flat master
in rows of 16, which the TPU's tiling pads eightfold in HBM). On ``hbar = stop_gradient(norm(x))``: ``qI = rot(hbar w_q)`` a head,
``kI = rot(LayerNorm(hbar w_k^T))`` (one head), ``w = hbar w_w^T *
(index_heads
index_dim)^-1/2`` in float32, ``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
kI[s])``; query ``t`` attends to the ``min(t + 1, index_topk)`` keys ``s <=
t`` of largest ``I[t, s]``, exactly that many, one set for all heads
(``ops.sparse_index.select_keys`` into ``flash_attention(select=)``), and
the indexer learns from ``L_I = mean_t KL(p[t] || softmax_{S_t} I[t])``
with ``p`` the detached head-mean of the attention's probabilities
(``ops.sparse_index.index_loss``). The two ``stop_gradient``s part the
gradients: ``index`` learns from ``L_I`` alone, every other leaf from the
rest of the loss alone. It runs under two sibling scopes,
``sparse_attention`` (the mixer less its indexer) and ``sparse_index``.

The **Kimi Delta Attention mixer** (``"kda"``; ``kda_heads`` heads of
``kda_head_dim``, keys and values alike): ``q~ = silu(conv(h W_q))``,
``k~``, ``v`` likewise, three projections each through a causal depthwise
convolution of its own (``conv_kernel`` taps, no bias); ``q = unit(q~) *
d^-1/2``, ``k = unit(k~)`` (L2 a head, in float32); **a decay a key
channel**, ``g = -exp(A_log[head]) softplus((h W_f1) W_f2 + dt_bias)`` in
``R^(heads x d)`` through a rank of ``d``, float32; ``beta = sigmoid(h
W_b^T)`` a head (``w_b`` lies ``[heads, hidden]``: a leaf 32 wide would be
a view of the flat master in rows of 32, which the TPU's tiling pads); the
recurrence is ``ops.gated_delta_rule`` with ``g [B, H, T, d]``; its output
is RMS-normalised a head (one weight of ``d``), gated by ``sigmoid((h
W_g1) W_g2 + b_g)`` and projected out. It runs under ``kda_attention``
around ``delta_rule`` (the op alone, as in the Gated DeltaNet mixer) and
hands out ``kda_chunk_decay_nats``, how far its fastest channel decays
inside one chunk (``ops.gated_delta_rule.chunk_decay_nats``).

The **short-convolution mixer**: ``[B | C | u] = h W_in``, three streams
``hidden`` wide; ``z = conv(B * u)``, a causal depthwise convolution of
``conv_kernel`` taps a channel (no bias, zeros before the first token);
``(C * z) W_out``. No activation function, no softmax, no state beyond
``conv_kernel - 1`` tokens: two matmuls and a chain of elementwise
passes between them.

The **latent attention mixer** (``num_heads`` heads; queries and keys
``qk_nope_dim + qk_rope_dim`` wide over values ``v_head_dim`` wide):
``q = h W_q``; ``[c | kr] = h W_kva`` with ``c`` the ``kv_lora_rank``-wide
latent and ``kr`` **one** rotary key head that all heads share; ``[kn |
v] = norm(c) W_kvb`` a head; rotary positions turn ``q``'s last
``qk_rope_dim`` and ``kr`` whole (with ``latent_rotary=False`` nothing
is turned: ``kr`` stays as one shared key head without positions, what
a model with no positions anywhere leaves of it); ``k = [kn | kr]`` with ``kr``
**broadcast to the heads in front of the kernel** (its transpose sums the
heads' ``dK_rope``). ``flash_attention`` takes one width for ``q``, ``k``
and ``v``, so ``v`` is padded to the keys' width and the result sliced, as
the source's own flash path does. Under ``remat`` the keys and values are
made again from the layer's input in the backward: the block's input is
kept, and the two arrays the flash forward kernel made.

The **sigmoid router's selection bias** (``router="sigmoid"``) is state
that no gradient reaches: ``[expert layers, num_experts]`` floats, zero at
first (:meth:`HybridLM.router_state`), an argument of
:meth:`HybridLM.loss_with_router_state`, which hands it back moved by the
step's own pairs an expert (``ExpertLayer.moved_bias``), as a ResNet's
batch statistics travel through ``train_step.build_step``.

Scopes (``prof.SCOPES``): ``embed``, ``linear_attention``,
``delta_rule``, ``kda_attention``, ``attention``, ``window_attention``,
``diffusion_attention``, ``sparse_attention``, ``sparse_index``,
``latent_attention``,
``short_conv``, ``mlp``, ``moe_route``,
``moe_experts``, ``head_loss``; siblings, never
nested. Regions (``prof.REGIONS``), around the scopes: ``layer_stack``
(a run's stacked leaves, the counters' ``concatenate``) and ``layer_scan``
(the run's ``lax.scan``), opened in :meth:`HybridLM.hidden_states`. What
``jax.checkpoint`` re-runs in the backward carries JAX's own path
component ``rematted_computation``; a test pins it.

``remat`` recomputes each block in the backward and keeps what the
attention kernel made: the block's ``jax.checkpoint`` saves the names
``flash_attention.SAVED_NAMES`` (the forward kernel's output and
log-sum-exp, ``bf16[B * heads, T, head width padded to 128 lanes]`` and
``f32[B * heads, T]`` a flash layer), so ``apex_flash_fwd`` runs once a
step, and nothing else. A block with no flash kernel (a Gated DeltaNet,
Kimi Delta Attention or conv layer, ``attn_impl="default"``) saves its
input alone. A
``"sparse"`` block also keeps ``ops.sparse_index.SAVED_NAMES``: its packed
key sets (``int32[B, T, T / 32]``) and the indexer's gradient, so neither
the search nor the indexer's loss runs again.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from apex_tpu.contrib.moe.expert_layer import ExpertLayer
from apex_tpu.contrib.multihead_attn.flash_attention import SAVED_NAMES
from apex_tpu.ops.gated_delta_rule import (chunk_decay_nats,
                                           gated_delta_rule)

__all__ = ["HybridLM"]

_F32 = jnp.float32
MIXERS = ("linear", "full", "latent", "conv", "window", "sparse", "kda")
PROBE_ROWS = 32     # rows of layer 0's attention a block-diffusion step shows
FFNS = ("experts", "dense")


def _norm0(x, w, eps, zero_centred: bool = True):
    """RMSNorm over the last axis, in float32: zero-centred (``* (1 +
    w)``) or plain (``* w``)."""
    xf = x.astype(_F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    w = w.astype(_F32)
    return (y * (1.0 + w if zero_centred else w)).astype(x.dtype)


class Yarn(NamedTuple):
    """The "full" layers' YaRN rotary scaling (``HybridLM.rope_yarn``)."""
    factor: float               # what the slow pairs' frequency is divided by
    positions: int              # the original positions
    beta_fast: float            # turns over them above which a pair is kept
    beta_slow: float            # turns below which it is divided by factor
    attention_factor: float     # on cos and on sin


def _yarn(freq, theta: float, rot: int, yarn: Yarn):
    """YaRN's frequencies from the plain ones ``freq [rot / 2]``. A pair
    that turns more than ``beta_fast`` times over the original positions
    keeps its frequency, one that turns less than ``beta_slow`` times has
    it divided by ``factor``, a linear ramp over the pairs between (its
    ends truncated to whole pairs)."""
    def pair(turns):        # the pair that turns ``turns`` times
        return rot * math.log(yarn.positions / (2 * math.pi * turns)) \
            / (2 * math.log(theta))
    low = max(math.floor(pair(yarn.beta_fast)), 0)
    high = min(math.ceil(pair(yarn.beta_slow)), rot - 1)
    ramp = jnp.clip((jnp.arange(rot // 2, dtype=_F32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return freq / yarn.factor * ramp + freq * (1.0 - ramp)


def _rotary(x, theta: float, rot: int, yarn: Optional[Yarn] = None,
            positions=None):
    """Rotary positions on the first ``rot`` of the last axis of
    ``x [B, T, H, D]``, half-split pairing; ``yarn``: ``_yarn``'s
    frequencies and its attention factor on ``cos`` and ``sin``;
    ``positions [T]``: the rows' positions, ``arange(T)`` where not given."""
    half = rot // 2
    freq = theta ** (-jnp.arange(half, dtype=_F32) / half)
    if yarn:
        freq = _yarn(freq, theta, rot, yarn)
    if positions is None:
        positions = jnp.arange(x.shape[1], dtype=_F32)
    ang = positions.astype(_F32)[:, None] * freq                # [T, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if yarn:
        cos, sin = (cos * yarn.attention_factor,
                    sin * yarn.attention_factor)
    x1, x2 = x[..., :half].astype(_F32), x[..., half:rot].astype(_F32)
    turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([turned.astype(x.dtype), x[..., rot:]], -1)


def _causal_conv(x, w):
    """Depthwise causal convolution of ``x [B, T, C]`` with taps
    ``w [K, C]`` (the last tap on the current token), as shifted products."""
    taps = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    t = x.shape[1]
    return sum(xp[:, j:j + t] * w[j] for j in range(taps))


@dataclasses.dataclass(frozen=True)
class HybridLM:
    vocab_size: int
    hidden: int
    layer_types: tuple          # a mixer kind a layer, of MIXERS
    ffn_types: tuple = ()       # an FFN kind a layer, of FFNS; () = experts
    # softmax attention
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    rotary_dim: int = 64
    rope_theta: float = 1e7
    attn_gate: bool = True      # W_q carries an output gate a head
    window: int = 0             # keys a "window" layer's query sees
    rope_yarn: Optional[Yarn] = None    # the "full" layers' rotary
    #                             scaling; None = the plain table
    block_diffusion: int = 0    # > 0: trained by block diffusion, in
    #                             blocks of that many positions
    # the "sparse" layers' indexer (ops.sparse_index)
    index_heads: int = 16
    index_dim: int = 64
    index_topk: int = 2048      # keys a query attends to
    index_coef: float = 1.0     # on the indexer's loss
    # latent attention (num_heads heads, rope_theta)
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    latent_rotary: bool = True  # False: no positions (q's and kr's stay)
    # Kimi Delta Attention (conv_kernel, delta_chunk)
    kda_heads: int = 32
    kda_head_dim: int = 128
    # Gated DeltaNet
    linear_k_heads: int = 16
    linear_v_heads: int = 32
    linear_k_dim: int = 128
    linear_v_dim: int = 128
    conv_kernel: int = 4        # taps: Gated DeltaNet's, the "conv" mixer's
    delta_chunk: int = 64
    # the expert layer (contrib.moe.ExpertLayer)
    num_experts: int = 512
    top_k: int = 10
    expert_ffn: int = 512
    shared_ffn: int = 512
    experts_held: tuple = ()
    dispatch_bound: int = 0
    router: str = "softmax"     # ExpertLayer's router kind
    routed_scale: float = 1.0
    bias_rate: float = 0.001    # the sigmoid router's bias, a step's move
    dense_ffn: int = 0          # the "dense" FFN's width
    aux_coef: float = 0.001
    rms_eps: float = 1e-6
    zero_centred_norm: bool = True
    tied_head: bool = False     # the head is the embedding
    attn_impl: str = "fast"     # "fast": the flash kernels; "default": jnp
    head_chunk: int = 0         # accepted (it must divide the vocabulary)
    #                             and unread: the head walks blocks of rows
    #                             sized from the batch (contrib.xentropy's
    #                             weighted_linear_cross_entropy); it stays
    #                             while the benchmark's configurations and
    #                             drivers pass it
    remat: bool = False         # recompute each block in the backward,
    #                             but for the flash forward's (o, lse)

    def __post_init__(self):
        bad = set(self.layer_types) - set(MIXERS)
        if bad or not self.layer_types:
            raise ValueError(f"layer_types: a tuple of {MIXERS}, got "
                             f"{self.layer_types}")
        if set(self.ffn_types) - set(FFNS) \
                or len(self.ffns) != len(self.layer_types):
            raise ValueError(f"ffn_types: one of {FFNS} a layer, got "
                             f"{self.ffn_types}")
        if self.num_heads % self.num_kv_heads \
                or self.linear_v_heads % self.linear_k_heads:
            raise ValueError("query heads must be a multiple of key/value "
                             "heads, value heads of key heads")
        if "sparse" in self.layer_types and self.attn_gate:
            raise ValueError("a \"sparse\" layer has no output gate "
                             "(attn_gate=False)")
        if "window" in self.layer_types and self.window < 1:
            raise ValueError("a \"window\" layer needs window >= 1")
        if self.rope_yarn is not None \
                and not isinstance(self.rope_yarn, Yarn):
            raise ValueError("rope_yarn: a hybrid_lm.Yarn or None")
        if self.block_diffusion and (
                set(self.layer_types) != {"full"} or self.attn_gate
                or self.block_diffusion < 0):
            raise ValueError(
                "block_diffusion: the block length, a positive integer, "
                "over \"full\" layers without an output gate (the mask is "
                "the block-diffusion one: no other mixer kind, no window "
                f"and no gate is defined under it), got "
                f"{self.block_diffusion} with {self.layer_types}, "
                f"attn_gate={self.attn_gate}")
        if self.head_chunk and self.vocab_size % self.head_chunk:
            raise ValueError(f"head_chunk ({self.head_chunk}) must divide "
                             f"vocab_size ({self.vocab_size})")

    @property
    def ffns(self) -> tuple:
        return tuple(self.ffn_types) or ("experts",) * len(self.layer_types)

    def _experts(self) -> ExpertLayer:
        return ExpertLayer(
            hidden=self.hidden, ffn=self.expert_ffn,
            num_experts=self.num_experts, top_k=self.top_k,
            experts_held=self.experts_held, shared_ffn=self.shared_ffn,
            dispatch_bound=self.dispatch_bound, router=self.router,
            routed_scale=self.routed_scale)

    def _norm(self, x, w):
        return _norm0(x, w, self.rms_eps, self.zero_centred_norm)

    def _head(self, params):
        return params["embed" if self.tied_head else "head"]

    def router_state(self):
        """The sigmoid router's selection biases at the start, a row an
        expert layer; ``None`` for a model whose router has none."""
        if self.router != "sigmoid":
            return None
        return jnp.zeros((self.ffns.count("experts"), self.num_experts),
                         _F32)

    # -- parameters ----------------------------------------------------------
    def init(self, key, scale: float = 0.02) -> dict:
        d, v = self.hidden, self.vocab_size
        kd = self.linear_k_heads * self.linear_k_dim
        vd = self.linear_v_heads * self.linear_v_dim
        keys = iter(jax.random.split(key, 3 + 8 * len(self.layer_types)))

        def w(*shape):
            return jax.random.normal(next(keys), shape) * scale

        def gain(n):        # a norm's weight at the start
            return (jnp.zeros if self.zero_centred_norm else jnp.ones)((n,))
        p = {"embed": w(v, d), "norm_f": gain(d)}
        if not self.tied_head:
            p["head"] = w(v, d)
        for i, (kind, ffn) in enumerate(zip(self.layer_types, self.ffns)):
            lp = {"norm1": gain(d), "norm2": gain(d)}
            if ffn == "experts":
                lp["moe"] = self._experts().init(next(keys), scale)
            else:
                f = self.dense_ffn
                lp["mlp"] = {"w_gate": w(d, f), "w_up": w(d, f),
                             "w_down": w(f, d)}
            if kind == "latent":
                h, dn, dr = self.num_heads, self.qk_nope_dim, self.qk_rope_dim
                r = self.kv_lora_rank
                lp["latent"] = {
                    "w_q": w(d, h * (dn + dr)), "w_kva": w(d, r + dr),
                    "kv_norm": gain(r),
                    "w_kvb": w(r, h * (dn + self.v_head_dim)),
                    "w_o": w(h * self.v_head_dim, d)}
            elif kind == "kda":
                hh, hd = self.kda_heads, self.kda_head_dim
                taps = self.conv_kernel
                # the mixer's twelve matrices from one key of the layer's
                # eight (the other kinds' draws stay what they were)
                mine = iter(jax.random.split(next(keys), 12))

                def wk(*shape):
                    return jax.random.normal(next(mine), shape) * scale
                lp["kda"] = {
                    "w_q": wk(d, hh * hd), "w_k": wk(d, hh * hd),
                    "w_v": wk(d, hh * hd), "conv_q": wk(taps, hh * hd),
                    "conv_k": wk(taps, hh * hd), "conv_v": wk(taps, hh * hd),
                    "w_f1": wk(d, hd), "w_f2": wk(hd, hh * hd),
                    "A_log": jnp.zeros((hh,)),
                    "dt_bias": jnp.ones((hh * hd,)), "w_b": wk(hh, d),
                    "w_g1": wk(d, hd), "w_g2": wk(hd, hh * hd),
                    "b_g": jnp.zeros((hh * hd,)), "norm": jnp.ones((hd,)),
                    "w_out": wk(hh * hd, d)}
            elif kind == "conv":
                lp["conv"] = {"w_in": w(d, 3 * d),
                              "taps": w(self.conv_kernel, d),
                              "w_out": w(d, d)}
            elif kind == "linear":
                lp["linear"] = {
                    "w_qkvz": w(d, 2 * kd + 2 * vd),
                    "w_ba": w(d, 2 * self.linear_v_heads),
                    "conv": w(self.conv_kernel, 2 * kd + vd),
                    "A_log": jnp.zeros((self.linear_v_heads,)),
                    "dt_bias": jnp.ones((self.linear_v_heads,)),
                    "norm": jnp.ones((self.linear_v_dim,)),
                    "w_out": w(vd, d)}
            else:
                h, kv, hd = self.num_heads, self.num_kv_heads, self.head_dim
                lp["attn"] = {
                    "w_q": w(d, h * hd * (1 + self.attn_gate)),
                    "w_k": w(d, kv * hd),
                    "w_v": w(d, kv * hd), "q_norm": gain(hd),
                    "k_norm": gain(hd), "w_o": w(h * hd, d)}
                if kind == "sparse":
                    hi, di = self.index_heads, self.index_dim
                    lp["index"] = {
                        "w_q": w(d, hi * di), "w_k": w(di, d),
                        "w_w": w(hi, d),
                        "k_norm": {"w": jnp.ones((di,)),
                                   "b": jnp.zeros((di,))}}
            p[f"layer_{i}"] = lp
        return p

    # -- the mixers ----------------------------------------------------------
    def _linear_mixer(self, lp, x):
        b, t, _ = x.shape
        hk, hv = self.linear_k_heads, self.linear_v_heads
        dk, dv = self.linear_k_dim, self.linear_v_dim
        kd, vd = hk * dk, hv * dv
        with jax.named_scope("linear_attention"):
            h = self._norm(x, lp["norm1"])
            p = lp["linear"]
            qkvz = h @ p["w_qkvz"]
            ba = (h @ p["w_ba"]).astype(_F32)
            z = qkvz[..., 2 * kd + vd:]
            qkv = jax.nn.silu(_causal_conv(qkvz[..., :2 * kd + vd],
                                           p["conv"]))
            beta = jax.nn.sigmoid(ba[..., :hv])
            g = -jnp.exp(p["A_log"].astype(_F32)) * jax.nn.softplus(
                ba[..., hv:] + p["dt_bias"].astype(_F32))

            def unit(a):        # L2-normalise a head, in float32
                af = a.astype(_F32)
                return af * jax.lax.rsqrt(jnp.sum(af * af, -1, keepdims=True)
                                          + 1e-6)
            q = unit(qkv[..., :kd].reshape(b, t, hk, dk)) * dk ** -0.5
            k = unit(qkv[..., kd:2 * kd].reshape(b, t, hk, dk))
            # [B, T, H, D] -> [B, Hv, T, D], key heads repeated to value heads
            q, k = (jnp.repeat(a.astype(x.dtype).transpose(0, 2, 1, 3),
                               hv // hk, axis=1) for a in (q, k))
            v = qkv[..., 2 * kd:].reshape(b, t, hv, dv).transpose(0, 2, 1, 3)
            g, beta = g.transpose(0, 2, 1), beta.transpose(0, 2, 1)
        with jax.named_scope("delta_rule"):
            o = gated_delta_rule(q, k, v, g, beta, chunk=self.delta_chunk)
        with jax.named_scope("linear_attention"):
            of = o.transpose(0, 2, 1, 3).astype(_F32)       # [B, T, Hv, dv]
            of = of * jax.lax.rsqrt(jnp.mean(of * of, -1, keepdims=True)
                                    + self.rms_eps) * p["norm"].astype(_F32)
            of = of * jax.nn.silu(z.reshape(b, t, hv, dv).astype(_F32))
            return x + of.reshape(b, t, vd).astype(x.dtype) @ p["w_out"]

    def _kda_mixer(self, lp, x):
        """``(x out, the mixer's aux)``: how far its fastest channel
        decays inside one chunk, in nats."""
        b, t, _ = x.shape
        hh, hd = self.kda_heads, self.kda_head_dim
        with jax.named_scope("kda_attention"):
            h = self._norm(x, lp["norm1"])
            p = lp["kda"]

            def heads(a):       # [B, T, H d] -> [B, H, T, d]
                return a.reshape(b, t, hh, hd).transpose(0, 2, 1, 3)

            def unit(a):        # L2-normalise a head, in float32
                af = a.astype(_F32)
                return af * jax.lax.rsqrt(jnp.sum(af * af, -1, keepdims=True)
                                          + 1e-6)
            q, k, v = (heads(jax.nn.silu(_causal_conv(
                h @ p["w_" + n], p["conv_" + n]))) for n in "qkv")
            q = (unit(q) * hd ** -0.5).astype(x.dtype)
            k = unit(k).astype(x.dtype)
            a = ((h @ p["w_f1"]) @ p["w_f2"]).astype(_F32) \
                + p["dt_bias"].astype(_F32)
            g = heads(jax.nn.softplus(a)) * -jnp.exp(
                p["A_log"].astype(_F32))[:, None, None]
            beta = jax.nn.sigmoid((h @ p["w_b"].T).astype(_F32)) \
                .transpose(0, 2, 1)
            aux = {"kda_chunk_decay_nats": chunk_decay_nats(
                g, self.delta_chunk)}
        with jax.named_scope("delta_rule"):
            o = gated_delta_rule(q, k, v, g, beta, chunk=self.delta_chunk)
        with jax.named_scope("kda_attention"):
            of = o.transpose(0, 2, 1, 3).astype(_F32)       # [B, T, H, d]
            of = of * jax.lax.rsqrt(jnp.mean(of * of, -1, keepdims=True)
                                    + self.rms_eps) * p["norm"].astype(_F32)
            gate = ((h @ p["w_g1"]) @ p["w_g2"]).astype(_F32) \
                + p["b_g"].astype(_F32)
            of = of.reshape(b, t, hh * hd) * jax.nn.sigmoid(gate)
            return x + of.astype(x.dtype) @ p["w_out"], aux

    def _qkv(self, p, hid, yarn=None, positions=None):
        """The attention mixers' heads from the layer's normed input:
        ``(q [B, T, heads, hd], k, v [B, T, kv heads, hd], the output
        gate)``, ``q`` and ``k`` normed a head and rotated (by
        ``positions [T]`` where given)."""
        b, t, _ = hid.shape
        h, kv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        qg = (hid @ p["w_q"]).reshape(b, t, h, hd * (1 + self.attn_gate))
        q, gate = qg[..., :hd], qg[..., hd:]    # no attn_gate: empty
        k = (hid @ p["w_k"]).reshape(b, t, kv, hd)
        v = (hid @ p["w_v"]).reshape(b, t, kv, hd)
        q = _rotary(self._norm(q, p["q_norm"]),
                    self.rope_theta, self.rotary_dim, yarn, positions)
        k = _rotary(self._norm(k, p["k_norm"]),
                    self.rope_theta, self.rotary_dim, yarn, positions)
        return q, k, v, gate

    def _full_mixer(self, lp, x, window=None):
        """The attention mixer; ``window``: the window mixer, which sees
        that many keys and turns its heads with the plain table. Under
        ``block_diffusion`` the ``T`` rows are ``T / 2`` positions twice
        (the noised copy, then the clean one) and the mask is the
        block-diffusion one, under a scope of its own."""
        from apex_tpu.contrib.multihead_attn.flash_attention import (
            flash_attention, reference_attention)
        b, t, _ = x.shape
        h, kv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        yarn = None if window else self.rope_yarn
        mask, positions = {"causal": True, "window": window}, None
        if self.block_diffusion:
            mask = {"block_diffusion": (self.block_diffusion, t // 2)}
            positions = jnp.tile(jnp.arange(t // 2), 2)
        with jax.named_scope("diffusion_attention" if self.block_diffusion
                             else "window_attention" if window
                             else "attention"):
            p = lp["attn"]
            q, k, v, gate = self._qkv(p, self._norm(x, lp["norm1"]), yarn,
                                      positions)
            # each key/value head serves h // kv query heads: broadcast in
            # front of the kernel (its transpose sums the group's dK, dV)
            q = q.transpose(0, 2, 1, 3)
            k, v = (jnp.repeat(a.transpose(0, 2, 1, 3), h // kv, axis=1)
                    for a in (k, v))
            attend = flash_attention if self.attn_impl == "fast" \
                else reference_attention
            a = attend(q, k, v, **mask,
                       scale=hd ** -0.5).transpose(0, 2, 1, 3)
            if self.attn_gate:
                a = a * jax.nn.sigmoid(gate.astype(_F32)).astype(x.dtype)
            out = x + a.reshape(b, t, h * hd) @ p["w_o"]
            if not self.block_diffusion:
                return out
            # what the heads made for the first noised rows, before any
            # router has read anything: a forward reading no routing moves
            return out, {"diffusion_probe": a[:, :PROBE_ROWS].reshape(
                b, -1, h * hd).astype(_F32)}

    def _index(self, p, hid):
        """The indexer of a "sparse" layer on the layer's normed input:
        ``(qI [B, T, heads, dim], kI [B, T, dim], w [B, T, heads] float32,
        the packed key sets)``. It reads the input and hands nothing back
        through it: it learns from its own loss alone."""
        from apex_tpu.ops import sparse_index
        b, t, _ = hid.shape
        hi, di = self.index_heads, self.index_dim
        with jax.named_scope("sparse_index"):
            hbar = jax.lax.stop_gradient(hid)
            qi = _rotary((hbar @ p["w_q"]).reshape(b, t, hi, di),
                         self.rope_theta, di)
            ki = (hbar @ p["w_k"].T).astype(_F32)
            ki = ki - jnp.mean(ki, -1, keepdims=True)
            ki = ki * jax.lax.rsqrt(jnp.mean(ki * ki, -1, keepdims=True)
                                    + self.rms_eps) \
                * p["k_norm"]["w"].astype(_F32) \
                + p["k_norm"]["b"].astype(_F32)
            ki = _rotary(ki.astype(hid.dtype)[:, :, None], self.rope_theta,
                         di)[:, :, 0]
            w = (hbar @ p["w_w"].T).astype(_F32) * (hi * di) ** -0.5
            select = sparse_index.select_keys(
                qi, ki, w, self.index_topk,
                impl="fast" if self.attn_impl == "fast" else "reference")
        return qi, ki, w, select

    def first_selection(self, params: dict, tokens):
        """The packed key sets (``ops.key_set.pack_select``: ``int32
        [B, T, 128 * ceil(T / 4096)]``) that layer 0, a "sparse" layer,
        selects for ``tokens [B, T]``: what its flash kernels read in a
        step on these parameters, for a check outside the step."""
        lp = params["layer_0"]
        hid = self._norm(params["embed"][tokens], lp["norm1"])
        return self._index(lp["index"], hid)[-1]

    def _sparse_mixer(self, lp, x):
        """``(x out, the indexer's aux)``: its loss, the pairs selected
        and the share of 512 x 512 tiles that hold one."""
        from apex_tpu.contrib.multihead_attn.flash_attention import (
            flash_attention, reference_attention)
        from apex_tpu.ops import sparse_index
        b, t, _ = x.shape
        h, kv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        impl = "fast" if self.attn_impl == "fast" else "reference"
        with jax.named_scope("sparse_attention"):
            hid = self._norm(x, lp["norm1"])
            q, k, v = (a.transpose(0, 2, 1, 3)
                       for a in self._qkv(lp["attn"], hid)[:3])
        qi, ki, w, select = self._index(lp["index"], hid)
        with jax.named_scope("sparse_attention"):
            attend = flash_attention if self.attn_impl == "fast" \
                else reference_attention
            a, lse = attend(q, *(jnp.repeat(a, h // kv, axis=1)
                                 for a in (k, v)), causal=True,
                            select=select, scale=hd ** -0.5, return_lse=True)
        with jax.named_scope("sparse_index"):
            aux = {"index_loss": sparse_index.index_loss(
                       qi, ki, w, q, k, lse, select, scale=hd ** -0.5,
                       impl=impl),
                   "select_pairs": jnp.sum(jax.lax.population_count(select)),
                   "select_live_tile_pct": sparse_index.live_tile_pct(
                       select)}
        with jax.named_scope("sparse_attention"):
            return x + a.transpose(0, 2, 1, 3).reshape(b, t, h * hd) \
                @ lp["attn"]["w_o"], aux

    def _conv_mixer(self, lp, x):
        d = self.hidden
        with jax.named_scope("short_conv"):
            p = lp["conv"]
            bcu = self._norm(x, lp["norm1"]) @ p["w_in"]
            z = _causal_conv(bcu[..., :d] * bcu[..., 2 * d:], p["taps"])
            return x + (bcu[..., d:2 * d] * z) @ p["w_out"]

    def _latent_mixer(self, lp, x):
        from apex_tpu.contrib.multihead_attn.flash_attention import (
            flash_attention, reference_attention)
        b, t, _ = x.shape
        h, r = self.num_heads, self.kv_lora_rank
        dn, dr, dv = self.qk_nope_dim, self.qk_rope_dim, self.v_head_dim
        with jax.named_scope("latent_attention"):
            p = lp["latent"]
            hid = self._norm(x, lp["norm1"])
            q = (hid @ p["w_q"]).reshape(b, t, h, dn + dr)
            kva = hid @ p["w_kva"]
            kv = (self._norm(kva[..., :r], p["kv_norm"])
                  @ p["w_kvb"]).reshape(b, t, h, dn + dv)
            kr = kva[..., None, r:]
            if self.latent_rotary:
                q = jnp.concatenate([q[..., :dn], _rotary(
                    q[..., dn:], self.rope_theta, dr)], -1)
                kr = _rotary(kr, self.rope_theta, dr)
            # one rotary key head serves every head: broadcast in front of
            # the kernel (its transpose sums the heads' dK_rope)
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(kr, (b, t, h, dr))], -1)
            q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, kv[..., dn:]))
            scale = (dn + dr) ** -0.5
            if self.attn_impl == "fast":
                # the kernels take one width: v padded to the keys'
                v = jnp.pad(v, ((0, 0),) * 3 + ((0, dn + dr - dv),))
                a = flash_attention(q, k, v, causal=True,
                                    scale=scale)[..., :dv]
            else:
                a = reference_attention(q, k, v, causal=True, scale=scale)
            return x + a.transpose(0, 2, 1, 3).reshape(b, t, h * dv) \
                @ p["w_o"]

    def _dense_ffn(self, lp, x):
        with jax.named_scope("mlp"):
            h, p = self._norm(x, lp["norm2"]), lp["mlp"]
            # the products leave in x's type: at 16,384 tokens x 11,264
            # float32 gates and their cotangents are 0.74 GB apiece
            g, u = h @ p["w_gate"], h @ p["w_up"]
            act = jax.nn.silu(g.astype(_F32)) * u.astype(_F32)
            return x + act.astype(x.dtype) @ p["w_down"]

    def _block(self, kind: str, lp, x, ffn: str = "experts", bias=None):
        """One layer: ``(x, the expert layer's aux | None)``, and for a
        "sparse" or a "kda" layer, and a "full" one under
        ``block_diffusion``, ``(x, (that, the mixer's aux))``.
        ``bias``: the sigmoid router's selection bias of this layer."""
        if kind in ("sparse", "kda") or self.block_diffusion:
            x, mixer_aux = {"sparse": self._sparse_mixer,
                            "kda": self._kda_mixer,
                            "full": self._full_mixer}[kind](lp, x)
            x, aux = self._ffn(lp, x, ffn, bias)
            return x, (aux, mixer_aux)
        x = {"linear": self._linear_mixer, "full": self._full_mixer,
             "window": functools.partial(self._full_mixer,
                                         window=self.window),
             "latent": self._latent_mixer,
             "conv": self._conv_mixer}[kind](lp, x)
        return self._ffn(lp, x, ffn, bias)

    def _ffn(self, lp, x, ffn: str, bias):
        if ffn == "dense":
            return self._dense_ffn(lp, x), None
        b, t, d = x.shape
        with jax.named_scope("moe_route"):
            h = self._norm(x, lp["norm2"])
        if self.router == "sigmoid":    # balances a sequence: [B, T, d]
            y, aux = self._experts().apply(lp["moe"], h, bias)
        else:
            y, aux = self._experts().apply(lp["moe"], h.reshape(b * t, d))
        with jax.named_scope("moe_route"):
            return x + y.reshape(b, t, d), aux

    # -- forward, loss -------------------------------------------------------
    def hidden_states(self, params: dict, tokens, router_bias=None):
        """``tokens [B, T]`` -> (the final norm's output ``[B, T, hidden]``,
        counters): the routers' summed load-balancing term, the pairs past
        the dispatch bound (all layers), the fullest layer's pairs on held
        experts and tiles of the dispatch buffer that hold a row, the worst
        layer's held-expert load over the mean and, for
        the sigmoid router, each expert layer's pairs an expert
        (``expert_pairs [expert layers, num_experts]``); with "sparse"
        layers, their summed indexer loss, the pairs they selected and the
        fullest layer's share of 512 x 512 tiles that hold one; with "kda"
        layers, ``kda_chunk_decay_nats_max``, how far the fastest channel
        of any of them decays inside one chunk.
        ``router_bias``:
        that router's selection biases, a row an expert layer."""
        with jax.named_scope("embed"):
            x = params["embed"][tokens]
        # a run of like layers is one scanned body over the run's stacked
        # parameters: three Gated DeltaNet layers compile once
        auxes, indexers, kdas, probes, first, row = [], [], [], [], 0, 0
        saved = SAVED_NAMES
        if "sparse" in self.layer_types:
            from apex_tpu.ops import sparse_index
            saved += sparse_index.SAVED_NAMES
        for (kind, ffn), run in itertools.groupby(
                zip(self.layer_types, self.ffns)):
            n = len(list(run))
            biased = router_bias is not None and ffn == "experts"

            def block(x, xs, _kind=kind, _ffn=ffn, _biased=biased):
                lp, bias = xs if _biased else (xs, None)
                return self._block(_kind, lp, x, _ffn, bias)
            if self.remat:
                block = jax.checkpoint(
                    block, policy=jax.checkpoint_policies
                    .save_only_these_names(*saved))
            layers = [params[f"layer_{i}"] for i in range(first, first + n)]
            # prof.REGIONS: what the run adds around its blocks, whose
            # ops keep their own scopes (metadata only)
            with jax.named_scope("layer_stack"):
                xs = jax.tree.map(lambda *a: jnp.stack(a), *layers)
                if biased:
                    xs, row = (xs, router_bias[row:row + n]), row + n
            with jax.named_scope("layer_scan"):
                x, aux = jax.lax.scan(block, x, xs)
            if kind in ("sparse", "kda") or self.block_diffusion:
                aux, mixer_aux = aux
                {"sparse": indexers, "kda": kdas,
                 "full": probes}[kind].append(mixer_aux)
            if aux is not None:
                auxes.append(aux)
            first += n
        with jax.named_scope("layer_stack"):    # the runs' counters joined
            aux = jax.tree.map(lambda *a: jnp.concatenate(a), *auxes)
            index_aux, kda_aux = (
                jax.tree.map(lambda *a: jnp.concatenate(a), *x) if x else {}
                for x in (indexers, kdas))
        with jax.named_scope("head_loss"):
            x = self._norm(x, params["norm_f"])
        counters = {"load_balance_loss": jnp.sum(aux["load_balance_loss"]),
                    "moe_overflow_pairs": jnp.sum(aux["overflow_pairs"]),
                    "moe_held_pairs_max": jnp.max(aux["held_pairs"]),
                    "moe_live_tiles_max": jnp.max(aux["live_tiles"]),
                    "expert_load_max_over_mean": jnp.max(
                        aux["load_max_over_mean"])}
        if "expert_pairs" in aux:
            counters["expert_pairs"] = aux["expert_pairs"]
        if index_aux:
            counters.update(
                index_loss=jnp.sum(index_aux["index_loss"]),
                select_pairs=jnp.sum(index_aux["select_pairs"]),
                select_live_tile_pct=jnp.max(
                    index_aux["select_live_tile_pct"]))
        if kda_aux:
            counters["kda_chunk_decay_nats_max"] = jnp.max(
                kda_aux["kda_chunk_decay_nats"])
        if probes:      # layer 0's
            counters["diffusion_probe"] = probes[0]["diffusion_probe"][0]
        return x, counters

    def apply(self, params: dict, tokens, router_bias=None):
        """Logits ``[B, T, vocab]`` in float32."""
        x, _ = self.hidden_states(params, tokens, router_bias)
        with jax.named_scope("head_loss"):
            return jnp.einsum("btd,vd->btv", x, self._head(params),
                              preferred_element_type=_F32)

    def loss_with_counters(self, params: dict, tokens, router_bias=None):
        """Mean next-token cross-entropy of ``tokens [B, T + 1]`` plus
        ``aux_coef`` times the load-balancing terms (plus ``index_coef``
        times the "sparse" layers' indexer losses), and the step's
        counters (``moe_overflow_pairs``, ``moe_held_pairs_max``,
        ``moe_live_tiles_max``, ``expert_load_max_over_mean``; the sigmoid
        router's ``expert_pairs``; the sparse layers' ``index_loss``,
        ``select_pairs``, ``select_live_tile_pct``; the "kda" layers'
        ``kda_chunk_decay_nats_max``). A model trained by block diffusion
        (``block_diffusion`` > 0) takes the triple ``(tokens, masked, p)``
        in ``tokens``' place: :meth:`diffusion_loss_with_counters`."""
        from apex_tpu.contrib.xentropy import weighted_linear_cross_entropy
        if self.block_diffusion:
            return self.diffusion_loss_with_counters(params, *tokens)
        x, c = self.hidden_states(params, tokens[:, :-1], router_bias)
        with jax.named_scope("head_loss"):
            targets = tokens[:, 1:].reshape(-1)
            loss = weighted_linear_cross_entropy(
                x.reshape(-1, self.hidden), self._head(params), targets,
                jnp.full(targets.shape, 1.0 / targets.size, _F32)) \
                + self.aux_coef * c.pop("load_balance_loss")
            if "index_loss" in c:       # stays among the counters too
                loss = loss + self.index_coef * c["index_loss"]
        return loss, c

    def diffusion_loss_with_counters(self, params: dict, tokens, masked, p):
        """The block-diffusion loss of ``tokens [R, L]`` with the positions
        ``masked [R, L]`` (bool) drawn at the probabilities ``p [R]``: the
        layers see each sequence twice, ``[noised ; clean]`` (``2 L`` rows;
        a masked position of the noised copy holds the mask token, id
        ``vocab_size - 1``; both copies at positions ``0 .. L - 1``), the
        head the noised copy alone, and a masked position predicts its own
        token (no shift) with weight ``1 / p``: ``mean over R of (1 / L)
        sum_masked xent / p``, plus ``aux_coef`` times the load-balancing
        terms over all ``2 L`` rows. The counters are
        :meth:`loss_with_counters`' and ``diffusion_masked_tokens`` (the
        positions that carried loss), ``diffusion_weight_max`` (the
        largest ``1 / p``) and ``diffusion_probe`` (``float32 [R,
        PROBE_ROWS, heads x head_dim]``: what layer 0's heads made for the
        first noised rows, before ``W_o``: a forward reading that no
        router's choice has touched)."""
        from apex_tpu.contrib.xentropy import weighted_linear_cross_entropy
        if not self.block_diffusion:
            raise ValueError("diffusion_loss_with_counters: the model has "
                             "block_diffusion=0, its loss is next-token")
        rows, length = tokens.shape
        with jax.named_scope("embed"):
            twice = jnp.concatenate([jnp.where(
                masked, self.vocab_size - 1, tokens), tokens], axis=1)
        x, c = self.hidden_states(params, twice)
        with jax.named_scope("head_loss"):
            p = p.astype(_F32)
            weight = masked / p[:, None] / (rows * length)
            loss = weighted_linear_cross_entropy(
                x[:, :length].reshape(-1, self.hidden), self._head(params),
                tokens.reshape(-1), weight.reshape(-1)) \
                + self.aux_coef * c.pop("load_balance_loss")
            c["diffusion_masked_tokens"] = jnp.sum(masked)
            c["diffusion_weight_max"] = jnp.max(1.0 / p)
        return loss, c

    def loss_with_router_state(self, params: dict, router_bias, tokens):
        """The sigmoid router's step: the loss under ``router_bias`` (no
        gradient reaches it), and beside it ``(the biases moved by this
        step's pairs an expert, counters)``; the counters gain
        ``router_bias_abs_max``, the largest moved bias."""
        loss, c = self.loss_with_counters(params, tokens, router_bias)
        with jax.named_scope("moe_route"):
            moved = ExpertLayer.moved_bias(router_bias, c["expert_pairs"],
                                           self.bias_rate)
            c["router_bias_abs_max"] = jnp.max(jnp.abs(moved))
        return loss, (moved, c)

    def loss(self, params: dict, tokens):
        return self.loss_with_counters(params, tokens)[0]
