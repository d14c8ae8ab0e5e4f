"""Self / encoder-decoder multihead attention modules.

Reference surface: ``SelfMultiheadAttn`` and ``EncdecMultiheadAttn``
(apex/contrib/multihead_attn/self_multihead_attn.py:24,
encdec_multihead_attn.py) — packed in-projections, ``impl='fast'`` (the
monolithic fused CUDA path) vs ``impl='default'`` (torch-composed), and
``include_norm_add`` variants that fuse a pre-LayerNorm + residual add
around the attention block.

Here ``impl='fast'`` routes the core through the Pallas flash kernel and
``impl='default'`` through the unfused jnp path — both numerically
interchangeable (the parity the reference tests assert between its two
impls, apex/contrib/test/multihead_attn/test_self_multihead_attn.py).

Functional API::

    mha = SelfMultiheadAttn(embed_dim=256, num_heads=8, impl='fast')
    params = mha.init(jax.random.key(0))
    out, _ = mha.apply(params, x)                 # x: [T, B, E] (time-major,
                                                  #  the reference layout)
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.contrib.multihead_attn.flash_attention import (
    flash_attention, reference_attention)
from apex_tpu.normalization import fused_layer_norm_affine

__all__ = ["SelfMultiheadAttn", "EncdecMultiheadAttn"]


def _xavier(key, shape):
    fan_in, fan_out = shape[0], shape[-1]
    bound = (6.0 / (fan_in + fan_out)) ** 0.5
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound)


def _split_heads(x, num_heads):
    # [T, B, E] -> [B*H, T, E/H]
    t, b, e = x.shape
    h = num_heads
    return x.reshape(t, b * h, e // h).transpose(1, 0, 2)


def _merge_heads(x, b):
    # [B*H, T, D] -> [T, B, H*D]
    bh, t, d = x.shape
    return x.transpose(1, 0, 2).reshape(t, b, (bh // b) * d)


def _masks_to_biases(key_padding_mask, attn_mask, h, sq, sk,
                     mask_additive=False):
    """Split the reference's two mask kinds onto the two kernel inputs:
    attn_mask [Sq, Sk] additive -> full bias (the reference fast kernels
    take additive masks); key_padding_mask [B, Sk] bool (True = pad) ->
    per-key kv_bias [B*H, Sk] (O(S) instead of O(Sq*Sk)). With
    ``mask_additive`` (self_multihead_attn.py:29,42) the
    key_padding_mask is ALREADY a float additive mask and rides through
    unconverted."""
    bias = None
    if attn_mask is not None:
        bias = jnp.broadcast_to(attn_mask.astype(jnp.float32)[None],
                                (1, sq, sk))
    kv_bias = None
    if key_padding_mask is not None:
        kp = key_padding_mask.astype(jnp.float32) if mask_additive \
            else jnp.where(key_padding_mask, -1.0e30, 0.0)
        kv_bias = jnp.repeat(kp, h, axis=0)   # [B, Sk] -> [B*H, Sk]
    return bias, kv_bias


def _dropout_seed(key):
    """Derive an int32 kernel seed from a jax PRNG key (traced scalar)."""
    return jax.random.bits(key, dtype=jnp.uint32).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class _AttnBase:
    # PERF: pick num_heads so head_dim = embed_dim/num_heads is 128 —
    # the flash kernel pads head_dim to the 128-lane MXU tile (64
    # leaves half the array idle) and softmax cost scales with the
    # head count. Measured on chip: head_dim 128 trains the same-FLOP
    # LM 30-76% faster than head_dim 64 (docs/PERF.md, r5
    # LMBENCH_*_h8d128 rows).
    embed_dim: int
    num_heads: int
    dropout: float = 0.0
    bias: bool = False
    include_norm_add: bool = False
    # 'fast' -> always the Pallas flash kernel; 'default' -> always the
    # composed jnp attention; 'auto' -> measured crossover dispatch:
    # flash at max(Sq, Sk) >= flash_min_s, composed below it (XLA's
    # composed attention beats the kernel at short S on TPU —
    # docs/PERF.md r04; same honesty as the BN-welford demotion)
    impl: str = "fast"
    # reference positions 7-8 (self_multihead_attn.py:29): separate
    # q/k/v parameter tensors instead of the packed in_proj, and a
    # FLOAT additive key_padding_mask instead of a bool one
    separate_qkv_params: bool = False
    mask_additive: bool = False
    # crossover override for impl='auto'; None = flash_attention.
    # flash_min_s() (env > 4096 default)
    flash_min_s: Optional[int] = None
    causal: bool = False
    # Sequence parallelism: when seq_axis is set, the attention core runs
    # ring attention over that mesh axis (call inside shard_map with the
    # TIME dim sharded). Beyond-reference capability (SURVEY.md §5).
    seq_axis: Optional[str] = None
    seq_axis_size: int = 0

    def __post_init__(self):
        if self.embed_dim % self.num_heads:
            raise ValueError("embed_dim must be divisible by num_heads")
        if self.impl not in ("fast", "default", "auto"):
            raise ValueError(f"impl must be 'fast', 'default' or 'auto', "
                             f"got {self.impl!r}")
        if self.mask_additive:
            # reference consistency rules (self_multihead_attn.py:42-44)
            if self.include_norm_add:
                raise ValueError(
                    "additive mask not supported with layer norm")
            if self.impl != "default" and not self.bias:
                raise ValueError("additive mask not supported for fast "
                                 "mode without bias")
        if self.seq_axis is not None and self.seq_axis_size < 2:
            raise ValueError("seq_axis requires seq_axis_size >= 2")

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.num_heads

    def _flash_wins(self, q, k) -> bool:
        """impl='auto' crossover: kernel at/above the measured crossover
        length, composed XLA attention below it. Shapes are static under
        jit, so this is a trace-time branch.

        Memory guard: the speed crossover is measured on a microbench
        shape, but composed attention materializes the [BH, Sq, Sk] fp32
        score matrix — at model scale (large batch x heads) that can
        exceed HBM below the speed crossover while the kernel's O(S)
        memory always fits. Below the crossover, route to the kernel
        anyway once the score matrix would exceed
        APEX_FLASH_COMPOSED_BYTES (default 2 GiB)."""
        import os
        from apex_tpu.contrib.multihead_attn.flash_attention import \
            flash_min_s
        thr = self.flash_min_s if self.flash_min_s is not None \
            else flash_min_s()
        sq, sk = q.shape[-2], k.shape[-2]
        if max(sq, sk) >= thr:
            return True
        bh = 1
        for d in q.shape[:-2]:
            bh *= d
        env = os.environ.get("APEX_FLASH_COMPOSED_BYTES")
        budget = int(env) if env else 2 << 30   # empty string = unset
        # peak composed-path HBM is a MULTIPLE of one score matrix:
        # forward holds scores, the exp'd scores and the normalized
        # probs concurrently, and backward adds their cotangents —
        # count ~6 live [BH, Sq, Sk] fp32 buffers against the budget
        return 6 * bh * sq * sk * 4 > budget

    def _core(self, q, k, v, bias, kv_bias, training, dropout_key):
        """Attention core. Dropout is applied IN-KERNEL to the softmax
        probabilities — the reference's fused softmax-dropout semantics
        (apex/contrib/csrc/multihead_attn/dropout.h + softmax.h; module
        arg self_multihead_attn.py:24) — via the coordinate-hash mask
        recomputed in fwd and bwd (flash_attention.dropout_bits)."""
        scale = 1.0 / float(self.head_dim) ** 0.5
        rate = self.dropout if (training and self.dropout > 0.0
                                and dropout_key is not None) else 0.0
        seed = _dropout_seed(dropout_key) if rate > 0.0 else 0
        if self.seq_axis is not None:
            if bias is not None:
                raise NotImplementedError(
                    "attn_mask is not supported under ring attention "
                    "(it would need the full [Sq, Sk_global] matrix); "
                    "key_padding_mask and causal=True are supported")
            from apex_tpu.parallel.ring_attention import ring_attention
            out = ring_attention(q, k, v, self.seq_axis,
                                 self.seq_axis_size, causal=self.causal,
                                 scale=scale, kv_bias=kv_bias,
                                 dropout_rate=rate, dropout_seed=seed)
        elif self.impl == "fast" or (self.impl == "auto"
                                     and self._flash_wins(q, k)):
            # bias here is always a constructed mask (key_padding/attn
            # masks, reference semantics: non-trainable) — declare it
            # non-differentiable so no O(S^2) bias gradient materializes
            out = flash_attention(q, k, v, bias, kv_bias=kv_bias,
                                  scale=scale, causal=self.causal,
                                  bias_grad=False, dropout_rate=rate,
                                  dropout_seed=seed)
        else:
            out = reference_attention(q, k, v, bias, kv_bias=kv_bias,
                                      scale=scale, causal=self.causal,
                                      dropout_rate=rate, dropout_seed=seed)
        return out


@dataclasses.dataclass(frozen=True)
class SelfMultiheadAttn(_AttnBase):
    """Self-attention with one packed [E, 3E] input projection (reference
    self_multihead_attn.py:24; in_proj_weight packs q,k,v)."""

    def init(self, key) -> dict:
        ks = jax.random.split(key, 4)
        e = self.embed_dim
        if self.separate_qkv_params:
            # reference layout + names (self_multihead_attn.py:45-58):
            # three separate [E, E] tensors instead of the packed in_proj
            p = {"q_weight": _xavier(ks[0], (e, e)),
                 "k_weight": _xavier(ks[2], (e, e)),
                 "v_weight": _xavier(ks[3], (e, e)),
                 "out_proj": _xavier(ks[1], (e, e))}
            if self.bias:
                p["q_bias"] = jnp.zeros((e,))
                p["k_bias"] = jnp.zeros((e,))
                p["v_bias"] = jnp.zeros((e,))
                p["out_proj_bias"] = jnp.zeros((e,))
        else:
            p = {
                "in_proj": _xavier(ks[0], (e, 3 * e)),
                "out_proj": _xavier(ks[1], (e, e)),
            }
            if self.bias:
                p["in_proj_bias"] = jnp.zeros((3 * e,))
                p["out_proj_bias"] = jnp.zeros((e,))
        if self.include_norm_add:
            p["lyr_nrm_gamma"] = jnp.ones((self.embed_dim,))
            p["lyr_nrm_beta"] = jnp.zeros((self.embed_dim,))
        return p

    def apply(self, params: dict, query: jax.Array, *,
              key_padding_mask: Optional[jax.Array] = None,
              attn_mask: Optional[jax.Array] = None,
              is_training: bool = True,
              dropout_key: Optional[jax.Array] = None):
        """query: [T, B, E] time-major. Returns (output [T, B, E], None) —
        the reference returns (out, attn_weights=None) for the fast path."""
        t, b, e = query.shape
        residual = query
        x = query
        if self.include_norm_add:
            # eps pinned: the reference norm-add kernels hardcode 1e-5
            # (self_multihead_attn_norm_add_cuda.cu:100)
            x = fused_layer_norm_affine(
                x, (self.embed_dim,), params["lyr_nrm_gamma"],
                params["lyr_nrm_beta"], 1e-5)
        if self.separate_qkv_params:
            q = x @ params["q_weight"]
            k = x @ params["k_weight"]
            v = x @ params["v_weight"]
            if self.bias:
                q = q + params["q_bias"]
                k = k + params["k_bias"]
                v = v + params["v_bias"]
        else:
            qkv = x @ params["in_proj"]
            if self.bias:
                qkv = qkv + params["in_proj_bias"]
            q, k, v = jnp.split(qkv, 3, axis=-1)
        q = _split_heads(q, self.num_heads)
        k = _split_heads(k, self.num_heads)
        v = _split_heads(v, self.num_heads)
        bias, kv_bias = _masks_to_biases(
            key_padding_mask, attn_mask, self.num_heads, t, t,
            mask_additive=self.mask_additive)
        out = self._core(q, k, v, bias, kv_bias, is_training, dropout_key)
        out = _merge_heads(out, b) @ params["out_proj"]
        if self.bias:
            out = out + params["out_proj_bias"]
        if self.include_norm_add:
            out = out + residual  # fused residual add variant
        return out, None

    __call__ = apply


@dataclasses.dataclass(frozen=True)
class EncdecMultiheadAttn(_AttnBase):
    """Encoder-decoder attention: q from the decoder stream, packed [E, 2E]
    k,v projection from the encoder memory (reference
    encdec_multihead_attn.py: in_proj_weight_q + in_proj_weight_kv)."""

    def __post_init__(self):
        # the reference Encdec signature stops at impl
        # (encdec_multihead_attn.py:29) — these Self-only flags must not
        # be silently accepted-and-ignored here
        if self.separate_qkv_params:
            raise ValueError("separate_qkv_params is a SelfMultiheadAttn "
                             "option (encdec already keeps q separate)")
        if self.mask_additive:
            raise ValueError(
                "mask_additive is a SelfMultiheadAttn option")
        super().__post_init__()

    def init(self, key) -> dict:
        ks = jax.random.split(key, 4)
        p = {
            "q_proj": _xavier(ks[0], (self.embed_dim, self.embed_dim)),
            "kv_proj": _xavier(ks[1], (self.embed_dim, 2 * self.embed_dim)),
            "out_proj": _xavier(ks[2], (self.embed_dim, self.embed_dim)),
        }
        if self.bias:
            p["q_proj_bias"] = jnp.zeros((self.embed_dim,))
            p["kv_proj_bias"] = jnp.zeros((2 * self.embed_dim,))
            p["out_proj_bias"] = jnp.zeros((self.embed_dim,))
        if self.include_norm_add:
            p["lyr_nrm_gamma"] = jnp.ones((self.embed_dim,))
            p["lyr_nrm_beta"] = jnp.zeros((self.embed_dim,))
        return p

    def apply(self, params: dict, query: jax.Array, key_value: jax.Array, *,
              key_padding_mask: Optional[jax.Array] = None,
              attn_mask: Optional[jax.Array] = None,
              is_training: bool = True,
              dropout_key: Optional[jax.Array] = None):
        """query: [Tq, B, E]; key_value: [Tk, B, E]."""
        tq, b, e = query.shape
        tk = key_value.shape[0]
        residual = query
        x = query
        if self.include_norm_add:
            # eps pinned: the reference norm-add kernels hardcode 1e-5
            # (self_multihead_attn_norm_add_cuda.cu:100)
            x = fused_layer_norm_affine(
                x, (self.embed_dim,), params["lyr_nrm_gamma"],
                params["lyr_nrm_beta"], 1e-5)
        q = x @ params["q_proj"]
        kv = key_value @ params["kv_proj"]
        if self.bias:
            q = q + params["q_proj_bias"]
            kv = kv + params["kv_proj_bias"]
        k, v = jnp.split(kv, 2, axis=-1)
        q = _split_heads(q, self.num_heads)
        k = _split_heads(k, self.num_heads)
        v = _split_heads(v, self.num_heads)
        bias, kv_bias = _masks_to_biases(key_padding_mask, attn_mask,
                                         self.num_heads, tq, tk)
        out = self._core(q, k, v, bias, kv_bias, is_training, dropout_key)
        out = _merge_heads(out, b) @ params["out_proj"]
        if self.bias:
            out = out + params["out_proj_bias"]
        if self.include_norm_add:
            out = out + residual
        return out, None

    __call__ = apply
