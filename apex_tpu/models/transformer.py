"""Transformer language model — the flagship consumer of the attention
stack (flash MHA + FusedLayerNorm + fused xentropy), with first-class
sequence parallelism.

The reference has no model zoo (apex is a library; its attention kernels
live bare in contrib). This model exists for the same reason the
reference's ResNet L1 driver does: an end-to-end vehicle exercising the
framework's pieces together — and, beyond the reference, the long-context
path (ring attention over a ``seq`` mesh axis, SURVEY.md §5).

Pre-LN decoder-only architecture:

    x  = tok_emb + pos_emb
    x += MHA(LN(x))            # flash kernel, causal
    x += MLP(LN(x))            # fused GeLU MLP
    logits = LN(x) @ W_out
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.contrib.multihead_attn import SelfMultiheadAttn
from apex_tpu.models import _remat
from apex_tpu.normalization import fused_layer_norm_affine

__all__ = ["TransformerLM"]


@dataclasses.dataclass(frozen=True)
class TransformerLM:
    vocab_size: int
    max_seq_len: int = 2048
    embed_dim: int = 512
    # PERF: choose num_heads for head_dim (embed_dim/num_heads) = 128
    # on TPU — measured 30-76% faster at identical params/FLOPs than
    # head_dim 64 (docs/PERF.md "Pick head_dim 128"). The default 8
    # here mirrors reference-typical shapes, not the TPU optimum.
    num_heads: int = 8
    num_layers: int = 6
    ffn_mult: int = 4
    dropout: float = 0.0
    attn_impl: str = "auto"
    # sequence parallelism: shard the TIME axis over this mesh axis and the
    # attention runs as a ring (call apply inside shard_map; pos offsets
    # are derived from lax.axis_index)
    seq_axis: Optional[str] = None
    seq_axis_size: int = 0
    # Mixture-of-Experts: replace every ``moe_every``-th MLP with a
    # Switch-MoE FFN of ``moe_experts`` experts (contrib.moe); set
    # expert_axis/_size to run the experts expert-parallel inside
    # shard_map (weights sharded P(expert_axis) on their expert dim)
    moe_experts: int = 0
    moe_top_k: int = 1     # 1 = Switch, 2 = GShard-style
    moe_every: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01   # Switch load-balance loss weight
    expert_axis: Optional[str] = None
    expert_axis_size: int = 0
    # The fused LM head: 0 computes full [B*T, V] logits through the
    # fused xentropy op; > 0 routes ``loss`` through
    # ``contrib.xentropy.weighted_linear_cross_entropy`` walking the
    # (tied) head in blocks of rows — peak memory O(rows*V) instead of
    # the O(N*V) fp32 logits temp (4 GB at B=8, T=4k, V=32k — the r4
    # long-context OOM), the head's gradients made beside the loss. The
    # value is a switch (and must divide the vocabulary): the rows of a
    # block come from the shapes.
    head_chunk: int = 0
    # rematerialize each transformer block in the backward
    # (jax.checkpoint): activation memory drops from O(layers) block
    # internals to O(layers) block BOUNDARIES at ~1/3 extra flops —
    # the standard lever for long sequences / deep stacks.
    # remat_policy picks what still gets SAVED inside a remat'd block
    # (jax.checkpoint_policies name, e.g. "dots_saveable" keeps matmul
    # outputs so only cheap elementwise work recomputes; None = save
    # nothing, the maximum-memory-savings default)
    remat: bool = False
    remat_policy: Optional[str] = None

    def __post_init__(self):
        _remat.validate_remat_config(self.remat, self.remat_policy)
        if self.head_chunk > 0 and \
                self.vocab_size % min(self.head_chunk, self.vocab_size):
            raise ValueError(
                f"head_chunk ({self.head_chunk}) must divide "
                f"vocab_size ({self.vocab_size})")
        if self.moe_experts > 0:
            if self.moe_every < 1:
                raise ValueError(f"moe_every must be >= 1, "
                                 f"got {self.moe_every}")
            if self.num_layers < self.moe_every:
                raise ValueError(
                    f"moe_experts={self.moe_experts} requested but no "
                    f"layer index hits moe_every={self.moe_every} with "
                    f"num_layers={self.num_layers} — the model would be "
                    f"silently dense")

    def _mha(self) -> SelfMultiheadAttn:
        return SelfMultiheadAttn(
            self.embed_dim, self.num_heads, dropout=self.dropout,
            bias=True, impl=self.attn_impl, causal=True,
            seq_axis=self.seq_axis, seq_axis_size=self.seq_axis_size)

    def _is_moe_layer(self, i: int) -> bool:
        return self.moe_experts > 0 and (i % self.moe_every
                                         == self.moe_every - 1)

    def _moe(self):
        from apex_tpu.contrib.moe import MoEMLP
        return MoEMLP(hidden=self.embed_dim,
                      ffn=self.ffn_mult * self.embed_dim,
                      num_experts=self.moe_experts,
                      top_k=self.moe_top_k,
                      capacity_factor=self.moe_capacity_factor,
                      expert_axis=self.expert_axis,
                      expert_axis_size=self.expert_axis_size)

    def init(self, key) -> dict:
        e, v = self.embed_dim, self.vocab_size
        keys = jax.random.split(key, 2 * self.num_layers + 3)
        scale = 0.02
        p = {
            "tok_emb": jax.random.normal(keys[0], (v, e)) * scale,
            "pos_emb": jax.random.normal(keys[1], (self.max_seq_len, e))
            * scale,
            "ln_f": {"g": jnp.ones((e,)), "b": jnp.zeros((e,))},
        }
        mha = self._mha()
        for i in range(self.num_layers):
            k1, k2 = keys[2 + 2 * i], keys[3 + 2 * i]
            f = self.ffn_mult * e
            lp = {
                "ln1": {"g": jnp.ones((e,)), "b": jnp.zeros((e,))},
                "attn": mha.init(k1),
                "ln2": {"g": jnp.ones((e,)), "b": jnp.zeros((e,))},
            }
            if self._is_moe_layer(i):
                lp["moe"] = self._moe().init(k2)
            else:
                lp["mlp"] = {
                    "w1": jax.random.normal(k2, (e, f)) * scale,
                    "b1": jnp.zeros((f,)),
                    "w2": jax.random.normal(
                        jax.random.fold_in(k2, 1), (f, e)) * scale,
                    "b2": jnp.zeros((e,)),
                }
            p[f"layer_{i}"] = lp
        return p

    def _ln(self, x, lnp):
        return fused_layer_norm_affine(x, (self.embed_dim,),
                                       lnp["g"], lnp["b"], 1e-5)

    def apply(self, params: dict, tokens: jax.Array, *,
              is_training: bool = False,
              dropout_key: Optional[jax.Array] = None,
              return_aux: bool = False, return_hidden: bool = False):
        """tokens: int32 [B, T] (T = local shard length under sequence
        parallelism). Returns logits fp32 [B, T, vocab] — or, with
        ``return_hidden=True``, the final-LN hidden states [B, T, E]
        (for the chunked fused head loss, which never builds the
        logits); with ``return_aux=True`` also a dict carrying the
        summed MoE load-balance loss and mean dropped fraction."""
        b, t = tokens.shape
        pos0 = 0
        total = t
        if self.seq_axis is not None:
            pos0 = jax.lax.axis_index(self.seq_axis) * t
            total = t * max(1, self.seq_axis_size)
        if total > self.max_seq_len:
            # beyond max_seq_len the pos_emb gather silently CLAMPS under
            # jit (every extra position reuses the last embedding) — same
            # guard generate() already has (ADVICE r4, via seq2seq)
            raise ValueError(
                f"sequence length {total} exceeds max_seq_len="
                f"{self.max_seq_len}; raise max_seq_len at construction")
        pos = pos0 + jnp.arange(t)
        # module boundaries are prof.SCOPES named scopes: metadata only
        # (HLO op names, so a device trace splits by module and direction)
        with jax.named_scope("embed"):
            x = params["tok_emb"][tokens] + params["pos_emb"][pos]
        mha = self._mha()

        moe_balance = jnp.asarray(0.0, jnp.float32)
        moe_dropped = jnp.asarray(0.0, jnp.float32)
        n_moe = 0
        zero = jnp.asarray(0.0, jnp.float32)
        for i in range(self.num_layers):
            is_moe = self._is_moe_layer(i)
            # fold the layer index into the dropout key: the in-kernel
            # mask is derived from the key's int32 seed, so an unfolded
            # key would give every layer a bit-identical dropout pattern
            layer_key = None if dropout_key is None \
                else jax.random.fold_in(dropout_key, i)

            def layer_body(x, lp, *, _moe=is_moe, _key=layer_key):
                with jax.named_scope("attention"):
                    h = self._ln(x, lp["ln1"])
                    # MHA modules are time-major [T, B, E]
                    attn_out, _ = mha.apply(lp["attn"], h.swapaxes(0, 1),
                                            is_training=is_training,
                                            dropout_key=_key)
                    x = x + attn_out.swapaxes(0, 1)
                with jax.named_scope("mlp"):
                    h = self._ln(x, lp["ln2"])
                    if _moe:
                        y, aux = self._moe().apply(
                            lp["moe"], h.reshape(-1, self.embed_dim))
                        return (x + y.reshape(h.shape),
                                aux["load_balance_loss"],
                                aux["dropped_fraction"])
                    h = jax.nn.gelu(h @ lp["mlp"]["w1"] + lp["mlp"]["b1"])
                    return x + (h @ lp["mlp"]["w2"] + lp["mlp"]["b2"]), \
                        zero, zero

            if self.remat:
                # trade FLOPs for HBM: drop each block's internal
                # activations in the forward and recompute them in the
                # backward — the standard long-context/deep-stack lever
                # (policy name validated in __post_init__; None is
                # jax.checkpoint's save-nothing default)
                layer_body = jax.checkpoint(
                    layer_body,
                    policy=_remat.resolve_remat_policy(self.remat_policy))
            x, bal, drop = layer_body(x, params[f"layer_{i}"])
            if is_moe:
                moe_balance = moe_balance + bal
                moe_dropped = moe_dropped + drop
                n_moe += 1

        with jax.named_scope("head_loss"):
            x = self._ln(x, params["ln_f"])
            if return_hidden:
                out = x
            else:
                out = (x @ params["tok_emb"].T).astype(jnp.float32)
        if return_aux:
            return out, {
                "moe_load_balance_loss": moe_balance,
                "moe_dropped_fraction": moe_dropped / max(n_moe, 1),
            }
        return out

    def _token_losses(self, params, out, targets_flat, weights):
        """``sum(weights * per-token losses)`` from apply()'s output —
        full logits through the fused xentropy op, or (head_chunk > 0)
        final hidden states through the reduced fused head, which makes
        the head's gradients beside the loss."""
        from apex_tpu.contrib.xentropy import (
            SoftmaxCrossEntropyLoss, weighted_linear_cross_entropy)
        with jax.named_scope("head_loss"):
            if self.head_chunk > 0:
                return weighted_linear_cross_entropy(
                    out.reshape(-1, self.embed_dim), params["tok_emb"],
                    targets_flat, weights)
            return jnp.sum(weights * SoftmaxCrossEntropyLoss.apply(
                out.reshape(-1, self.vocab_size), targets_flat,
                padding_idx=None))  # no padding token in this LM

    def loss(self, params: dict, tokens: jax.Array, *,
             is_training: bool = True,
             dropout_key: Optional[jax.Array] = None) -> jax.Array:
        """Next-token cross entropy via the fused xentropy op.

        Under sequence parallelism (``seq_axis`` set) the full local shard
        goes through ``apply`` — truncating ``tokens[:, :-1]`` per shard
        would shrink the local length and misalign every shard's absolute
        positions. Targets are shifted across the shard boundary via
        ppermute, and the single position with no target (the global last
        token) is masked; the returned loss is the global mean."""
        moe = self.moe_experts > 0
        hid = self.head_chunk > 0
        if self.seq_axis is None:
            out = self.apply(params, tokens[:, :-1],
                             is_training=is_training,
                             dropout_key=dropout_key, return_aux=moe,
                             return_hidden=hid)
            out, aux = out if moe else (out, None)
            targets = tokens[:, 1:].reshape(-1)
            loss = self._token_losses(
                params, out, targets,
                jnp.full(targets.shape, 1.0 / targets.size, jnp.float32))
            if moe:  # Switch aux objective keeps the router balanced
                loss = loss + self.moe_aux_weight * \
                    aux["moe_load_balance_loss"]
            return loss

        n = self.seq_axis_size
        b, t = tokens.shape
        out = self.apply(params, tokens, is_training=is_training,
                         dropout_key=dropout_key, return_aux=moe,
                         return_hidden=hid)
        out, aux = out if moe else (out, None)       # [B, t, V] or [B, t, E]
        # target for local position j is token j+1; for the last local
        # position that's the NEXT shard's first token.
        nxt_first = jax.lax.ppermute(
            tokens[:, :1], self.seq_axis,
            [((i + 1) % n, i) for i in range(n)])
        targets = jnp.concatenate([tokens[:, 1:], nxt_first], axis=1)
        # the global final position (last shard's last token) has no target
        is_last_shard = jax.lax.axis_index(self.seq_axis) == n - 1
        mask = jnp.ones((b, t), jnp.float32).at[:, -1].set(
            jnp.where(is_last_shard, 0.0, 1.0))
        total = jax.lax.psum(self._token_losses(
            params, out, targets.reshape(-1), mask.reshape(-1)),
            self.seq_axis)
        count = jax.lax.psum(jnp.sum(mask), self.seq_axis)
        loss = total / count
        if moe:
            loss = loss + self.moe_aux_weight * \
                aux["moe_load_balance_loss"]
        return loss

    # -- incremental decoding (KV cache) ---------------------------------

    def _cached_blocks(self, params, x, pos0, caches):
        """THE inference block stack — shared by the one-token decode
        step (T=1) and the batched prompt pre-fill (T=P), so the block
        math exists once on the inference side (apply() stays separate:
        it is the training path with the flash kernel, dropout, remat,
        and the generate-vs-apply parity test pins the seam).

        x: [B, T, E] embedded inputs for absolute positions
        pos0..pos0+T-1; caches: dict ``layer_i -> (k, v)`` with k/v
        [B, H, T_max, hd] — this chunk's K/V are written at pos0 and
        attention runs against the WHOLE cache with absolute causal
        masking (``q_start=pos0`` masks both the future and the
        not-yet-written tail). The attention core is
        ``reference_attention`` (fp32 score math — the kernel tests'
        numerics oracle). Returns (final-LN hidden [B, T, E], caches).

        MoE layers use the capacity-free mixture (contrib.moe decode):
        apply()'s capacity bounds the TRAINING dispatch buffer; at
        inference every token is served. decode computes all experts
        densely — for a long prompt that is num_experts/top_k times
        the minimal FLOPs, the price of exactness without a dispatch
        sort (a drop-free capacity dispatch needs capacity_factor =
        num_experts, whose padded queues cost the same)."""
        from apex_tpu.contrib.multihead_attn.flash_attention import (
            reference_attention)
        e, h = self.embed_dim, self.num_heads
        hd = e // h
        b, t, _ = x.shape
        new_caches = {}
        for i in range(self.num_layers):
            lp = params[f"layer_{i}"]
            hidd = self._ln(x, lp["ln1"])
            qkv = hidd @ lp["attn"]["in_proj"]
            if "in_proj_bias" in lp["attn"]:
                qkv = qkv + lp["attn"]["in_proj_bias"]
            q, k, v = jnp.split(qkv, 3, axis=-1)          # [B, T, E]
            ck, cv = caches[f"layer_{i}"]
            ck = jax.lax.dynamic_update_slice(
                ck, k.reshape(b, t, h, hd).transpose(0, 2, 1, 3),
                (0, 0, pos0, 0))
            cv = jax.lax.dynamic_update_slice(
                cv, v.reshape(b, t, h, hd).transpose(0, 2, 1, 3),
                (0, 0, pos0, 0))
            new_caches[f"layer_{i}"] = (ck, cv)
            qh = q.reshape(b, t, h, hd).transpose(0, 2, 1, 3)
            a = reference_attention(qh, ck, cv, causal=True,
                                    q_start=pos0)
            a = a.transpose(0, 2, 1, 3).reshape(b, t, e) \
                @ lp["attn"]["out_proj"]
            if "out_proj_bias" in lp["attn"]:
                a = a + lp["attn"]["out_proj_bias"]
            x = x + a
            hidd = self._ln(x, lp["ln2"])
            if self._is_moe_layer(i):
                y = self._moe().decode(lp["moe"], hidd.reshape(b * t, e))
                x = x + y.reshape(b, t, e)
            else:
                hidd = jax.nn.gelu(hidd @ lp["mlp"]["w1"]
                                   + lp["mlp"]["b1"])
                x = x + (hidd @ lp["mlp"]["w2"] + lp["mlp"]["b2"])
        return self._ln(x, params["ln_f"]), new_caches

    def _decode_one(self, params, tok, pos, caches):
        """One-token decode step: tok int32 [B] at scalar position
        ``pos``. Returns (final-LN hidden [B, E], updated caches)."""
        x = (params["tok_emb"][tok] + params["pos_emb"][pos])[:, None]
        hid, caches = self._cached_blocks(params, x, pos, caches)
        return hid[:, 0], caches

    def _decode_slots(self, params, toks, pos, caches, *,
                      attn_impl: str = "auto", page_table=None,
                      page_size: "int | None" = None):
        """Fused slot-batched decode step — the serving engine's hot
        path (``apex_tpu/serve``). One token per SLOT at per-slot
        positions: toks int32 [S], pos int32 [S]; caches ``layer_i ->
        (k, v)`` each [S, H, max_len, hd] (the pool arena). Returns
        (final-LN hidden [S, E], updated caches).

        Where ``_decode_one`` handles one scalar position for a whole
        batch (and the engine used to vmap it over slots), this runs
        the block stack natively on the slot dim: per layer ONE fused
        LN (``fused_layer_norm_affine``), ONE QKV matmul [S, 3E], a
        per-slot K/V write at each slot's own position, and the
        single-query attention through ``slot_decode_attention`` —
        the Pallas scale->mask->softmax->PV kernel on TPU, its
        bit-comparable lax twin elsewhere (``attn_impl`` forces a
        side). Greedy outputs are bit-equal to the vmapped
        ``_decode_one`` path (test-pinned, tests/test_transformer.py /
        test_serve.py).

        ``page_table``/``page_size`` (r20): the PAGED arena — caches
        are page pools ``[P_phys, H, page_size, hd]`` and ``page_table``
        (i32 [S, max_pages]) maps each slot's logical pages to
        physical ones. The step writes this token's K/V at
        ``(page_table[s, pos // page], pos % page)`` — a retired
        slot's table rows point at the null page 0, so its frozen
        writes can never corrupt a reused page — and attention gathers
        by page indices inside ``slot_decode_attention``. Values
        written and read are byte-identical to the dense layout, so
        greedy streams stay bit-equal (the r20 tentpole invariant).

        ``toks``/``pos`` may instead be i32 [S, Q] (r21 speculative
        scoring): Q query rows per slot at per-row absolute positions —
        ONE forward scores a slot's last committed token plus its Q-1
        draft proposals. Row j's K/V is written before attention runs,
        and each row masks to its OWN length, so row j sees exactly
        the prefix the 1-query path would see after j sequential
        commits — the property that keeps greedy speculative streams
        token-equal to the non-speculative baseline. Returns
        ([S, Q, E], caches). The 1-query [S] path is untouched
        (bit-pinned by the serve parity tests)."""
        from apex_tpu.contrib.multihead_attn.decode_attention import (
            slot_decode_attention)
        e, h = self.embed_dim, self.num_heads
        hd = e // h
        s = toks.shape[0]
        paged = page_table is not None
        if paged and not page_size:
            raise ValueError("paged _decode_slots needs page_size")
        multi = toks.ndim == 2
        if multi:
            q_dim = toks.shape[1]
            x = params["tok_emb"][toks] + params["pos_emb"][pos]
            lengths = pos + 1      # [S, Q]: each row its own prefix
            if paged:
                pg = pos // page_size
                off = pos % page_size
                phys = jnp.take_along_axis(page_table, pg, axis=1)

                def write(c, u, _pos):
                    # u [S, H, Q, hd]; the advanced indices phys/off
                    # [S, Q] move to the front, so the update operand
                    # is [S, Q, H, hd]. Duplicate targets only arise
                    # from position clamping past a slot's budget and
                    # from the null page — positions no committed
                    # row's attention ever reads
                    return c.at[phys, :, off, :].set(
                        u.transpose(0, 2, 1, 3))
            else:
                rows_ix = jnp.arange(s)[:, None]

                def write(c, u, p):
                    # scatter each row at its own position; advanced
                    # dims (rows_ix/p broadcast [S, Q]) lead again
                    return c.at[rows_ix, :, p, :].set(
                        u.transpose(0, 2, 1, 3))
        else:
            q_dim = 1
            # activations stay [S, 1, E] (the _cached_blocks layout):
            # XLA's CPU backend lowers the [S, 1, E] @ [E, F] chain
            # measurably faster than the squeezed [S, E] twin (~1.8x on
            # the serve smoke shapes), and the extra unit dim costs
            # nothing on TPU
            x = (params["tok_emb"][toks] + params["pos_emb"][pos])[:, None]
            lengths = pos + 1      # each slot attends its own prefix
            if paged:
                pg = pos // page_size
                off = pos % page_size
                phys = jnp.take_along_axis(page_table, pg[:, None],
                                           axis=1)[:, 0]      # [S]

                def write(c, u, _pos):
                    # u [S, H, 1, hd] -> one row of each slot's current
                    # page; duplicate phys ids only ever target the null
                    # page (retired slots), which nothing reads unmasked
                    return c.at[phys, :, off, :].set(u[:, :, 0, :])
            else:
                write = jax.vmap(
                    lambda c, u, p: jax.lax.dynamic_update_slice(
                        c, u, (0, p, 0)))
        new_caches = {}
        for i in range(self.num_layers):
            lp = params[f"layer_{i}"]
            hidd = self._ln(x, lp["ln1"])
            qkv = hidd @ lp["attn"]["in_proj"]            # ONE matmul
            if "in_proj_bias" in lp["attn"]:
                qkv = qkv + lp["attn"]["in_proj_bias"]
            q, k, v = jnp.split(qkv, 3, axis=-1)          # [S, Q, E]
            ck, cv = caches[f"layer_{i}"]
            ck = write(ck,
                       k.reshape(s, q_dim, h, hd).transpose(0, 2, 1, 3),
                       pos)
            cv = write(cv,
                       v.reshape(s, q_dim, h, hd).transpose(0, 2, 1, 3),
                       pos)
            new_caches[f"layer_{i}"] = (ck, cv)
            a = slot_decode_attention(
                q.reshape(s, q_dim, h, hd) if multi
                else q.reshape(s, h, hd),
                ck, cv, lengths, impl=attn_impl,
                page_table=(page_table if paged else None))
            a = a.reshape(s, q_dim, e) @ lp["attn"]["out_proj"]
            if "out_proj_bias" in lp["attn"]:
                a = a + lp["attn"]["out_proj_bias"]
            x = x + a
            hidd = self._ln(x, lp["ln2"])
            if self._is_moe_layer(i):
                y = self._moe().decode(lp["moe"],
                                       hidd.reshape(s * q_dim, e))
                x = x + y.reshape(s, q_dim, e)
            else:
                hidd = jax.nn.gelu(hidd @ lp["mlp"]["w1"]
                                   + lp["mlp"]["b1"])
                x = x + (hidd @ lp["mlp"]["w2"] + lp["mlp"]["b2"])
        out = self._ln(x, params["ln_f"])
        return (out if multi else out[:, 0]), new_caches

    @staticmethod
    def _filter_logits(logits, top_k, top_p):
        """Standard sampling filters: keep the top_k largest logits
        and/or the smallest nucleus with cumulative probability >=
        top_p; everything else goes to -inf before the categorical."""
        if top_k is not None:
            kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
            logits = jnp.where(logits >= kth, logits, -jnp.inf)
        if top_p is not None:
            probs = jax.nn.softmax(logits, axis=-1)
            sorted_p = jnp.sort(probs, axis=-1)[:, ::-1]     # desc
            csum = jnp.cumsum(sorted_p, axis=-1)
            # number of tokens in the nucleus: the first index where
            # cumulative mass reaches top_p, inclusive. Clamp to the
            # vocab size: float rounding can leave even the FULL cumsum
            # fractionally below top_p=1.0, and the resulting
            # out-of-range gather would FILL NaN (jit semantics) and
            # -inf the whole row.
            n_keep = jnp.minimum(
                1 + jnp.sum((csum < top_p).astype(jnp.int32),
                            axis=-1, keepdims=True),
                logits.shape[-1])
            cutoff = jnp.take_along_axis(sorted_p, n_keep - 1, axis=-1)
            logits = jnp.where(probs >= cutoff, logits, -jnp.inf)
        return logits

    def _prefill(self, params, prompt, total):
        """Batched prompt pre-fill: ONE causal pass over the prompt
        (instead of P sequential decode steps) through the shared
        ``_cached_blocks`` stack, filling fresh K/V caches sized to
        ``total``. Returns the final-LN hidden state of the LAST prompt
        position (whose head projection yields the first generated
        token) and the caches."""
        h, hd = self.num_heads, self.embed_dim // self.num_heads
        b, p = prompt.shape
        dt = params["tok_emb"].dtype   # caches follow the param dtype
        caches = {
            f"layer_{i}": (jnp.zeros((b, h, total, hd), dt),
                           jnp.zeros((b, h, total, hd), dt))
            for i in range(self.num_layers)
        }
        x = params["tok_emb"][prompt] + params["pos_emb"][jnp.arange(p)]
        hid, caches = self._cached_blocks(params, x, 0, caches)
        return hid[:, -1], caches

    def generate(self, params: dict, prompt: jax.Array, *,
                 max_new_tokens: int, temperature: float = 0.0,
                 top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 eos_id: Optional[int] = None,
                 key: Optional[jax.Array] = None) -> jax.Array:
        """Jit-friendly autoregressive generation with per-layer K/V
        caches — O(T) work per token instead of the full-prefix
        recompute (beyond-parity; the reference has no inference path).

        prompt: int32 [B, P] (fixed length, no padding). Returns
        int32 [B, P + max_new_tokens]. ``temperature=0`` is greedy;
        ``temperature>0`` samples (``key`` required), with the step
        index folded in so each position draws fresh randomness;
        ``top_k``/``top_p`` restrict sampling to the k most likely
        tokens / the smallest nucleus with mass >= top_p (ignored when
        greedy).
        ``eos_id`` arms per-sequence early stop: once a sequence emits
        ``eos_id`` its done flag latches and every later emitted
        position is frozen to ``eos_id`` (the output stays the fixed
        [B, P + max_new_tokens] shape — this is a masking contract, not
        a shape change; the serving engine's per-slot retirement,
        apex_tpu/serve, uses the same semantics).
        Single-device only (``seq_axis`` must be None). MoE layers
        decode capacity-free (every token served), so generation matches
        the training forward exactly whenever apply()'s capacity does
        not bind — see ``contrib.moe.MoEMLP.decode``."""
        if self.seq_axis is not None:
            raise NotImplementedError(
                "generate() decodes against a local KV cache; run it "
                "outside sequence parallelism (seq_axis=None)")
        if temperature < 0.0:
            raise ValueError(f"temperature must be >= 0, "
                             f"got {temperature}")
        if temperature > 0.0 and key is None:
            raise ValueError("temperature > 0 requires a PRNG key")
        if top_k is not None and not 0 < top_k <= self.vocab_size:
            raise ValueError(f"top_k must be in [1, vocab_size], "
                             f"got {top_k}")
        if top_p is not None and not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, "
                             f"got {max_new_tokens}")
        if eos_id is not None and not 0 <= eos_id < self.vocab_size:
            raise ValueError(f"eos_id must be in [0, vocab_size), "
                             f"got {eos_id}")
        b, p = prompt.shape
        total = p + max_new_tokens
        if total > self.max_seq_len:
            raise ValueError(
                f"prompt ({p}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_seq_len ({self.max_seq_len})")

        buf = jnp.zeros((b, total), jnp.int32)
        buf = jax.lax.dynamic_update_slice(buf, prompt, (0, 0))

        def produce(t, hid):
            """Token from the final-LN hidden state at position t (the
            draw key is folded with t, so the pre-fill restructure
            keeps the sampled streams identical)."""
            logits = (hid @ params["tok_emb"].T).astype(jnp.float32)
            if temperature > 0.0:
                filt = self._filter_logits(logits / temperature,
                                           top_k, top_p)
                return jax.random.categorical(
                    jax.random.fold_in(key, t), filt,
                    axis=-1).astype(jnp.int32)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        # batched pre-fill: one causal pass over the whole prompt fills
        # the caches and yields the first generated token — O(1)
        # sequential steps for the prompt instead of O(P)
        hid, caches = self._prefill(params, prompt, total)
        first = produce(p - 1, hid)
        done = (first == eos_id) if eos_id is not None \
            else jnp.zeros((b,), bool)
        buf = buf.at[:, p].set(first)

        def step(t, carry):
            buf, caches, done = carry
            hid, caches = self._decode_one(params, buf[:, t], t, caches)
            tok = produce(t, hid)
            if eos_id is not None:
                # latch: a finished sequence keeps emitting eos_id (the
                # buffer stays rectangular; the cache keeps filling with
                # eos positions nothing downstream reads)
                tok = jnp.where(done, eos_id, tok)
                done = done | (tok == eos_id)
            return buf.at[:, t + 1].set(tok), caches, done

        buf, _, _ = jax.lax.fori_loop(p, total - 1, step,
                                      (buf, caches, done))
        return buf

    def __call__(self, params, tokens, **kw):
        return self.apply(params, tokens, **kw)
