"""Multihead attention tests: flash kernel vs unfused oracle, impl parity,
mask semantics, norm-add variants, grads (reference test model:
apex/contrib/test/multihead_attn/test_self_multihead_attn.py asserts
fast-vs-default parity for outputs and input grads)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.contrib.multihead_attn import (
    SelfMultiheadAttn, EncdecMultiheadAttn,
    flash_attention, reference_attention)
from apex_tpu.contrib.multihead_attn.flash_attention import NEG_INF

# On real TPU, fp32 matmul operands pass through the MXU as bf16 by default
# (both the kernel and the jnp oracle, with different rounding structure) —
# kernel-vs-oracle agreement is bf16-level there, fp32-level on CPU.
_TPU = jax.default_backend() == "tpu"
RTOL = 5e-3 if _TPU else 1e-5
ATOL = 5e-3 if _TPU else 1e-5
GTOL = 2e-2 if _TPU else 1e-4


def _qkv(bh=4, sq=48, sk=48, d=32, key=0):
    ks = jax.random.split(jax.random.key(key), 3)
    return (jax.random.normal(ks[0], (bh, sq, d), jnp.float32),
            jax.random.normal(ks[1], (bh, sk, d), jnp.float32),
            jax.random.normal(ks[2], (bh, sk, d), jnp.float32))


class TestFlashKernel:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, causal=causal)
        ref = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=RTOL, atol=ATOL)

    def test_ragged_cross_attention(self):
        q, k, v = _qkv(sq=37, sk=53, d=24)
        out = flash_attention(q, k, v)
        ref = reference_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=RTOL, atol=ATOL)

    def test_bias(self):
        q, k, v = _qkv()
        bias = jax.random.normal(jax.random.key(7), (1, 48, 48)) * 0.5
        out = flash_attention(q, k, v, bias)
        ref = reference_attention(q, k, v, bias)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=RTOL, atol=ATOL)

    def test_causal_offsets(self):
        # sequence-shard offsets: q block placed mid-sequence (ring/SP use)
        q, k, v = _qkv(sq=16, sk=64)
        out = flash_attention(q, k, v, causal=True, q_start=32)
        ref = reference_attention(q, k, v, causal=True, q_start=32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=RTOL, atol=ATOL)

    def test_fully_masked_rows_are_zero_and_finite(self):
        q, k, v = _qkv(sq=8, sk=16)
        out = flash_attention(q, k, v, causal=True, k_start=100)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_array_equal(np.asarray(out), 0.0)

    def test_lse_matches(self):
        q, k, v = _qkv()
        _, lse = flash_attention(q, k, v, causal=True, return_lse=True)
        _, lse_ref = reference_attention(q, k, v, causal=True,
                                         return_lse=True)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                                   rtol=RTOL, atol=ATOL)

    def test_grads_match_reference(self):
        q, k, v = _qkv(sq=32, sk=32)
        bias = jax.random.normal(jax.random.key(9), (1, 32, 32)) * 0.3

        def f_flash(q, k, v, b):
            return jnp.sum(flash_attention(q, k, v, b, causal=True) ** 2)

        def f_ref(q, k, v, b):
            return jnp.sum(reference_attention(q, k, v, b, causal=True) ** 2)

        g1 = jax.grad(f_flash, argnums=(0, 1, 2, 3))(q, k, v, bias)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2, 3))(q, k, v, bias)
        for a, b, name in zip(g1, g2, "qkvb"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=GTOL, atol=GTOL,
                                       err_msg=f"grad {name}")

    @pytest.mark.parametrize("bq,bk", [(32, 64), (64, 32), (128, 128)])
    def test_bwd_block_override_matches_default(self, bq, bk):
        """Independent backward block sizes (the on-chip sweep knob) must
        not change gradients — only kernel tiling."""
        q, k, v = _qkv(sq=128, sk=128)

        def loss(q, k, v, **kw):
            return jnp.sum(
                flash_attention(q, k, v, causal=True, **kw)
                .astype(jnp.float32) ** 2)

        g0 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        g1 = jax.grad(functools.partial(loss, bwd_block_q=bq,
                                        bwd_block_k=bk),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g0, g1, "qkv"):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=GTOL, atol=GTOL,
                                       err_msg=f"grad {name} bq={bq}")

    def test_bwd_block_must_tile_padded_length(self):
        q, k, v = _qkv(sq=128, sk=128)
        with pytest.raises(ValueError, match="must divide"):
            flash_attention(q, k, v, bwd_block_q=96)

    @pytest.mark.parametrize("cfg", [
        dict(),                                   # plain
        dict(causal=True),                        # causal
        dict(sq=37, sk=53, d=24),                 # ragged (k_len masking)
        dict(causal=True, sq=16, sk=64, q_start=32),  # shard offsets
        dict(bias="bh"), dict(bias="one"),        # per-bh / broadcast bias
        dict(causal=True, sk=40, bias="one"),     # bias + k padding
    ], ids=["plain", "causal", "ragged", "offsets", "bias_bh", "bias_one",
            "bias_pad"])
    def test_pallas_backward_matches_chunked(self, cfg, monkeypatch):
        """The Pallas dq/dkdv kernels against the jnp chunked-scan oracle
        (the 'python build vs kernel build' axis of the reference's L1,
        tests/L1/common/run_test.sh)."""
        cfg = dict(cfg)
        bias_mode = cfg.pop("bias", None)
        q_start = cfg.pop("q_start", 0)
        causal = cfg.pop("causal", False)
        q, k, v = _qkv(**cfg, key=3)
        bh, sq, _ = q.shape
        sk = k.shape[1]
        bias = None
        if bias_mode:
            nb = bh if bias_mode == "bh" else 1
            bias = jax.random.normal(jax.random.key(11),
                                     (nb, sq, sk)) * 0.3

        def f(q, k, v, b):
            out, lse = flash_attention(
                q, k, v, b, causal=causal, q_start=q_start,
                return_lse=True)
            # touch lse too so its cotangent path is exercised
            return jnp.sum(out ** 2) + 0.1 * jnp.sum(jnp.where(
                lse > NEG_INF * 0.5, lse, 0.0))

        args = (q, k, v, bias)
        argnums = (0, 1, 2, 3) if bias is not None else (0, 1, 2)
        monkeypatch.setenv("APEX_TPU_FLASH_BWD", "pallas")
        g_pl = jax.grad(f, argnums=argnums)(*args)
        monkeypatch.setenv("APEX_TPU_FLASH_BWD", "chunked")
        g_ch = jax.grad(f, argnums=argnums)(*args)
        for a, b, name in zip(g_pl, g_ch, "qkvb"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=GTOL, atol=GTOL,
                                       err_msg=f"grad {name}")

    def test_bf16_storage(self):
        q, k, v = _qkv()
        out = flash_attention(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                              v.astype(jnp.bfloat16))
        assert out.dtype == jnp.bfloat16
        ref = reference_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), rtol=0.05, atol=0.05)

    def test_kv_bias_matches_full_bias(self):
        # per-key bias must equal the same mask expressed as a full bias
        q, k, v = _qkv(key=5)
        bh, sq, _ = q.shape
        sk = k.shape[1]
        pad = jnp.arange(sk) >= sk - 7                    # last 7 keys padded
        kvb = jnp.where(pad, NEG_INF, 0.0)[None, :]       # [1, Sk]
        full = jnp.broadcast_to(kvb[:, None, :], (1, sq, sk))
        out_kvb = flash_attention(q, k, v, kv_bias=kvb)
        out_full = flash_attention(q, k, v, full, bias_grad=False)
        np.testing.assert_allclose(np.asarray(out_kvb), np.asarray(out_full),
                                   rtol=RTOL, atol=ATOL)
        # grads flow through q, k, v with the kv_bias applied
        g = jax.grad(lambda q: jnp.sum(
            flash_attention(q, k, v, kv_bias=kvb) ** 2))(q)
        assert np.isfinite(np.asarray(g)).all()


class TestInKernelDropout:
    """Fixed-seed parity of the in-kernel softmax-probability dropout
    against the jnp oracle (reference semantics: dropout on the softmax
    results, apex/contrib/csrc/multihead_attn/dropout.h; the oracle
    reproduces the kernel's coordinate-hash mask bit-exactly)."""

    def test_fwd_matches_oracle(self):
        q, k, v = _qkv(key=7)
        out = flash_attention(q, k, v, dropout_rate=0.3, dropout_seed=42)
        want = reference_attention(q, k, v, dropout_rate=0.3,
                                   dropout_seed=42)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=RTOL, atol=ATOL)
        # ...and the mask actually drops something
        plain = flash_attention(q, k, v)
        assert float(jnp.max(jnp.abs(out - plain))) > 1e-3

    def test_rate_zero_is_identity(self):
        q, k, v = _qkv(key=8)
        out = flash_attention(q, k, v, dropout_rate=0.0, dropout_seed=9)
        plain = flash_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(plain))

    def test_seed_changes_mask(self):
        q, k, v = _qkv(key=9)
        o1 = flash_attention(q, k, v, dropout_rate=0.5, dropout_seed=1)
        o2 = flash_attention(q, k, v, dropout_rate=0.5, dropout_seed=2)
        assert float(jnp.max(jnp.abs(o1 - o2))) > 1e-3

    def test_drop_fraction_near_rate(self):
        from apex_tpu.contrib.multihead_attn.flash_attention import (
            dropout_bits, _drop_threshold)
        rate = 0.35
        bits = dropout_bits(123, 0, jnp.arange(256)[:, None],
                            jnp.arange(256)[None, :])
        frac = float(jnp.mean(bits < jnp.uint32(_drop_threshold(rate))))
        assert abs(frac - rate) < 0.01

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_pallas_vs_chunked(self, causal, monkeypatch):
        # both backward impls recompute the SAME hash mask
        q, k, v = _qkv(sq=32, sk=40, key=10)

        def f(q, k, v):
            out = flash_attention(q, k, v, causal=causal,
                                  dropout_rate=0.25, dropout_seed=77)
            return jnp.sum(out ** 2)

        monkeypatch.setenv("APEX_TPU_FLASH_BWD", "pallas")
        g_pl = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        monkeypatch.setenv("APEX_TPU_FLASH_BWD", "chunked")
        g_ch = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g_pl, g_ch, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=GTOL, atol=GTOL,
                                       err_msg=f"grad {name}")

    def test_grad_matches_autodiff_oracle(self):
        # the custom backward against jax autodiff through the jnp oracle
        q, k, v = _qkv(sq=24, sk=24, key=11)

        def f_kernel(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, dropout_rate=0.2, dropout_seed=5) ** 2)

        def f_oracle(q, k, v):
            return jnp.sum(reference_attention(
                q, k, v, dropout_rate=0.2, dropout_seed=5) ** 2)

        g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_oracle, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g1, g2, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=GTOL, atol=GTOL,
                                       err_msg=f"grad {name}")


class TestSelfMultiheadAttn:
    T, B, E, H = 20, 2, 64, 4

    def _x(self):
        return jax.random.normal(jax.random.key(1), (self.T, self.B, self.E))

    @pytest.mark.parametrize("norm_add", [False, True])
    def test_impl_parity(self, norm_add):
        # the reference's core contrib test: fast and default impls agree
        fast = SelfMultiheadAttn(self.E, self.H, impl="fast", bias=True,
                                 include_norm_add=norm_add)
        dflt = SelfMultiheadAttn(self.E, self.H, impl="default", bias=True,
                                 include_norm_add=norm_add)
        p = fast.init(jax.random.key(0))
        o1, _ = fast.apply(p, self._x(), is_training=False)
        o2, _ = dflt.apply(p, self._x(), is_training=False)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=RTOL, atol=ATOL)

    def test_grad_parity(self):
        x = self._x()
        fast = SelfMultiheadAttn(self.E, self.H, impl="fast")
        dflt = SelfMultiheadAttn(self.E, self.H, impl="default")
        p = fast.init(jax.random.key(0))
        g1 = jax.grad(lambda q: jnp.sum(fast.apply(p, q)[0] ** 2))(x)
        g2 = jax.grad(lambda q: jnp.sum(dflt.apply(p, q)[0] ** 2))(x)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=GTOL, atol=GTOL)

    def test_key_padding_mask_zeroes_influence(self):
        mha = SelfMultiheadAttn(self.E, self.H, impl="fast")
        p = mha.init(jax.random.key(0))
        x = self._x()
        kpm = jnp.zeros((self.B, self.T), bool).at[:, -4:].set(True)
        out_m, _ = mha.apply(p, x, key_padding_mask=kpm, is_training=False)
        # perturb masked positions; unmasked outputs must not change
        x2 = x.at[-1].add(10.0)
        out_m2, _ = mha.apply(p, x2, key_padding_mask=kpm, is_training=False)
        np.testing.assert_allclose(np.asarray(out_m[:4]),
                                   np.asarray(out_m2[:4]), rtol=1e-5,
                                   atol=1e-6)

    def test_causal_attn_mask(self):
        mha = SelfMultiheadAttn(self.E, self.H, impl="fast")
        p = mha.init(jax.random.key(0))
        x = self._x()
        causal = jnp.where(
            jnp.arange(self.T)[:, None] >= jnp.arange(self.T)[None, :],
            0.0, -1e30)
        out, _ = mha.apply(p, x, attn_mask=causal, is_training=False)
        # output at t must not depend on inputs after t
        x2 = x.at[-1].add(5.0)
        out2, _ = mha.apply(p, x2, attn_mask=causal, is_training=False)
        np.testing.assert_allclose(np.asarray(out[:-1]),
                                   np.asarray(out2[:-1]), rtol=1e-5,
                                   atol=1e-6)

    def test_norm_add_is_residual(self):
        mha = SelfMultiheadAttn(self.E, self.H, include_norm_add=True)
        p = mha.init(jax.random.key(0))
        x = self._x()
        out, _ = mha.apply(p, x, is_training=False)
        assert "lyr_nrm_gamma" in p
        # residual path present: zeroing projections leaves identity
        p0 = dict(p, in_proj=jnp.zeros_like(p["in_proj"]),
                  out_proj=jnp.zeros_like(p["out_proj"]))
        out0, _ = mha.apply(p0, x, is_training=False)
        np.testing.assert_allclose(np.asarray(out0), np.asarray(x),
                                   rtol=1e-6, atol=1e-6)

    def test_dropout_train_vs_eval(self):
        mha = SelfMultiheadAttn(self.E, self.H, dropout=0.5)
        p = mha.init(jax.random.key(0))
        x = self._x()
        o_eval, _ = mha.apply(p, x, is_training=False)
        o_tr, _ = mha.apply(p, x, is_training=True,
                            dropout_key=jax.random.key(3))
        assert not np.allclose(np.asarray(o_eval), np.asarray(o_tr))


class TestEncdecMultiheadAttn:
    def test_impl_parity_and_shapes(self):
        Tq, Tk, B, E, H = 12, 18, 2, 32, 4
        q = jax.random.normal(jax.random.key(0), (Tq, B, E))
        mem = jax.random.normal(jax.random.key(1), (Tk, B, E))
        fast = EncdecMultiheadAttn(E, H, impl="fast", bias=True)
        dflt = EncdecMultiheadAttn(E, H, impl="default", bias=True)
        p = fast.init(jax.random.key(2))
        o1, _ = fast.apply(p, q, mem, is_training=False)
        o2, _ = dflt.apply(p, q, mem, is_training=False)
        assert o1.shape == (Tq, B, E)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=RTOL, atol=ATOL)

    def test_encoder_padding_mask(self):
        Tq, Tk, B, E, H = 8, 16, 2, 32, 4
        q = jax.random.normal(jax.random.key(0), (Tq, B, E))
        mem = jax.random.normal(jax.random.key(1), (Tk, B, E))
        mha = EncdecMultiheadAttn(E, H, impl="fast")
        p = mha.init(jax.random.key(2))
        kpm = jnp.zeros((B, Tk), bool).at[:, -6:].set(True)
        out, _ = mha.apply(p, q, mem, key_padding_mask=kpm,
                           is_training=False)
        mem2 = mem.at[-1].add(100.0)
        out2, _ = mha.apply(p, q, mem2, key_padding_mask=kpm,
                            is_training=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(out2),
                                   rtol=RTOL, atol=ATOL)


def test_default_bwd_blocks_odd_and_long_lengths():
    """Default backward-block selection: long sequences cap bwd_block_q
    at a {256,192,128} divisor of the padded length (the bwd-512 VMEM
    cliff, docs/PERF.md r04 block sweep); odd mid-lengths like S=300 (padded
    304, no such divisor) keep the forward block instead of collapsing
    to a sliver tile. Values AND grads must match the reference at both
    kinds of length."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    for s in (300, 768):
        ks = jax.random.split(jax.random.key(s), 3)
        q, k, v = (jax.random.normal(kk, (2, s, 32), jnp.float32)
                   for kk in ks)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-3)


class TestAutoCrossoverDispatch:
    """impl='auto' (VERDICT r4 #2): measured crossover routing — the
    composed XLA attention below flash_min_s, the Pallas kernel at or
    above it. Same honesty pattern as the measured BN-welford demotion."""
    T, B, E, H = 20, 2, 64, 4

    def _x(self):
        return jax.random.normal(jax.random.key(1), (self.T, self.B, self.E))

    def _routed(self, monkeypatch):
        """Record which attention core impl='auto' actually calls."""
        import apex_tpu.contrib.multihead_attn.modules as M
        calls = []
        real_flash, real_ref = M.flash_attention, M.reference_attention

        def spy_flash(*a, **k):
            calls.append("flash")
            return real_flash(*a, **k)

        def spy_ref(*a, **k):
            calls.append("reference")
            return real_ref(*a, **k)

        monkeypatch.setattr(M, "flash_attention", spy_flash)
        monkeypatch.setattr(M, "reference_attention", spy_ref)
        return calls

    def test_short_seq_routes_to_composed(self, monkeypatch):
        calls = self._routed(monkeypatch)
        mha = SelfMultiheadAttn(self.E, self.H, impl="auto",
                                flash_min_s=64)   # T=20 < 64
        p = mha.init(jax.random.key(0))
        mha.apply(p, self._x(), is_training=False)
        assert "reference" in calls and "flash" not in calls

    def test_long_seq_routes_to_flash(self, monkeypatch):
        calls = self._routed(monkeypatch)
        mha = SelfMultiheadAttn(self.E, self.H, impl="auto",
                                flash_min_s=16)   # T=20 >= 16
        p = mha.init(jax.random.key(0))
        mha.apply(p, self._x(), is_training=False)
        assert "flash" in calls and "reference" not in calls

    def test_auto_parity_across_the_crossover(self):
        # routing must be invisible in the numbers: auto == fast == default
        x = self._x()
        outs = {}
        for name, mod in [
            ("auto_ref", SelfMultiheadAttn(self.E, self.H, impl="auto",
                                           bias=True, flash_min_s=10**6)),
            ("auto_flash", SelfMultiheadAttn(self.E, self.H, impl="auto",
                                             bias=True, flash_min_s=1)),
            ("default", SelfMultiheadAttn(self.E, self.H, impl="default",
                                          bias=True)),
        ]:
            p = mod.init(jax.random.key(0))
            outs[name], _ = mod.apply(p, x, is_training=False)
        np.testing.assert_allclose(np.asarray(outs["auto_ref"]),
                                   np.asarray(outs["default"]),
                                   rtol=RTOL, atol=ATOL)
        np.testing.assert_allclose(np.asarray(outs["auto_flash"]),
                                   np.asarray(outs["default"]),
                                   rtol=RTOL, atol=ATOL)

    def test_threshold_resolution_env_beats_default(self, monkeypatch):
        """The kernel choice is a function of the environment and of
        committed code — no untracked file beside the module moves
        it."""
        import importlib
        import os
        # the package __init__ re-exports the flash_attention FUNCTION
        # under the submodule's name; import_module gets the module
        FA = importlib.import_module(
            "apex_tpu.contrib.multihead_attn.flash_attention")
        DA = importlib.import_module(
            "apex_tpu.contrib.multihead_attn.decode_attention")
        monkeypatch.delenv("APEX_FLASH_MIN_S", raising=False)
        monkeypatch.delenv("APEX_DECODE_MIN_L", raising=False)
        here = os.path.dirname(FA.__file__)
        written = [os.path.join(here, n) for n in
                   ("_crossover.json", "_decode_crossover.json")]
        try:
            for path, body in zip(written, ('{"flash_min_s": 2048}',
                                            '{"decode_min_l": 64}')):
                with open(path, "w") as f:
                    f.write(body)
            assert FA.flash_min_s() == FA.DEFAULT_FLASH_MIN_S
            assert DA.decode_min_l() == DA.DEFAULT_DECODE_MIN_L
        finally:
            for path in written:
                os.remove(path)
        monkeypatch.setenv("APEX_FLASH_MIN_S", "1024")
        monkeypatch.setenv("APEX_DECODE_MIN_L", "256")
        assert FA.flash_min_s() == 1024
        assert DA.decode_min_l() == 256

    def test_crossover_threshold_rule(self):
        import sys as _sys
        import os as _os
        _sys.path.insert(0, _os.path.join(_os.path.dirname(__file__),
                                          "..", "tools"))
        from kernel_bench import crossover_threshold

        def row(s, p, x):
            return {"bench": "flash_crossover", "config": f"bh16 s{s} d64",
                    "pallas_ms": p, "xla_ms": x}
        # kernel wins at 4096+: threshold 4096
        rows = [row(1024, 26.9, 2.2), row(2048, 12.0, 8.0),
                row(4096, 17.1, 31.6), row(8192, 40.0, 130.0)]
        assert crossover_threshold(rows) == 4096
        # a noisy single win below a loss must NOT lower the threshold
        rows = [row(1024, 2.0, 2.2), row(2048, 12.0, 8.0),
                row(4096, 17.1, 31.6)]
        assert crossover_threshold(rows) == 4096
        # kernel never qualifies -> None
        rows = [row(1024, 26.9, 2.2), row(4096, 50.0, 31.6)]
        assert crossover_threshold(rows) is None
        # within-5% tie at the small end counts as a win
        rows = [row(1024, 2.3, 2.2), row(4096, 17.1, 31.6)]
        assert crossover_threshold(rows) == 1024

    def test_memory_guard_overrides_short_seq_routing(self, monkeypatch):
        """Below the speed crossover but with a score matrix over the
        composed-memory budget, auto must still take the kernel (flash's
        O(S) memory always fits; composed would materialize [BH,Sq,Sk]
        fp32)."""
        calls = self._routed(monkeypatch)
        # T=20, B=2, H=4 -> BH=8; scores bytes = 8*20*20*4 = 12,800
        monkeypatch.setenv("APEX_FLASH_COMPOSED_BYTES", "1000")
        mha = SelfMultiheadAttn(self.E, self.H, impl="auto",
                                flash_min_s=10**6)
        p = mha.init(jax.random.key(0))
        mha.apply(p, self._x(), is_training=False)
        assert "flash" in calls and "reference" not in calls


class TestReferenceModuleSurface:
    """Reference positions 7-8 of SelfMultiheadAttn
    (self_multihead_attn.py:29): separate_qkv_params (distinct q/k/v
    parameter tensors, reference names) and mask_additive (float
    key_padding_mask), with the reference's consistency rules."""
    T, B, E, H = 12, 2, 32, 4

    def _x(self):
        return jax.random.normal(jax.random.key(1), (self.T, self.B, self.E))

    def test_separate_qkv_params_layout_and_parity(self):
        packed = SelfMultiheadAttn(self.E, self.H, bias=True)
        sep = SelfMultiheadAttn(self.E, self.H, 0.0, True, False, "fast",
                                True)   # reference positional order
        ps = sep.init(jax.random.key(0))
        assert set(ps) >= {"q_weight", "k_weight", "v_weight", "q_bias",
                           "k_bias", "v_bias", "out_proj"}
        # numerics: separate params packed back together must match the
        # packed module exactly
        pp = packed.init(jax.random.key(2))
        pp = dict(pp,
                  in_proj=jnp.concatenate(
                      [ps["q_weight"], ps["k_weight"], ps["v_weight"]],
                      axis=-1),
                  in_proj_bias=jnp.concatenate(
                      [ps["q_bias"], ps["k_bias"], ps["v_bias"]]),
                  out_proj=ps["out_proj"],
                  out_proj_bias=ps["out_proj_bias"])
        o_sep, _ = sep.apply(ps, self._x(), is_training=False)
        o_pack, _ = packed.apply(pp, self._x(), is_training=False)
        np.testing.assert_allclose(np.asarray(o_sep), np.asarray(o_pack),
                                   rtol=1e-5, atol=1e-6)

    def test_mask_additive_float_padding_mask(self):
        mha = SelfMultiheadAttn(self.E, self.H, bias=True,
                                mask_additive=True)
        boolm = SelfMultiheadAttn(self.E, self.H, bias=True)
        p = mha.init(jax.random.key(0))
        x = self._x()
        pad_bool = jnp.zeros((self.B, self.T), bool).at[:, -3:].set(True)
        pad_add = jnp.where(pad_bool, -1.0e30, 0.0)
        o_add, _ = mha.apply(p, x, key_padding_mask=pad_add,
                             is_training=False)
        o_bool, _ = boolm.apply(p, x, key_padding_mask=pad_bool,
                                is_training=False)
        np.testing.assert_allclose(np.asarray(o_add), np.asarray(o_bool),
                                   rtol=1e-5, atol=1e-6)

    def test_mask_additive_consistency_rules(self):
        with pytest.raises(ValueError, match="layer norm"):
            SelfMultiheadAttn(self.E, self.H, mask_additive=True,
                              include_norm_add=True, bias=True)
        with pytest.raises(ValueError, match="without bias"):
            SelfMultiheadAttn(self.E, self.H, mask_additive=True,
                              bias=False, impl="fast")
        SelfMultiheadAttn(self.E, self.H, mask_additive=True, bias=False,
                          impl="default")   # allowed by the reference
