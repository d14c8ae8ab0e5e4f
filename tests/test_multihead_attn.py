"""Multihead attention tests: flash kernel vs unfused oracle, impl parity,
mask semantics, norm-add variants, grads (reference test model:
apex/contrib/test/multihead_attn/test_self_multihead_attn.py asserts
fast-vs-default parity for outputs and input grads)."""

import functools
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.contrib.multihead_attn import (
    SelfMultiheadAttn, EncdecMultiheadAttn,
    flash_attention, reference_attention)
from apex_tpu.contrib.multihead_attn.flash_attention import NEG_INF
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the module: the package's ``flash_attention`` is the function
fa = sys.modules["apex_tpu.contrib.multihead_attn.flash_attention"]

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


# ---------------------------------------------------------------------------
# Block kinds (PR 35): the kernels against a frozen copy of their parent's
# ---------------------------------------------------------------------------
# The three kernels as they stood before they classified their blocks
# (commit e79671d): a rectangle of grid steps, the index maps moving on
# every one, dead or live. Kept here, and nowhere in the package, as the
# fixed point the kernels' outputs and gradients must equal bit for bit.

def _parent_masked_scores(s, off_ref, qb, kb, causal):
    """Apply causal (global positions from SMEM offsets) and k-length
    (local padding, offs[2]) masks to a [bq, bk] score block."""
    bq, bk = s.shape
    k_local = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    s = jnp.where(k_local < off_ref[2], s, NEG_INF)
    if causal:
        q_pos = off_ref[0] + qb * bq + \
            jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = off_ref[1] + kb * bk + \
            jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    return s


def _parent_kvb_spec(kvb, block_k):
    """BlockSpec for the per-key bias [1|BH, 1, Sk]: a (1, 1, block_k)
    column slice, shared across batch-heads when the leading dim is 1."""
    shared = kvb.shape[0] == 1
    return pl.BlockSpec(
        (1, 1, block_k),
        (lambda b, i, j: (0, 0, j)) if shared else
        (lambda b, i, j: (b, 0, j)))


def _parent_block_live(off_ref, qb, kb, bq, bk, causal):
    """False when the (qb, kb) block is entirely masked (above the causal
    diagonal or past the k length) and its compute can be skipped."""
    live = kb * bk < off_ref[2]
    if causal:
        q_max = off_ref[0] + qb * bq + bq - 1
        k_min = off_ref[1] + kb * bk
        live = jnp.logical_and(live, q_max >= k_min)
    return live


def _parent_fwd_kernel(nk: int, causal: bool, has_bias: bool, has_kvb: bool,
                scale: float, dropout: float, *refs):
    refs = list(refs)
    off_ref, q_ref, k_ref, v_ref = refs[:4]
    del refs[:4]
    bias_ref = refs.pop(0) if has_bias else None
    kvb_ref = refs.pop(0) if has_kvb else None
    o_ref, lse_ref, acc_ref, m_ref, l_ref = refs

    bh_i, qb, kb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(_parent_block_live(off_ref, qb, kb, bq, bk, causal))
    def _body():
        q = q_ref[0].astype(jnp.float32)           # [bq, d]
        k = k_ref[0].astype(jnp.float32)           # [bk, d]
        v = v_ref[0].astype(jnp.float32)           # [bk, d]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [bq, bk]
        if has_bias:
            s = s + bias_ref[0].astype(jnp.float32)
        if has_kvb:
            s = s + kvb_ref[0].astype(jnp.float32)  # (1, bk) row-broadcast
        s = _parent_masked_scores(s, off_ref, qb, kb, causal)

        m_prev = m_ref[:, :1]                      # [bq, 1]
        row_max = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, row_max)
        # Rows with nothing unmasked yet must keep p == 0 (exp(NEG - NEG)
        # would otherwise contribute 1).
        p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - m_new), 0.0)  # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)            # [bq, 1]

        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        # dropout on the (to-be-normalized) probabilities: the softmax
        # denominator keeps ALL probs (reference dropout.h semantics —
        # dropout is applied to softmax results), so l accumulates the
        # undropped p while acc accumulates the masked, rescaled p.
        pa = p
        if dropout > 0.0:
            keep = fa._keep_mask(off_ref, bh_i, qb, kb, p.shape, dropout)
            pa = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout))
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            pa, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kb == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)
        lse = jnp.where(l > 0.0, m_ref[:, :1] + jnp.log(safe_l), NEG_INF)
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)


def _parent_flash_fwd(q, k, v, bias, kvb, offs, *, causal, scale, block_q, block_k,
               dropout=0.0):
    """q,k,v: [BH, S, D], pre-padded so block sizes divide S and D == lane
    multiple. offs: int32[4] = (q_start, k_start, k_len, seed) — k_len is
    the UNPADDED key length, masked in-kernel (no O(S^2) pad-bias tensor);
    seed drives the in-kernel dropout mask when ``dropout`` > 0.
    kvb: optional per-KEY additive bias [1|BH, 1, Sk] (key-padding masks)
    — O(S) instead of the O(S^2) bias tensor.
    Returns (o, lse[BH,S])."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq = sq // block_q
    nk = sk // block_k

    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),                     # offs
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),  # q
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),  # k
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),  # v
    ]
    args = [offs, q, k, v]
    has_bias = bias is not None
    if has_bias:
        bb = bias.shape[0]
        in_specs.append(pl.BlockSpec(
            (1, block_q, block_k),
            (lambda b, i, j: (0, i, j)) if bb == 1 else
            (lambda b, i, j: (b, i, j))))
        args.append(bias)
    has_kvb = kvb is not None
    if has_kvb:
        in_specs.append(_parent_kvb_spec(kvb, block_k))
        args.append(kvb)

    kernel = functools.partial(_parent_fwd_kernel, nk, causal, has_bias, has_kvb,
                               float(scale), float(dropout))
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, fa.LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            fa._sds((bh, sq, d), q.dtype, vma=fa._vma(q, k, v)),
            fa._sds((bh, sq, fa.LANES), jnp.float32, vma=fa._vma(q, k, v)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, fa.LANES), jnp.float32),
            pltpu.VMEM((block_q, fa.LANES), jnp.float32),
        ],
        interpret=fa._interpret(),
        name="apex_flash_fwd",
    )(*args)
    return o, lse[:, :, 0]


def _parent_recompute_p_ds(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
                    bias_ref, kvb_ref, bh_i, qb, kb, causal, scale, dropout):
    """Shared bwd block math: recompute p from saved lse, return (pd, ds, q,
    k, do) as fp32 — ``pd`` is the (dropout-masked, rescaled) probability
    used for dv. ds = p * (mask*dp/keep - delta); delta = rowsum(dO·O)
    already equals sum_k pd*dp so no extra correction is needed, and the
    lse cotangent is pre-folded into delta host-side (lse is dropout-free,
    and d(lse)/ds = p undropped, which is exactly the factor outside)."""
    q = q_ref[0].astype(jnp.float32)               # [bq, d]
    k = k_ref[0].astype(jnp.float32)               # [bk, d]
    v = v_ref[0].astype(jnp.float32)               # [bk, d]
    do = do_ref[0].astype(jnp.float32)             # [bq, d]
    lse = lse_ref[0][:, :1]                        # [bq, 1]
    delta = dlt_ref[0][:, :1]                      # [bq, 1]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if bias_ref is not None:
        s = s + bias_ref[0].astype(jnp.float32)
    if kvb_ref is not None:
        s = s + kvb_ref[0].astype(jnp.float32)
    s = _parent_masked_scores(s, off_ref, qb, kb, causal)

    # exp(NEG - NEG) guard: fully-masked rows have lse == NEG_INF
    p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - lse), 0.0)   # [bq, bk]
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                   # [bq, bk]
    if dropout > 0.0:
        keep = fa._keep_mask(off_ref, bh_i, qb, kb, p.shape, dropout)
        inv = 1.0 / (1.0 - dropout)
        pd = jnp.where(keep, p, 0.0) * inv
        dp = jnp.where(keep, dp, 0.0) * inv
    else:
        pd = p
    ds = p * (dp - delta)
    return pd, ds, q, k, do


def _parent_bwd_dq_kernel(nk: int, causal: bool, has_bias: bool, has_kvb: bool,
                   emit_dbias: bool, scale: float, dropout: float, *refs):
    refs = list(refs)
    (off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref) = refs[:7]
    del refs[:7]
    bias_ref = refs.pop(0) if has_bias else None
    kvb_ref = refs.pop(0) if has_kvb else None
    dq_ref = refs.pop(0)
    dbias_ref = refs.pop(0) if emit_dbias else None
    dq_acc = refs.pop(0)

    # program_id must be read OUTSIDE pl.when bodies: interpret mode only
    # substitutes grid indices for top-level reads
    bh_i, qb, kb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @pl.when(kb == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    live = _parent_block_live(off_ref, qb, kb, bq, bk, causal)

    @pl.when(live)
    def _body():
        _, ds, _, k, _ = _parent_recompute_p_ds(
            off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
            bias_ref, kvb_ref, bh_i, qb, kb, causal, scale, dropout)
        if dbias_ref is not None:
            dbias_ref[0] = ds
        dq_acc[...] += jax.lax.dot_general(
            ds * scale, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if dbias_ref is not None:
        @pl.when(jnp.logical_not(live))
        def _zero_dbias():
            dbias_ref[0] = jnp.zeros_like(dbias_ref[0])

    @pl.when(kb == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _parent_bwd_dkv_kernel(nq: int, causal: bool, has_bias: bool, has_kvb: bool,
                    scale: float, dropout: float, *refs):
    refs = list(refs)
    (off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref) = refs[:7]
    del refs[:7]
    bias_ref = refs.pop(0) if has_bias else None
    kvb_ref = refs.pop(0) if has_kvb else None
    dk_ref, dv_ref, dk_acc, dv_acc = refs

    bh_i, kb, qb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @pl.when(qb == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_parent_block_live(off_ref, qb, kb, bq, bk, causal))
    def _body():
        pd, ds, q, _, do = _parent_recompute_p_ds(
            off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
            bias_ref, kvb_ref, bh_i, qb, kb, causal, scale, dropout)
        dv_acc[...] += jax.lax.dot_general(
            pd, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bk, d]
        dk_acc[...] += jax.lax.dot_general(
            ds * scale, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bk, d]

    @pl.when(qb == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _parent_bwd_pallas(res, do, dlse, *, causal, scale, block_q, block_k,
                bias_grad, dropout=0.0):
    """Pallas flash backward over the padded residuals. Returns
    (dq, dk, dv, dbias) with dbias None when no bias was supplied and
    zeros when ``bias_grad`` is False (mask-only biases)."""
    q, k, v, bias, kvb, offs, lse, o = res
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq = sq // block_q
    nk = sk // block_k
    has_bias = bias is not None
    has_kvb = kvb is not None
    emit_dbias = has_bias and bias_grad
    # broadcast bias grads accumulate over bh in a dedicated kernel
    dbias_in_dq = emit_dbias and bias.shape[0] != 1

    do = do.astype(jnp.float32)
    # delta = rowsum(dO * O); the lse cotangent folds into the same
    # per-row subtraction: ds = p * (dp - (delta - dlse)).
    delta = jnp.sum(do * o.astype(jnp.float32), axis=-1)       # [bh, sq]
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    # lane-replicate row stats (the TPU-friendly [.., sq, 128] layout)
    lse_r = jnp.broadcast_to(lse[..., None], (*lse.shape, fa.LANES))
    dlt_r = jnp.broadcast_to(delta[..., None], (*delta.shape, fa.LANES))

    stat_spec_i = pl.BlockSpec((1, block_q, fa.LANES), lambda b, i, j: (b, i, 0))
    common = [
        pl.BlockSpec(memory_space=pltpu.SMEM),                      # offs
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),   # q
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),   # k
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),   # v
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),   # do
        stat_spec_i,                                                # lse
        stat_spec_i,                                                # delta
    ]
    args = [offs, q, k, v, do, lse_r, dlt_r]
    opt_specs = []
    if has_bias:
        bb = bias.shape[0]
        bias_spec = pl.BlockSpec(
            (1, block_q, block_k),
            (lambda b, i, j: (0, i, j)) if bb == 1 else
            (lambda b, i, j: (b, i, j)))
        args.append(bias)
        opt_specs.append(bias_spec)
    if has_kvb:
        kvb_spec = _parent_kvb_spec(kvb, block_k)
        args.append(kvb)
        opt_specs.append(kvb_spec)

    vma = fa._vma(q, k, v, do)

    # --- dq (+ per-bh dbias) over grid (bh, nq, nk) ------------------------
    dq_out_specs = [pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))]
    dq_out_shape = [fa._sds((bh, sq, d), q.dtype, vma=vma)]
    if dbias_in_dq:
        dq_out_specs.append(pl.BlockSpec(
            (1, block_q, block_k), lambda b, i, j: (b, i, j)))
        dq_out_shape.append(
            fa._sds((bh, sq, sk), jnp.float32, vma=vma))
    dq_res = pl.pallas_call(
        functools.partial(_parent_bwd_dq_kernel, nk, causal, has_bias, has_kvb,
                          dbias_in_dq, float(scale), float(dropout)),
        grid=(bh, nq, nk),
        in_specs=common + opt_specs,
        out_specs=dq_out_specs,
        out_shape=dq_out_shape,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=fa._interpret(),
        name="apex_flash_bwd_dq",
    )(*args)
    if dbias_in_dq:
        dq, dbias = dq_res
        dbias = dbias.astype(bias.dtype)
    else:
        (dq,) = dq_res if isinstance(dq_res, (list, tuple)) else (dq_res,)
        dbias = None
    assert not (emit_dbias and not dbias_in_dq), "per-bh bias only"
    if has_bias and not emit_dbias:
        dbias = jnp.zeros_like(bias)

    # --- dk / dv over grid (bh, nk, nq) ------------------------------------
    def _swap(spec):
        # same block shapes, but grid axes are (b, kb, qb): j := axis 1,
        # i := axis 2
        return pl.BlockSpec(spec.block_shape,
                            lambda b, j, i, _m=spec.index_map: _m(b, i, j))

    dkv_in_specs = [common[0]] + [_swap(s) for s in common[1:] + opt_specs]
    dk, dv = pl.pallas_call(
        functools.partial(_parent_bwd_dkv_kernel, nq, causal, has_bias, has_kvb,
                          float(scale), float(dropout)),
        grid=(bh, nk, nq),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            fa._sds((bh, sk, d), k.dtype, vma=vma),
            fa._sds((bh, sk, d), v.dtype, vma=vma),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=fa._interpret(),
        name="apex_flash_bwd_dkv",
    )(*args)
    return dq, dk, dv, dbias


def _parent_kernels(monkeypatch):
    # the parent's grids were rectangles: no use for offsets known ahead,
    # and it had no window (the calls here give none)
    monkeypatch.setattr(
        fa, "_flash_fwd", lambda *a, known=None, window=None, **kw:
        _parent_flash_fwd(*a, **kw))
    monkeypatch.setattr(
        fa, "_bwd_pallas", lambda *a, known=None, window=None, **kw:
        _parent_bwd_pallas(*a, **kw))


def _shard(q_start, k_start, s=512):
    """A ring step's call: one shard of queries against one of keys."""
    return dict(sq=s, sk=s, causal=True, q_start=q_start, k_start=k_start,
                block_q=256, block_k=256)


# sq, sk, d, then flash_attention's keywords; blocks default to 512 x 512
# forward and 256 x 512 backward from S = 1024 up
KIND_CASES = {
    "causal_d128": dict(sq=1536, sk=1536, d=128, causal=True),
    "causal_d256": dict(sq=1024, sk=1024, d=256, causal=True),
    "full_d128": dict(sq=1024, sk=1024, d=128),
    "key_pad": dict(sq=600, sk=1100, block_q=512, block_k=512),
    "causal_key_pad": dict(sq=1100, sk=1100, causal=True,
                           block_q=512, block_k=512),
    "causal_cross": dict(sq=512, sk=1536, causal=True, q_start=700),
    "dead_columns": dict(sq=300, sk=520, block_k=512, bwd_block_k=128),
    "small": dict(sq=37, sk=53, d=24, causal=True),
    "ring_dead": _shard(0, 1024),
    "ring_interior": _shard(1024, 0),
    "ring_diagonal": _shard(512, 512),
    "ring_straddle": _shard(512, 384),
    "bias": dict(sq=768, sk=768, causal=True, bias=True),
    "bias_full": dict(sq=512, sk=640, bias=True, block_q=256, block_k=256),
    "kv_bias": dict(sq=768, sk=768, causal=True, kv_bias=True),
    "kv_bias_full": dict(sq=512, sk=700, kv_bias=True),
    "dropout": dict(sq=1024, sk=1024, causal=True, dropout_rate=0.2,
                    dropout_seed=11),
    "dropout_bias": dict(sq=512, sk=512, causal=True, bias=True,
                         kv_bias=True, dropout_rate=0.1, dropout_seed=3,
                         block_q=256, block_k=256),
}


def _kind_case(sq, sk, d=128, bias=False, kv_bias=False, traced=False,
               q_start=0, k_start=0, **kw):
    bh = 2
    ks = jax.random.split(jax.random.key(sq + sk + d), 7)
    q, k, v = (jax.random.normal(kk, (bh, s, d), jnp.float32).astype(
        jnp.bfloat16) for kk, s in zip(ks, (sq, sk, sk)))
    # cotangents of o and of lse (the ring's merge differentiates both)
    do = jax.random.normal(ks[3], (bh, sq, d), jnp.float32).astype(
        jnp.bfloat16)
    dlse = jax.random.normal(ks[4], (bh, sq), jnp.float32)
    operands = [q, k, v]
    if bias:        # a bias that masks: the NEG_INF guard's case
        b = jax.random.normal(ks[5], (bh, sq, sk), jnp.float32)
        operands.append(jnp.where(b > 1.0, NEG_INF, b))
    if kv_bias:     # two keys in three padded out
        operands.append(jnp.where(
            jax.random.uniform(ks[6], (bh, sk)) > 0.33, 0.0, NEG_INF))

    def run(q_start, k_start, *ops):
        def f(q, k, v, *rest):
            rest = list(rest)
            return flash_attention(
                q, k, v, rest.pop(0) if bias else None,
                kv_bias=rest.pop(0) if kv_bias else None,
                q_start=q_start, k_start=k_start, return_lse=True, **kw)
        out, vjp = jax.vjp(f, *ops)
        return out, vjp((do, dlse))[:4 if bias else 3]

    if traced:      # a ring step's shard offsets: a block a step, dead too
        return jax.jit(run)(jnp.int32(q_start), jnp.int32(k_start),
                            *operands)
    return run(q_start, k_start, *operands)     # known: live blocks only


@pytest.mark.parametrize("case", sorted(KIND_CASES))
@pytest.mark.parametrize("traced", [False, True], ids=["known", "traced"])
def test_block_kinds_bitwise_equal_parent(case, traced, monkeypatch):
    """o, lse, dq, dk, dv (and dbias) of the kernels that give dead blocks
    no step (offsets known) or an empty one (traced) are the parent's, bit
    for bit: a live block's body, and the order of a line's blocks, are
    what they were."""
    got = _kind_case(traced=traced, **KIND_CASES[case])
    _parent_kernels(monkeypatch)
    want = _kind_case(traced=traced, **KIND_CASES[case])
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    names = ("o", "lse", "dq", "dk", "dv", "dbias")[:len(want)]
    for name, a, b in zip(names, got, want, strict=True):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a.shape == b.shape, name
        np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32),
                                      err_msg=f"{case}: {name}")
    assert np.isfinite(np.asarray(got[0], np.float32)).all()


def _census_case(backward, sq, sk, causal=False, q_start=0, k_start=0,
                 block_q=None, block_k=None, bwd_block_q=None,
                 bwd_block_k=None, **_):
    """block_census's arguments for a case's forward or backward grid
    (the backward tiles the forward's padded lengths)."""
    fq, fk, bq, bk = fa.block_sizes(sq, sk, block_q, block_k, bwd_block_q,
                                    bwd_block_k)
    c = dict(sq=sq, sk=sk, block_q=fq, block_k=fk, causal=causal,
             q_start=q_start, k_start=k_start, k_len=sk)
    if backward:
        c.update(sq=-(-sq // fq) * fq, sk=-(-sk // fk) * fk, block_q=bq,
                 block_k=bk)
    return c


@pytest.mark.parametrize("case", sorted(KIND_CASES))
@pytest.mark.parametrize("backward", [False, True])
def test_block_census_counts_the_mask(case, backward):
    """block_census against a brute-force count over the element-wise
    mask the kernels apply: a block with no element kept is dead, with
    every element kept interior, anything else edge."""
    c = _census_case(backward, **KIND_CASES[case])
    bq, bk = c["block_q"], c["block_k"]
    nq, nk = -(-c["sq"] // bq), -(-c["sk"] // bk)
    k_local = np.arange(nk * bk)[None, :]
    keep = np.broadcast_to(k_local < c["k_len"], (nq * bq, nk * bk))
    if c["causal"]:
        keep = keep & (c["q_start"] + np.arange(nq * bq)[:, None]
                       >= c["k_start"] + k_local)
    blocks = keep.reshape(nq, bq, nk, bk).transpose(0, 2, 1, 3).reshape(
        nq * nk, -1)
    want = {"dead": int((~blocks.any(1)).sum()),
            "interior": int(blocks.all(1).sum())}
    want["edge"] = nq * nk - want["dead"] - want["interior"]
    assert fa.block_census(**c) == want
    assert sum(want.values()) == nq * nk


@pytest.mark.parametrize("case", sorted(KIND_CASES))
@pytest.mark.parametrize("by_col", [False, True], ids=["rows", "columns"])
def test_step_table_sweeps_the_live_blocks(case, by_col):
    """The kernels' grid: every live block once, line after line (a q
    row's k blocks; ``by_col`` a k column's q blocks), each line's first
    and last step flagged, a line with no live block keeping one dead
    step; traced offsets pad the same steps to a block a step."""
    c = _census_case(by_col, **KIND_CASES[case])
    bq, bk, causal = c["block_q"], c["block_k"], c["causal"]
    nq, nk = -(-c["sq"] // bq), -(-c["sk"] // bk)
    offs = (c["q_start"], c["k_start"], c["k_len"])
    live = np.broadcast_to(fa._block_kind(
        offs, np.arange(nq)[:, None], np.arange(nk)[None, :], bq, bk,
        causal)[0], (nq, nk))

    def decode(steps):
        steps = np.asarray(steps)
        return (steps & fa._IDX, (steps >> fa._KB) & fa._IDX,
                (steps >> fa._LIVE) & 1, (steps >> fa._FIRST) & 1,
                (steps >> fa._LAST) & 1)

    known = fa._steps(offs, None, nq, nk, bq, bk, causal, by_col)
    qb, kb, is_live, first, last = decode(known)
    census = fa.block_census(**c)
    lines = live.T if by_col else live
    assert len(known) == census["interior"] + census["edge"] \
        + int((~lines.any(1)).sum())
    assert (is_live == live[qb, kb]).all()
    got = np.zeros((nq, nk), int)
    np.add.at(got, (qb, kb), 1)
    assert (got[live] == 1).all() and got.sum() == len(known)
    # a line's steps are consecutive and in order, first and last flagged
    line, sweep = (kb, qb) if by_col else (qb, kb)
    assert (np.diff(line) >= 0).all()
    assert (np.diff(sweep)[np.diff(line) == 0] > 0).all()
    starts = np.r_[True, np.diff(line) != 0]
    assert (first == starts).all() and (last == np.r_[starts[1:], True]).all()
    assert sorted(set(line)) == list(range(len(lines)))

    traced = fa._steps(None, jnp.asarray(offs), nq, nk, bq, bk, causal,
                       by_col)
    assert len(traced) == nq * nk
    np.testing.assert_array_equal(traced[:len(known)], known)
    tq, tk, is_live, first, last = decode(traced[len(known):])
    assert (tq == qb[-1]).all() and (tk == kb[-1]).all()     # no fetch
    assert not (is_live.any() or first.any() or last.any())

    every = fa._steps(offs, None, nq, nk, bq, bk, causal, by_col, every=True)
    qb, kb, is_live, _, _ = decode(every)
    assert len(every) == nq * nk and (is_live == live[qb, kb]).all()
    assert len(set(zip(qb, kb))) == nq * nk
    # where the table is the identity the index maps compute the block
    # from the step's number; anywhere else they read the table
    at = fa._at(every, offs, nq, nk, by_col)
    assert all(at(7, t) == (7, qb[t], kb[t]) for t in range(nq * nk))
    for steps, given in ((known, offs), (traced, None)):
        at = fa._at(steps, given, nq, nk, by_col)
        assert (at is fa._step_block) == (
            given is None or len(steps) < nq * nk)
        b, tq, tk = at(7, len(known) - 1, np.asarray(steps), None)
        assert (b, tq, tk) == (7, decode(known)[0][-1], decode(known)[1][-1])


def test_block_census_of_the_cells():
    """What tools/kernel_bench.py prints and docs/API.md tabulates."""
    assert fa.block_census(8192, 8192, 512, 512, True) == {
        "dead": 120, "interior": 120, "edge": 16}
    assert fa.block_census(8192, 8192, 256, 512, True) == {
        "dead": 240, "interior": 240, "edge": 32}
    assert fa.block_census(2048, 2048, 512, 512, True) == {
        "dead": 6, "interior": 6, "edge": 4}
    assert fa.block_census(2048, 2048, 256, 512, True) == {
        "dead": 12, "interior": 12, "edge": 8}
    # one ring step over four shards of 2,048: a shard wholly in the
    # future, wholly in the past, and the diagonal's own
    assert fa.block_census(2048, 2048, 512, 512, True, 0, 2048) == {
        "dead": 16, "interior": 0, "edge": 0}
    assert fa.block_census(2048, 2048, 512, 512, True, 4096, 2048) == {
        "dead": 0, "interior": 16, "edge": 0}
    # no mask but the k length: its last block is the only edge
    assert fa.block_census(1024, 1100, 512, 512, False) == {
        "dead": 0, "interior": 4, "edge": 2}


@pytest.mark.parametrize("policy, forwards", [
    (None, 2), ("nothing_saveable", 2), ("saved_names", 1)])
def test_the_forwards_two_outputs_are_saved_by_name(policy, forwards):
    """Differentiated, ``o`` and ``lse`` carry ``fa.SAVED_NAMES``: a
    ``jax.checkpoint`` whose policy saves those names runs
    ``apex_flash_fwd`` once where one that saves nothing runs it again
    in the backward (the names alone change nothing), and the gradient is
    bitwise the one without ``jax.checkpoint``."""
    q, k, v = _qkv(bh=2, sq=64, sk=64)

    def f(q, k, v):
        return jnp.sum(flash_attention(q * 2.0, k, v, causal=True) ** 2)
    policy = {None: None, "nothing_saveable":
              jax.checkpoint_policies.nothing_saveable,
              "saved_names": jax.checkpoint_policies.save_only_these_names(
                  *fa.SAVED_NAMES)}[policy]
    grad = jax.grad(jax.checkpoint(f, policy=policy), argnums=(0, 1, 2))
    text = str(jax.make_jaxpr(grad)(q, k, v))
    assert fa.SAVED_NAMES == ("apex_flash_out", "apex_flash_lse")
    assert all(f"name[name={name}]" in text for name in fa.SAVED_NAMES)
    assert len(re.findall(r"name=apex_flash_fwd\b", text)) == forwards
    assert len(re.findall(r"name=apex_flash_bwd_dq\b", text)) == 1
    for a, b in zip(grad(q, k, v), jax.grad(f, argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_array_equal(a, b)
