"""Fused xentropy vs plain log_softmax+NLL (reference:
apex/contrib/test/xentropy/test_label_smoothing.py shape: compare against a
composed PyTorch implementation, values and grads, with/without smoothing).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.contrib.xentropy import (SoftmaxCrossEntropyLoss,
                                       softmax_cross_entropy_loss)


def ref_loss(logits, labels, smoothing=0.0):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if smoothing == 0.0:
        return nll
    smooth = -jnp.mean(logp, axis=-1)
    return (1 - smoothing) * nll + smoothing * smooth


@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_values_match_composed(smoothing):
    rs = np.random.RandomState(0)
    logits = jnp.asarray(rs.randn(16, 10), jnp.float32)
    labels = jnp.asarray(rs.randint(1, 10, 16), jnp.int32)  # avoid pad=0
    got = softmax_cross_entropy_loss(logits, labels, smoothing)
    want = ref_loss(logits, labels, smoothing)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("smoothing", [0.0, 0.2])
def test_grads_match_composed(smoothing):
    rs = np.random.RandomState(1)
    logits = jnp.asarray(rs.randn(8, 12), jnp.float32)
    labels = jnp.asarray(rs.randint(1, 12, 8), jnp.int32)
    g1 = jax.grad(lambda l: jnp.sum(
        softmax_cross_entropy_loss(l, labels, smoothing)))(logits)
    g2 = jax.grad(lambda l: jnp.sum(ref_loss(l, labels, smoothing)))(logits)
    # The memory-saving backward recomputes softmax from the saved
    # max_log_sum_exp residual, so grads differ from the composed autodiff
    # path in the last fp32 ulps; the reference's own numerics bar is 1e-3
    # (reference: tests/L0/run_optimizers/test_adam.py:9-11).
    np.testing.assert_allclose(g1, g2, atol=1e-4, rtol=1e-4)


def test_padding_idx_masks_loss_and_grad():
    rs = np.random.RandomState(2)
    logits = jnp.asarray(rs.randn(6, 5), jnp.float32)
    labels = jnp.asarray([0, 1, 2, 0, 3, 4], jnp.int32)
    losses = SoftmaxCrossEntropyLoss.apply(logits, labels)
    assert float(losses[0]) == 0.0 and float(losses[3]) == 0.0
    g = jax.grad(lambda l: jnp.sum(
        softmax_cross_entropy_loss(l, labels)))(logits)
    np.testing.assert_allclose(g[0], 0.0)
    np.testing.assert_allclose(g[3], 0.0)
    assert float(jnp.abs(g[1]).sum()) > 0


def test_no_padding_mask():
    rs = np.random.RandomState(3)
    logits = jnp.asarray(rs.randn(4, 5), jnp.float32)
    labels = jnp.zeros((4,), jnp.int32)
    losses = softmax_cross_entropy_loss(logits, labels, padding_idx=None)
    assert float(jnp.abs(losses).sum()) > 0


def test_half_to_float_dtypes():
    rs = np.random.RandomState(4)
    logits = jnp.asarray(rs.randn(4, 8), jnp.bfloat16)
    labels = jnp.asarray(rs.randint(1, 8, 4), jnp.int32)
    out32 = softmax_cross_entropy_loss(logits, labels, half_to_float=True)
    out16 = softmax_cross_entropy_loss(logits, labels, half_to_float=False)
    assert out32.dtype == jnp.float32
    assert out16.dtype == jnp.bfloat16
    # grads keep the logit dtype either way
    g = jax.grad(lambda l: jnp.sum(
        softmax_cross_entropy_loss(l, labels)))(logits)
    assert g.dtype == jnp.bfloat16


def test_batched_leading_dims():
    rs = np.random.RandomState(5)
    logits = jnp.asarray(rs.randn(2, 7, 9), jnp.float32)
    labels = jnp.asarray(rs.randint(1, 9, (2, 7)), jnp.int32)
    got = softmax_cross_entropy_loss(logits, labels, 0.1)
    want = ref_loss(logits, labels, 0.1)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


class TestLinearCrossEntropy:
    """Chunked fused head+xentropy vs materialized logits + fused xent —
    losses and grads wrt BOTH hidden and weight must agree."""

    def _data(self, n=24, d=16, v=40, dtype=jnp.float32, seed=0):
        rs = np.random.RandomState(seed)
        h = jnp.asarray(rs.randn(n, d), dtype)
        w = jnp.asarray(rs.randn(v, d) * 0.1, dtype)
        labels = jnp.asarray(rs.randint(0, v, n), jnp.int32)
        return h, w, labels

    @pytest.mark.parametrize("smoothing", [0.0, 0.1])
    @pytest.mark.parametrize("chunk", [8, 40, 1 << 20])
    def test_matches_materialized(self, smoothing, chunk):
        from apex_tpu.contrib.xentropy import linear_cross_entropy
        h, w, labels = self._data()
        got = linear_cross_entropy(h, w, labels, smoothing=smoothing,
                                   chunk=chunk)
        want = softmax_cross_entropy_loss(
            (h @ w.T).astype(jnp.float32), labels, smoothing,
            padding_idx=None)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("smoothing", [0.0, 0.1])
    def test_grads_match_materialized(self, smoothing):
        from apex_tpu.contrib.xentropy import linear_cross_entropy
        h, w, labels = self._data()

        def fused(h, w):
            return jnp.mean(linear_cross_entropy(
                h, w, labels, smoothing=smoothing, chunk=8))

        def materialized(h, w):
            return jnp.mean(softmax_cross_entropy_loss(
                (h @ w.T).astype(jnp.float32), labels, smoothing,
                padding_idx=None))

        gh, gw = jax.grad(fused, argnums=(0, 1))(h, w)
        rh, rw = jax.grad(materialized, argnums=(0, 1))(h, w)
        np.testing.assert_allclose(np.asarray(gh), np.asarray(rh),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                                   rtol=1e-5, atol=1e-5)

    def test_padding_idx(self):
        from apex_tpu.contrib.xentropy import linear_cross_entropy
        h, w, labels = self._data()
        labels = labels.at[3].set(7)
        # padded rows: zero loss and zero hidden grad
        per_row = linear_cross_entropy(h, w, labels, padding_idx=7, chunk=8)
        assert float(per_row[3]) == 0.0
        gh = jax.grad(lambda h: linear_cross_entropy(
            h, w, labels, padding_idx=7, chunk=8).sum())(h)
        np.testing.assert_array_equal(np.asarray(gh[3]), 0.0)
        assert np.all(np.abs(np.asarray(gh[:3])) > 0)

    def test_bf16_inputs(self):
        from apex_tpu.contrib.xentropy import linear_cross_entropy
        h, w, labels = self._data(dtype=jnp.bfloat16)
        got = linear_cross_entropy(h, w, labels, chunk=8)
        want = softmax_cross_entropy_loss(
            (h.astype(jnp.float32) @ w.astype(jnp.float32).T), labels, 0.0,
            padding_idx=None)
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-2, atol=3e-2)
        gh = jax.grad(lambda h: linear_cross_entropy(
            h, w, labels, chunk=8).sum())(h)
        assert gh.dtype == jnp.bfloat16

    def test_bad_chunk_raises(self):
        from apex_tpu.contrib.xentropy import linear_cross_entropy
        h, w, labels = self._data(v=40)
        with pytest.raises(ValueError, match="chunk"):
            linear_cross_entropy(h, w, labels, chunk=7)

    def test_extreme_logit_magnitudes_stable(self):
        """Online logsumexp must stay finite and accurate when chunk
        maxima differ wildly (rescale path) and logits are large —
        compared against a float64 composed oracle."""
        from apex_tpu.contrib.xentropy import linear_cross_entropy
        rs = np.random.RandomState(3)
        h = jnp.asarray(rs.randn(8, 16) * 30.0, jnp.float32)
        w = jnp.asarray(rs.randn(64, 16) * 30.0, jnp.float32)
        labels = jnp.asarray(rs.randint(0, 64, 8), jnp.int32)
        got = linear_cross_entropy(h, w, labels, chunk=8)
        assert bool(jnp.all(jnp.isfinite(got)))
        z = np.asarray(h, np.float64) @ np.asarray(w, np.float64).T
        lse = np.log(np.sum(np.exp(z - z.max(1, keepdims=True)), 1)) \
            + z.max(1)
        want = lse - z[np.arange(8), np.asarray(labels)]
        # fp32 matmul of ~1e3-scale values: relative agreement
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4)

    def test_all_labels_in_last_chunk(self):
        """Label logits accumulate correctly when every label lands in
        the final scan chunk (off-by-one in the offset math would zero
        them)."""
        from apex_tpu.contrib.xentropy import linear_cross_entropy
        rs = np.random.RandomState(4)
        h = jnp.asarray(rs.randn(12, 8), jnp.float32)
        w = jnp.asarray(rs.randn(32, 8), jnp.float32)
        labels = jnp.asarray(rs.randint(24, 32, 12), jnp.int32)
        got = linear_cross_entropy(h, w, labels, chunk=8)
        want = softmax_cross_entropy_loss((h @ w.T), labels,
                                          padding_idx=None)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


class TestWeightedLinearCrossEntropy:
    """The reduced fused head (``sum(row_weights * rows' losses)`` over
    blocks of rows, its gradients made beside the loss) against the per-row
    op under the same weights: value, ``dh`` and ``dW``."""

    N, D, V = 24, 16, 40

    def _data(self, dtype, weights, seed=0):
        rs = np.random.RandomState(seed)
        h = jnp.asarray(rs.randn(self.N, self.D), dtype)
        w = jnp.asarray(rs.randn(self.V, self.D) * 0.1, dtype)
        labels = jnp.asarray(rs.randint(0, self.V, self.N), jnp.int32)
        labels = labels.at[3].set(7).at[20].set(7)
        p = rs.uniform(0.2, 0.9, self.N).astype(np.float32)
        rw = {"mask": rs.rand(self.N) < 0.5,            # zeros: no loss
              "over_p": 1.0 / p}[weights]               # non-uniform
        return h, w, labels, jnp.asarray(rw, jnp.float32)

    # one block of rows (the op's own choice), several, and a size the
    # rows do not divide by
    @pytest.mark.parametrize("rows", [None, 8, 7])
    @pytest.mark.parametrize("weights", ["mask", "over_p"])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("padding_idx", [None, 7])
    @pytest.mark.parametrize("smoothing", [0.0, 0.1])
    def test_value_and_gradients_are_the_per_row_ops(
            self, smoothing, padding_idx, dtype, weights, rows):
        from apex_tpu.contrib.xentropy import (
            linear_cross_entropy, weighted_linear_cross_entropy)
        h, w, labels, rw = self._data(jnp.dtype(dtype), weights)
        kw = dict(smoothing=smoothing, padding_idx=padding_idx)

        # a scaled loss: the cotangent that reaches the op is 48, not 1
        def reduced(h, w):
            return 48.0 * weighted_linear_cross_entropy(
                h, w, labels, rw, _rows=rows, **kw)

        def per_row(h, w):
            return 48.0 * jnp.sum(rw * linear_cross_entropy(
                h, w, labels, chunk=8, **kw))

        got, (gh, gw) = jax.value_and_grad(reduced, (0, 1))(h, w)
        want, (want_h, want_w) = jax.value_and_grad(per_row, (0, 1))(h, w)
        assert got.dtype == jnp.float32 and got.shape == ()
        assert gh.dtype == h.dtype and gw.dtype == w.dtype
        # bf16: both round a float32 sum once, at the end; an ulp apart
        tol = dict(rtol=1e-5, atol=1e-4) if dtype == "float32" \
            else dict(rtol=2e-2, atol=2e-2)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(gh, np.float32),
                                   np.asarray(want_h, np.float32), **tol)
        np.testing.assert_allclose(np.asarray(gw, np.float32),
                                   np.asarray(want_w, np.float32), **tol)
        # rows at weight zero and padded rows: no gradient at all
        dead = np.asarray(rw) == 0
        if padding_idx is not None:
            dead |= np.asarray(labels) == padding_idx
        np.testing.assert_array_equal(np.asarray(gh, np.float32)[dead], 0.0)
        # not differentiated: the same loss
        np.testing.assert_allclose(float(reduced(h, w)), float(got),
                                   rtol=1e-6)

    @pytest.mark.parametrize("vocab, rows", [
        (8192, 1024), (18992, 1024), (20480, 1024), (24576, 1024),
        (24577, 2048), (50257, 2048), (151936, 2048)])
    def test_the_blocks_rows_come_from_the_vocabulary(self, vocab, rows):
        """1,024 rows where their float32 logits fit the share of VMEM
        that XLA gives a loop's temporary (the hybrid cells' vocabularies),
        2,048 above it (the dense cell's)."""
        from apex_tpu.contrib.xentropy import linear_xentropy
        assert linear_xentropy._block_rows(vocab) == rows
        assert (4 * rows * vocab <= linear_xentropy.VMEM_LOGITS) \
            == (rows == 1024)

    def test_no_gradient_reaches_the_weights_and_shapes_are_checked(self):
        from apex_tpu.contrib.xentropy import weighted_linear_cross_entropy
        h, w, labels, rw = self._data(jnp.float32, "over_p")
        g = jax.grad(lambda rw: weighted_linear_cross_entropy(
            h, w, labels, rw))(rw)
        np.testing.assert_array_equal(np.asarray(g), 0.0)
        with pytest.raises(ValueError, match="row_weights"):
            weighted_linear_cross_entropy(h, w, labels, rw[:-1])
        with pytest.raises(ValueError, match="labels"):
            weighted_linear_cross_entropy(h, w, labels[:-1], rw[:-1])

    @staticmethod
    def _vocabulary_wide_matmuls(jaxpr, v):
        """``dot_general``s with a dimension of ``v`` in an operand or the
        result, through every sub-jaxpr (a scan's body counts once: a
        block)."""
        n = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general" and any(
                    v in x.aval.shape for x in eqn.invars + eqn.outvars):
                n += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                n += TestWeightedLinearCrossEntropy._vocabulary_wide_matmuls(
                    sub, v)
        return n

    @pytest.mark.parametrize("op, matmuls", [("reduced", 3), ("per_row", 4)])
    def test_the_differentiated_step_holds_three_matmuls_a_block(
            self, op, matmuls):
        """The mechanism's counter: logits, ``dh`` and ``dW`` and no second
        pass of logits, where the per-row op (one chunk: the whole
        vocabulary) makes its logits again in the backward."""
        from apex_tpu.contrib.xentropy import (
            linear_cross_entropy, weighted_linear_cross_entropy)
        h, w, labels, rw = self._data(jnp.float32, "over_p")
        loss = {
            "reduced": lambda h, w: weighted_linear_cross_entropy(
                h, w, labels, rw, _rows=8),
            "per_row": lambda h, w: jnp.sum(rw * linear_cross_entropy(
                h, w, labels, chunk=self.V))}[op]
        jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1)))(h, w)
        assert self._vocabulary_wide_matmuls(jaxpr.jaxpr, self.V) == matmuls
        if op == "reduced":     # and alone it is one: the logits
            assert self._vocabulary_wide_matmuls(
                jax.make_jaxpr(loss)(h, w).jaxpr, self.V) == 1


@pytest.mark.parametrize("seed", range(6))
def test_fuzz_vs_torch_cross_entropy(seed):
    """Randomized fuzz against the REAL torch oracle: random N/V (odd,
    non-128 sizes), random label smoothing, with/without an
    ignore_index (the reference's padding_idx), values and logit
    grads. The fixed cases above compare against composed-jnp math;
    this pins the semantics to torch's own cross_entropy."""
    torch = pytest.importorskip("torch")
    rng = np.random.default_rng(7000 + seed)
    n = int(rng.integers(3, 40))
    v = int(rng.integers(5, 700))
    smoothing = float(rng.choice([0.0, 0.05, 0.3]))
    use_pad = bool(rng.integers(0, 2))
    logits_np = rng.normal(size=(n, v)).astype(np.float32) * 3.0
    labels_np = rng.integers(0, v, n).astype(np.int64)
    pad = 0 if use_pad else None
    if use_pad:
        labels_np[: max(1, n // 4)] = 0  # some rows genuinely padded

    lt = torch.tensor(logits_np, requires_grad=True)
    want = torch.nn.functional.cross_entropy(
        lt, torch.tensor(labels_np), reduction="none",
        label_smoothing=smoothing,
        ignore_index=0 if use_pad else -100)
    want.sum().backward()

    logits = jnp.asarray(logits_np)
    labels = jnp.asarray(labels_np, jnp.int32)
    got = softmax_cross_entropy_loss(logits, labels, smoothing,
                                     padding_idx=pad)
    np.testing.assert_allclose(np.asarray(got), want.detach().numpy(),
                               rtol=2e-5, atol=2e-5)
    g = jax.grad(lambda l: jnp.sum(softmax_cross_entropy_loss(
        l, labels, smoothing, padding_idx=pad)))(logits)
    np.testing.assert_allclose(np.asarray(g), lt.grad.numpy(),
                               rtol=2e-4, atol=2e-4)
