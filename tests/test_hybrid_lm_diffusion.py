"""``HybridLM(block_diffusion=B)``: the block-diffusion loss (every
sequence twice through the layers, its noised copy beside its clean one;
the masked positions of the noised copy carry the loss, weighted by 1 / p)
against the plain reference ``benchmarks/reference/sdar.py``, that nothing
leaks across the mask, the scanned run, the step builder taking the triple,
and what the model refuses. A file of its own beside ``test_hybrid_lm.py``
(the suite's longest), so that the test run's workers can take it apart
from that file."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.hybrid_lm import MIXERS, HybridLM
from benchmarks.reference import sdar

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

BLOCK, LENGTH, VOCAB = 4, 16, 96
# the reference's keys for the tiny model below: 8 query heads over 2
# key/value heads of 8, a router 8 wide over the 4 experts held here
CFG = {"vocab_size": VOCAB, "num_hidden_layers": 2, "block_length": BLOCK,
       "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 8,
       "rms_norm_eps": 1e-6, "rope_theta": 1e6, "num_experts_per_tok": 3,
       "num_experts": 4, "expert_chips": 2, "expert_chip": 0,
       "router_aux_loss_coef": 0.001}


def _diffusion(**kw):
    base = dict(
        vocab_size=VOCAB, hidden=32, layer_types=("full",) * 2,
        block_diffusion=BLOCK, num_heads=8, num_kv_heads=2, head_dim=8,
        rotary_dim=8, rope_theta=1e6, attn_gate=False, num_experts=8,
        top_k=3, expert_ffn=16, shared_ffn=0, experts_held=(0, 4),
        zero_centred_norm=False)
    return HybridLM(**{**base, **kw})


def _batch(rows=2, length=LENGTH, key=0):
    """``(tokens, masked, p)``: data ids below the mask token's, a
    probability a sequence, each position masked with it."""
    k1, k2, k3 = jax.random.split(jax.random.key(key), 3)
    p = jax.random.uniform(k2, (rows,), minval=0.2, maxval=0.9)
    return (jax.random.randint(k1, (rows, length), 0, VOCAB - 1),
            jax.random.uniform(k3, (rows, length)) < p[:, None], p)


def _params(lm, key=0):
    p = lm.init(jax.random.key(key), scale=0.2)
    p["embed"] = p["embed"] * 5.0       # rows of unit scale, as the cell's
    return p


@pytest.mark.parametrize("impl", ["fast", "default"])
def test_loss_and_every_gradient_against_the_plain_reference(impl):
    """Two sequences of 16 positions in blocks of 4 through two layers: the
    loss and every leaf's gradient are ``reference/sdar.py``'s, through the
    flash kernels under the block-diffusion mask and through plain
    attention; the counters count what carried loss."""
    lm = _diffusion(attn_impl=impl, head_chunk=32)
    params, batch = _params(lm), _batch()
    (loss, counters), grad = jax.jit(jax.value_and_grad(
        lm.loss_with_counters, has_aux=True))(params, batch)
    want, want_grad, pairs, probe = sdar.batch_loss_and_grad(params, batch,
                                                             CFG)
    np.testing.assert_allclose(loss, want, rtol=2e-6)
    # what layer 0's heads made for the first noised rows, before W_o
    assert probe.shape == (2, sdar.PROBE_ROWS, 8 * 8)
    np.testing.assert_allclose(counters["diffusion_probe"], probe, atol=2e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grad),
                            jax.tree.leaves(want_grad)):
        np.testing.assert_allclose(
            a, b, atol=2e-6 + 1e-4 * float(jnp.abs(b).max()),
            err_msg=jax.tree_util.keystr(path))
    assert int(counters["diffusion_masked_tokens"]) == int(batch[1].sum())
    np.testing.assert_allclose(counters["diffusion_weight_max"],
                               1.0 / batch[2].min(), rtol=1e-6)
    # every one of the 2L rows a sequence chose its experts
    assert int(pairs.sum()) == 2 * 2 * (2 * LENGTH) * 3
    assert "load_balance_loss" not in counters
    assert int(counters["moe_overflow_pairs"]) == 0


def _through_the_layers(lm, params, x):
    for i, kind in enumerate(lm.layer_types):
        x, _ = lm._block(kind, params[f"layer_{i}"], x)
    return x


@pytest.mark.parametrize("impl", ["fast", "default"])
def test_nothing_leaks(impl):
    """From the 2L rows' embeddings through both layers: what the noised
    rows of block ``b`` come out as has an exactly zero gradient with
    respect to the clean rows of blocks ``>= b`` and the noised rows of
    every other block, and a non-zero one with respect to its own noised
    rows and the clean blocks before it; the clean rows' own outputs do not
    move when the noised half does."""
    lm = _diffusion(attn_impl=impl)
    params = _params(lm, key=1)
    length = LENGTH
    x = jax.random.normal(jax.random.key(2), (1, 2 * length, 32))
    w = jax.random.normal(jax.random.key(3), (length, 32))
    blocks = length // BLOCK

    def of_block(x, b):     # a scalar of block b's noised rows' outputs
        rows = slice(b * BLOCK, (b + 1) * BLOCK)
        return jnp.sum(_through_the_layers(lm, params, x)[0, rows] * w[rows])
    grads = jax.jit(lambda x: jnp.stack([
        jax.grad(of_block)(x, b)[0] for b in range(blocks)]))(x)
    moved = np.abs(np.asarray(grads)).reshape(
        blocks, 2, blocks, BLOCK * 32).max(-1)  # [b, noised | clean, block]
    for b in range(blocks):
        others = [c for c in range(blocks) if c != b]
        assert (moved[b, 0, others] == 0.0).all()
        assert (moved[b, 1, b:] == 0.0).all()
        assert moved[b, 0, b] > 1e-4 and (moved[b, 1, :b] > 1e-6).all()
    run = jax.jit(lambda x: _through_the_layers(lm, params, x))
    other = x.at[:, :length].add(1.0)
    np.testing.assert_array_equal(run(x)[:, length:], run(other)[:, length:])
    assert float(jnp.abs(run(x) - run(other))[:, :length].max()) > 1e-3


def test_five_layers_are_one_scanned_body_under_its_own_scope():
    """The run of five is one ``lax.scan``; under ``remat`` its body holds
    the forward kernel once, under its own name; the mixer's scope is
    ``diffusion_attention``, and ``MIXERS`` stays at seven."""
    assert len(MIXERS) == 7
    lm = _diffusion(layer_types=("full",) * 5, remat=True)
    params, batch = _params(lm), _batch()
    jaxpr = jax.make_jaxpr(lm.hidden_states)(
        params, jnp.concatenate([batch[0], batch[0]], 1))
    assert [e.params["length"] for e in jaxpr.eqns
            if e.primitive.name == "scan"] == [5]
    text = str(jax.make_jaxpr(jax.grad(
        lambda p, b: lm.loss_with_counters(p, b)[0]))(params, batch))
    for kernel in ("fwd", "bwd_dq", "bwd_dkv"):
        assert len(re.findall(rf"name=apex_flash_bd_{kernel}\b", text)) == 1
    assert not re.findall(r"name=apex_flash_(fwd|bwd|win|sel)", text)
    hlo = jax.jit(lambda p, b: lm.loss_with_counters(p, b)[0]).lower(
        params, batch).compile().as_text()
    assert "diffusion_attention" in hlo
    assert not re.search(r"[/(]attention[/)]", hlo)


def test_with_block_diffusion_0_the_model_is_todays():
    """It refuses the triple, and ``loss_with_counters`` is the next-token
    loss: the mean cross-entropy of ``apply``'s logits a position on, plus
    the balance term."""
    lm = _diffusion(block_diffusion=0)
    params, batch = _params(lm), _batch()
    with pytest.raises(ValueError, match="block_diffusion=0"):
        lm.diffusion_loss_with_counters(params, *batch)
    with pytest.raises(TypeError):
        lm.loss_with_counters(params, batch)
    toks = batch[0]
    loss, counters = jax.jit(lm.loss_with_counters)(params, toks)
    logp = jax.nn.log_softmax(lm.apply(params, toks[:, :-1]))
    xent = -jnp.mean(jnp.take_along_axis(logp, toks[:, 1:, None], -1))
    _, c = lm.hidden_states(params, toks[:, :-1])
    np.testing.assert_allclose(
        loss, xent + lm.aux_coef * c["load_balance_loss"], rtol=1e-5)
    assert "diffusion_masked_tokens" not in counters


@pytest.mark.parametrize("kw", [
    dict(layer_types=("full", "window"), window=8),
    dict(layer_types=("linear",)), dict(attn_gate=True),
    dict(block_diffusion=-4)])
def test_what_the_model_refuses_under_block_diffusion(kw):
    with pytest.raises(ValueError, match="block_diffusion"):
        _diffusion(**kw)


def test_the_triple_rides_through_the_step_builder():
    """``tools/lm_bench.build_train_step`` as the six hybrid cells use it:
    the batch is one pytree argument, ``(tokens, masked, p)``, through
    ``build_step``, ``place_for_plan`` and the compiled step; AMP O2 over
    the flat master, FusedAdam, the state donated; and the masked loss falls
    over a few steps on one batch."""
    import lm_bench
    from apex_tpu.parallel import compile_step_with_plan, make_mesh
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    lm = _diffusion(head_chunk=32, remat=True)
    opt, state, step, plan = lm_bench.build_train_step(
        lm, _params(lm), mesh, half=jnp.bfloat16, lr=3e-3)
    state, batch = lm_bench.place_for_plan(state, _batch(), plan)
    run = compile_step_with_plan(step, plan)
    losses = []
    for _ in range(6):
        state, (loss, counters) = run(state, batch)
        losses.append(float(loss))
    assert int(state[0].step) == 6 and np.isfinite(losses).all()
    assert losses[-1] < losses[0] - 0.1
    assert int(counters["diffusion_masked_tokens"]) == int(batch[1].sum())
    # a length the blocks do not divide is refused where the mask is made
    with pytest.raises(ValueError, match="block_diffusion"):
        lm.loss_with_counters(_params(lm), _batch(length=LENGTH + 2))
