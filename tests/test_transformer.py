"""TransformerLM tests: causality, training, and sequence-parallel parity
with the single-device model (the long-context story end to end).

check_vma=False throughout: TransformerLM's attention is the flash
pallas_call (interpret-mode on CPU), which does not support shard_map's
vma checking."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.models import TransformerLM
from apex_tpu.optimizers import FusedAdam
from apex_tpu.parallel import make_mesh

V, T, B = 50, 32, 2


def _model(**kw):
    cfg = dict(vocab_size=V, max_seq_len=64, embed_dim=32, num_heads=4,
               num_layers=2)
    cfg.update(kw)
    return TransformerLM(**cfg)


def _tokens(key=0):
    return jax.random.randint(jax.random.key(key), (B, T), 0, V)


def test_forward_shape_and_dtype():
    m = _model()
    p = m.init(jax.random.key(0))
    logits = m.apply(p, _tokens())
    assert logits.shape == (B, T, V)
    assert logits.dtype == jnp.float32


def test_causality():
    m = _model()
    p = m.init(jax.random.key(0))
    t1 = _tokens()
    t2 = t1.at[:, -1].set((t1[:, -1] + 1) % V)
    l1 = m.apply(p, t1)
    l2 = m.apply(p, t2)
    np.testing.assert_allclose(np.asarray(l1[:, :-1]),
                               np.asarray(l2[:, :-1]), rtol=1e-5, atol=1e-5)
    assert not np.allclose(np.asarray(l1[:, -1]), np.asarray(l2[:, -1]))


def test_impl_parity():
    fast = _model(attn_impl="fast")
    dflt = _model(attn_impl="default")
    p = fast.init(jax.random.key(0))
    l1 = fast.apply(p, _tokens())
    l2 = dflt.apply(p, _tokens())
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=1e-4, atol=1e-4)


def test_training_reduces_loss():
    m = _model()
    p = m.init(jax.random.key(0))
    opt = FusedAdam(p, lr=3e-3)
    table = opt._tables[0]
    state = opt.init_state()
    toks = _tokens()

    from apex_tpu.ops import flat as F

    @jax.jit
    def step(state):
        params = F.unflatten(state[0].master, table)
        loss, grads = jax.value_and_grad(
            lambda q: m.loss(q, toks))(params)
        fg = F.flatten(grads, table=table, dtype=jnp.float32)[0]
        return opt.apply_update(state, [fg]), loss

    losses = []
    for _ in range(12):
        state, loss = step(state)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.2, losses


N = 4


def test_sequence_parallel_matches_single_device():
    mesh = make_mesh({"seq": N}, devices=jax.devices()[:N])
    single = _model()
    sp = _model(seq_axis="seq", seq_axis_size=N)
    p = single.init(jax.random.key(0))
    toks = _tokens()

    logits_single = single.apply(p, toks)

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=(P(), P(None, "seq")),
             out_specs=P(None, "seq"), check_vma=False)
    def run_sp(p, toks):
        return sp.apply(p, toks)

    logits_sp = run_sp(p, toks)
    np.testing.assert_allclose(np.asarray(logits_sp),
                               np.asarray(logits_single),
                               rtol=2e-4, atol=2e-4)


def test_sequence_parallel_loss_matches_single_device():
    # loss() under seq_axis must keep the full-length shard (no per-shard
    # truncation) and shift targets across shard boundaries (ADVICE r1).
    mesh = make_mesh({"seq": N}, devices=jax.devices()[:N])
    single = _model()
    sp = _model(seq_axis="seq", seq_axis_size=N)
    p = single.init(jax.random.key(0))
    toks = _tokens()

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=(P(), P(None, "seq")),
             out_specs=P(), check_vma=False)
    def sp_loss(p, toks):
        return sp.loss(p, toks, is_training=False)

    # single-device oracle with the same target convention: predict token
    # j+1 from position j for every position except the global last.
    def oracle(q):
        logits = single.apply(q, toks)[:, :-1]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, toks[:, 1:, None], -1))

    got = sp_loss(p, toks)
    np.testing.assert_allclose(float(got), float(oracle(p)), rtol=2e-4)

    # grads through shard_map from outside (AD transposes the replicated
    # in_spec with a psum) must match the single-device oracle
    g1 = jax.grad(oracle)(p)
    g2 = jax.grad(lambda q: sp_loss(q, toks))(p)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=1e-5)


def test_sequence_parallel_grads_inside_shard_map():
    # The examples/lm/train_ring.py pattern: grad of model.loss taken
    # INSIDE shard_map. psum's transpose is psum, so each shard's raw grad
    # is n x its partial contribution; pmean reassembles the global grad.
    mesh = make_mesh({"seq": N}, devices=jax.devices()[:N])
    single = _model()
    sp = _model(seq_axis="seq", seq_axis_size=N)
    p = single.init(jax.random.key(0))
    toks = _tokens()

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=(P(), P(None, "seq")),
             out_specs=P(), check_vma=False)
    def sp_grads(p, toks):
        g = jax.grad(lambda q: sp.loss(q, toks, is_training=False))(p)
        return jax.tree.map(lambda x: jax.lax.pmean(x, "seq"), g)

    def oracle(q):
        logits = single.apply(q, toks)[:, :-1]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, toks[:, 1:, None], -1))

    g1 = jax.grad(oracle)(p)
    g2 = sp_grads(p, toks)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=1e-5)


def test_sequence_parallel_grads_match():
    mesh = make_mesh({"seq": N}, devices=jax.devices()[:N])
    single = _model()
    sp = _model(seq_axis="seq", seq_axis_size=N)
    p = single.init(jax.random.key(0))
    toks = _tokens()

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=(P(), P(None, "seq")),
             out_specs=P(), check_vma=False)
    def sp_loss(p, toks):
        logits = sp.apply(p, toks)
        # local mean of logit^2 -> global mean over shards
        return jax.lax.pmean(jnp.mean(logits ** 2), "seq")

    g1 = jax.grad(lambda q: jnp.mean(single.apply(q, toks) ** 2))(p)
    g2 = jax.grad(lambda q: sp_loss(q, toks))(p)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=1e-5)


class TestMoETransformer:
    """TransformerLM with Switch-MoE FFN layers (moe_experts set)."""

    def test_moe_lm_trains(self):
        from apex_tpu.models import TransformerLM
        lm = TransformerLM(vocab_size=512, max_seq_len=32, embed_dim=32,
                           num_heads=2, num_layers=2, moe_experts=4,
                           moe_every=2, moe_capacity_factor=2.0)
        params = lm.init(jax.random.key(0))
        assert "moe" in params["layer_1"] and "mlp" in params["layer_0"]
        rs = np.random.RandomState(0)
        base = rs.randint(0, 512, (4, 4))
        toks = jnp.asarray(np.repeat(base, 4, axis=1), jnp.int32)

        @jax.jit
        def step(p, toks):
            loss, g = jax.value_and_grad(lambda p: lm.loss(p, toks))(p)
            return jax.tree.map(lambda p, g: p - 0.5 * g, p, g), loss

        losses = []
        for _ in range(10):
            params, loss = step(params, toks)
            losses.append(float(loss))
        assert losses[-1] < losses[0] - 0.5, losses

    def test_moe_lm_expert_parallel_matches_dense(self):
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from apex_tpu.models import TransformerLM
        from apex_tpu.parallel import make_mesh
        ep = 4
        kw = dict(vocab_size=512, max_seq_len=32, embed_dim=32,
                  num_heads=2, num_layers=2, moe_experts=4, moe_every=2,
                  moe_capacity_factor=2.0)
        lm_d = TransformerLM(**kw)
        lm_p = TransformerLM(**kw, expert_axis="expert",
                             expert_axis_size=ep)
        params = lm_d.init(jax.random.key(1))
        toks = jax.random.randint(jax.random.key(2), (4, 17), 0, 512)
        loss_d = lm_d.loss(params, toks)

        mesh = make_mesh({"expert": ep}, devices=jax.devices()[:ep])
        especs = jax.tree.map(lambda _: P(), params)
        especs["layer_1"]["moe"] = {
            "router": P(), "w1": P("expert"), "b1": P("expert"),
            "w2": P("expert"), "b2": P("expert")}

        @jax.jit
        @partial(jax.shard_map, mesh=mesh, in_specs=(especs, P()),
                 out_specs=P(), check_vma=False)
        def loss_p(p, toks):
            return lm_p.loss(p, toks)

        np.testing.assert_allclose(float(loss_p(params, toks)),
                                   float(loss_d), rtol=2e-5, atol=2e-5)


def test_remat_grads_match():
    """remat=True must be a pure memory/flops tradeoff: identical loss
    and (allclose) identical gradients to the un-rematerialized model."""
    import dataclasses
    from apex_tpu.models import TransformerLM

    lm = TransformerLM(vocab_size=256, max_seq_len=32, embed_dim=64,
                       num_heads=4, num_layers=2)
    lm_r = dataclasses.replace(lm, remat=True)
    params = lm.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 17), 0, 256)

    l0, g0 = jax.value_and_grad(lambda p: lm.loss(p, toks))(params)
    l1, g1 = jax.value_and_grad(lambda p: lm_r.loss(p, toks))(params)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_remat_with_moe():
    import dataclasses
    from apex_tpu.models import TransformerLM

    lm = TransformerLM(vocab_size=128, max_seq_len=16, embed_dim=32,
                       num_heads=2, num_layers=2, moe_experts=4,
                       moe_every=2)
    lm_r = dataclasses.replace(lm, remat=True)
    params = lm.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 9), 0, 128)
    l0 = float(lm.loss(params, toks))
    l1 = float(lm_r.loss(params, toks))
    np.testing.assert_allclose(l0, l1, rtol=1e-6)
    g = jax.grad(lambda p: lm_r.loss(p, toks))(params)
    assert all(np.isfinite(np.asarray(x, np.float32)).all()
               for x in jax.tree.leaves(g))


@pytest.mark.parametrize("policy", [None, "dots_saveable",
                                    "nothing_saveable"])
def test_remat_policies_preserve_values_and_grads(policy):
    """remat (+ named jax.checkpoint_policies) must not change math."""
    kw = dict(vocab_size=32, max_seq_len=16, embed_dim=16, num_heads=2,
              num_layers=2)
    base = TransformerLM(**kw)
    rlm = TransformerLM(**kw, remat=True, remat_policy=policy)
    params = base.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 16), 0, 32)
    l0, g0 = jax.value_and_grad(lambda p: base.loss(p, toks))(params)
    l1, g1 = jax.value_and_grad(lambda p: rlm.loss(p, toks))(params)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(g0),
            jax.tree_util.tree_leaves_with_path(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))


def test_remat_policy_validation():
    # unknown names and factory attributes are rejected at construction
    for bad in ("not_a_policy", "save_only_these_names", "__doc__"):
        with pytest.raises(ValueError, match="remat_policy"):
            TransformerLM(vocab_size=32, max_seq_len=16, embed_dim=16,
                          num_heads=2, num_layers=1, remat=True,
                          remat_policy=bad)
    # a policy without remat would be silently ignored -> error
    with pytest.raises(ValueError, match="remat=False"):
        TransformerLM(vocab_size=32, max_seq_len=16, embed_dim=16,
                      num_heads=2, num_layers=1,
                      remat_policy="dots_saveable")


def _per_row_loss(m, p, toks):
    """The fused head's loss as it was before the reduced op: the mean of
    the per-row op's losses over the final hidden states."""
    from apex_tpu.contrib.xentropy import linear_cross_entropy
    hidden = m.apply(p, toks[:, :-1], is_training=False, return_hidden=True)
    return jnp.mean(linear_cross_entropy(
        hidden.reshape(-1, m.embed_dim), p["tok_emb"],
        toks[:, 1:].reshape(-1), chunk=m.head_chunk))


@pytest.mark.parametrize("oracle", ["whole_logits", "per_row_op"])
def test_head_chunk_loss_and_grads_match(oracle):
    """head_chunk routes loss through the reduced fused head (blocks of
    rows, the head's gradients made beside the loss); values and grads
    must match the materialized-logits path and the per-row op's mean
    (V=50 with chunk 10: multi-chunk label placement there)."""
    base = _model()
    chunked = _model(head_chunk=10)
    p = base.init(jax.random.key(0))
    toks = _tokens()
    want = {"whole_logits": lambda q: base.loss(q, toks, is_training=False),
            "per_row_op": lambda q: _per_row_loss(chunked, q, toks)}[oracle]
    l0 = want(p)
    l1 = chunked.loss(p, toks, is_training=False)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)
    g0 = jax.grad(want)(p)
    g1 = jax.grad(lambda q: chunked.loss(q, toks, is_training=False))(p)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_head_chunk_sequence_parallel_matches():
    mesh = make_mesh({"seq": N}, devices=jax.devices()[:N])
    dense = _model(head_chunk=10)
    sp = _model(seq_axis="seq", seq_axis_size=N, head_chunk=10)
    p = dense.init(jax.random.key(0))
    toks = _tokens()

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=(P(), P(None, "seq")),
             out_specs=P(), check_vma=False)
    def sp_loss(p, toks):
        return sp.loss(p, toks, is_training=False)

    def oracle(q):
        logits = dense.apply(q, toks)[:, :-1]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, toks[:, 1:, None], -1))

    np.testing.assert_allclose(float(sp_loss(p, toks)), float(oracle(p)),
                               rtol=2e-4)


def test_head_chunk_must_divide_vocab():
    with pytest.raises(ValueError, match="head_chunk"):
        _model(head_chunk=7)


def _per_row_sp_loss(sp, p, toks):
    """``TransformerLM.loss``'s sequence-parallel arm as it was before the
    reduced op (inside shard_map): the per-row op's losses, the global last
    position masked, the global mean by psum."""
    from apex_tpu.contrib.xentropy import linear_cross_entropy
    n, (b, t) = sp.seq_axis_size, toks.shape
    hidden = sp.apply(p, toks, is_training=False, return_hidden=True)
    nxt_first = jax.lax.ppermute(toks[:, :1], sp.seq_axis,
                                 [((i + 1) % n, i) for i in range(n)])
    targets = jnp.concatenate([toks[:, 1:], nxt_first], axis=1)
    losses = linear_cross_entropy(
        hidden.reshape(-1, sp.embed_dim), p["tok_emb"], targets.reshape(-1),
        chunk=sp.head_chunk).reshape(b, t)
    is_last_shard = jax.lax.axis_index(sp.seq_axis) == n - 1
    mask = jnp.ones((b, t), losses.dtype).at[:, -1].set(
        jnp.where(is_last_shard, 0.0, 1.0))
    return jax.lax.psum(jnp.sum(losses * mask), sp.seq_axis) \
        / jax.lax.psum(jnp.sum(mask), sp.seq_axis)


@pytest.mark.parametrize("oracle", ["whole_logits", "per_row_op"])
def test_head_chunk_sequence_parallel_grads_match(oracle):
    """Gradients of the fused-head custom_vjp through shard_map +
    ppermute target shift must match the single-device materialized
    oracle — the long-context SP training configuration the fused head
    exists for — and, with the loss, the same arm written with the
    per-row op."""
    mesh = make_mesh({"seq": N}, devices=jax.devices()[:N])
    dense = _model()
    sp = _model(seq_axis="seq", seq_axis_size=N, head_chunk=10)
    p = dense.init(jax.random.key(0))
    toks = _tokens()

    def sharded(fn):
        return jax.jit(partial(
            shard_map, mesh=mesh, in_specs=(P(), P(None, "seq")),
            out_specs=P(), check_vma=False)(fn))

    sp_loss = sharded(lambda p, toks: sp.loss(p, toks, is_training=False))

    def whole_logits(q):
        logits = dense.apply(q, toks)[:, :-1]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, toks[:, 1:, None], -1))

    per_row = sharded(partial(_per_row_sp_loss, sp))
    oracle, tol = {
        "whole_logits": (whole_logits, dict(rtol=5e-3, atol=1e-5)),
        "per_row_op": (lambda q: per_row(q, toks),
                       dict(rtol=1e-4, atol=1e-6))}[oracle]
    np.testing.assert_allclose(float(sp_loss(p, toks)), float(oracle(p)),
                               rtol=2e-4)
    g1 = jax.grad(oracle)(p)
    g2 = jax.grad(lambda q: sp_loss(q, toks))(p)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **tol)


# ---------------------------------------------------------------------------
# KV-cache generation
# ---------------------------------------------------------------------------

def _oracle_greedy(m, p, prompt, max_new):
    """Reference decode: repeated FULL forward + argmax (no cache)."""
    buf = np.asarray(prompt)
    for _ in range(max_new):
        logits = m.apply(p, jnp.asarray(buf))
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        buf = np.concatenate([buf, nxt[:, None].astype(np.int32)], axis=1)
    return buf


def test_generate_matches_full_recompute_greedy():
    """The KV-cache incremental decode must produce exactly the token
    sequence of repeated full forwards — the parity check that keeps
    _decode_one's re-implemented attention honest."""
    m = _model()
    p = m.init(jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (2, 5), 0, V)
    out = jax.jit(lambda p, t: m.generate(
        p, t, max_new_tokens=6))(p, prompt)
    want = _oracle_greedy(m, p, prompt, 6)
    np.testing.assert_array_equal(np.asarray(out), want)


def test_generate_moe_matches_full_recompute():
    m = _model(moe_experts=4, moe_every=2, moe_capacity_factor=4.0)
    p = m.init(jax.random.key(2))
    prompt = jax.random.randint(jax.random.key(3), (2, 4), 0, V)
    out = m.generate(p, prompt, max_new_tokens=4)
    want = _oracle_greedy(m, p, prompt, 4)
    np.testing.assert_array_equal(np.asarray(out), want)


@pytest.mark.parametrize("moe", [False, True])
def test_decode_slots_matches_vmapped_decode_one(moe):
    """The fused slot-batched decode step (r14 serve hot path) must be
    BIT-equal to ``_decode_one`` vmapped over slots — hidden states and
    cache writes — at per-slot positions, dense and MoE stacks alike.
    This is the model-level half of the serve engine's fused/unfused
    parity contract."""
    m = _model(moe_experts=2, moe_every=2) if moe else _model()
    p = m.init(jax.random.key(0))
    s, max_len = 3, 32
    h, hd = m.num_heads, m.embed_dim // m.num_heads
    key = jax.random.key(1)
    caches = {f"layer_{i}": (
        jax.random.normal(jax.random.fold_in(key, 2 * i),
                          (s, h, max_len, hd)),
        jax.random.normal(jax.random.fold_in(key, 2 * i + 1),
                          (s, h, max_len, hd)))
        for i in range(m.num_layers)}
    toks = jnp.asarray([3, 11, 42], jnp.int32)
    pos = jnp.asarray([0, 5, 17], jnp.int32)   # ragged slot positions

    def one(tok, pos, c):
        c1 = jax.tree.map(lambda x: x[None], c)
        hid, c1 = m._decode_one(p, tok[None], pos, c1)
        return hid[0], jax.tree.map(lambda x: x[0], c1)

    hid_v, c_v = jax.vmap(one)(toks, pos, caches)
    hid_f, c_f = m._decode_slots(p, toks, pos, caches)
    np.testing.assert_array_equal(np.asarray(hid_v), np.asarray(hid_f))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), c_v, c_f)


def test_generate_sampling_and_validation():
    m = _model()
    p = m.init(jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (2, 4), 0, V)
    s1 = m.generate(p, prompt, max_new_tokens=5, temperature=1.0,
                    key=jax.random.key(7))
    s2 = m.generate(p, prompt, max_new_tokens=5, temperature=1.0,
                    key=jax.random.key(7))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    assert s1.shape == (2, 9)
    np.testing.assert_array_equal(np.asarray(s1[:, :4]),
                                  np.asarray(prompt))
    with pytest.raises(ValueError, match="requires a PRNG key"):
        m.generate(p, prompt, max_new_tokens=2, temperature=1.0)
    with pytest.raises(ValueError, match="max_seq_len"):
        m.generate(p, prompt, max_new_tokens=m.max_seq_len)
    with pytest.raises(NotImplementedError, match="sequence parallel"):
        _model(seq_axis="seq", seq_axis_size=2).generate(
            p, prompt, max_new_tokens=2)


def test_generate_top_k_and_top_p():
    """top_k=1 at any temperature must equal greedy (only the argmax
    survives the filter); top_p filtering stays within the top-k=1
    vocabulary when p is tiny; filter validation raises."""
    m = _model()
    p = m.init(jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (2, 4), 0, V)
    greedy = m.generate(p, prompt, max_new_tokens=5)
    k1 = m.generate(p, prompt, max_new_tokens=5, temperature=1.0,
                    top_k=1, key=jax.random.key(9))
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(k1))
    # a tiny nucleus degenerates to the argmax as well
    p1 = m.generate(p, prompt, max_new_tokens=5, temperature=1.0,
                    top_p=1e-6, key=jax.random.key(9))
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(p1))
    # top_p=1.0 keeps the full distribution = plain sampling
    s_full = m.generate(p, prompt, max_new_tokens=5, temperature=1.0,
                        key=jax.random.key(3))
    s_p1 = m.generate(p, prompt, max_new_tokens=5, temperature=1.0,
                      top_p=1.0, key=jax.random.key(3))
    np.testing.assert_array_equal(np.asarray(s_full), np.asarray(s_p1))
    with pytest.raises(ValueError, match="top_k"):
        m.generate(p, prompt, max_new_tokens=2, temperature=1.0,
                   top_k=0, key=jax.random.key(0))
    with pytest.raises(ValueError, match="top_p"):
        m.generate(p, prompt, max_new_tokens=2, temperature=1.0,
                   top_p=1.5, key=jax.random.key(0))


def test_generate_eos_early_stop_matches_oracle():
    """eos_id semantics (the serving engine's retirement rule, exposed
    on generate): once a sequence emits eos_id its later positions are
    frozen to eos_id. Pinned against the uncached full-forward oracle
    with the identical latch applied."""
    m = _model()
    p = m.init(jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(4), (3, 5), 0, V)
    plain = np.asarray(m.generate(p, prompt, max_new_tokens=8))
    # an eos value greedy decode REALLY emits mid-stream for some row
    eos = int(plain[0, 5 + 3])
    got = np.asarray(m.generate(p, prompt, max_new_tokens=8,
                                eos_id=eos))

    # oracle: repeated full forwards, same latch
    buf = np.asarray(prompt)
    done = np.zeros(3, bool)
    for _ in range(8):
        logits = m.apply(p, jnp.asarray(buf))
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1),
                         np.int32)
        nxt = np.where(done, eos, nxt)
        done |= nxt == eos
        buf = np.concatenate([buf, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(got, buf)
    # the latch really froze a tail (row 0 hit eos at offset 3)
    assert (got[0, 5 + 3:] == eos).all()
    # rows that never emit eos are untouched vs the plain run
    untouched = ~(plain == eos).any(axis=1)
    if untouched.any():
        np.testing.assert_array_equal(got[untouched], plain[untouched])
    with pytest.raises(ValueError, match="eos_id"):
        m.generate(p, prompt, max_new_tokens=2, eos_id=V)


def test_prefill_caches_match_sequential_decode():
    """The batched pre-fill must fill the K/V caches (and final hidden)
    identically to P sequential one-token decode steps — pins the cache
    CONTENTS of the shared inference block stack, not just the argmax
    outcomes the oracle tests compare."""
    m = _model()
    p = m.init(jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (2, 6), 0, V)
    total = 9

    hid_batch, caches_batch = m._prefill(p, prompt, total)

    h, hd = m.num_heads, m.embed_dim // m.num_heads
    caches_seq = {
        f"layer_{i}": (jnp.zeros((2, h, total, hd)),
                       jnp.zeros((2, h, total, hd)))
        for i in range(m.num_layers)
    }
    for t in range(6):
        hid_seq, caches_seq = m._decode_one(p, prompt[:, t], t,
                                            caches_seq)
    np.testing.assert_allclose(np.asarray(hid_batch),
                               np.asarray(hid_seq), atol=1e-5,
                               rtol=1e-5)
    for i in range(m.num_layers):
        for a, b in zip(caches_batch[f"layer_{i}"],
                        caches_seq[f"layer_{i}"]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5)


def test_forward_rejects_overlong_sequence():
    """Same guard as generate(): the training forward must refuse t >
    max_seq_len instead of silently clamping the pos_emb gather."""
    lm = _model()
    p = lm.init(jax.random.key(0))
    over = jax.random.randint(jax.random.key(1), (2, lm.max_seq_len + 1),
                              0, V)
    with pytest.raises(ValueError, match="max_seq_len"):
        lm.apply(p, over)
