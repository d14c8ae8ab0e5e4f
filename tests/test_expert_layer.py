"""The chip's share of a many-expert layer, under both router kinds:
against a dense computation of the same mathematics, the shares adding up
to the uncut layer, no pair on a held expert dropped under a skewed
router, pairs past the bound counted; the sigmoid router's bias moving
the choice and not the weights, and its move after a step by hand; a
buffer whose dead rows hold tokens' rows under no mask, which their zero
weights keep out of the result and of every gradient; the map between
pairs and rows read both ways, and the two sums between tokens and rows
as gather-sums by it: the row a pair names is the row that holds it, a
dead row is kept out by the mask, the gradients are the oracle's, the
gradient's program scatters nothing into ``[tokens, hidden]``, and what
the sums add runs under ``moe_route``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.analysis import walker
from apex_tpu.contrib.moe import ExpertLayer, expert_layer

D, F, E, K = 32, 16, 32, 4
ROUTERS = ["softmax", "sigmoid"]
SCALE = 2.5     # the sigmoid router's factor on the weights


class SmallTiles(ExpertLayer):
    tile = 8        # the layer's is 128: the chip's; no constructor knob


def _layer(held=(), router="softmax", experts=E, shared=F, top=K, **kw):
    return SmallTiles(hidden=D, ffn=F, num_experts=experts, top_k=top,
                      experts_held=held, shared_ffn=shared, router=router,
                      routed_scale=SCALE if router == "sigmoid" else 1.0,
                      **kw)


def _params(key=0, scale=0.3, **kw):
    return _layer(**kw).init(jax.random.key(key), scale)


def _share(params, lo, hi):
    """The leaves a chip holding experts ``[lo, hi)`` has."""
    return {**params, **{k: params[k][lo:hi]
                         for k in ("w_gate", "w_up", "w_down")}}


def _dense(params, x, lo=0, hi=None, router="softmax", bias=0.0, top=K):
    """Every expert of ``[lo, hi)`` over every token, weighted by the
    token's renormalised top-k weight for it (a constant in a share's
    backward). The sigmoid router chooses on score + bias and weighs by
    the scores themselves, times its factor."""
    experts = params["router"].shape[1]
    hi = experts if hi is None else hi
    if router == "sigmoid":
        scores = jax.nn.sigmoid(x @ params["router"])
        _, idx = jax.lax.top_k(scores + bias, top)
        w = jnp.take_along_axis(scores, idx, -1)
        w = SCALE * w / w.sum(-1, keepdims=True)
    else:
        probs = jax.nn.softmax(x @ params["router"], -1)
        w, idx = jax.lax.top_k(probs, top)
        w = w / w.sum(-1, keepdims=True)
    if hi - lo < experts:
        w = jax.lax.stop_gradient(w)
    y = jnp.zeros_like(x)
    for e in range(lo, hi):
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), -1, keepdims=True)
        h = jax.nn.silu(x @ params["w_gate"][e]) * (x @ params["w_up"][e])
        y = y + w_e * (h @ params["w_down"][e])
    return y


def _dense_loss(params, lo, hi, router="softmax"):
    """``sum(sin(_dense))`` as a function of a share's leaves and ``x``,
    the share's experts set into the whole layer's ``params``."""
    def loss(p, x):
        full = {**params, **{k: params[k].at[lo:hi].set(p[k])
                             for k in ("w_gate", "w_up", "w_down")},
                "router": p["router"]}
        return jnp.sum(jnp.sin(_dense(full, x, lo, hi, router)))
    return loss


def _x(n=64, key=1):
    return jax.random.normal(jax.random.key(key), (n, D))


@pytest.mark.parametrize("router", ROUTERS)
def test_the_uncut_layer_is_the_dense_mixture_plus_the_shared_expert(router):
    params, x = _params(router=router), _x()
    layer = _layer(router=router)
    y, aux = layer.apply(params, x)
    want = _dense(params, x, router=router) + layer.shared(params, x)
    np.testing.assert_allclose(y, want, atol=1e-5)
    assert int(aux["overflow_pairs"]) == 0
    # the shared expert has a gate of its own under the softmax router only
    assert ("gate" in params["shared"]) == (router == "softmax")


@pytest.mark.parametrize("router", ROUTERS)
@pytest.mark.parametrize("experts, chips, shared, top", [
    (E, 4, F, K), (E, 32, F, K), (64, 8, F, K), (64, 8, 0, K),
    (64, 4, 0, 8), (128, 8, 0, 8)])
def test_the_shares_add_up(experts, chips, shared, top, router):
    """The parts all shares give, the shared expert counted once, are the
    uncut layer (the eight shares of a 64-expert top-4 layer among them,
    with a shared expert and, the bias-balanced sigmoid layer of the
    short-convolution hybrids, with none; the four shares of a 64-expert
    top-8 layer with none, the softmax layer of the sliding-window
    models; the eight shares of a 128-expert top-8 layer with none, the
    block-diffusion and the learned-key-set models'); and a share computes
    its own experts' part, nothing that
    stands in for the others."""
    kind = dict(router=router, experts=experts, shared=shared, top=top)
    params, x = _params(2, **kind), _x(48, 3)
    bias = 0.0 if router == "softmax" else 0.2 * jax.random.normal(
        jax.random.key(4), (experts,))
    uncut, _ = _layer(**kind).apply(params, x, bias)
    per = experts // chips
    total = 0.0
    for c in range(chips):
        lo, hi = c * per, (c + 1) * per
        layer = _layer((lo, hi), **kind)
        part, aux = layer.routed(_share(params, lo, hi), x, bias)
        np.testing.assert_allclose(
            part, _dense(params, x, lo, hi, router, bias, top), atol=1e-5)
        assert int(aux["overflow_pairs"]) == 0
        total = total + part
    assert ("shared" in params) == bool(shared)
    if shared:
        total = total + _layer(**kind).shared(params, x)
    np.testing.assert_allclose(total, uncut, atol=1e-5)


def test_the_bias_moves_the_choice_and_not_the_weights():
    """The sigmoid router chooses on score + bias and weighs by the
    scores: a bias that lifts expert 5 into every token's choice leaves
    each chosen expert's weight what its own score makes it, and no
    gradient reaches the bias."""
    params, x = _params(14, router="sigmoid"), _x(40, 15)
    layer = _layer(router="sigmoid")
    w0, idx0, scores = layer.route(params, x)
    bias = jnp.zeros((E,)).at[5].set(10.0)
    w, idx, _ = layer.route(params, x, bias)
    assert bool(jnp.all(jnp.any(idx == 5, -1)))
    assert not bool(jnp.all(jnp.any(idx0 == 5, -1)))
    picked = jnp.take_along_axis(scores, idx, -1)
    np.testing.assert_allclose(
        w, SCALE * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(w.sum(-1), SCALE, rtol=1e-6)
    g = jax.grad(lambda b: jnp.sum(jnp.sin(layer.apply(params, x, b)[0])))(
        bias)
    assert float(jnp.abs(g).max()) == 0.0
    # every expert's pairs come out beside the result, under the bias
    pairs = layer.routed(params, x, bias)[1]["expert_pairs"]
    assert int(pairs[5]) == 40 and int(pairs.sum()) == 40 * K


def test_the_bias_moves_against_the_load_by_hand():
    """``b_e += u sign(mean(c) - c_e)`` on a skewed count, a row a layer:
    the overloaded expert goes down, the starved up, one at the mean
    stays."""
    pairs = jnp.array([[10, 2, 6, 6], [0, 0, 0, 8]])
    bias = jnp.array([[0.5, 0.0, -0.25, 0.0], [0.0, 0.0, 0.0, 0.0]])
    got = ExpertLayer.moved_bias(bias, pairs, 0.01)
    np.testing.assert_allclose(
        got, [[0.49, 0.01, -0.25, 0.0], [0.01, 0.01, 0.01, -0.01]],
        atol=1e-7)


@pytest.mark.parametrize("lo,hi", [(8, 16), (0, E)])
def test_gradients_are_the_dense_mixtures(lo, hi):
    params, x = _params(4), _x(40, 5)
    layer = _layer((lo, hi))
    mine = _share(params, lo, hi)

    def ours(p, x):
        return jnp.sum(jnp.sin(layer.routed(p, x)[0]))
    got = jax.grad(ours, argnums=(0, 1))(mine, x)
    want = jax.grad(_dense_loss(params, lo, hi), argnums=(0, 1))(mine, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_a_shares_router_learns_from_the_balancing_term_alone():
    """The held experts' outputs reward the router only where every
    expert is held: a share's weights are constants in the backward (or
    the router runs away to the experts that are here), and its
    load-balancing term still reaches the router."""
    params, x = _params(12), _x(56, 13)

    def through_outputs(layer, p):
        return jax.grad(lambda p: jnp.sum(jnp.sin(layer.routed(p, x)[0])))(p)
    share = _layer((8, 16))
    mine = _share(params, 8, 16)
    assert float(jnp.abs(through_outputs(share, mine)["router"]).max()) == 0
    assert float(jnp.abs(through_outputs(share, mine)["w_down"]).max()) > 0
    assert float(jnp.abs(through_outputs(_layer(), params)["router"])
                 .max()) > 0
    balance = jax.grad(lambda p: share.routed(p, x)[1]["load_balance_loss"])(
        mine)
    assert float(jnp.abs(balance["router"]).max()) > 0


def _skewed(router, big=()):
    """A router that sends nearly every token to experts 8..10 (a
    constant column of ``x`` that it weighs heavily), and ``x`` with the
    rows ``big`` a thousand times larger and sent to experts 0..3 (a
    second column), which the share ``(8, 16)`` does not hold."""
    params, x = _params(6, router=router), _x(96, 7)
    params = {**params, "router": params["router"] * 0.05}
    x = x.at[:, 0].set(4.0).at[:, 1].set(0.0)
    params["router"] = params["router"].at[0, 8:11].set(3.0) \
        .at[1, :].set(0.0).at[1, 0:K].set(1.0)
    big = jnp.asarray(big, jnp.int32)
    return params, x.at[big].multiply(1e3).at[big, 0].set(0.0) \
        .at[big, 1].set(1e3)


def test_no_pair_on_a_held_expert_is_dropped_under_a_skewed_router():
    """Nearly every token sends a pair to each of experts 8..10: there
    is no capacity an expert to drop them, and the default bound holds
    the worst case."""
    params, x = _skewed("softmax")
    lo, hi = 8, 16
    layer = _layer((lo, hi))
    part, aux = layer.routed(_share(params, lo, hi), x)
    assert float(aux["load_max_over_mean"]) > 2.0        # skewed indeed
    assert int(aux["overflow_pairs"]) == 0
    np.testing.assert_allclose(part, _dense(params, x, lo, hi), atol=1e-5)


@pytest.mark.parametrize("router", ROUTERS)
@pytest.mark.parametrize("lo,hi", [(8, 16), (0, E)])
def test_what_the_dead_rows_hold_reaches_nothing(lo, hi, router):
    """A bound four times the worst case under a skewed router: most of
    the buffer is dead rows, and a dead row holds some token's row of
    ``x`` as it is (the pairs that follow in the sorted order: the next
    group's, those on absent experts), kept out by its zero weight
    alone. The result and every gradient are the dense mixture's, with
    rows of ``x`` a thousand times the others among those that only dead
    rows hold (the share; the whole layer has no such token); and to the
    bit what the worst-case bound, with a quarter of the dead rows,
    gives."""
    big = (3, 40, 95) if hi - lo < E else ()
    params, x = _skewed(router, big)
    mine = _share(params, lo, hi)
    tight = _layer((lo, hi), router=router)
    roomy = _layer((lo, hi), router=router,
                   dispatch_bound=4 * tight.bound(x.shape[0]))

    def run(layer):
        return jax.value_and_grad(lambda p, x: (lambda y, aux: (
            jnp.sum(jnp.sin(y)), (y, aux)))(*layer.routed(p, x)),
            (0, 1), has_aux=True)(mine, x)
    (_, (y, aux)), grads = run(roomy)
    (_, (y1, _)), grads1 = run(tight)
    assert float(aux["load_max_over_mean"]) > 2.0
    assert int(aux["overflow_pairs"]) == 0
    # at least three quarters of the roomy buffer holds no pair
    assert int(aux["live_tiles"]) * 4 <= roomy.dispatch_bound // roomy.tile
    if big:     # sent to absent experts alone: in the buffer, never live
        _, idx, _ = roomy.route(mine, x[jnp.asarray(big)])
        assert bool(jnp.all(idx < K))

    np.testing.assert_allclose(y, _dense(params, x, lo, hi, router),
                               atol=1e-5)
    want = jax.grad(_dense_loss(params, lo, hi, router), (0, 1))(mine, x)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            a, b, atol=1e-5 * max(1.0, float(jnp.abs(b).max())))
    for i in big:       # no held expert saw them: no gradient, exactly
        assert not np.asarray(grads[1][i]).any()
    np.testing.assert_array_equal(y, y1)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads1)):
        np.testing.assert_array_equal(a, b)


def test_pairs_past_the_bound_are_counted_not_lost_in_silence():
    params, x = _params(8), _x(96, 9)
    lo, hi = 0, 16
    sound, aux0 = _layer((lo, hi)).routed(_share(params, lo, hi), x)
    _, idx, _ = _layer().route(params, x)
    held_pairs = int(jnp.sum((idx >= lo) & (idx < hi)))
    small = _layer((lo, hi), dispatch_bound=64)     # 8 tiles of 8 rows
    part, aux = small.routed(_share(params, lo, hi), x)
    assert int(aux0["overflow_pairs"]) == 0
    assert held_pairs > 64
    # every pair is either in the buffer or counted
    assert int(aux["overflow_pairs"]) >= held_pairs - 64
    assert int(aux["overflow_pairs"]) < held_pairs
    assert float(jnp.abs(part - sound).max()) > 0       # and it shows


def test_the_sigmoid_routers_balancing_term_is_taken_a_sequence():
    """``sum_e f_e P_e`` a sequence, ``f_e = E / (K T)`` times the
    sequence's pairs on ``e``, ``P_e`` its mean of ``s_e / sum_j s_j``,
    averaged over the sequences; its gradient reaches the router."""
    params = _params(16, router="sigmoid")
    x = _x(3 * 20, 17).reshape(3, 20, D)
    layer = _layer(router="sigmoid")
    y, aux = layer.routed(params, x)
    assert y.shape == x.shape
    want = 0.0
    for seq in x:
        s = jax.nn.sigmoid(seq @ params["router"])
        _, idx = jax.lax.top_k(s, K)
        f = jnp.zeros((E,)).at[idx.reshape(-1)].add(1.0) * E / (K * 20)
        want = want + jnp.sum(f * (s / s.sum(-1, keepdims=True)).mean(0)) / 3
    np.testing.assert_allclose(aux["load_balance_loss"], want, rtol=1e-5)
    flat, _ = layer.routed(params, x.reshape(60, D))    # one sequence of 60
    np.testing.assert_allclose(flat, y.reshape(60, D), atol=1e-6)
    g = jax.grad(lambda p: layer.routed(p, x)[1]["load_balance_loss"])(params)
    assert float(jnp.abs(g["router"]).max()) > 0


def test_the_load_balancing_term_is_the_switch_form():
    params, x = _params(10), _x(80, 11)
    _, aux = _layer().routed(params, x)
    probs = jax.nn.softmax(x @ params["router"], -1)
    _, idx = jax.lax.top_k(probs, K)
    f = jnp.zeros((E,)).at[idx.reshape(-1)].add(1.0) / x.shape[0]
    want = E * jnp.sum(f * probs.mean(0))
    np.testing.assert_allclose(aux["load_balance_loss"], want, rtol=1e-5)


@pytest.mark.parametrize("kw", [
    dict(experts_held=(8, 4)), dict(experts_held=(0, E + 1)),
    dict(top_k=0), dict(dispatch_bound=100), dict(router="tanh")])
def test_what_the_layer_refuses(kw):
    base = dict(hidden=D, ffn=F, num_experts=E, top_k=K)
    with pytest.raises(ValueError):
        SmallTiles(**{**base, **kw})


# -- the map between pairs and rows, and the two sums that go by it ----------

def _routing(case):
    """``(layer, local [N, K], n_e [held], rows)`` of a case: a router's
    own choice for a share or the whole layer, or one made by hand."""
    n = 48
    if case in ("softmax-share", "sigmoid-share", "softmax-whole",
                "sigmoid-whole", "overflow"):
        router = case.split("-")[0] if "-" in case else "softmax"
        held = (0, E) if case.endswith("whole") else (8, 16)
        layer = _layer(held, router=router,
                       dispatch_bound=32 if case == "overflow" else 0)
        _, idx, _ = layer.route(_params(20, router=router), _x(n, 21))
    else:
        layer = _layer((8, 16))
        # K distinct experts a token, as top_k's are, of 0..7
        idx = np.argsort(np.asarray(jax.random.uniform(
            jax.random.key(22), (n, 8))), axis=1)[:, :K]
        if case == "an-expert-with-no-pair":    # 8..15 but 11, and absent
            idx = np.where(idx == 3, 20, 8 + idx)
        else:                                   # every held pair on 9
            assert case == "all-on-one-expert"
            idx[:, 0] = 9
        idx = jnp.asarray(idx)
    lo, hi = layer.held
    local = jnp.where((idx >= lo) & (idx < hi), idx - lo, hi - lo)
    n_e = jnp.sum(local.reshape(-1, 1) == jnp.arange(hi - lo), 0)
    return layer, local, n_e, layer.bound(n)


CASES = ["softmax-share", "sigmoid-share", "softmax-whole", "sigmoid-whole",
         "an-expert-with-no-pair", "all-on-one-expert", "overflow"]


@pytest.mark.parametrize("case", CASES)
def test_a_pairs_row_is_the_row_that_holds_it(case):
    """``pos`` names, from the pair's side, the row ``pair`` gives it:
    on every live row ``r``, ``pos[pair[r]] == r`` and ``ok`` holds;
    ``ok`` is false exactly on the pairs no live row holds, which are
    those on absent experts and those past the bound."""
    layer, local, n_e, rows = _routing(case)
    at = jax.tree.map(np.asarray, layer.placed(local, n_e, rows))
    pair, live = at["pair"], at["live"]
    pos, ok = at["pos"].reshape(-1), at["ok"].reshape(-1)
    r = np.flatnonzero(live)
    np.testing.assert_array_equal(pos[pair[r]], r)
    assert ok[pair[r]].all() and ok.sum() == live.sum()
    held = np.asarray(local).reshape(-1) < n_e.shape[0]
    assert not ok[~held].any()
    lost = int(held.sum()) - int(ok.sum())      # held, yet in no row
    if case == "overflow":
        assert lost > 0 and live.sum() <= rows
    else:
        assert lost == 0
    if case == "an-expert-with-no-pair":
        assert int(n_e[3]) == 0 and int(n_e.min()) == 0
    if case == "all-on-one-expert":
        assert int(n_e[1]) == 48 == int(n_e.sum())


@pytest.mark.parametrize("case", CASES)
def test_the_mask_keeps_a_dead_row_out_of_the_combine(case):
    """The combine by ``pos`` / ``ok`` is ``zeros.at[tok].add`` over the
    live rows, with the dead rows holding a large finite junk value: no
    pair names one, so what it holds reaches nothing (the mask, not the
    zeros); and its transpose is ``dy[tok]``."""
    layer, local, n_e, rows = _routing(case)
    at = layer.placed(local, n_e, rows)
    tok, live = at["pair"] // K, at["live"]
    yb = jax.random.normal(jax.random.key(23), (rows, D))
    want = jnp.zeros((local.shape[0], D)).at[tok].add(
        jnp.where(live[:, None], yb, 0.0))
    junk = jnp.where(live[:, None], yb, 3e37)
    got, back = jax.vjp(lambda yb: expert_layer._combined(
        yb, tok, (at["pos"], at["ok"], local), n_e.shape[0]), junk)
    np.testing.assert_allclose(got, want, atol=1e-5)
    dy = jax.random.normal(jax.random.key(24), got.shape)
    np.testing.assert_array_equal(back(dy)[0], dy[tok])


@pytest.mark.parametrize("case", CASES)
def test_dx_is_added_in_float32_and_rounded_once(case):
    """The transpose of ``xb = x[tok]`` with ``x`` in bfloat16: the sum by
    ``pos`` / ``ok`` adds a token's rows' cotangents in float32 and rounds
    once, so it is no further from the sum in float64 than the scatter-add
    JAX writes for ``x[tok]`` (the parent's ``dx``), which rounds at every
    addition; and what a dead row's cotangent holds reaches nothing."""
    layer, local, n_e, rows = _routing(case)
    at = layer.placed(local, n_e, rows)
    tok, live = at["pair"] // K, np.asarray(at["live"])
    x = _x(local.shape[0], 25).astype(jnp.bfloat16)
    dxb = jax.random.normal(jax.random.key(26), (rows, D)).astype(
        jnp.bfloat16)
    zeroed = jnp.where(live[:, None], dxb, 0)     # as a weight of zero does
    old, = jax.vjp(lambda x: x[tok], x)[1](zeroed)
    new, = jax.vjp(lambda x: expert_layer._rows_of(
        x, tok, (at["pos"], at["ok"], local), n_e.shape[0]), x)[1](
            jnp.where(live[:, None], dxb, 3e37))
    assert new.dtype == old.dtype == jnp.bfloat16
    exact = np.zeros(x.shape)
    np.add.at(exact, np.asarray(tok)[live], np.asarray(dxb, np.float64)[live])

    def off(dx):
        return float(np.abs(np.asarray(dx, np.float64) - exact).max())
    assert off(new) <= off(old) < 0.1
    # once rounded: the float64 sum's nearest bfloat16
    np.testing.assert_array_equal(
        np.asarray(new), np.asarray(jnp.asarray(exact, jnp.bfloat16)))


def _loss_gradient(layer, n=64):
    params = _share(_params(28), *layer.held)
    return jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(jnp.sin(
        layer.routed(p, x)[0])), (0, 1)))(params, _x(n, 29))


def test_the_gradients_program_scatters_nothing_into_the_tokens():
    """As JAX writes them the combine and ``dx`` are two scatter-adds
    into ``[tokens, hidden]``; the layer's loss gradient holds none, and
    gathers ``[tokens, hidden]`` a slot of ``top_k`` twice instead."""
    def count(fn, prim, *args):
        return sum(v.eqn.primitive.name == prim
                   and v.eqn.outvars[0].aval.shape == (64, D)
                   for v in walker.iter_eqns(fn(*args)))
    tok = jnp.arange(128) % 64
    plain = jax.make_jaxpr(jax.grad(lambda x: jnp.sum(jnp.sin(
        jnp.zeros((64, D)).at[tok].add(jnp.cos(x[tok]))))))
    assert count(plain, "scatter-add", _x()) == 2
    layer = _layer((8, 16))
    assert count(_loss_gradient, "scatter-add", layer) == 0
    assert count(_loss_gradient, "gather", layer) == 2 * K


def test_what_the_sums_add_runs_under_moe_route():
    """Every gather of the routed layer's loss gradient that reads or
    makes a ``[rows, hidden]`` or ``[tokens, hidden]`` array (the two
    sums' ``top_k`` a sum, ``x[tok]`` and ``dy[tok]``: forward and in
    both backward rules), every equation over the maps' ``[tokens,
    top_k, held]`` counts and ``[tokens, top_k]`` positions, and every
    add and select over ``[tokens, hidden]`` outside the experts
    carries ``moe_route`` in its name stack."""
    layer = _layer((8, 16))
    rows, held = layer.bound(64), 8
    maps, wide = {(64, K, held), (64, K)}, {(64, D), (rows, D)}
    back = []
    for v in walker.iter_eqns(_loss_gradient(layer)):
        eqn, name = v.eqn, v.eqn.primitive.name
        shapes = {a.aval.shape for a in eqn.outvars + eqn.invars
                  if hasattr(a.aval, "shape")}
        if shapes & maps or (shapes & wide and name == "gather") or (
                (64, D) in shapes and name in ("add", "select_n")
                and v.scope != "moe_experts"):
            assert v.scope == "moe_route", (name, v.scope)
            back += [name] * ("transpose" in str(eqn.source_info.name_stack))
    # dx's gathers, selects and adds; dy[tok]
    assert back.count("gather") == K + 1 and back.count("add") == K - 1
