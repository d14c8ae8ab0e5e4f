"""The routed experts' grouped matmuls as Pallas kernels (``apex_moe_gmm``,
``apex_moe_tgmm``; interpreter mode here) against their oracle, the einsum
over each tile's gathered weights: values and every gradient, at a power
of two and an odd multiple of 128 (the two benchmark cells' kinds of
width), over loads with an expert without a token, a group that ends on a
tile, one live tile, and more dead tiles than live ones; the layer under
``jax.checkpoint`` and inside a ``lax.scan`` over stacked layers. Each
with and without a scale a row (the combine's weights on the down
projection): the product times the scale, the scale's own gradient, and
rows of scale zero that hold anything finite and give and take nothing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.contrib.moe import ExpertLayer
from apex_tpu.ops import dispatch
from apex_tpu.ops.pallas import grouped_matmul as gmm

TILE = gmm.TILE
WIDTHS = [(256, 128), (256, 384)]       # (hidden, ffn)
E, HELD, K = 8, 4, 2
BOUND = 16 * TILE
# pairs on each of the four held experts -> live tiles of the 16
LOADS = {
    "an_expert_without_a_token": ([600, 0, 500, 300], 12),
    "a_group_ends_on_a_tile": ([128, 256, 5, 70], 5),
    "one_live_tile": ([0, 0, 90, 0], 1),
    "mostly_dead_tiles": ([100, 20, 30, 10], 4),
}


def _tiles(loads, tiles):
    """``(tile_e, live)`` as ``ExpertLayer.routed`` makes them."""
    per = [-(-n // TILE) for n in loads]
    tile_e = np.repeat(np.arange(len(loads)), per)
    live = len(tile_e)
    tile_e = np.concatenate([tile_e, np.full(tiles - live, len(loads) - 1)])
    return jnp.asarray(tile_e, jnp.int32), jnp.asarray(live, jnp.int32)


def _oracle(lhs, w, tile_e, live):
    t = tile_e.shape[0]
    x = jnp.where((jnp.arange(t) < live)[:, None, None],
                  lhs.reshape(t, TILE, -1), 0)
    return jnp.einsum("tmk,tkn->tmn", x, w[tile_e],
                      preferred_element_type=jnp.float32).reshape(
                          lhs.shape[0], -1)


def _kernels(lhs, w, tile_e, live, scale=None):
    out, = gmm.grouped_matmul(lhs, (w,), tile_e, live, scale)
    return out


def _scaled_oracle(lhs, w, tile_e, live, scale):
    return _oracle(lhs, w, tile_e, live) * scale.reshape(-1, 1)


def _live_rows(loads, tiles):
    """Which rows of the buffer hold a pair: the first ``loads[e]`` of
    expert ``e``'s whole tiles."""
    rows = [np.arange(-(-n // TILE) * TILE) < n for n in loads]
    return np.concatenate(rows + [np.zeros(
        tiles * TILE - sum(len(r) for r in rows), bool)])


def _operands(depth, width, dtype, key=0):
    ks = jax.random.split(jax.random.key(key), 3)
    return (jax.random.normal(ks[0], (BOUND, depth), dtype),
            jax.random.normal(ks[1], (HELD, depth, width), dtype) * 0.1,
            jax.random.normal(ks[2], (BOUND, width)))


@pytest.mark.parametrize("load", sorted(LOADS))
@pytest.mark.parametrize("depth,width", WIDTHS + [(384, 256)])
@pytest.mark.parametrize("scaled", [False, True], ids=["plain", "scaled"])
def test_kernels_against_the_einsum_over_gathered_weights(scaled, depth,
                                                          width, load):
    """Both directions of a layer's products (``hidden -> ffn`` and, at
    (384, 256), ``ffn -> hidden`` with the odd multiple contracted);
    ``scaled``: times a scale a row, one shape of it a case, against the
    einsum times the scale, the scale's gradient with the others."""
    tile_e, live = _tiles(LOADS[load][0], BOUND // TILE)
    lhs, w, seed = _operands(depth, width, jnp.float32)
    scale = (jax.random.normal(jax.random.key(7), (
        (BOUND, 1) if depth == 384 else (BOUND,))),) if scaled else ()

    def scalar(fn):
        return lambda lhs, w, *scale: jnp.sum(
            fn(lhs, w, tile_e, live, *scale) * seed)
    over = tuple(range(2 + scaled))
    with dispatch.backend("pallas"):
        got = _kernels(lhs, w, tile_e, live, *scale)
        g_lhs, g_w, *g_scale = jax.grad(scalar(_kernels), over)(
            lhs, w, *scale)
    oracle = _scaled_oracle if scaled else _oracle
    want = oracle(lhs, w, tile_e, live, *scale)
    w_lhs, w_w, *w_scale = jax.grad(scalar(oracle), over)(lhs, w, *scale)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=1e-4)
    np.testing.assert_allclose(g_lhs, w_lhs, atol=1e-4)
    np.testing.assert_allclose(g_w, w_w, atol=2e-4)
    for a, b in zip(g_scale, w_scale):
        assert a.shape == scale[0].shape
        np.testing.assert_allclose(a, b, atol=2e-4)
    # dead tiles: zeros out and no gradient in, whatever the rows hold
    rows = int(live) * TILE
    assert not np.asarray(got[rows:]).any()
    assert not np.asarray(g_lhs[rows:]).any()
    for e, n in enumerate(LOADS[load][0]):      # no tile: a zero gradient
        assert bool(np.asarray(g_w[e]).any()) == (n > 0)


@pytest.mark.parametrize("scaled", [False, True], ids=["plain", "scaled"])
def test_bfloat16_operands_accumulate_in_float32_and_round_once(scaled):
    """``scaled``: the float32 product times the float32 scale, the
    result never rounded between them; the cotangent times the scale in
    float32, rounded where it enters the backward's products."""
    tile_e, live = _tiles([600, 0, 500, 300], BOUND // TILE)
    lhs, w, seed = _operands(256, 384, jnp.bfloat16, key=1)
    scale = jax.random.normal(jax.random.key(8), (BOUND,)) if scaled \
        else jnp.ones((BOUND,))
    by = (scale,) if scaled else ()

    def scalar(fn):
        return lambda lhs, w: jnp.sum(fn(lhs, w, tile_e, live, *by) * seed)
    with dispatch.backend("pallas"):
        got = _kernels(lhs, w, tile_e, live, *by)
        g_lhs, g_w = jax.grad(scalar(_kernels), (0, 1))(lhs, w)
    assert (got.dtype, g_lhs.dtype, g_w.dtype) == (
        jnp.float32, jnp.bfloat16, jnp.bfloat16)
    f32 = lambda a: a.astype(jnp.float32)
    np.testing.assert_allclose(
        got, _scaled_oracle(lhs, w, tile_e, live, scale), atol=1e-4)
    # the gradients against float32 arithmetic on the same rounded
    # operands and cotangent: one rounding of the result apart
    w_lhs, w_w = jax.grad(lambda lhs, w: jnp.sum(
        _oracle(lhs, w, tile_e, live) * f32(
            (seed * scale[:, None]).astype(jnp.bfloat16))),
        (0, 1))(f32(lhs), f32(w))
    for a, b in ((g_lhs, w_lhs), (g_w, w_w)):
        np.testing.assert_allclose(f32(a), b, rtol=2 ** -7,
                                   atol=2 ** -8 * float(jnp.abs(b).max()))


@pytest.mark.parametrize("load", sorted(LOADS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_rows_of_scale_zero_give_and_take_nothing(dtype, load):
    """What ``ExpertLayer`` leans on for its dead rows: where the scale
    is zero (a live tile's tail, the tiles past the last live one) the
    rows may hold anything finite, here large values, and the result,
    ``d lhs`` and ``d w`` are to the bit what zeros there leave."""
    loads = LOADS[load][0]
    tile_e, live = _tiles(loads, BOUND // TILE)
    lhs, w, seed = _operands(384, 256, dtype, key=4)
    holds = _live_rows(loads, BOUND // TILE)
    assert holds.sum() == sum(loads) and not holds.all()
    scale = jnp.where(holds, jax.random.normal(jax.random.key(9),
                                               (BOUND,)), 0.0)

    def run(lhs):
        return jax.value_and_grad(lambda lhs, w: (lambda y: (
            jnp.sum(y * seed), y))(_kernels(lhs, w, tile_e, live, scale)),
            (0, 1), has_aux=True)(lhs, w)
    with dispatch.backend("pallas"):
        (_, y), (g_lhs, g_w) = run(jnp.where(holds[:, None], lhs,
                                              3e4 * (1 + jnp.abs(lhs))))
        (_, y0), (g_lhs0, g_w0) = run(jnp.where(holds[:, None], lhs, 0))
    np.testing.assert_array_equal(y, y0)
    np.testing.assert_array_equal(g_lhs, g_lhs0)
    np.testing.assert_array_equal(g_w, g_w0)
    assert not np.asarray(y)[~holds].any()
    assert not np.asarray(g_lhs)[~holds].any()
    assert np.asarray(y)[holds].any()


def test_two_weights_share_a_call_and_their_row_gradients_one_rounding():
    """A layer's gate and up: two results from one read of the rows, and
    ``d lhs`` their float32 sum rounded once, not two rounded parts."""
    tile_e, live = _tiles([128, 256, 5, 70], BOUND // TILE)
    lhs, w_gate, seed = _operands(256, 384, jnp.bfloat16, key=2)
    _, w_up, seed_up = _operands(256, 384, jnp.bfloat16, key=3)
    f32 = lambda a: a.astype(jnp.float32)

    def scalar(fn):
        def loss(lhs, w_gate, w_up):
            g, u = fn(lhs, w_gate, w_up)
            return jnp.sum(g * seed + u * seed_up)
        return jax.value_and_grad(loss, (0, 1, 2))
    with dispatch.backend("pallas"):
        jaxpr = jax.make_jaxpr(scalar(lambda lhs, *ws: gmm.grouped_matmul(
            lhs, ws, tile_e, live)))(lhs, w_gate, w_up)
        got, grads = scalar(lambda lhs, *ws: gmm.grouped_matmul(
            lhs, ws, tile_e, live))(lhs, w_gate, w_up)
    from tests.test_pallas_kernels import _pallas_names
    assert sorted(_pallas_names(jaxpr)) == [
        "apex_moe_gmm", "apex_moe_gmm", "apex_moe_tgmm", "apex_moe_tgmm"]
    rounded = lambda s: f32(s.astype(jnp.bfloat16))
    want, w_grads = jax.value_and_grad(lambda lhs, w_gate, w_up: jnp.sum(
        _oracle(lhs, w_gate, tile_e, live) * rounded(seed)
        + _oracle(lhs, w_up, tile_e, live) * rounded(seed_up)), (0, 1, 2))(
            f32(lhs), f32(w_gate), f32(w_up))
    np.testing.assert_allclose(got, want, rtol=1e-2)
    assert grads[0].dtype == jnp.bfloat16
    # one rounding: the float32 sum's nearest bfloat16, half an ulp off
    np.testing.assert_allclose(f32(grads[0]), w_grads[0], rtol=2 ** -8,
                               atol=1e-5)
    for a, b in zip(grads[1:], w_grads[1:]):
        np.testing.assert_allclose(f32(a), b, rtol=2 ** -7,
                                   atol=2 ** -8 * float(jnp.abs(b).max()))


def _layer(hidden, ffn, experts_held=(0, HELD), **kw):
    return ExpertLayer(hidden=hidden, ffn=ffn, num_experts=E, top_k=K,
                       experts_held=experts_held, dispatch_bound=BOUND, **kw)


def _routed_to(loads, hidden, key=2):
    """``(params' router, x)`` that send exactly ``loads[e]`` pairs to held
    expert ``e``: a token's first choice is its held expert, its second an
    absent one, through the first ``E`` features and an identity router."""
    first = np.repeat(np.arange(HELD), loads)
    n = len(first)
    x = np.asarray(jax.random.normal(jax.random.key(key), (n, hidden))) * 0.5
    x[:, :E] = 0.0
    x[np.arange(n), first] = 2.0
    x[np.arange(n), HELD + np.arange(n) % (E - HELD)] = 1.0
    router = np.zeros((hidden, E), np.float32)
    router[:E] = 8.0 * np.eye(E)
    return jnp.asarray(router), jnp.asarray(x[np.random.RandomState(
        0).permutation(n)], jnp.float32)


def _both_sides(fn, *args):
    """``fn(*args)`` through the einsum over gathered weights and through
    the kernels (a wrapper each: a trace is cached by function)."""
    want = (lambda *a: fn(*a))(*args)
    with dispatch.backend("pallas"):
        got = (lambda *a: fn(*a))(*args)
    return got, want


@pytest.mark.parametrize("load", sorted(LOADS))
@pytest.mark.parametrize("hidden,ffn", WIDTHS)
def test_the_layer_on_the_kernels_is_the_layer_on_the_einsum(hidden, ffn,
                                                             load):
    loads, live = LOADS[load]
    layer = _layer(hidden, ffn)
    params = layer.init(jax.random.key(3), 0.1)
    params["router"], x = _routed_to(loads, hidden)

    def run(params, x):
        (loss, aux), grads = jax.value_and_grad(
            lambda p, x: (lambda y, aux: (jnp.sum(jnp.sin(y)), aux))(
                *layer.routed(p, x)), (0, 1), has_aux=True)(params, x)
        return loss, aux, grads
    (loss, aux, grads), (w_loss, w_aux, w_grads) = _both_sides(run, params,
                                                               x)
    for side in (aux, w_aux):       # the oracle's count, on both sides
        assert int(side["live_tiles"]) == live
        assert int(side["held_pairs"]) == sum(loads)
        assert int(side["overflow_pairs"]) == 0
    np.testing.assert_allclose(loss, w_loss, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(w_grads)):
        np.testing.assert_allclose(a, b, atol=1e-4)
    for e, n in enumerate(loads):
        assert bool(np.asarray(grads[0]["w_down"][e]).any()) == (n > 0)


@pytest.mark.parametrize("hidden,ffn", WIDTHS)
def test_a_layer_that_holds_every_expert_learns_its_router_through_the_scale(
        hidden, ffn):
    """Where every expert is held the tokens' weights are differentiated:
    they reach the down projection's kernel as its scale, whose gradient
    is the router's, the einsum side's and the kernels' alike."""
    layer = _layer(hidden, ffn, experts_held=())
    params = layer.init(jax.random.key(3), 0.1)
    x = jax.random.normal(jax.random.key(5), (300, hidden))

    def run(params, x):
        return jax.value_and_grad(lambda p, x: jnp.sum(jnp.sin(
            layer.routed(p, x)[0])), (0, 1))(params, x)
    (loss, grads), (w_loss, w_grads) = _both_sides(run, params, x)
    np.testing.assert_allclose(loss, w_loss, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(w_grads)):
        np.testing.assert_allclose(a, b, atol=1e-4)
    assert float(jnp.abs(grads[0]["router"]).max()) > 1e-3


def test_live_tiles_stops_at_the_buffers_end():
    """Pairs past the bound are counted; the live tiles are the
    buffer's."""
    layer = ExpertLayer(hidden=256, ffn=128, num_experts=E, top_k=K,
                        experts_held=(0, HELD), dispatch_bound=4 * TILE)
    params = layer.init(jax.random.key(3), 0.1)
    params["router"], x = _routed_to([300, 200, 100, 90], 256)
    (_, aux), (_, w_aux) = _both_sides(layer.routed, params, x)
    for side in (aux, w_aux):
        assert int(side["live_tiles"]) == 4
        assert int(side["overflow_pairs"]) == 690 - (300 + 128)


def _dense_block(p, x, lo, hi):
    """An expert layer with a shared expert as a dense mixture: every held
    expert over every token, by ``jax.numpy`` alone and in ``x``'s type.
    Nothing of ``ExpertLayer``: no buffer, no map, no kernel."""
    probs = jax.nn.softmax(x @ p["router"], -1)
    w, idx = jax.lax.top_k(probs, K)
    w = w / w.sum(-1, keepdims=True)
    if hi - lo < E:
        w = jax.lax.stop_gradient(w)

    def swiglu(x, gate, up, down):
        return (jax.nn.silu(x @ gate) * (x @ up)) @ down
    y = sum(jnp.sum(jnp.where(idx == e, w, 0.0), -1, keepdims=True) * swiglu(
        x, p["w_gate"][e - lo], p["w_up"][e - lo], p["w_down"][e - lo])
        for e in range(lo, hi))
    s = p["shared"]
    return y + jax.nn.sigmoid(x @ s["gate"]) * swiglu(
        x, s["w_gate"], s["w_up"], s["w_down"])


# the most a gradient's leaf is off the dense float64 layers', in the
# leaf's largest value, on the kernels' side and on the einsum's (readings
# here, PR 43), and the most the two sides are apart in the same measure
_SCAN_OFF = {("a_share", 128): (1.9e-5, 1.9e-5, 4.3e-6),
             ("a_share", 384): (6.8e-5, 7.1e-5, 1.3e-5),
             ("whole", 128): (4.6e-5, 4.2e-5, 7.2e-6),
             ("whole", 384): (1.8e-4, 1.5e-4, 8.8e-5)}


@pytest.mark.parametrize("hidden,ffn", WIDTHS)
@pytest.mark.parametrize("held", ["a_share", "whole"])
def test_under_checkpoint_and_a_scan_over_stacked_layers(held, hidden, ffn):
    """What ``HybridLM`` does with a run of expert layers: the blocks
    recomputed in the backward, one scanned body over two layers' stacked
    leaves. A share's weights are constants of its backward; the whole
    layer's get their gradient through the down projection's scale.

    Each side is held to the two layers as dense mixtures differentiated
    by JAX alone in float64 (:func:`_dense_block`, which shares no code
    with the layer), leaf by leaf at three times its reading. The sides
    themselves are not equal to the bit inside one compiled scan body: the
    combine's float32 sum is ``apex_moe_rowsum``'s on the kernels' side
    (three bfloat16 parts a row, added by the MXU), and on the einsum's
    XLA:CPU takes the product with the pairs' weights again inside each
    gather's loop and contracts it into the addition (the forward of one
    layer under ``jit`` is an ulp apart for it, where ``yb`` itself and its
    scatter-add are equal to the bit); the sine of sums in the hundreds
    over two layers spreads an ulp to 2e-6 .. 9e-5 of a leaf's largest.
    That is a tenth to a half of what float32 costs either side against
    float64, and the test holds them to it: no further from each other
    than from the reference."""
    layer = _layer(hidden, ffn, experts_held=(0, HELD) if held == "a_share"
                   else (), shared_ffn=128)
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *(
        layer.init(jax.random.key(k), 0.1) for k in (4, 5)))
    x = jax.random.normal(jax.random.key(6), (2, 200, hidden))

    def loss(stacked, x):
        @jax.checkpoint
        def block(x, p):
            y, aux = layer.apply(p, x)
            return x + y, aux["live_tiles"]
        y, live = jax.lax.scan(block, x, stacked)
        return jnp.sum(jnp.sin(y)), live

    def run(stacked, x):
        return jax.value_and_grad(loss, (0, 1), has_aux=True)(stacked, x)
    ((got, live), grads), ((want, w_live), w_grads) = _both_sides(
        run, stacked, x)
    np.testing.assert_array_equal(live, w_live)
    assert live.shape == (2,) and int(live.min()) >= 1
    np.testing.assert_allclose(got, want, rtol=1e-5)

    def dense(stacked, x):
        for i in range(2):
            x = x + _dense_block(jax.tree.map(lambda a: a[i], stacked), x,
                                 *layer.held)
        return jnp.sum(jnp.sin(x))
    with jax.enable_x64():
        exact, d_exact = jax.value_and_grad(dense, (0, 1))(*jax.tree.map(
            lambda a: jnp.asarray(np.asarray(a), jnp.float64), (stacked, x)))
    np.testing.assert_allclose(got, float(exact), rtol=2e-4)
    kernels_off, einsum_off, apart = _SCAN_OFF[held, ffn]
    for a, b, r in zip(*map(jax.tree.leaves, (grads, w_grads, d_exact))):
        a, b, r = (np.asarray(v, np.float64) for v in (a, b, r))
        top = max(float(np.abs(r).max()), 1.0)
        off_a, off_b = np.abs(a - r).max(), np.abs(b - r).max()
        assert off_a <= 3 * kernels_off * top and off_b <= 3 * einsum_off * top
        assert np.abs(a - b).max() <= min(max(off_a, off_b), 3 * apart * top)


def test_the_path_is_read_from_the_platform_and_the_shapes():
    """The kernels where ``dispatch.use_pallas()`` holds and both widths
    are whole lanes with tiles of 128; the einsum on the CPU, under
    ``backend("reference")`` and at any other shape. The combine's sum
    (``apex_moe_rowsum``, above that choice) goes by ``hidden`` and the
    buffer's rows alone."""
    from tests.test_pallas_kernels import _pallas_names

    def names(layer, hidden):
        params = jax.eval_shape(layer.init, jax.random.key(0))
        x = jax.ShapeDtypeStruct((64, hidden), jnp.float32)
        return set(_pallas_names(jax.make_jaxpr(
            lambda p, x: jax.grad(lambda p: jnp.sum(layer.routed(p, x)[0]))(
                p))(params, x)))
    kernels, the_sum = {"apex_moe_gmm", "apex_moe_tgmm"}, {"apex_moe_rowsum"}
    assert names(_layer(256, 384), 256) == set()            # the CPU
    with dispatch.backend("pallas"):
        assert names(_layer(256, 384), 256) == kernels | the_sum
        assert names(_layer(256, 192), 256) == the_sum      # ffn: no lanes
        assert names(_layer(192, 256), 192) == set()

        class SmallTiles(ExpertLayer):
            tile = 8
        assert names(SmallTiles(hidden=256, ffn=128, num_experts=E,
                                top_k=K), 256) == set()
        with dispatch.backend("reference"):
            assert names(_layer(256, 384), 256) == set()
    assert not gmm.takes(2048, 1400, 128) and gmm.takes(2048, 1408, 128)
