"""Pallas kernels vs jnp reference — the kernel-numerics tier.

The analog of the reference's multi_tensor kernel tests
(tests/L0/run_amp/test_multi_tensor_scale.py, test_multi_tensor_axpby.py,
test_multi_tensor_l2norm.py; optimizer numerics tests
tests/L0/run_optimizers/) with the Python-vs-CUDA build axis replaced by
reference-vs-Pallas-interpreter (SURVEY.md §4): on CPU the Pallas kernels
run in interpreter mode, which exercises the same kernel code that compiles
on TPU. Includes the reference suite's inf/nan injection at buffer
boundaries to verify the overflow flag.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import dispatch
from apex_tpu.ops import reference as R
from apex_tpu.ops.pallas import multi_tensor as P

SIZES = [128, 128 * 8, 128 * 1037]  # one row, one block row, ragged grid
DTYPES = [jnp.float32, jnp.bfloat16]


def _buf(rs, n, dtype):
    return jnp.asarray(rs.randn(n), dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_scale_matches_reference(n, dtype):
    rs = np.random.RandomState(0)
    x = _buf(rs, n, dtype)
    got, ginf = P.scale(x, 0.125)
    want, winf = R.scale(x, 0.125)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))
    assert bool(ginf) == bool(winf) == False  # noqa: E712


@pytest.mark.parametrize("pos", [0, 64, 128 * 9 - 1])
@pytest.mark.parametrize("bad", [np.inf, -np.inf, np.nan])
def test_scale_overflow_flag(pos, bad):
    rs = np.random.RandomState(1)
    x = _buf(rs, 128 * 9, jnp.float32).at[pos].set(bad)
    _, inf = P.scale(x, 1.0)
    assert bool(inf)


@pytest.mark.parametrize("arg_to_check", [-1, 0, 1])
def test_axpby_matches_reference_and_checks_selected_arg(arg_to_check):
    rs = np.random.RandomState(2)
    n = 128 * 11
    x, y = _buf(rs, n, jnp.float32), _buf(rs, n, jnp.float32)
    got, ginf = P.axpby(0.5, x, 2.0, y, arg_to_check)
    want, winf = R.axpby(0.5, x, 2.0, y, arg_to_check)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert not bool(ginf) and not bool(winf)

    x_bad = x.at[3].set(np.nan)
    _, inf = P.axpby(0.5, x_bad, 2.0, y, arg_to_check)
    assert bool(inf) == (arg_to_check in (-1, 0))
    _, inf = P.axpby(0.5, x, 2.0, y.at[n - 1].set(np.inf), arg_to_check)
    assert bool(inf) == (arg_to_check in (-1, 1))


@pytest.mark.parametrize("n", SIZES)
def test_l2norm_matches_reference(n):
    rs = np.random.RandomState(3)
    x = _buf(rs, n, jnp.float32)
    np.testing.assert_allclose(P.l2norm(x), R.l2norm(x), rtol=1e-5)


def _segments(n_rows_per_seg=(3, 1, 7, 2)):
    ids = np.concatenate([np.full(r * 128, i, np.int32)
                          for i, r in enumerate(n_rows_per_seg)])
    return jnp.asarray(ids), len(n_rows_per_seg)


def test_per_segment_norms_match_reference():
    rs = np.random.RandomState(4)
    ids, nseg = _segments()
    x = _buf(rs, ids.shape[0], jnp.float32)
    np.testing.assert_allclose(
        P.l2norm_per_segment(x, ids, nseg),
        R.l2norm_per_segment(x, ids, nseg), rtol=1e-5)
    np.testing.assert_allclose(
        P.maxnorm_per_segment(x, ids, nseg),
        R.maxnorm_per_segment(x, ids, nseg), rtol=1e-6)


@pytest.mark.parametrize("mode", [R.MODE_L2, R.MODE_DECOUPLED])
@pytest.mark.parametrize("dtype", DTYPES)
def test_adam_step_matches_reference(mode, dtype):
    rs = np.random.RandomState(5)
    n = 128 * 9
    g = _buf(rs, n, dtype)
    p = _buf(rs, n, jnp.float32)
    m = jnp.abs(_buf(rs, n, jnp.float32)) * 0.01
    v = jnp.abs(_buf(rs, n, jnp.float32)) * 0.01
    kw = dict(lr=1e-2, beta1=0.9, beta2=0.999, eps=1e-8, step=3,
              mode=mode, weight_decay=0.01)
    for got, want in zip(P.adam_step(g, p, m, v, **kw),
                         R.adam_step(g, p, m, v, **kw)):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **_tol(dtype))


def test_adagrad_step_matches_reference():
    rs = np.random.RandomState(6)
    n = 128 * 5
    g, p = _buf(rs, n, jnp.float32), _buf(rs, n, jnp.float32)
    h = jnp.abs(_buf(rs, n, jnp.float32))
    kw = dict(lr=1e-2, eps=1e-10, weight_decay=0.1)
    for got, want in zip(P.adagrad_step(g, p, h, **kw),
                         R.adagrad_step(g, p, h, **kw)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("nesterov", [False, True])
@pytest.mark.parametrize("first_run", [False, True])
def test_sgd_step_matches_reference(nesterov, first_run):
    rs = np.random.RandomState(7)
    n = 128 * 6
    g, p, mom = (_buf(rs, n, jnp.float32) for _ in range(3))
    kw = dict(wd=1e-4, momentum=0.9, dampening=0.0, lr=0.1,
              nesterov=nesterov, first_run=first_run, scale=0.5)
    for got, want in zip(P.sgd_step(g, p, mom, **kw),
                         R.sgd_step(g, p, mom, **kw)):
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("norm_type", [R.NORM_L2, R.NORM_LINF])
def test_novograd_step_matches_reference(norm_type):
    rs = np.random.RandomState(8)
    ids, nseg = _segments()
    n = ids.shape[0]
    g, p, m = (_buf(rs, n, jnp.float32) for _ in range(3))
    v_norms = jnp.abs(jnp.asarray(rs.randn(nseg), jnp.float32))
    kw = dict(lr=1e-2, beta1=0.95, beta2=0.98, eps=1e-8, step=2,
              weight_decay=0.01, norm_type=norm_type)
    for got, want in zip(
            P.novograd_step(g, p, m, v_norms, ids, **kw),
            R.novograd_step(g, p, m, v_norms, ids, **kw)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# --- the in-place contract and the overflow skip of the step kernels (PR 25)

_ROWS = 1500            # three row blocks of 512, the last one ragged


def _step_cases():
    """name -> (step(backend module, g, *state, skip=), state builder):
    the five optimizer steps with the arguments the parity tests above
    use, at a size whose last block is ragged."""
    rs = np.random.RandomState(25)
    n = 128 * _ROWS
    ids = jnp.repeat(jnp.arange(4, dtype=jnp.int32), n // 4)
    pos = lambda: jnp.abs(_buf(rs, n, jnp.float32)) * 0.01
    adam = dict(lr=1e-2, beta1=0.9, beta2=0.999, eps=1e-8, step=3,
                weight_decay=0.01)
    return {
        "adam": (lambda B, g, p, m, v, **kw: B.adam_step(
            g, p, m, v, **adam, **kw),
            lambda: (_buf(rs, n, jnp.float32), pos(), pos())),
        "adagrad": (lambda B, g, p, h, **kw: B.adagrad_step(
            g, p, h, lr=1e-2, eps=1e-10, weight_decay=0.1, **kw),
            lambda: (_buf(rs, n, jnp.float32), pos())),
        "sgd": (lambda B, g, p, mom, **kw: B.sgd_step(
            g, p, mom, wd=1e-4, momentum=0.9, dampening=0.0, lr=0.1,
            nesterov=True, scale=0.5, **kw),
            lambda: (_buf(rs, n, jnp.float32), _buf(rs, n, jnp.float32))),
        "novograd": (lambda B, g, p, m, vn, **kw: B.novograd_step(
            g, p, m, vn, ids, lr=1e-2, beta1=0.95, beta2=0.98, eps=1e-8,
            step=2, weight_decay=0.01, **kw),
            lambda: (_buf(rs, n, jnp.float32), pos(),
                     jnp.abs(jnp.asarray(rs.randn(4), jnp.float32)))),
        "lamb": (lambda B, g, p, m, v, **kw: B.lamb_step(
            g, p, m, v, ids, 4, **{**adam, "eps": 1e-6},
            global_grad_norm=3.0, max_grad_norm=1.0, **kw),
            lambda: (_buf(rs, n, jnp.float32), pos(), pos())),
    }


STEP_CASES = _step_cases()
# LAMB has one side (XLA's won on the chip, ops/kernels.py)
STEP_SIDES = [(name, backend) for name in sorted(STEP_CASES)
              for backend in ("pallas", "reference")
              if (name, backend) != ("lamb", "pallas")]


def _bits(arrays):
    return [np.asarray(a).view(np.uint32) for a in arrays]


@pytest.mark.parametrize("name,backend", STEP_SIDES)
def test_step_kernel_skips_in_kernel_and_updates_in_place(name, backend):
    """One signature on both backends: ``skip`` set returns the state that
    came in, bit for bit, whatever the gradient holds; ``skip`` clear is
    the result without the argument; a caller that keeps its state finds
    it intact; a caller that donates it gets the same result."""
    B = P if backend == "pallas" else R
    step, make_state = STEP_CASES[name]
    rs = np.random.RandomState(26)
    g = _buf(rs, 128 * _ROWS, jnp.float32)
    state = make_state()
    before = _bits(state)
    plain = jax.jit(lambda g, *st: step(B, g, *st))
    skipping = jax.jit(lambda g, s, *st: step(B, g, *st, skip=s))
    donating = jax.jit(lambda g, s, st: step(B, g, *st, skip=s),
                       donate_argnums=(2,))

    want = skipping(g, jnp.asarray(False), *state)
    assert any((w != b).any() for w, b in zip(_bits(want), before))
    # the same arithmetic as without the argument (to the last bit on the
    # chip; the CPU's compiler contracts a multiply-add differently once a
    # select follows it, an ulp in a few elements)
    for got, ref in zip(want, plain(g, *state)):
        np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-7)
    want = _bits(want)
    # an overflowing gradient, in the first and in the ragged last block
    bad = g.at[7].set(jnp.inf).at[-3].set(jnp.nan)
    for flag in (jnp.asarray(True), jnp.asarray(1.0, jnp.float32)):
        for got, ref in zip(_bits(skipping(bad, flag, *state)), before):
            np.testing.assert_array_equal(got, ref)
    for now, ref in zip(_bits(state), before):       # not donated: intact
        np.testing.assert_array_equal(now, ref)
    for got, ref in zip(_bits(donating(g, jnp.asarray(False),
                                       jax.tree.map(jnp.copy, state))), want):
        np.testing.assert_array_equal(got, ref)
    for got, ref in zip(_bits(donating(bad, jnp.asarray(True),
                                       jax.tree.map(jnp.copy, state))),
                        before):
        np.testing.assert_array_equal(got, ref)


def _fused(name, params):
    from apex_tpu import optimizers as O
    return {"adam": lambda: O.FusedAdam(params, lr=1e-2, weight_decay=0.01),
            "lamb": lambda: O.FusedLAMB(params, lr=1e-2),
            "sgd": lambda: O.FusedSGD(params, lr=0.1, momentum=0.9),
            "adagrad": lambda: O.FusedAdagrad(params, lr=1e-2),
            "novograd": lambda: O.FusedNovoGrad(params, lr=1e-2)}[name]()


@pytest.mark.parametrize("name,backend", STEP_SIDES)
def test_apply_update_found_inf_keeps_state_and_step(name, backend):
    """Through the optimizers: ``found_inf`` set leaves master, slots and
    the step counter as they were (NovoGrad's not-yet-seeded norms stay
    NaN, so the overflowing first gradient never seeds them);
    ``found_inf`` clear is the update without the argument."""
    rs = np.random.RandomState(27)
    params = {"w": jnp.asarray(rs.randn(64, 32), jnp.float32),
              "b": jnp.asarray(rs.randn(32), jnp.float32)}
    with dispatch.backend(backend):
        opt = _fused(name, params)
        fg = opt.flatten_grads(jax.tree.map(lambda x: x * 0.1, params))
        for state in (opt.init_state(),                          # step 0
                      opt.apply_update(opt.init_state(), fg)):   # step 1
            start = int(state[0].step)
            want = opt.apply_update(state, fg)
            assert int(want[0].step) == start + 1
            ran = opt.apply_update(state, fg, found_inf=jnp.asarray(False))
            bad = [fg[0].at[5].set(jnp.inf)]
            skipped = opt.apply_update(state, bad,
                                       found_inf=jnp.asarray(True))
            assert int(ran[0].step) == start + 1
            assert int(skipped[0].step) == start
            assert ran[0].slots.keys() == skipped[0].slots.keys() \
                == state[0].slots.keys()
            close = functools.partial(np.testing.assert_allclose,
                                      rtol=1e-6, atol=1e-7)
            for got, ref, same in ((ran, want, close),
                                   (skipped, state,
                                    np.testing.assert_array_equal)):
                same(got[0].master, ref[0].master)
                for k in ref[0].slots:
                    same(got[0].slots[k], ref[0].slots[k])


def test_dispatch_backend_context_switches_paths():
    from apex_tpu.ops import kernels as K
    rs = np.random.RandomState(10)
    x = _buf(rs, 128 * 4, jnp.float32)
    with dispatch.backend("pallas"):
        got, _ = K.scale(x, 2.0)
    with dispatch.backend("reference"):
        want, _ = K.scale(x, 2.0)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_kernels_fall_back_on_unaligned_buffers():
    from apex_tpu.ops import kernels as K
    x = jnp.ones((100,), jnp.float32)  # not 128-aligned
    with dispatch.backend("pallas"):
        out, inf = K.scale(x, 3.0)
    np.testing.assert_allclose(out, 3.0)
    assert not bool(inf)


def test_optimizer_end_to_end_pallas_vs_reference_backend():
    """FusedAdam trained under both backends stays allclose — the
    framework-level analog of the reference's L1 Python-vs-CUDA criterion
    (tests/L1/common/run_test.sh:57-137)."""
    from apex_tpu.optimizers import FusedAdam
    rs = np.random.RandomState(11)
    params = {"w": jnp.asarray(rs.randn(64, 32), jnp.float32),
              "b": jnp.asarray(rs.randn(32), jnp.float32)}
    results = {}
    for backend in ("reference", "pallas"):
        with dispatch.backend(backend):
            opt = FusedAdam(params, lr=1e-2, weight_decay=0.01)
            for i in range(3):
                grads = {"w": params["w"] * 0.1, "b": params["b"] * 0.1}
                out = opt.step(grads)
            results[backend] = out
    np.testing.assert_allclose(results["reference"]["w"],
                               results["pallas"]["w"], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("seed", range(6))
def test_fuzz_random_segments_all_ops(seed):
    """Randomized segment-table fuzz over the whole multi-tensor kernel
    family: random segment count/sizes (one row up to dozens, the
    ragged tail included), random inf/nan placement, both dtypes.
    Pallas (interpreter) and the jnp reference must agree on values,
    per-segment norms and overflow flags — the boundary-bug net for any
    future kernel edit beyond the fixed-shape cases above."""
    rng = np.random.default_rng(2000 + seed)
    rows = [int(rng.integers(1, 40)) for _ in range(int(rng.integers(2, 9)))]
    ids = np.concatenate([np.full(r * 128, i, np.int32)
                          for i, r in enumerate(rows)])
    ids, nseg, n = jnp.asarray(ids), len(rows), int(ids.shape[0])
    dtype = [jnp.float32, jnp.bfloat16][int(rng.integers(0, 2))]
    x = jnp.asarray(rng.normal(size=n), dtype)
    tol = _tol(dtype)

    # scale + flag with a random bad value at a random position
    got = P.scale(x, 1.7)
    want = R.scale(x, 1.7)
    np.testing.assert_allclose(np.asarray(got[0], np.float32),
                               np.asarray(want[0], np.float32), **tol)
    assert bool(got[1]) == bool(want[1]) == False  # noqa: E712
    bad = x.at[int(rng.integers(0, n))].set(
        [jnp.inf, -jnp.inf, jnp.nan][int(rng.integers(0, 3))])
    assert bool(P.scale(bad, 1.0)[1]) and bool(R.scale(bad, 1.0)[1])

    # per-segment norms over the random table
    xf = x.astype(jnp.float32)
    np.testing.assert_allclose(P.l2norm_per_segment(xf, ids, nseg),
                               R.l2norm_per_segment(xf, ids, nseg),
                               rtol=1e-5)
    np.testing.assert_allclose(P.maxnorm_per_segment(xf, ids, nseg),
                               R.maxnorm_per_segment(xf, ids, nseg),
                               rtol=1e-6)


# -- every pallas_call carries a stable name (PR 24) ------------------------
# A device trace names a kernel's HLO instruction after the call's
# ``name=``; unnamed, it takes the name of whatever encloses it and the next
# refactor changes it. One case per call site: tracing the wrapper (abstract
# shapes, nothing runs) shows a pallas_call of the documented name.

def _pallas_names(jaxpr) -> list:
    from apex_tpu.analysis import walker
    return [v.eqn.params["name"] for v in walker.iter_eqns(jaxpr)
            if v.eqn.primitive.name == "pallas_call"]


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _flash_grad(with_bias):
    from apex_tpu.contrib.multihead_attn import flash_attention

    def loss(q, k, v, bias):
        return flash_attention(q, k, v, bias if with_bias else None,
                               causal=True).sum()
    return jax.grad(loss, argnums=(0, 1, 2, 3))


def _gdn_grad():
    from apex_tpu.ops.pallas import gated_delta_rule as K
    return jax.grad(lambda *a: K.chunk_scan(*a).sum(),
                    argnums=(0, 1, 2, 3, 4))


def _kda_grad():
    from apex_tpu.ops.pallas import kda_delta_rule as K
    return jax.grad(lambda *a: K.chunk_scan(*a).sum(),
                    argnums=(0, 1, 2, 3, 4, 5))


def _kda_local_grad():
    from apex_tpu.ops.gated_delta_rule import _levels
    from apex_tpu.ops.pallas import kda_delta_rule as K
    return jax.grad(lambda *a: sum(x.sum() for x in K.local_products(
        _levels(64), *a)), argnums=(0, 1, 2))


def _moe_grad():
    from apex_tpu.ops.pallas import grouped_matmul as G
    return jax.grad(lambda lhs, w, tile_e, live: G.grouped_matmul(
        lhs, (w,), tile_e, live)[0].sum(), argnums=(0, 1))


def _site(fn, *args, **kw):
    return lambda: jax.make_jaxpr(lambda *a: fn(*a, **kw))(*args)


def _kernel_sites() -> dict:
    """``{documented name: thunk tracing the wrapper that holds the site}``."""
    from apex_tpu.ops.pallas import (decode_attn as D, row_sum as R,
                                     sparse_index as I)
    n = 128 * 16
    buf, rows = _f32(n), _i32(n // 128)
    hp = dict(lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8, step=1)
    qkv = _f32(2, 256, 128)
    # two chunks of 64 tokens of two heads of 128
    gdn = [_f32(1, 2, 2, 64, 128)] * 4 + [_f32(1, 2, 2, 64)]
    # the same under a decay a channel: q, k, the running decay a channel
    # G, w, u, and the masked products as an operand
    kda = [_f32(1, 2, 2, 64, 128)] * 5 + [_f32(1, 2, 2, 64, 64)]
    # two tiles of 128 rows over two experts' [128, 256]
    moe = [_f32(256, 128), _f32(2, 128, 256), _i32(2), _i32()]
    # the indexer's kernels: 64 queries of 2 heads against 128 keys; the
    # first site is also apex_idx_scores (tests/test_sparse_index.py)
    idx = [_f32(1, 2, 64, 128), _f32(1, 1, 128, 128), _f32(1, 64, 128)]
    return {
        "apex_idx_probs": _site(lambda *a: I.pair_sum(*a, 0, probs=True),
                                *idx),
        "apex_idx_grad": _site(lambda *a: I.grad(*a, 0), *idx,
                               _f32(1, 64, 128)),
        # the 16 best of 128 keys for each of 64 queries
        "apex_idx_search": _site(lambda i: I.search(i, 0, 16),
                                 _f32(1, 64, 128)),
        "apex_moe_gmm": _site(_moe_grad(), *moe),
        "apex_moe_tgmm": _site(_moe_grad(), *moe),
        # a block of tokens' rows on two experts, of a buffer of 256
        "apex_moe_rowsum": _site(R.sum_rows, _f32(256, 128),
                                 _i32(R.BLOCK, 2)),
        "apex_gdn_fwd": _site(_gdn_grad(), *gdn),
        "apex_gdn_bwd": _site(_gdn_grad(), *gdn),
        "apex_kda_fwd": _site(_kda_grad(), *kda),
        "apex_kda_bwd": _site(_kda_grad(), *kda),
        # the products inside a chunk, from q, k and the running decay
        "apex_kda_local_fwd": _site(_kda_local_grad(), *kda[:3]),
        "apex_kda_local_bwd": _site(_kda_local_grad(), *kda[:3]),
        "apex_mt_scale": _site(P.scale, buf, scale_factor=2.0),
        "apex_mt_axpby": _site(lambda x, y: P.axpby(1.0, x, 2.0, y), buf, buf),
        "apex_mt_l2norm": _site(P.l2norm, buf),
        "apex_mt_rowsumsq": _site(P.rowsumsq, buf),
        "apex_mt_rowmaxabs": _site(P.rowmaxabs, buf),
        "apex_mt_adam": _site(P.adam_step, buf, buf, buf, buf, **hp),
        "apex_mt_adagrad": _site(P.adagrad_step, buf, buf, buf, lr=1e-3,
                                 eps=1e-8),
        "apex_mt_sgd": _site(P.sgd_step, buf, buf, buf, wd=0.0, momentum=0.9,
                             dampening=0.0, lr=1e-3),
        "apex_mt_novograd": _site(P.novograd_step, buf, buf, buf, _f32(2),
                                  rows, **hp),
        "apex_decode_dense": _site(D.decode_attention, _f32(2, 4, 128),
                                   _f32(2, 4, 64, 128), _f32(2, 4, 64, 128),
                                   _i32(2)),
        "apex_decode_paged": _site(
            lambda q, k, v, n, pt: D.paged_decode_attention(
                q, k, v, n, page_table=pt),
            _f32(2, 4, 128), _f32(9, 4, 16, 128), _f32(9, 4, 16, 128),
            _i32(2), _i32(2, 4)),
        "apex_flash_fwd": _site(_flash_grad(False), qkv, qkv, qkv, qkv),
        "apex_flash_bwd_dq": _site(_flash_grad(False), qkv, qkv, qkv, qkv),
        "apex_flash_bwd_dkv": _site(_flash_grad(False), qkv, qkv, qkv, qkv),
        "apex_flash_bwd_dbias": _site(_flash_grad(True), qkv, qkv, qkv,
                                      _f32(1, 256, 256)),     # head-shared
    }


KERNEL_SITES = _kernel_sites()


@pytest.mark.parametrize("name", sorted(KERNEL_SITES))
def test_pallas_call_site_carries_its_documented_name(name):
    assert name in _pallas_names(KERNEL_SITES[name]())


def test_no_pallas_call_is_unnamed_and_no_two_sites_share_a_name():
    """The source of truth is the source: every ``pl.pallas_call(`` of the
    package is followed by its own ``name="apex_..."``."""
    import pathlib
    import re

    import apex_tpu
    found = []
    for path in pathlib.Path(apex_tpu.__file__).parent.rglob("*.py"):
        calls = path.read_text().split("pl.pallas_call(")[1:]
        for body in calls:
            m = re.search(r'\bname="(apex_\w+)"', body.split(")(")[0])
            assert m, f"a pallas_call of {path} has no name="
            found.append(m.group(1))
    assert sorted(found) == sorted(KERNEL_SITES)
    assert len(set(found)) == len(found)


def test_every_kernel_is_picked_by_the_rule_or_asked_for_by_name():
    """No module reaches a kernel by comparing the forced backend with
    ``"pallas"``, and every site is one the rule picks on a TPU
    (``test_dispatch.FAMILIES``) or one a caller names: a kernel added
    later is dispatched or asked for, never parked behind a switch."""
    import pathlib
    import re

    import apex_tpu
    from test_dispatch import ASKED_BY_NAME, FAMILIES
    for path in pathlib.Path(apex_tpu.__file__).parent.rglob("*.py"):
        assert not re.search(r"""get_backend\(\)\s*[!=]=\s*["']pallas""",
                             path.read_text()), path
    picked = set().union(*(names for _, names in FAMILIES.values()))
    named = {n for n in KERNEL_SITES if n.startswith(ASKED_BY_NAME)}
    assert not picked & named
    assert picked | named == set(KERNEL_SITES)
