"""Distributed tests on the 8-device CPU mesh.

Covers the reference's tests/distributed suite without hardware:
- DDP grad-averaging semantics incl. predivide and fp32-allreduce
  (reference: tests/distributed/DDP/ddp_race_condition_test.py analytic
  grad checks);
- SyncBatchNorm vs single-device BN over the concatenated batch (reference:
  tests/distributed/synced_batchnorm/two_gpu_unit_test.py);
- group sub-syncing (reference: test_groups.py on 4 GPUs).
"""

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

from apex_tpu.parallel import (DistributedDataParallel, Reducer,
                               SyncBatchNorm, broadcast_params,
                               create_syncbn_process_group, make_mesh)

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 (virtual) devices")


def test_mesh_and_broadcast():
    mesh = make_mesh({"data": 8})
    params = {"w": jnp.arange(6.0).reshape(2, 3)}
    rep = broadcast_params(params, mesh)
    assert rep["w"].sharding.is_fully_replicated


def test_ddp_grad_average_matches_global_batch():
    mesh = make_mesh({"data": 8})
    ddp = DistributedDataParallel(axis_name="data")
    w = jnp.asarray(np.random.RandomState(0).randn(4), jnp.float32)
    x = jnp.asarray(np.random.RandomState(1).randn(16, 4), jnp.float32)
    y = jnp.asarray(np.random.RandomState(2).randn(16), jnp.float32)

    def loss_fn(w, x, y):
        return jnp.mean((x @ w - y) ** 2)

    @partial(shard_map, mesh=mesh, in_specs=(P(), P("data"), P("data")),
             out_specs=P())
    def dist_grads(w, x, y):
        return ddp.grad(loss_fn)(w, x, y)

    got = dist_grads(w, x, y)
    want = jax.grad(loss_fn)(w, x, y)  # global-batch gradient
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-6)


def test_ddp_predivide_and_fp32_allreduce():
    mesh = make_mesh({"data": 8})
    ddp = DistributedDataParallel(axis_name="data",
                                  gradient_predivide_factor=4.0,
                                  allreduce_always_fp32=True)
    g_half = jnp.full((8, 16), 3.0, jnp.bfloat16)  # one row per device

    @partial(shard_map, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    def reduce(g):
        out = ddp.average_gradients(g)
        return out

    out = reduce(g_half)
    assert out.dtype == jnp.bfloat16
    # average of identical grads is the grad itself
    np.testing.assert_allclose(np.asarray(out, np.float32), 3.0)


def test_ddp_no_average_sums():
    mesh = make_mesh({"data": 8})
    ddp = DistributedDataParallel(axis_name="data", gradient_average=False)

    @partial(shard_map, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    def reduce(g):
        return ddp.average_gradients(g)

    out = reduce(jnp.ones((8, 4), jnp.float32))
    np.testing.assert_allclose(out, 8.0)


def test_reducer_subgroups():
    mesh = make_mesh({"data": 8})
    groups = create_syncbn_process_group(4, 8)
    assert groups == [[0, 1, 2, 3], [4, 5, 6, 7]]
    red = Reducer(axis_name="data", axis_index_groups=tuple(
        tuple(g) for g in groups))
    vals = jnp.arange(8.0).reshape(8, 1)

    @partial(shard_map, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    def reduce(v):
        return red(v)

    out = np.asarray(reduce(vals)).ravel()
    np.testing.assert_allclose(out[:4], np.mean([0, 1, 2, 3]))
    np.testing.assert_allclose(out[4:], np.mean([4, 5, 6, 7]))


# ---------------------------------------------------------------------------
# SyncBatchNorm
# ---------------------------------------------------------------------------

def _local_bn(x, axes, eps=1e-5):
    mean = np.mean(x, axis=axes, keepdims=True)
    var = np.var(x, axis=axes, keepdims=True)
    return (x - mean) / np.sqrt(var + eps)


def test_syncbn_matches_global_batch_bn():
    """BN stats synced over 8 shards == BN over the concatenated batch
    (reference: two_gpu_unit_test.py asserts the same)."""
    mesh = make_mesh({"data": 8})
    bn = SyncBatchNorm(6, axis_name="data")
    params, state = bn.init()
    x = jnp.asarray(np.random.RandomState(0).randn(16, 5, 6), jnp.float32)

    @partial(shard_map, mesh=mesh,
             in_specs=(P(), P(), P("data")), out_specs=(P("data"), P()))
    def fwd(params, state, x):
        y, new_state = bn.apply(params, state, x, training=True)
        return y, new_state

    y, new_state = fwd(params, state, x)
    want = _local_bn(np.asarray(x), axes=(0, 1))
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5, rtol=1e-5)

    # running stats: momentum 0.1 from (0,1) toward global batch stats
    gm = np.mean(np.asarray(x), axis=(0, 1))
    gv = np.var(np.asarray(x), axis=(0, 1)) * (16 * 5) / (16 * 5 - 1)
    np.testing.assert_allclose(new_state["running_mean"], 0.1 * gm,
                               atol=1e-5)
    np.testing.assert_allclose(new_state["running_var"],
                               0.9 * 1.0 + 0.1 * gv, atol=1e-5)
    assert int(new_state["num_batches_tracked"]) == 1


def test_syncbn_backward_matches_global_autodiff():
    """Analytic custom_vjp == autodiff of global-batch BN (reference:
    single_gpu_unit_test.py grad comparisons)."""
    mesh = make_mesh({"data": 8})
    bn = SyncBatchNorm(4, axis_name="data", track_running_stats=False)
    params, state = bn.init()
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(8, 3, 4), jnp.float32)

    def global_loss(params, x):
        xf = x
        mean = jnp.mean(xf, axis=(0, 1), keepdims=True)
        var = jnp.mean((xf - mean) ** 2, axis=(0, 1), keepdims=True)
        xhat = (xf - mean) * jax.lax.rsqrt(var + bn.eps)
        out = xhat * params["weight"] + params["bias"]
        return jnp.sum(jnp.sin(out))

    @partial(shard_map, mesh=mesh, in_specs=(P(), P("data")),
             out_specs=(P(), P("data")))
    def dist_grads(params, x):
        def loss(p, xs):
            y, _ = bn.apply(p, state, xs, training=True)
            local = jnp.sum(jnp.sin(y))
            return jax.lax.psum(local, "data")
        gp, gx = jax.grad(loss, argnums=(0, 1))(params, x)
        # param grads arrive already globally summed: autodiff against
        # replicated params inserts the psum (jax vma semantics).
        return gp, gx

    gp, gx = dist_grads(params, x)
    gp_want, gx_want = jax.grad(global_loss, argnums=(0, 1))(params, x)
    np.testing.assert_allclose(gx, gx_want, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(gp["weight"], gp_want["weight"], atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(gp["bias"], gp_want["bias"], atol=1e-4,
                               rtol=1e-4)


def test_syncbn_folded_upcast_opt_in_parity(monkeypatch):
    """APEX_BN_FOLDED_UPCAST=1 (r06 convert-seam A/B arm: each moments
    reduction owns its single-consumer upcast, square in storage dtype)
    must match the split-sums default — exactly in fp32 (the upcasts are
    no-ops there), to bf16-rounding tolerance for half inputs with a
    mean offset (the square rounds to bf16 before fp32 accumulation).
    Mesh-free on purpose: the moment-shape numerics are orthogonal to
    the collectives, and this parity must hold on any backend."""
    bn = SyncBatchNorm(4, axis_name=None, track_running_stats=False,
                       fuse_relu=True)
    params, state = bn.init()
    rs = np.random.RandomState(13)

    def grads(x):
        jax.clear_caches()   # the moment shape is read at trace time

        def loss(p, xs):
            y, _ = bn.apply(p, state, xs, training=True)
            return jnp.sum(jnp.sin(y))

        l = loss(params, x)
        gp, gx = jax.grad(loss, argnums=(0, 1))(params, x)
        return l, gp, gx

    for dtype, off, tol in ((jnp.float32, 0.0, 1e-6),
                            (jnp.bfloat16, 3.0, 2e-2)):
        x = jnp.asarray(rs.randn(8, 5, 4) + off, dtype)
        monkeypatch.delenv("APEX_BN_FOLDED_UPCAST", raising=False)
        l_def, gp_def, gx_def = grads(x)
        monkeypatch.setenv("APEX_BN_FOLDED_UPCAST", "1")
        l_fold, gp_fold, gx_fold = grads(x)
        np.testing.assert_allclose(l_def, l_fold, rtol=max(tol, 1e-6))
        np.testing.assert_allclose(np.asarray(gx_def, np.float32),
                                   np.asarray(gx_fold, np.float32),
                                   atol=tol, rtol=tol)
        np.testing.assert_allclose(gp_def["weight"], gp_fold["weight"],
                                   atol=tol, rtol=tol)
        np.testing.assert_allclose(gp_def["bias"], gp_fold["bias"],
                                   atol=tol, rtol=tol)


def test_syncbn_groups():
    """group_size=4: two independent stat groups (reference:
    synced_batchnorm/test_groups.py)."""
    mesh = make_mesh({"data": 8})
    groups = tuple(tuple(g) for g in create_syncbn_process_group(4, 8))
    bn = SyncBatchNorm(2, axis_name="data", axis_index_groups=groups,
                       affine=False, track_running_stats=False)
    rs = np.random.RandomState(4)
    x = jnp.asarray(rs.randn(16, 2), jnp.float32)  # 2 rows per device

    @partial(shard_map, mesh=mesh, in_specs=(P(), P(), P("data")),
             out_specs=P("data"))
    def fwd(params, state, x):
        y, _ = bn.apply(params, state, x, training=True)
        return y

    y = np.asarray(fwd({}, {}, x))
    xn = np.asarray(x)
    np.testing.assert_allclose(y[:8], _local_bn(xn[:8], (0,)), atol=1e-5)
    np.testing.assert_allclose(y[8:], _local_bn(xn[8:], (0,)), atol=1e-5)
    assert not np.allclose(y[:8], _local_bn(xn, (0,))[:8], atol=1e-3)


def test_syncbn_eval_uses_running_stats():
    bn = SyncBatchNorm(3, axis_name=None)
    params, state = bn.init()
    state = {**state,
             "running_mean": jnp.asarray([1.0, 2.0, 3.0]),
             "running_var": jnp.asarray([4.0, 4.0, 4.0])}
    x = jnp.ones((2, 3))
    y, new_state = bn.apply(params, state, x, training=False)
    want = (1.0 - np.array([1, 2, 3])) / np.sqrt(4 + bn.eps)
    np.testing.assert_allclose(y[0], want, atol=1e-6)
    assert int(new_state["num_batches_tracked"]) == 0


def test_syncbn_fused_add_relu():
    """z-add + fused ReLU forward/backward (reference:
    optimized_sync_batchnorm.py:70-85, batch_norm_add_relu.cu)."""
    bn = SyncBatchNorm(4, axis_name=None, fuse_relu=True,
                       track_running_stats=False)
    params, _ = bn.init()
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.randn(6, 4), jnp.float32)
    z = jnp.asarray(rs.randn(6, 4), jnp.float32)

    def fused(p, x, z):
        y, _ = bn.apply(p, {}, x, z=z, training=True)
        return jnp.sum(y ** 2)

    def manual(p, x, z):
        mean = jnp.mean(x, axis=0, keepdims=True)
        var = jnp.mean((x - mean) ** 2, axis=0, keepdims=True)
        xhat = (x - mean) * jax.lax.rsqrt(var + bn.eps)
        out = jnp.maximum(xhat * p["weight"] + p["bias"] + z, 0.0)
        return jnp.sum(out ** 2)

    np.testing.assert_allclose(fused(params, x, z), manual(params, x, z),
                               atol=1e-5)
    g1 = jax.grad(fused, argnums=(0, 1, 2))(params, x, z)
    g2 = jax.grad(manual, argnums=(0, 1, 2))(params, x, z)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5),
        g1, g2)


def test_syncbn_channel_axis_nchw():
    """channel_axis=1 (the reference's default NCHW layout)."""
    bn = SyncBatchNorm(5, axis_name=None, channel_axis=1,
                       track_running_stats=False, affine=False)
    x = jnp.asarray(np.random.RandomState(6).randn(2, 5, 3, 3), jnp.float32)
    y, _ = bn.apply({}, {}, x, training=True)
    want = _local_bn(np.asarray(x), axes=(0, 2, 3))
    np.testing.assert_allclose(y, want, atol=1e-5, rtol=1e-5)


@pytest.mark.slow
def test_syncbn_ddp_parity_under_check_vma_false():
    """The classic-semantics contract (vma tracking OFF, as forced by any
    pallas_call in the region): SyncBN's vjp leaves weight/bias grads as
    per-shard partials and DDP.average_gradients does the psum — the
    pair must reproduce the global-batch gradients exactly. This is the
    regression test for the r4 session-3 bug where empty vma sets made
    average_gradients skip the psum entirely.

    Marked slow (r15 tier-1 runtime guard): ~26 s, while the same
    SyncBN vjp's psum over a mesh stays covered in-tier by
    test_syncbn_backward_matches_global_autodiff."""
    from jax import shard_map as new_shard_map  # check_vma kwarg
    from apex_tpu.models import ResNet
    from apex_tpu.ops import flat as F
    from apex_tpu.optimizers import FusedSGD

    mesh = make_mesh({"data": 8})
    ddp = DistributedDataParallel(axis_name="data")
    kw = dict(block_sizes=(1, 1), bottleneck=True, width=8, num_classes=10)
    model = ResNet(**kw)                          # local BN (global ref)
    model_sync = ResNet(**kw, bn_axis_name="data")
    params, bn = model.init(jax.random.key(0))
    opt = FusedSGD(params, lr=0.1)
    table = opt._tables[0]
    master = opt.init_state()[0].master
    x = jax.random.normal(jax.random.key(1), (16, 24, 24, 3))
    y = jax.random.randint(jax.random.key(2), (16,), 0, 10)

    def flat_grad(master, bn, x, y, mdl):
        def loss_fn(m):
            p = F.unflatten(m, table)
            logits, _ = mdl.apply(p, bn, x, training=True)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            return -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))
        return jax.grad(loss_fn)(master)

    g_global = flat_grad(master, bn, x, y, model)

    @partial(new_shard_map, mesh=mesh,
             in_specs=(P(), P(), P("data"), P("data")), out_specs=P(),
             check_vma=False)   # the flagship example's exact flags
    def dp_grad(master, bn, x, y):
        return ddp.average_gradients(flat_grad(master, bn, x, y,
                                               model_sync))

    g_dp = dp_grad(master, bn, x, y)
    np.testing.assert_allclose(np.asarray(g_global), np.asarray(g_dp),
                               atol=1e-5, rtol=1e-5)


def test_vma_tracking_active_probe():
    """The per-region constant behind average_gradients' psum decision:
    True under check_vma=True, False under check_vma=False, False
    outside any shard_map."""
    from jax import shard_map as new_shard_map
    from apex_tpu.parallel.collectives import vma_tracking_active

    mesh = make_mesh({"data": 8})
    seen = {}

    for cv in (True, False):
        @partial(new_shard_map, mesh=mesh, in_specs=P("data"),
                 out_specs=P("data"), check_vma=cv)
        def f(x, *, _cv=cv):
            seen[_cv] = vma_tracking_active("data")
            return x

        f(jnp.arange(8.0))
    assert seen[True] is True
    assert seen[False] is False
    assert vma_tracking_active("data") is False  # outside shard_map


class TestReferenceSignatureParity:
    """The reference's keyword (and, where meaningful, positional)
    surfaces must be drop-in: every kwarg name it accepts, we accept
    (scheduling knobs accepted-and-ignored; process_group/channel_last
    mapped onto the mesh/axis concepts)."""

    def test_ddp_accepts_full_reference_kwarg_list(self):
        d = DistributedDataParallel(
            axis_name="data", message_size=1 << 20, delay_allreduce=True,
            shared_param=None, allreduce_trigger_params=None,
            retain_allreduce_buffers=True, allreduce_always_fp32=True,
            num_allreduce_streams=2, allreduce_communicators=None,
            gradient_average=True, gradient_predivide_factor=2.0,
            gradient_average_split_factor=None, prof=False)
        assert d.gradient_predivide_factor == 2.0

    def test_syncbn_reference_positional_order(self):
        from apex_tpu.parallel import create_syncbn_process_group
        # (num_features, eps, momentum, affine, track_running_stats,
        #  process_group, channel_last, fuse_relu)
        bn = SyncBatchNorm(64, 1e-5, 0.1, True, True, None, False, True)
        assert bn.channel_axis == 1 and bn.fuse_relu
        g = create_syncbn_process_group(2, axis_size=8)
        bn2 = SyncBatchNorm(64, process_group=g)
        assert bn2.axis_index_groups == tuple(tuple(x) for x in g)
        with pytest.raises(ValueError, match="not both"):
            SyncBatchNorm(64, process_group=g, axis_index_groups=g)

    def test_convert_syncbn_reference_positional_order(self):
        from apex_tpu.models import ResNet
        from apex_tpu.parallel import (convert_syncbn_model,
                                       create_syncbn_process_group)
        g = create_syncbn_process_group(2, axis_size=8)
        m = ResNet(block_sizes=(1,), bottleneck=False, width=8,
                   num_classes=4)
        m2 = convert_syncbn_model(m, g, False)   # ref positional shape
        assert m2.bn_axis_index_groups == g

    def test_optimizer_compat_kwargs(self):
        import jax.numpy as jnp
        from apex_tpu.optimizers import (FusedAdam, FusedLAMB, FusedSGD,
                                         FusedAdagrad, FusedNovoGrad)
        p = {"w": jnp.ones((4,))}
        FusedAdam(p, set_grad_none=False)
        FusedLAMB(p, set_grad_none=False)
        FusedSGD(p, 0.1, materialize_master_grads=False)
        FusedAdagrad(p, set_grad_none=False)
        FusedNovoGrad(p, set_grad_none=False)
        with pytest.raises(RuntimeError, match="AMSGrad"):
            FusedNovoGrad(p, amsgrad=True)

    def test_grouped_syncbn_affine_grads_vma_on_off_agree(self):
        """Grouped BN + affine param grads: with vma checking ON the vjp
        must emit a FULL-axis-summed (unvarying) weight cotangent — a
        group-psummed value is still varying and was rejected (r5 drive
        finding); with vma OFF the psum is DDP's. Both routes must yield
        the same final averaged gradient."""
        from functools import partial
        from apex_tpu.parallel import create_syncbn_process_group
        mesh = make_mesh({"data": 8}, devices=jax.devices()[:8])
        g = create_syncbn_process_group(4, axis_size=8)
        bn = SyncBatchNorm(16, axis_name="data", axis_index_groups=g)
        bp, bst = bn.init()
        ddp = DistributedDataParallel(axis_name="data")
        x = jax.random.normal(jax.random.key(2), (32, 4, 4, 16))
        y = jax.random.normal(jax.random.key(3), x.shape)

        def run(check_vma):
            @jax.jit
            @partial(jax.shard_map, mesh=mesh,
                     in_specs=(P(), P(), P("data"), P("data")),
                     out_specs=P(), check_vma=check_vma)
            def step(bp, bst, x, y):
                def lf(bp):
                    out, _ = bn.apply(bp, bst, x, training=True)
                    return jnp.mean((out.astype(jnp.float32) - y) ** 2)
                gr = jax.grad(lf)(bp)
                return ddp.average_gradients(gr)
            return step(bp, bst, x, y)

        g_on = run(True)
        g_off = run(False)
        for k in ("weight", "bias"):
            np.testing.assert_allclose(np.asarray(g_on[k]),
                                       np.asarray(g_off[k]),
                                       rtol=1e-5, atol=1e-6, err_msg=k)

    def test_stale_positional_axis_name_fails_loudly(self):
        from apex_tpu.parallel import convert_syncbn_model
        with pytest.raises(TypeError, match="keyword-only"):
            SyncBatchNorm(16, 1e-5, 0.1, True, True, "data")
        with pytest.raises(TypeError, match="keyword-only"):
            convert_syncbn_model(object(), "data")


# -- DDP's gradient buckets: the policy, and the step that uses it ---------

@pytest.mark.parametrize("sizes,message_size,expect", [
    # a bucket closes once it HOLDS message_size elements
    ((4, 4, 4, 4), 8, (2, 2)),
    ((4, 4, 4, 4), 7, (2, 2)),
    ((4, 4, 4, 4), 9, (3, 1)),
    ((16, 1, 1, 16), 8, (1, 3)),            # small leaves ride with the next
    ((1, 1, 1), 100, (3,)),                 # never full: one bucket
    ((5,), 1, (1,)),
    ((3, 3, 3), 1, (1, 1, 1)),
], ids=str)
def test_ddp_buckets_close_at_message_size(sizes, message_size, expect):
    ddp = DistributedDataParallel(message_size=message_size)
    assert ddp.buckets(sizes) == expect
    assert sum(ddp.buckets(sizes)) == len(sizes)
    # delay_allreduce: everything waits for the end, in one bucket
    late = DistributedDataParallel(message_size=message_size,
                                   delay_allreduce=True)
    assert late.buckets(sizes) == (len(sizes),)


def _lm_bench():
    import importlib
    import os
    import sys
    tools = os.path.join(os.path.dirname(__file__), "..", "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    return importlib.import_module("lm_bench")


def _tiny_lm():
    from apex_tpu.models import TransformerLM
    return TransformerLM(vocab_size=256, max_seq_len=32, embed_dim=64,
                         num_heads=2, num_layers=2, head_chunk=128)


@pytest.fixture(scope="module")
def ddp_steps():
    """One step from the same state and batch on four virtual devices:
    ``build_train_step``'s DDP arm with buckets of 20,000 elements (the
    policy's own default holds this toy in one), and the step it replaced,
    written out: the flat master differentiated whole, ONE psum of ONE
    buffer. Plus what ``record_collective`` saw while the first traced."""
    import apex_tpu.parallel as par
    from apex_tpu.ops import flat as F
    from apex_tpu.parallel import collectives as C
    from apex_tpu.parallel import Plan, compile_step_with_plan
    lm_bench = _lm_bench()
    lm, half = _tiny_lm(), jnp.bfloat16
    params = lm.init(jax.random.key(0))
    mesh = make_mesh({"data": 4}, devices=jax.devices()[:4])
    toks = jax.random.randint(jax.random.key(1), (8, 33), 0, 256)
    real = par.DistributedDataParallel
    par.DistributedDataParallel = partial(real, message_size=20_000)
    try:
        opt, state, step, plan = lm_bench.build_train_step(
            lm, params, mesh, half=half)
    finally:
        par.DistributedDataParallel = real
    table = opt._tables[0]
    state, toks = lm_bench.place_for_plan(state, toks, plan)
    keep = jax.tree.map(jnp.copy, state)
    C.reset_collective_bytes()
    got_state, got_loss = compile_step_with_plan(step, plan)(state, toks)
    tally = C.collective_bytes()
    ddp = real(axis_name="data")

    def one_psum(state, toks):
        loss, fg = jax.value_and_grad(lambda m: lm.loss(
            F.unflatten(m, table, dtype=half), toks))(state[0].master)
        return opt.apply_update(state, [ddp.average_gradients(fg)]), \
            jax.lax.pmean(loss, "data")
    ref_state, ref_loss = compile_step_with_plan(
        one_psum, Plan(mesh=mesh, in_specs=plan.in_specs,
                       out_specs=plan.out_specs))(keep, toks)
    return dict(table=table, tally=tally, got=(got_state[0], got_loss),
                ref=(ref_state[0], ref_loss))


def test_bucketed_ddp_step_is_the_one_psum_step(ddp_steps):
    """Loss, the flat gradient (read back through ``exp_avg``, as the
    benchmark reads it) and the master: each bucket is the same float32
    sum over the same four devices, so to rounding at the worst."""
    (got, got_loss), (ref, ref_loss) = ddp_steps["got"], ddp_steps["ref"]
    assert float(got_loss) == float(ref_loss)
    g, r = np.asarray(got.slots["exp_avg"]), np.asarray(ref.slots["exp_avg"])
    assert np.abs(r).max() > 0
    np.testing.assert_allclose(g, r, rtol=1e-6, atol=1e-9)
    np.testing.assert_allclose(np.asarray(got.slots["exp_avg_sq"]),
                               np.asarray(ref.slots["exp_avg_sq"]),
                               rtol=1e-6, atol=1e-12)
    np.testing.assert_allclose(np.asarray(got.master),
                               np.asarray(ref.master), rtol=0, atol=1e-7)
    assert int(got.step) == int(ref.step) == 1


def test_bucketed_ddp_step_issues_one_psum_a_bucket(ddp_steps):
    """K psums of float32 buckets whose bytes sum to the flat gradient's,
    and the loss's scalar."""
    table = ddp_steps["table"]
    k = len(DistributedDataParallel(message_size=20_000).buckets(
        table.padded_sizes))
    assert k > 3
    psum = ddp_steps["tally"]["ops"]["psum[data]"]
    assert psum["calls"] == k
    assert psum["bytes"] == 4 * table.total


def test_one_device_step_is_one_unflatten_and_no_psum():
    """One chip is one bucket: the buffer itself through ONE ``unflatten``
    (one cast of the whole master), no slice of it and no collective."""
    lm_bench = _lm_bench()
    lm = _tiny_lm()
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    _, state, step, plan = lm_bench.build_train_step(
        lm, lm.init(jax.random.key(0)), mesh, half=jnp.bfloat16)
    assert plan.lowering() == "jit"
    text = str(jax.make_jaxpr(step)(state, jnp.zeros((2, 33), jnp.int32)))
    assert "psum" not in text and "all_reduce" not in text
    n = state[0].master.shape[0]
    # the whole master cast once, forward; its transpose is one concat of
    # the leaves' gradients and one convert back
    assert len(re.findall(rf"bf16\[{n}\] = convert_element_type", text)) == 1
    assert len(re.findall(rf"bf16\[{n}\] = concatenate", text)) == 1
    assert len(re.findall(rf"f32\[{n}\] = convert_element_type", text)) == 1
    assert not re.search(r"f32\[\d+\] = slice\[", text)    # no bucket cut
