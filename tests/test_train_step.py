"""``apex_tpu.train_step``: the one place a training step is differentiated
with respect to the flat master, and the two thin functions
(``bench.build_train_step``, ``tools/lm_bench.build_train_step``) the
benchmark's drivers import around it.

The hand-written bodies kept here are the steps the two scripts held
before the builder: the builder must lower to their text, so that the
cells' programs are the ones the ledger's numbers came from.
"""

import inspect
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu import amp, train_step as T
from apex_tpu.ops import flat as F
from apex_tpu.optimizers import FusedAdam, FusedLAMB, FusedSGD
from apex_tpu.parallel import (DistributedDataParallel,
                               compile_step_with_plan, make_mesh)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (REPO, os.path.join(REPO, "tools")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

BF16 = jnp.bfloat16


# -- the one-device body against the bodies it replaced ---------------------

def _dense_lm_case():
    """(no handle, no aux): FusedAdam on ``TransformerLM.loss``."""
    from apex_tpu.models import TransformerLM
    lm = TransformerLM(vocab_size=64, max_seq_len=17, embed_dim=32,
                       num_heads=2, num_layers=1, head_chunk=32)
    opt = FusedAdam(lm.init(jax.random.key(0)), lr=1e-4)
    table = opt._tables[0]
    toks = jnp.zeros((2, 17), jnp.int32)
    body = T.build_step(opt, lm.loss, half=BF16)

    def step(state, toks):
        state, _, loss, _ = body(state, None, toks)
        return state, loss

    def written(state, toks):
        loss, fg = jax.value_and_grad(
            lambda m: lm.loss(F.unflatten(m, table, dtype=BF16),
                              toks))(state[0].master)
        return opt.apply_update(state, [fg]), loss

    return step, written, (opt.init_state(), toks)


def _resnet_case():
    """(handle, batch-norm aux): FusedLAMB on the softmax head under a
    dynamic loss scale, the batch-norm state beside the loss."""
    from apex_tpu.contrib.xentropy import select_label_logits
    from apex_tpu.models import ResNet
    model = ResNet(block_sizes=(1,), bottleneck=True, num_classes=10,
                   width=8)
    params, bn = model.init(jax.random.key(0))
    _, handle = amp.initialize(opt_level="O2", loss_scale="dynamic",
                               verbosity=0)
    half = handle.policy.cast_model_dtype
    opt = FusedLAMB(params, lr=1e-3)
    table = opt._tables[0]

    def head(logits, y):
        with jax.named_scope("head"):
            logits = logits.astype(jnp.float32)
            logp = jax.nn.log_softmax(logits)
            return -jnp.mean(select_label_logits(logp, y))

    def loss_fn(p, bn_state, x, y):
        logits, new_bn = model.apply(p, bn_state, x, training=True)
        return head(logits, y), new_bn

    body = T.build_step(opt, loss_fn, half=half, handle=handle)

    def step(opt_state, bn_state, amp_state, x, y):
        opt_state, amp_state, loss, bn_state = body(
            opt_state, amp_state, bn_state, x, y)
        return opt_state, bn_state, amp_state, loss

    def written(opt_state, bn_state, amp_state, x, y):
        def scaled(master, bn_state, amp_state, x, y):
            p_half = F.unflatten(master, table, dtype=half)
            logits, new_st = model.apply(p_half, bn_state, x, training=True)
            loss = head(logits, y)
            return handle.scale_loss(loss, amp_state), (loss, new_st)
        fg, (loss, new_bn) = jax.grad(scaled, has_aux=True)(
            opt_state[0].master, bn_state, amp_state, x, y)
        fg, found_inf = handle.unscale(fg, amp_state)
        new_opt = opt.apply_update(opt_state, [fg], found_inf=found_inf)
        new_amp = handle.update(amp_state, found_inf)
        return new_opt, new_bn, new_amp, loss

    x = jnp.zeros((4, 16, 16, 3), half)
    y = jnp.zeros((4,), jnp.int32)
    return step, written, (opt.init_state(), bn, handle.init_state(), x, y)


def _hybrid_lm_case():
    """(no handle, counters aux): FusedAdam on ``HybridLM.
    loss_with_counters``, the counters beside the loss."""
    from apex_tpu.models import HybridLM
    lm = HybridLM(
        vocab_size=96, hidden=32, layer_types=("linear", "full"),
        num_heads=4, num_kv_heads=2, head_dim=16, rotary_dim=4,
        linear_k_heads=2, linear_v_heads=4, linear_k_dim=8, linear_v_dim=8,
        delta_chunk=16, num_experts=8, top_k=2, expert_ffn=16, shared_ffn=16,
        experts_held=(2, 6))
    opt = FusedAdam(lm.init(jax.random.key(0)), lr=1e-4)
    table = opt._tables[0]
    toks = jnp.zeros((2, 33), jnp.int32)
    body = T.build_step(opt, lm.loss_with_counters, half=BF16)

    def step(state, toks):
        state, _, loss, counters = body(state, None, toks)
        return state, (loss, counters)

    def written(state, toks):
        (loss, counters), fg = jax.value_and_grad(
            lambda m: lm.loss_with_counters(
                F.unflatten(m, table, dtype=BF16), toks),
            has_aux=True)(state[0].master)
        return opt.apply_update(state, [fg]), (loss, counters)

    return step, written, (opt.init_state(), toks)


@pytest.mark.parametrize("case", [_dense_lm_case, _resnet_case,
                                  _hybrid_lm_case],
                         ids=["plain", "handle-batchnorm", "counters"])
def test_one_device_body_lowers_to_the_hand_written_step(case):
    """One bucket is the buffer itself and a missing handle is no
    operation: the builder's body is, to the character of its lowered
    text, the step each script wrote out for itself."""
    step, written, args = case()
    step.__name__ = written.__name__ = "step"
    got = jax.jit(step).lower(*args).as_text()
    want = jax.jit(written).lower(*args).as_text()
    assert got == want
    assert "all_reduce" not in got and "all-reduce" not in got


# -- a loss-scaled step under DDP's buckets ---------------------------------

def _mlp(key, width=64):
    k = jax.random.split(key, 3)
    return {"w1": jax.random.normal(k[0], (16, width)) * 0.2,
            "b1": jnp.zeros((width,)),
            "w2": jax.random.normal(k[1], (width, width)) * 0.1,
            "w3": jax.random.normal(k[2], (width, 4)) * 0.1}


def _mlp_loss(p, x, y):
    h = jnp.tanh(x.astype(p["w1"].dtype) @ p["w1"] + p["b1"])
    out = (jnp.tanh(h @ p["w2"]) @ p["w3"]).astype(jnp.float32)
    return jnp.mean(jnp.sum((out - y) ** 2, axis=-1))


def _scaled_mlp_step(n_dev, opt_cls, message_size=None, half=BF16,
                     **opt_kw):
    """(compiled step over ``n_dev`` devices, its initial state, the
    number of buckets) for the MLP under a dynamic loss scale; the body
    takes ``((opt_state, amp_state), (x, y))``."""
    _, handle = amp.initialize(opt_level="O2", loss_scale="dynamic",
                               verbosity=0)
    opt = opt_cls(_mlp(jax.random.key(0)), **opt_kw)
    ddp = None
    if n_dev > 1:
        ddp = DistributedDataParallel(axis_name="data") \
            if message_size is None else DistributedDataParallel(
                axis_name="data", message_size=message_size)
    body = T.build_step(opt, _mlp_loss, half=half, handle=handle, ddp=ddp)

    def step(state, batch):
        opt_state, amp_state, loss, _ = body(*state, *batch)
        return (opt_state, amp_state), loss

    mesh = make_mesh({"data": n_dev}, devices=jax.devices()[:n_dev])
    plan = T.step_plan(mesh, P() if n_dev > 1 else None)
    k = 1 if ddp is None else len(ddp.buckets(opt._tables[0].padded_sizes))
    return (compile_step_with_plan(step, plan), plan,
            (opt.init_state(), handle.init_state()), k)


def _batch(rows=8, poison=None):
    x = jax.random.normal(jax.random.key(3), (rows, 16))
    y = jax.random.normal(jax.random.key(4), (rows, 4))
    if poison is not None:
        x = x.at[poison, 0].set(jnp.inf)
    return x, y


@pytest.mark.parametrize("message_size", [1000, None],
                         ids=["buckets", "one-bucket"])
def test_scaled_ddp_step_is_the_one_device_step_on_the_whole_batch(
        message_size):
    """Four virtual devices, each on its two rows, under a loss scale of
    2**16 and the policy's buckets, against one device on all eight rows:
    the same loss, the same averaged gradient (read through SGD's
    update), the same scaler state. Neither script could build this: the
    scaled step knew no buckets and the bucketed step no handle."""
    # float32 parameters: in bf16 one device rounds the sum of eight
    # rows' weight gradient where four round two rows' each
    one, plan1, state1, _ = _scaled_mlp_step(1, FusedSGD, half=None, lr=0.1)
    many, plan4, state4, k = _scaled_mlp_step(4, FusedSGD, message_size,
                                              half=None, lr=0.1)
    assert k == (3 if message_size else 1)
    before = np.asarray(state1[0][0].master)
    s1, l1 = one(*T.place_for_plan(state1, _batch(), plan1))
    s4, l4 = many(*T.place_for_plan(state4, _batch(), plan4))
    np.testing.assert_allclose(float(l4), float(l1), rtol=1e-6)
    moved1 = np.asarray(s1[0][0].master) - before
    moved4 = np.asarray(s4[0][0].master) - before
    assert np.abs(moved1).max() > 1e-4
    np.testing.assert_allclose(moved4, moved1, rtol=1e-4, atol=1e-7)
    for a, b in zip(jax.tree.leaves(s4[1]), jax.tree.leaves(s1[1])):
        assert np.asarray(a) == np.asarray(b)
    assert float(s4[1][0].scale) == 2.0 ** 16


@pytest.mark.parametrize("n_dev", [1, 4], ids=["one-device", "ddp4"])
def test_overflowing_step_skips_and_halves_the_scale(n_dev):
    """An infinity in one row (under DDP: on one device only) skips the
    update everywhere, master, m and v bit for bit, and halves the loss
    scale; the next clean step moves the master again."""
    step, plan, state, _ = _scaled_mlp_step(n_dev, FusedAdam, 1000, lr=0.1)
    keep = jax.tree.map(np.asarray, state[0][0])
    state, loss = step(*T.place_for_plan(state, _batch(poison=7), plan))
    group = state[0][0]
    assert np.array_equal(np.asarray(group.master), keep.master)
    for name, was in keep.slots.items():
        assert np.array_equal(np.asarray(group.slots[name]), was)
    assert float(state[1][0].scale) == 2.0 ** 15
    assert int(state[1][0].overflow_count) == 1
    state, loss = step(state, T.place_for_plan(state, _batch(), plan)[1])
    assert np.isfinite(float(loss))
    assert not np.array_equal(np.asarray(state[0][0].master), keep.master)
    assert float(state[1][0].scale) == 2.0 ** 15


def test_step_plan_and_placement():
    """One device: plain jit and one bulk transfer; a mesh: shard_map, the
    state replicated and donated, the batch split over the data axis."""
    one = T.step_plan(make_mesh({"data": 1}, devices=jax.devices()[:1]))
    assert one.lowering() == "jit" and one.donate_argnums == (0,)
    mesh = make_mesh({"data": 4}, devices=jax.devices()[:4])
    plan = T.step_plan(mesh, P())
    assert plan.lowering() == "shard_map" and plan.donate_argnums == (0,)
    assert plan.in_specs == (P(), P("data")) and plan.out_specs == (P(), P())
    state, batch = T.place_for_plan({"w": jnp.ones((8, 2))},
                                    jnp.arange(8.0), plan)
    assert state["w"].sharding.is_fully_replicated
    assert len(batch.addressable_shards) == 4
    assert batch.addressable_shards[0].data.shape == (2,)


# -- the lint audits the composition the cells run --------------------------

def test_lint_bench_program_is_the_benchmarks_resnet_step():
    """``analysis.programs``' ``bench_o2`` lowers to the text of
    ``bench.build_train_step`` at the same sizes: the package's audit and
    the cell go through one builder, and the same choice of optimizer and
    loss."""
    import bench
    from apex_tpu.analysis import programs
    from apex_tpu.models import ResNet
    view = programs.bench_step_program("O2")
    model = ResNet(block_sizes=(1, 1), bottleneck=True, num_classes=10,
                   width=8)
    params, _ = model.init(jax.random.key(0))
    _, handle = amp.initialize(opt_level="O2", verbosity=0,
                               half_dtype="bfloat16")
    _, _, train_step = bench.build_train_step(model, params, handle)
    mine = jax.jit(train_step, donate_argnums=(0, 1, 2))
    assert view.fn.lower(*view.example_args).as_text() == \
        mine.lower(*view.example_args).as_text()


def test_lint_lm_program_is_the_benchmarks_lm_step():
    """``analysis.programs``' ``lm`` lowers to the text of ``tools/
    lm_bench.build_train_step`` over the same devices at the same sizes
    (the test process holds eight: DDP's buckets under shard_map)."""
    import lm_bench
    from apex_tpu.analysis import programs
    from apex_tpu.models import TransformerLM
    view = programs.lm_step_program()
    n_dev = len(jax.devices())
    assert view.name == f"lm.train_step@shard_mapx{n_dev}"
    lm = TransformerLM(vocab_size=512, max_seq_len=128, embed_dim=128,
                       num_heads=4, num_layers=2, head_chunk=512)
    _, _, step, plan = lm_bench.build_train_step(
        lm, lm.init(jax.random.key(0)), make_mesh({"data": n_dev}),
        half=BF16)
    assert plan == view.plan
    assert view.fn.lower(*view.example_args).as_text() == \
        compile_step_with_plan(step, plan).lower(
            *view.example_args).as_text()


# -- the two scripts are the names the drivers import -----------------------

_BARE = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
_BARE["JAX_PLATFORMS"] = "cpu"


@pytest.mark.parametrize("module, path, names", [
    ("bench", "",
     {"build_train_step": "(model, params, handle, *, lr=0.001)"}),
    ("lm_bench", "tools",
     {"build_train_step":
      "(lm, params, mesh, *, half, zero=False, lr=0.0001)",
      "place_for_plan": "(state, batch, plan"}),
], ids=["bench", "lm_bench"])
def test_script_imports_from_a_foreign_directory(module, path, names,
                                                 tmp_path):
    """As ``benchmarks/drivers/`` import them: the script's directory on
    ``sys.path``, nothing else given, any working directory. The
    signatures are the ones the drivers call."""
    code = (f"import sys; sys.path.insert(0, {os.path.join(REPO, path)!r});"
            f"import inspect, {module} as m;"
            "print({n: str(inspect.signature(getattr(m, n)))"
            f" for n in {sorted(names)!r}}})")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, cwd=tmp_path, env=_BARE)
    assert r.returncode == 0, r.stderr[-800:]
    got = eval(r.stdout.strip().splitlines()[-1])
    for name, sig in names.items():
        assert got[name].startswith(sig), (name, got[name])


@pytest.mark.parametrize("path", ["bench.py", "tools/lm_bench.py"])
def test_script_is_a_choice_of_optimizer_and_loss(path):
    """No ``main``, no argument parser, no environment name: what is left
    is one function around the package's builder."""
    with open(os.path.join(REPO, path)) as f:
        text = f.read()
    for gone in ("def main", "argparse", "os.environ", "__main__",
                 "fori_loop"):
        assert gone not in text, gone
    assert len(re.findall(r"^def ", text, re.M)) == 1
    assert "apex_tpu.train_step" in text or "train_step as T" in text
    assert len(text.splitlines()) < 100


def test_build_step_signature_has_no_switches():
    """The handle and the DDP policy are inputs: a caller that has none
    passes none."""
    sig = inspect.signature(T.build_step)
    assert list(sig.parameters) == ["opt", "loss_fn", "half", "handle",
                                    "ddp"]
    assert all(sig.parameters[n].default is None
               for n in ("half", "handle", "ddp"))
