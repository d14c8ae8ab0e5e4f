"""The one rule for which implementation of an op runs (``ops/dispatch.py``).

Under ``"auto"`` a kernel runs because the platform is a TPU and the kernel's
module takes the shapes; nothing else reaches one. For every op family that
has two sides, traced on both platforms (nothing runs): off the TPU the
program holds no ``pallas_call``, on it exactly the family's documented
names, and none at shapes the kernel's module does not take. On the chip
``chip_smoke.py``'s ``kernels`` phase finds the same kernels as
``tpu_custom_call``s; here the rule is read without one.
"""

import jax
import pytest

from apex_tpu.ops import dispatch
from apex_tpu.ops import kernels as K

from test_pallas_kernels import _f32, _i32, _pallas_names

_N = 128 * 16
_BUF, _ROW_IDS = _f32(_N), _i32(_N)
_HP = dict(lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8, step=1)


def _trace(fn, *args):
    """A thunk tracing ``fn`` anew (a trace is cached by function, and the
    platform is no part of the key)."""
    return lambda: jax.make_jaxpr(lambda *a: fn(*a))(*args)


def _gdn(head_dim=128):
    from apex_tpu.ops.gated_delta_rule import gated_delta_rule
    head, gate = _f32(1, 2, 128, head_dim), _f32(1, 2, 128)
    return _trace(jax.grad(lambda *a: gated_delta_rule(*a, chunk=64).sum(),
                           argnums=(0, 1, 2, 3, 4)),
                  head, head, head, gate, gate)


def _kda(head_dim=128, chunk=64):
    """The delta rule under a decay a channel (``g [B, H, L, dk]``)."""
    from apex_tpu.ops.gated_delta_rule import gated_delta_rule
    head, beta = _f32(1, 2, 128, head_dim), _f32(1, 2, 128)
    return _trace(jax.grad(lambda *a: gated_delta_rule(*a, chunk=chunk).sum(),
                           argnums=(0, 1, 2, 3, 4)),
                  head, head, head, head, beta)


def _experts(hidden=128):
    def trace():
        from apex_tpu.contrib.moe.expert_layer import ExpertLayer
        layer = ExpertLayer(hidden=hidden, ffn=128, num_experts=4, top_k=2)
        params = jax.eval_shape(layer.init, jax.random.key(0))
        return _trace(jax.grad(lambda p, x: layer.apply(p, x)[0].sum(),
                               argnums=(0, 1)), params, _f32(256, hidden))()
    return trace


def _decode(paged, below=0, head_dim=128):
    def trace():
        from apex_tpu.contrib.multihead_attn.decode_attention import (
            decode_min_l, slot_decode_attention)
        # the crossover: the kernel from decode_min_l() keys on
        length = decode_min_l() - below
        q, lengths = _f32(2, 4, head_dim), _i32(2)
        if not paged:
            arena = _f32(2, 4, length, head_dim)
            return _trace(slot_decode_attention, q, arena, arena, lengths)()
        pool = _f32(9, 4, 16, head_dim)
        return _trace(lambda q, k, v, n, pt: slot_decode_attention(
            q, k, v, n, page_table=pt), q, pool, pool, lengths,
            _i32(2, length // 16))()
    return trace


# family -> (thunk tracing the op as its callers call it, the kernels the
# rule picks for it on a TPU). With the kernels a caller asks for by name
# (ASKED_BY_NAME) this is every name of test_pallas_kernels.KERNEL_SITES.
FAMILIES = {
    "scale": (_trace(lambda x: K.scale(x, 2.0), _BUF), {"apex_mt_scale"}),
    "axpby": (_trace(lambda x, y: K.axpby(1.0, x, 2.0, y), _BUF, _BUF),
              {"apex_mt_axpby"}),
    "l2norm": (_trace(K.l2norm, _BUF), {"apex_mt_l2norm"}),
    "segment_norms": (
        _trace(lambda x, ids: (
            K.l2norm_per_segment(x, ids, 2, aligned_segments=True),
            K.maxnorm_per_segment(x, ids, 2, aligned_segments=True)),
            _BUF, _ROW_IDS),
        {"apex_mt_rowsumsq", "apex_mt_rowmaxabs"}),
    "adam": (_trace(lambda *a: K.adam_step(*a, **_HP), *[_BUF] * 4),
             {"apex_mt_adam"}),
    "adagrad": (_trace(lambda *a: K.adagrad_step(*a, lr=1e-3, eps=1e-8),
                       *[_BUF] * 3), {"apex_mt_adagrad"}),
    "sgd": (_trace(lambda *a: K.sgd_step(*a, wd=0.0, momentum=0.9,
                                         dampening=0.0, lr=1e-3),
                   *[_BUF] * 3), {"apex_mt_sgd"}),
    "novograd": (
        _trace(lambda g, p, m, v, ids: K.novograd_step(
            g, p, m, v, ids, aligned_segments=True, **_HP),
            _BUF, _BUF, _BUF, _f32(2), _ROW_IDS),
        {"apex_mt_novograd", "apex_mt_rowsumsq"}),
    # one side on every platform: XLA's won on the chip (docs/PERF.md r03)
    "lamb": (
        _trace(lambda g, p, m, v, ids: K.lamb_step(
            g, p, m, v, ids, 2, aligned_segments=True, global_grad_norm=1.0,
            **_HP), *[_BUF] * 4, _ROW_IDS),
        set()),
    "gated_delta_rule": (_gdn(), {"apex_gdn_fwd", "apex_gdn_bwd"}),
    "gated_delta_rule_vector_gate": (_kda(), {
        "apex_kda_local_fwd", "apex_kda_local_bwd", "apex_kda_fwd",
        "apex_kda_bwd"}),
    "expert_layer": (_experts(), {"apex_moe_gmm", "apex_moe_tgmm",
                                  "apex_moe_rowsum"}),
    "decode_dense": (_decode(paged=False), {"apex_decode_dense"}),
    "decode_paged": (_decode(paged=True), {"apex_decode_paged"}),
}

# flash attention and the indexer: the caller names the kernel
# (``attn_impl=`` / ``impl=``), the rule picks nothing
ASKED_BY_NAME = ("apex_flash_", "apex_idx_")


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_auto_picks_by_platform(family, platform, monkeypatch):
    trace, kernels = FAMILIES[family]
    monkeypatch.setattr(dispatch, "_default_platform", lambda: platform)
    with dispatch.backend("auto"):
        got = set(_pallas_names(trace()))
    assert got == (kernels if platform == "tpu" else set())


# on a TPU, at shapes a kernel's module does not take: the jnp side
UNTAKEN = {
    "buffer_not_in_whole_lanes": _trace(
        lambda *a: K.adam_step(*a, **_HP), *[_f32(100)] * 4),
    "segments_not_known_aligned": _trace(
        lambda g, p, m, v, ids: K.novograd_step(g, p, m, v, ids, **_HP),
        _BUF, _BUF, _BUF, _f32(2), _ROW_IDS),
    "delta_rule_heads_of_64": _gdn(head_dim=64),
    "vector_gate_delta_rule_heads_of_64": _kda(head_dim=64),
    "vector_gate_delta_rule_chunks_of_32": _kda(chunk=32),
    "experts_of_half_a_lane_tile": _experts(hidden=64),
    "decode_dense_below_crossover": _decode(paged=False, below=16),
    "decode_paged_below_crossover": _decode(paged=True, below=16),
    "decode_heads_of_64": _decode(paged=False, head_dim=64),
}


@pytest.mark.parametrize("case", sorted(UNTAKEN))
def test_auto_leaves_shapes_a_kernel_does_not_take(case, monkeypatch):
    monkeypatch.setattr(dispatch, "_default_platform", lambda: "tpu")
    with dispatch.backend("auto"):
        assert _pallas_names(UNTAKEN[case]()) == []
