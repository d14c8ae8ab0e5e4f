"""Compile the main path's Pallas kernels for a DESCRIBED v5e.

The TPU's compiler is installed beside the CPU backend and compiles for
a chip that is described, not attached (``on-chip-measurement`` guide,
section 2): what it refuses here — a misaligned slice, too much VMEM, a
program that does not fit HBM — it would refuse on the chip, and costs
no chip time to find. Nothing runs, so these are not chip runs and say
nothing about results or speed.

The kernels read ``jax.default_backend()`` to choose interpret mode and
the dispatch side; the fixture steers that here, in the test.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
FLAT = 128 * 1024 * 1024            # >= the dense LM's 138.5M-param masters


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2 (four chips, none attached), or skip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:            # no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """One described v5e device."""
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_chip(monkeypatch):
    """Take the on-chip branches (interpret off, Pallas dispatch) and
    keep the persistent compile cache out of it: an entry compiled for
    a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    from apex_tpu.ops import dispatch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dispatch._default_platform.cache_clear()
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
    dispatch._default_platform.cache_clear()


def _flash(bwd, **kw):
    from apex_tpu.contrib.multihead_attn import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, **kw)
    if not bwd:
        return fwd
    return jax.grad(lambda q, k, v: jnp.sum(
        fwd(q, k, v).astype(F32) ** 2), argnums=(0, 1, 2))


def _flash_bd(q, k, v):
    from apex_tpu.contrib.multihead_attn import flash_attention
    return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, block_diffusion=(4, q.shape[2] // 2)).astype(F32) ** 2),
        argnums=(0, 1, 2))(q, k, v)


def _flash_sel(q, k, v, select):
    from apex_tpu.contrib.multihead_attn import flash_attention
    return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, select=select).astype(F32) ** 2),
        argnums=(0, 1, 2))(q, k, v)


def _qkv(b, h, s, d):
    return [((b, h, s, d), BF16)] * 3


def _dense_decode(q, k, v, n):
    from apex_tpu.ops.pallas.decode_attn import decode_attention
    return decode_attention(q, k, v, n)


def _paged_decode(q, k, v, n, table):
    from apex_tpu.ops.pallas.decode_attn import paged_decode_attention
    return paged_decode_attention(q, k, v, n, page_table=table)


def _dense_args(s, h, ln, hd):
    return [((s, h, hd), BF16), ((s, h, ln, hd), BF16),
            ((s, h, ln, hd), BF16), ((s,), I32)]


def _paged_args(s, h, page, pages, hd):
    pool = ((s * pages + 1, h, page, hd), BF16)
    return [((s, h, hd), BF16), pool, pool, ((s,), I32),
            ((s, pages), I32)]


def _multi_tensor(name, **kw):
    from apex_tpu.ops.pallas import multi_tensor
    return functools.partial(getattr(multi_tensor, name), **kw)


_ADAM = dict(lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8, step=1)

# (id, builder of the traced function, [(shape, dtype), ...],
#  the kernels' names: the HLO instructions a device trace will show)
KERNELS = [
    ("dense_decode-32x8x8192x128", lambda: _dense_decode,
     _dense_args(32, 8, 8192, 128),
     ("apex_decode_dense",)),
    ("dense_decode-32x8x2048x128", lambda: _dense_decode,
     _dense_args(32, 8, 2048, 128),
     ("apex_decode_dense",)),
    ("paged_decode-page32x64", lambda: _paged_decode,
     _paged_args(32, 8, 32, 64, 128),
     ("apex_decode_paged",)),
    ("paged_decode-page16x128", lambda: _paged_decode,
     _paged_args(32, 8, 16, 128, 128),
     ("apex_decode_paged",)),
    ("flash_fwd-B8H8S4096D128", lambda: _flash(False),
     _qkv(8, 8, 4096, 128),
     ("apex_flash_fwd",)),
    ("flash_fwd_bwd-B8H8S4096D128", lambda: _flash(True),
     _qkv(8, 8, 4096, 128),
     ("apex_flash_fwd", "apex_flash_bwd_dq", "apex_flash_bwd_dkv")),
    ("flash_fwd-B1H8S16384D128", lambda: _flash(False),
     _qkv(1, 8, 16384, 128),
     ("apex_flash_fwd",)),
    ("flash_fwd_bwd-B1H8S16384D128", lambda: _flash(True),
     _qkv(1, 8, 16384, 128),
     ("apex_flash_fwd", "apex_flash_bwd_dq", "apex_flash_bwd_dkv")),
    ("flash_fwd-B8H16S4096D64", lambda: _flash(False),
     _qkv(8, 16, 4096, 64),
     ("apex_flash_fwd",)),
    ("flash_fwd_bwd-B8H16S4096D64", lambda: _flash(True),
     _qkv(8, 16, 4096, 64),
     ("apex_flash_fwd", "apex_flash_bwd_dq", "apex_flash_bwd_dkv")),
    # the hybrid LM's full-attention layer: 16 heads of 256 (K and V
    # broadcast from 2 in front of the kernel), 2 rows of 8192
    ("flash_fwd-B2H16S8192D256", lambda: _flash(False),
     _qkv(2, 16, 8192, 256),
     ("apex_flash_fwd",)),
    ("flash_fwd_bwd-B2H16S8192D256", lambda: _flash(True),
     _qkv(2, 16, 8192, 256),
     ("apex_flash_fwd", "apex_flash_bwd_dq", "apex_flash_bwd_dkv")),
    # LFM2's attention layer (32 heads of 64, two side by side in a lane
    # tile's 128) and the dense LM's (24 rows of 2048, 16 heads of 128):
    # with the two above, every shape a benchmark cell gives the kernels
    ("flash_fwd-B2H32S8192D128", lambda: _flash(False),
     _qkv(2, 32, 8192, 128),
     ("apex_flash_fwd",)),
    ("flash_fwd_bwd-B2H32S8192D128", lambda: _flash(True),
     _qkv(2, 32, 8192, 128),
     ("apex_flash_fwd", "apex_flash_bwd_dq", "apex_flash_bwd_dkv")),
    # a sliding-window layer's band (window 1024, the band's default
    # blocks: 1024 x 1024 forward, 512 x 512 backward) at heads of 128
    # and, the widest the blocks must still fit VMEM at, of 256
    ("flash_win_fwd_bwd-B2H32S8192D128W1024",
     lambda: _flash(True, window=1024), _qkv(2, 32, 8192, 128),
     ("apex_flash_win_fwd", "apex_flash_win_bwd_dq",
      "apex_flash_win_bwd_dkv")),
    ("flash_win_fwd_bwd-B2H16S8192D256W1024",
     lambda: _flash(True, window=1024), _qkv(2, 16, 8192, 256),
     ("apex_flash_win_fwd", "apex_flash_win_bwd_dq",
      "apex_flash_win_bwd_dkv")),
    # Keye-VL 2.0's sparse layer: one row of 16,384, 32 heads of 128 over a
    # packed per-query key set shared by the heads (a bit a key)
    ("flash_sel_fwd_bwd-B1H32S16384D128", lambda: _flash_sel,
     _qkv(1, 32, 16384, 128) + [((1, 16384, 512), I32)],
     ("apex_flash_sel_fwd", "apex_flash_sel_bwd_dq",
      "apex_flash_sel_bwd_dkv")),
    # SDAR's block-diffusion layer: 8,192 positions twice (the noised copy
    # beside the clean one), 32 heads of 128, blocks of 4
    ("flash_bd_fwd_bwd-B1H32S16384D128", lambda: _flash_bd,
     _qkv(1, 32, 16384, 128),
     ("apex_flash_bd_fwd", "apex_flash_bd_bwd_dq", "apex_flash_bd_bwd_dkv")),
    ("flash_fwd-B24H16S2048D128", lambda: _flash(False),
     _qkv(24, 16, 2048, 128),
     ("apex_flash_fwd",)),
    ("flash_fwd_bwd-B24H16S2048D128", lambda: _flash(True),
     _qkv(24, 16, 2048, 128),
     ("apex_flash_fwd", "apex_flash_bwd_dq", "apex_flash_bwd_dkv")),
    ("scale-128M", lambda: _multi_tensor("scale", scale_factor=0.5),
     [((FLAT,), F32)],
     ("apex_mt_scale",)),
    ("l2norm-128M", lambda: _multi_tensor("l2norm"), [((FLAT,), F32)],
     ("apex_mt_l2norm",)),
    ("adam_step-128M", lambda: _multi_tensor("adam_step", **_ADAM),
     [((FLAT,), F32)] * 4,
     ("apex_mt_adam",)),
]


def _compile(fn, args, sharding):
    specs = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
             for shape, dtype in args]
    return jax.jit(fn).lower(*specs).compile()


@pytest.mark.parametrize("make_fn,args,names",
                         [pytest.param(m, a, n, id=i)
                          for i, m, a, n in KERNELS])
def test_kernel_compiles_for_v5e(chip, for_chip, make_fn, args, names):
    compiled = _compile(make_fn(), args, chip)
    text = compiled.as_text()
    assert "tpu_custom_call" in text                 # not interpreted
    # each kernel's instruction is named after its pallas_call's name=:
    # %apex_mt_adam.1 under jit or a named scope; under a bare grad the
    # transformation wraps it (%transpose_jvp_apex_flash_bwd_dq__.1)
    assert [n for n in names
            if not re.search(rf"%(\w+_)?{n}_*\.\d+ = ", text)] == []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes < 16e9            # fits one v5e's HBM


def _delta_rule():
    from apex_tpu.ops.gated_delta_rule import gated_delta_rule
    return jax.grad(lambda q, k, v, g, b: jnp.sum(gated_delta_rule(
        q, k, v, g, b, chunk=64).astype(F32)), argnums=(0, 1, 2, 3, 4))


def _expert_layer(**kw):
    """The gradient of one ``ExpertLayer`` (with no shared expert: of its
    routed part alone) in its parameters and its input."""
    def make():
        from apex_tpu.contrib.moe import ExpertLayer
        layer = ExpertLayer(hidden=2048, **kw)
        return lambda p, x: jax.grad(lambda p, x: jnp.sum(
            layer.apply(p, x)[0].astype(F32)), argnums=(0, 1))(p, x)
    return make


def _expert_args(f, experts, held, shared=None, d=2048):
    p = {"router": ((d, experts), BF16), "w_gate": ((held, d, f), BF16),
         "w_up": ((held, d, f), BF16), "w_down": ((held, f, d), BF16)}
    if shared:
        p["shared"] = shared
    return [p, ((2, 8192, d), BF16)]


_QNEXT_SHARED = {"w_gate": ((2048, 512), BF16), "w_up": ((2048, 512), BF16),
                 "w_down": ((512, 2048), BF16), "gate": ((2048, 1), BF16)}


def _indexer_args(s, heads=16, dim=64):
    return [((1, s, heads, dim), BF16), ((1, s, dim), BF16),
            ((1, s, heads), F32)]


def _select_keys(qi, ki, w):
    from apex_tpu.ops import sparse_index
    return sparse_index.select_keys(qi, ki, w, 2048)


def _index_loss(qi, ki, w, q, k, lse, select):
    from apex_tpu.ops import sparse_index
    return jax.value_and_grad(lambda qi, ki, w: sparse_index.index_loss(
        qi, ki, w, q, k, lse, select, scale=128 ** -0.5),
        argnums=(0, 1, 2))(qi, ki, w)


def _grouped_matmul():
    from apex_tpu.ops.pallas.grouped_matmul import grouped_matmul
    return jax.grad(lambda lhs, w, tile_e, live: jnp.sum(grouped_matmul(
        lhs, (w,), tile_e, live)[0] ** 2), argnums=(0, 1))


def _grouped_args(rows, held, depth, width):
    return [((rows, depth), BF16), ((held, depth, width), BF16),
            ((rows // 128,), I32), ((), I32)]


_KDA_ARGS = [((2, 32, 8192, 128), BF16)] * 3 + [((2, 32, 8192, 128), F32),
                                                ((2, 32, 8192), F32)]


# (id, builder, arguments (trees of (shape, dtype)), temporaries allowed in
#  GB, the kernels the program has to hold): the hybrid LM's new ops at the
#  published widths and the benchmark cells' 2 x 8192 tokens. The delta
#  rule's chunk-local part is jax.numpy and its loop over chunks the Pallas
#  pair; the expert layer is jax.numpy around the grouped matmuls' kernels
NEW_OPS = [
    # the routed experts' products, both cells' shapes, both directions of a
    # layer (hidden -> ffn, ffn -> hidden): forward, by rows, by experts
    ("grouped_matmul_fwd_bwd-kvl-24576x2048x8x1408", _grouped_matmul,
     _grouped_args(24576, 8, 2048, 1408), 0.5,
     ("apex_moe_gmm", "apex_moe_tgmm")),
    ("grouped_matmul_fwd_bwd-kvl-24576x1408x8x2048", _grouped_matmul,
     _grouped_args(24576, 8, 1408, 2048), 0.5,
     ("apex_moe_gmm", "apex_moe_tgmm")),
    ("grouped_matmul_fwd_bwd-qnext-9216x2048x16x512", _grouped_matmul,
     _grouped_args(9216, 16, 2048, 512), 0.2,
     ("apex_moe_gmm", "apex_moe_tgmm")),
    ("grouped_matmul_fwd_bwd-qnext-9216x512x16x2048", _grouped_matmul,
     _grouped_args(9216, 16, 512, 2048), 0.2,
     ("apex_moe_gmm", "apex_moe_tgmm")),
    # one routed layer of kvl_train_s8192 (1.06 GB; the einsum over
    # gathered weights took 6.89)
    ("expert_layer_routed_fwd_bwd-kvl-N16384E64held8", _expert_layer(
        ffn=1408, num_experts=64, top_k=6, experts_held=(0, 8),
        dispatch_bound=24576, router="sigmoid", routed_scale=2.446),
     _expert_args(1408, 64, 8), 1.5, ("apex_moe_gmm", "apex_moe_tgmm")),
    # the loop over chunks and its backward: one 128 x 128 state a chunk a
    # head (0.54 GB), never one a token (34 GB)
    ("gated_delta_rule_fwd_bwd-B2H32S8192D128", _delta_rule,
     [((2, 32, 8192, 128), BF16)] * 3 + [((2, 32, 8192), F32)] * 2, 4.0,
     ("apex_gdn_fwd", "apex_gdn_bwd")),
    # Kimi Delta Attention's rule at the published shape ([64, 8192, 128],
    # chunk 64): a decay a key channel, the chunk-local products level by
    # level in one Pallas pair, the loop over chunks in the other with the
    # state transposed and its decayed operands made in VMEM (3.36 GB of
    # temporaries, and 5% of room; 3.90 with exp(G) q, exp(G_last - G) k
    # made in jax.numpy, 4.86 with the levels' operands too); g's
    # cotangent float32 [2, 32, 8192, 128]
    ("kda_delta_rule_fwd_bwd-B2H32S8192D128", _delta_rule,
     _KDA_ARGS, 3.53,
     ("apex_kda_local_fwd", "apex_kda_local_bwd", "apex_kda_fwd",
      "apex_kda_bwd")),
    # Keye-VL 2.0's lightning indexer at the published shapes (16 heads of
    # 64 over one key head, a row of 16,384, the 2,048 best keys a query):
    # the search a chunk of queries at a time, never [S, S] float32 (1 GB):
    # a chunk's scores (67 MB) are the program's one temporary, the search
    # holds a block of them in VMEM and writes the packed words
    ("sparse_index_select-B1S16384H16D64", lambda: _select_keys,
     _indexer_args(16384), 0.1, ("apex_idx_scores", "apex_idx_search")),
    ("sparse_index_loss_fwd_bwd-B1S16384H16D64", lambda: _index_loss,
     _indexer_args(16384) + [((1, 32, 16384, 128), BF16),
                             ((1, 4, 16384, 128), BF16),
                             ((1, 32, 16384), F32), ((1, 16384, 512), I32)],
     1.0, ("apex_idx_scores", "apex_idx_probs", "apex_idx_grad")),
    # 512-way routing, the sort, grouped matmuls over 16 held experts
    ("expert_layer_fwd_bwd-N16384E512held16", _expert_layer(
        ffn=512, num_experts=512, top_k=10, experts_held=(0, 16),
        shared_ffn=512, dispatch_bound=12288),
     _expert_args(512, 512, 16, _QNEXT_SHARED), 1.5,
     ("apex_moe_gmm", "apex_moe_tgmm")),
]


def _specs(args, chip):
    """Trees of ``(shape, dtype)`` as arguments on the described chip."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a[0], a[1], sharding=chip), args,
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))


@pytest.mark.parametrize("make_fn,args,temporaries,names",
                         [pytest.param(m, a, t, n, id=i)
                          for i, m, a, t, n in NEW_OPS])
def test_jnp_op_compiles_and_fits_for_v5e(chip, for_chip, make_fn, args,
                                          temporaries, names):
    compiled = jax.jit(make_fn()).lower(*_specs(args, chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < temporaries * 1e9
    text = compiled.as_text()
    assert [n for n in names
            if not re.search(rf"%(\w+_)?{n}_*\.\d+ = ", text)] == []
    # no expert's weights gathered a tile, no gradient a tile, of any type:
    # [tiles, hidden, ffn] or [tiles, ffn, hidden] (kvl: [192, 2048, 1408])
    assert re.findall(r"\[(192|72|96),(2048,(1408|512)|(1408|512),2048)\]",
                      text) == []


@functools.cache
def _kda_gradient_text(chip) -> str:
    """The compiled gradient of the vector gate's rule at the published
    shape, for the two tests that read it."""
    return jax.jit(_delta_rule()).lower(*_specs(_KDA_ARGS, chip)).compile() \
        .as_text()


def test_the_vector_gates_rule_forms_no_array_of_two_token_axes_and_a_channel_axis(
        chip, for_chip):
    """At the published shape no buffer of the compiled gradient has two
    token axes of a chunk (64 x 64) or of a sub-block (16 x 16) beside the
    128 channels (17 GB and 4.3 GB in float32), nor ``[L, L]``: the decay
    a channel goes into the operands, level by level, never into a
    ``[C, C, dk]`` mask. And no level's operands or product (``q``'s rows
    beside ``k``'s, ``[.., 2, 64, 128]`` and ``[.., 2, 64, 64]``) is a
    buffer at all: they are made and used in VMEM."""
    text = _kda_gradient_text(chip)
    shapes = set(re.findall(r"(?:f32|bf16)\[([0-9,]+)\]", text))
    assert any(s.endswith("64,128") for s in shapes)
    assert not [s for s in shapes if re.search(
        r"(^|,)(64,64,128|16,16,128|8192,8192)(,|$)|,2,64,(128|64)$", s)]
    # g's cotangent: float32, a number a key channel
    assert re.search(r"ENTRY[^\n]*->[^\n]*f32\[2,32,8192,128\]", text)


def test_the_vector_gates_scan_decays_its_operands_in_vmem_for_v5e(
        chip, for_chip):
    """XLA makes no decayed operand of the loop over chunks: of the
    exponentials the compiled gradient holds outside its kernels (a
    kernel's own are in its serialized body, not in the text) every one is
    ``exp(G)`` over ``[2, 32, 128, 64, 128]``, the operand of ``W = T (beta
    exp(G) k)`` and its transposes (4; 9 with ``exp(G) q`` and ``exp(G_last
    - G) k`` in ``jax.numpy``): none of a difference (``G_last - G``), none
    of a slice (``exp(G_last) [.., 1, 128]``), none over the scan pair's
    ``[64, 128, 64, 128]``."""
    text = _kda_gradient_text(chip)
    over = re.findall(r" = f32\[([0-9,]+)\][^ ]* exponential\(%([a-z_]+)",
                      text)
    assert over and {shape for shape, _ in over} == {"2,32,128,64,128"}
    assert len(over) <= 4
    assert not [of for _, of in over if of.startswith(("sub", "slice"))]


def test_the_combine_and_the_dead_rows_cost_no_pass_for_v5e(chip, for_chip):
    """One routed layer of ``mellum2_train_s8192`` (a share: 16 of 64
    experts, a bound of 65,536 rows of 2304): the pairs' weights reach
    ``apex_moe_gmm`` as the down projection's scale, so under
    ``moe_route`` the program multiplies no ``f32[65536, 2304]`` by them
    and selects no ``bf16[65536, 2304]`` for the dead rows, and it holds
    the kernels' four calls and no fifth: the scale's own gradient, the
    unscaled products again, is dead code where the weights are constants
    of the backward."""
    from apex_tpu.contrib.moe import ExpertLayer
    layer = ExpertLayer(hidden=2304, ffn=896, num_experts=64, top_k=8,
                        experts_held=(0, 16), dispatch_bound=65536)
    text = jax.jit(jax.grad(lambda p, x: jnp.sum(jnp.sin(layer.routed(
        p, x)[0])), argnums=(0, 1))).lower(*_specs(
            _expert_args(896, 64, 16, d=2304), chip)).compile().as_text()
    # a computation's name -> its instructions; a fusion is its root's
    bodies = {m.group(1): m.group(2) for m in re.finditer(
        r"^(?:ENTRY )?%?([\w.\-]+) \([^\n]*\{\n(.*?)^\}", text,
        re.M | re.S)}
    buffer = r" = (f32|bf16)\[65536,2304\]\S* "
    passes = []
    for line in "".join(body for name, body in bodies.items()
                        if "fused_computation" not in name).splitlines():
        if not (re.search(buffer, line) and "moe_route" in line):
            continue
        called = re.search(r"calls=%([\w.\-]+)", line)
        root = line if not called else re.search(
            r"^\s*ROOT [^\n]*", bodies[called.group(1)], re.M).group()
        passes += re.findall(buffer + r"(multiply|select)\(", root)
    assert passes == []
    assert len(re.findall(r"%(\w+_)?apex_moe_gmm_*\.\d+ = ", text)) == 4
    assert len(re.findall(r"%(\w+_)?apex_moe_tgmm_*\.\d+ = ", text)) == 3


@pytest.mark.parametrize("hidden,ffn,experts,held,bound,was,kernels", [
    pytest.param(2304, 896, 64, 16, 65536, 2.10, 2, id="mellum2"),
    pytest.param(2048, 768, 128, 16, 65536, 1.58, 2, id="keye"),
    pytest.param(2304, 896, 64, 64, 0, 4.86, 1, id="a_whole_layer_of_64")])
def test_the_two_sums_scatter_nothing_and_fit_for_v5e(
        chip, for_chip, hidden, ffn, experts, held, bound, was, kernels):
    """One routed layer of ``mellum2_train_s8192`` and one of
    ``keye_train_s16384`` (16,384 tokens, top-8, 16 experts held, a bound
    of 65,536 rows): the loss gradient scatters into no ``[tokens,
    hidden]`` array and gathers none a slot either: the combine and ``dx``
    are ``apex_moe_rowsum``, one call each, which holds a block's sum and
    a round's chunks in VMEM and nothing in HBM, so the layer's
    temporaries stay within a hundredth of the parent's (``was``, the GB
    its layer read here; a ``[tokens, top_k, hidden]`` float32 array, which
    a gather-sum in XLA writes, would be 1.2 and 1.1 GB more). And the
    first where a chip holds all 64 experts (the worst-case bound, 139,264
    rows), which ``row_sum.takes`` for ``dx``'s bfloat16 rows and not for
    the combine's float32 ones (its readings): that sum is ``top_k``
    gathers of ``f32[tokens, hidden]`` added in one pass, no scatter and no
    ``[tokens, top_k, hidden]`` array either."""
    from apex_tpu.contrib.moe import ExpertLayer
    layer = ExpertLayer(hidden=hidden, ffn=ffn, num_experts=experts,
                        top_k=8, experts_held=(0, held), dispatch_bound=bound)
    compiled = jax.jit(jax.grad(lambda p, x: jnp.sum(jnp.sin(layer.routed(
        p, x)[0])), argnums=(0, 1))).lower(*_specs(
            _expert_args(ffn, experts, held, d=hidden), chip)).compile()
    text = compiled.as_text()
    wide = re.findall(rf" = (\w+)\[16384,{hidden}\]\S* (scatter|gather)\(",
                      text)
    assert wide == [("f32", "gather")] * (8 * (2 - kernels))
    assert len(re.findall(r"%(\w+_)?apex_moe_rowsum_*\.\d+ = ",
                          text)) == kernels
    assert compiled.memory_analysis().temp_size_in_bytes < was * 1.01e9


def _step(name, skip, **kw):
    """``multi_tensor.<name>`` over (g, p, m, v[, segment ids]) and, with
    ``skip``, a traced overflow flag as the last argument."""
    fn = _multi_tensor(name, **kw)

    def traced(g, p, m, v, *rest):
        if skip:
            *rest, flag = rest
            return fn(g, p, m, v, *rest, skip=flag)
        return fn(g, p, m, v, *rest)
    return traced


# (id, step, arguments after (g, p, m, v), kernels, state-sized temporaries
#  allowed)
IN_PLACE = [
    ("adam_step-128M", "adam_step", _ADAM, [],
     ("apex_mt_adam",), 1),
]


@pytest.mark.parametrize("skip", [False, True], ids=["donated",
                                                     "donated-skip"])
@pytest.mark.parametrize("name,kw,more,names,temporaries",
                         [pytest.param(*c[1:], id=c[0]) for c in IN_PLACE])
def test_step_kernel_updates_donated_state_in_place(
        chip, for_chip, name, kw, more, names, temporaries, skip):
    """The in-place contract on the chip's compiler: with p, m and v
    donated, the program holds no copy of a state-shaped buffer (the
    kernel's outputs alias its inputs, so XLA has nothing to protect) and
    its temporaries stay under what the step itself needs, with or
    without the overflow flag."""
    args = [((FLAT,), F32)] * 4 + more + ([((), jnp.bool_)] if skip else [])
    specs = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
             for shape, dtype in args]
    compiled = jax.jit(_step(name, skip, **kw),
                       donate_argnums=(1, 2, 3)).lower(*specs).compile()
    text = compiled.as_text()
    assert [n for n in names
            if not re.search(rf"%(\w+_)?{n}_*\.\d+ = ", text)] == []
    state = rf"f32\[({FLAT}|{FLAT // 128},128)\]"
    assert re.findall(rf"= {state}(\{{[^}}]*\}})? copy\(", text) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 3 * 4 * FLAT      # p, m, v: in place
    assert mem.temp_size_in_bytes < temporaries * 4 * FLAT * 1.01


def test_engine_paged_decode_program_compiles_for_v5e(chip, for_chip):
    """The serving engine's paged decode step, as ``lint_programs()``
    describes it, at the dense LM's widths (depth and arena cut so the
    host copy stays small)."""
    from apex_tpu.models import TransformerLM
    from apex_tpu.serve import ContinuousBatchingEngine
    lm = TransformerLM(vocab_size=32768, max_seq_len=2048, embed_dim=1024,
                       num_heads=8, num_layers=2)
    params = jax.tree.map(lambda t: t.astype(BF16) if t.dtype == F32 else t,
                          lm.init(jax.random.key(0)))
    engine = ContinuousBatchingEngine(
        lm, params, slots=8, max_len=2048, prefill_chunk=32, fused=True,
        paged=True, page_size=32, prefix_share=True)
    decode, = [p for p in engine.lint_programs()
               if p["name"].endswith(".decode")]
    specs = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        np.shape(x), x.dtype, sharding=chip), decode["args"])
    text = decode["fn"].lower(*specs).compile().as_text()
    # one paged decode-attention kernel per layer
    assert text.count("tpu_custom_call") >= lm.num_layers


# -- the dense-LM train step: DDP's gradient buckets on the 2x2 -------------

def _lm_step(devices, layers=2):
    """``tools/lm_bench.build_train_step`` at the benchmark configuration's
    widths (Cerebras-GPT-1.3B's; ``layers`` of them, 4 rows of 2048+1
    tokens a chip) compiled for the described ``devices``: (compiled
    step, the optimizer's table)."""
    import sys
    from jax.sharding import NamedSharding, PartitionSpec as P
    from apex_tpu.models import TransformerLM
    from apex_tpu.parallel import compile_step_with_plan, make_mesh
    tools = os.path.join(os.path.dirname(__file__), "..", "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    import lm_bench
    lm = TransformerLM(vocab_size=50257, max_seq_len=2048, embed_dim=2048,
                       num_heads=16, num_layers=layers, ffn_mult=4,
                       attn_impl="fast", head_chunk=1733)
    mesh = make_mesh({"data": len(devices)}, devices=list(devices))
    # the optimizer flattens real arrays: zeros, on the host
    with jax.default_device(jax.devices("cpu")[0]):
        params = jax.tree.map(lambda s: jnp.zeros(s.shape, F32),
                              jax.eval_shape(lm.init, jax.random.key(0)))
        opt, state, step, plan = lm_bench.build_train_step(
            lm, params, mesh, half=BF16)
    many = len(devices) > 1
    rep = NamedSharding(mesh, P()) if many \
        else jax.sharding.SingleDeviceSharding(devices[0])
    rows = NamedSharding(mesh, P("data")) if many else rep
    state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=rep), state)
    toks = jax.ShapeDtypeStruct((4 * len(devices), 2049), I32, sharding=rows)
    compiled = compile_step_with_plan(step, plan).lower(state,
                                                        toks).compile()
    return compiled, opt._tables[0]


def _entry(text):
    """(the scheduled ENTRY computation's lines, every computation's body
    by name) of a compiled module's text."""
    bodies = {m.group(2): m.group(3) for m in re.finditer(
        r"^(ENTRY )?%(\S+) \(.*?\{\n(.*?)^\}", text, re.S | re.M)}
    name = re.search(r"^ENTRY %(\S+) ", text, re.M).group(1)
    return bodies[name].splitlines(), bodies


def test_ddp_step_reduces_its_buckets_under_the_backward(topo, for_chip):
    """The four-chip step holds one all-reduce a bucket (the combiner has
    not tied them back into one), most of them asynchronous, and backward
    matmul fusions run between the first one's start and the last one's
    done: the schedule hides them, which the compiler's defaults do not
    (``parallel/plan.py`` ``_TPU_SHARD_MAP_OPTIONS``)."""
    from apex_tpu.parallel import DistributedDataParallel
    compiled, table = _lm_step(topo.devices)
    k = len(DistributedDataParallel().buckets(table.padded_sizes))
    assert k >= 6                                 # three a layer, the rest
    lines, bodies = _entry(compiled.as_text())
    starts = [i for i, l in enumerate(lines)
              if re.match(r"\s+%async-collective-start\S* = ", l)]
    dones = [i for i, l in enumerate(lines)
             if re.match(r"\s+%async-collective-done\S* = ", l)]
    sync = [i for i, l in enumerate(lines) if " all-reduce(" in l]
    assert len(starts) == len(dones) >= k // 2
    assert len(starts) + len(sync) == k + 1       # the buckets, the loss
    for i in starts:                              # each reduces a bucket
        called = re.search(r"calls=%([^,\s]+)", lines[i]).group(1)
        assert " all-reduce(" in bodies[called]

    def backward_matmul(line):
        called = re.search(r"calls=%([^,\s]+)", line)
        return called is not None and "transpose(jvp(" in line \
            and " convolution(" in bodies.get(called.group(1), "")
    hidden_under = [l for l in lines[starts[0]:dones[-1]]
                    if backward_matmul(l)]
    assert len(hidden_under) >= 1
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


def test_one_chip_step_has_no_all_reduce(topo, for_chip):
    """One chip is one bucket and no mesh axis: no collective at all, and
    the kernels the cell counts on."""
    compiled, _ = _lm_step(topo.devices[:1])
    text = compiled.as_text()
    assert "all-reduce" not in text and "async-collective" not in text
    assert len(re.findall(r"%apex_mt_adam\S* = ", text)) == 1
    assert "apex_flash_fwd" in text


# -- the reduced fused head: where a block's logits live ---------------------

@pytest.mark.parametrize("n,d,v,rows,in_vmem", [
    (16384, 2304, 20480, 1024, True),       # klin: 80 MiB a block
    (16384, 2304, 24576, 1024, True),       # mellum2: 96 MiB, the edge
    (8192, 2048, 50257, 2048, False),       # cgpt: 412 MB, HBM
], ids=["klin", "mellum2", "cgpt"])
def test_the_reduced_heads_logits_live_where_its_rows_were_chosen_for(
        chip, for_chip, n, d, v, rows, in_vmem):
    """``weighted_linear_cross_entropy``'s gradient at the cells' shapes:
    a block is ``_block_rows(V)`` rows, its float32 logits carry ``S(1)``
    (VMEM) where the rule chose 1,024 rows for that and not where the
    vocabulary is too wide, and the float32 ``[V, D]`` accumulator of
    ``dW`` is updated in place: no copy of that shape in the loop."""
    from apex_tpu.contrib.xentropy import weighted_linear_cross_entropy

    def grad(h, w, labels):
        return jax.value_and_grad(
            lambda h, w: weighted_linear_cross_entropy(
                h, w, labels, jnp.full((n,), 1.0 / n, F32)), (0, 1))(h, w)
    h, w, labels = (jax.ShapeDtypeStruct(s, t, sharding=chip) for s, t in
                    (((n, d), BF16), ((v, d), BF16), ((n,), I32)))
    text = jax.jit(grad).lower(h, w, labels).compile().as_text()
    logits = re.findall(rf"= f32\[{rows},{v}\]\{{[^}}]*\}}", text)
    assert logits, f"no [{rows}, {v}] float32 logits in the program"
    assert any("S(1)" in l for l in logits) == in_vmem
    assert not re.search(rf"= f32\[{v},{d}\]\{{[^}}]*\}} copy\(", text)
    assert len(re.findall(r" convolution\(", text)) == 3
