"""ResNet (18/34/50/101/152) — the framework's flagship benchmark model.

The reference ships no models; its headline measurement is torchvision
ResNet-50 driven by examples/imagenet/main_amp.py (img/s =
world_size*batch/batch_time, main_amp.py:390-398) under AMP + DDP +
fused optimizers. This module provides the equivalent model TPU-first:

- **NHWC layout** throughout — channels map to TPU lanes; the reference's
  ``channels_last`` opt-in (main_amp.py:30-47 memory_format) is the default
  here;
- convs via ``lax.conv_general_dilated`` (MXU-tiled by XLA), bf16-friendly:
  all math follows input dtype, BN statistics in fp32 via
  :class:`apex_tpu.parallel.SyncBatchNorm` (axis_name=None -> local BN,
  set to a mesh axis for cross-replica stat sync);
- functional init/apply: ``params`` (trainable) and ``state`` (BN running
  stats) are separate pytrees, so the whole model jits/shard_maps cleanly.

Matches torchvision resnet v1 architecture (the weights the reference
example trains): 7x7 stem, maxpool, 4 stages of basic/bottleneck blocks,
stride-2 downsample convs, global average pool, fc.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.parallel.sync_batchnorm import SyncBatchNorm


def conv(params, x, *, stride=1, padding="SAME"):
    """NHWC conv with HWIO kernel."""
    return jax.lax.conv_general_dilated(
        x, params.astype(x.dtype),
        window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _conv_init(rng, kh, kw, cin, cout, dtype):
    # he_normal fan_out, matching torchvision's kaiming_normal_ mode=fan_out
    fan_out = kh * kw * cout
    std = math.sqrt(2.0 / fan_out)
    return std * jax.random.normal(rng, (kh, kw, cin, cout), dtype)


class ResNet:
    """ResNet v1. ``block_sizes``/``bottleneck`` select the variant:

    - ResNet-18: [2,2,2,2], bottleneck=False
    - ResNet-50: [3,4,6,3], bottleneck=True (default)

    ``bn_axis_name`` switches every BN to cross-replica SyncBatchNorm
    (the ``convert_syncbn_model`` analog, reference:
    apex/parallel/__init__.py:21-56 — a constructor flag instead of a
    recursive module rewrite).
    """

    def __init__(self, block_sizes: Sequence[int] = (3, 4, 6, 3),
                 bottleneck: bool = True, num_classes: int = 1000,
                 width: int = 64, bn_axis_name: Optional[str] = None,
                 bn_axis_index_groups=None, param_dtype=jnp.float32,
                 stem_pool: str = "max", stem: str = "conv"):
        self.block_sizes = tuple(block_sizes)
        self.bottleneck = bool(bottleneck)
        self.num_classes = int(num_classes)
        self.width = int(width)
        self.bn_axis_name = bn_axis_name
        self.bn_axis_index_groups = bn_axis_index_groups
        self.param_dtype = jnp.dtype(param_dtype)
        if stem_pool not in ("max", "avg"):
            raise ValueError(f"stem_pool must be 'max' or 'avg', "
                             f"got {stem_pool!r}")
        # 'avg' swaps the stem maxpool for an average pool — a perf
        # diagnostic (maxpool's backward is a select_and_scatter, which
        # can dominate on some backends) and an accuracy-neutral-ish
        # variant some production RN50 recipes use.
        self.stem_pool = stem_pool
        if stem not in ("conv", "space_to_depth"):
            raise ValueError(f"stem must be 'conv' or 'space_to_depth', "
                             f"got {stem!r}")
        # 'space_to_depth': EXACT algebraic rewrite of the 7x7/s2 stem as
        # a 4x4/s1 conv on 2x2-space-to-depth input (the MLPerf TPU RN50
        # trick): 3 input channels starve the MXU's 128-deep contraction,
        # 12 channels at stride 1 feed it 4x better. Same params (the 7x7
        # kernel is rearranged on the fly), same math — checkpoints and
        # the flat store are unaffected.
        self.stem = stem
        self._bn = partial(SyncBatchNorm, axis_name=bn_axis_name,
                           axis_index_groups=bn_axis_index_groups,
                           channel_axis=-1)
        self.expansion = 4 if self.bottleneck else 1

    def replace(self, **kw) -> "ResNet":
        """Rebuild with changed config (used by
        ``parallel.convert_syncbn_model`` to flip BN to cross-replica)."""
        cfg = dict(block_sizes=self.block_sizes, bottleneck=self.bottleneck,
                   num_classes=self.num_classes, width=self.width,
                   bn_axis_name=self.bn_axis_name,
                   bn_axis_index_groups=self.bn_axis_index_groups,
                   param_dtype=self.param_dtype, stem_pool=self.stem_pool,
                   stem=self.stem)
        cfg.update(kw)
        return type(self)(**cfg)

    # -- init ---------------------------------------------------------------
    def init(self, rng: jax.Array) -> tuple[dict, dict]:
        dt = self.param_dtype
        params, state = {}, {}
        rng, k = jax.random.split(rng)
        params["conv_stem"] = _conv_init(k, 7, 7, 3, self.width, dt)
        bn = self._bn(self.width)
        params["bn_stem"], state["bn_stem"] = bn.init()

        cin = self.width
        for s, nblocks in enumerate(self.block_sizes):
            cmid = self.width * (2 ** s)
            cout = cmid * self.expansion
            for b in range(nblocks):
                name = f"stage{s}_block{b}"
                stride = 2 if (s > 0 and b == 0) else 1
                rng, *ks = jax.random.split(rng, 5)
                blk_p, blk_s = {}, {}
                if self.bottleneck:
                    blk_p["conv1"] = _conv_init(ks[0], 1, 1, cin, cmid, dt)
                    blk_p["conv2"] = _conv_init(ks[1], 3, 3, cmid, cmid, dt)
                    blk_p["conv3"] = _conv_init(ks[2], 1, 1, cmid, cout, dt)
                    for i, f in enumerate((cmid, cmid, cout), 1):
                        p, st = self._bn(f).init()
                        blk_p[f"bn{i}"], blk_s[f"bn{i}"] = p, st
                else:
                    blk_p["conv1"] = _conv_init(ks[0], 3, 3, cin, cmid, dt)
                    blk_p["conv2"] = _conv_init(ks[1], 3, 3, cmid, cout, dt)
                    for i, f in enumerate((cmid, cout), 1):
                        p, st = self._bn(f).init()
                        blk_p[f"bn{i}"], blk_s[f"bn{i}"] = p, st
                if b == 0 and (stride != 1 or cin != cout):
                    blk_p["conv_proj"] = _conv_init(ks[3], 1, 1, cin, cout, dt)
                    p, st = self._bn(cout).init()
                    blk_p["bn_proj"], blk_s["bn_proj"] = p, st
                params[name], state[name] = blk_p, blk_s
                cin = cout

        rng, k1, k2 = jax.random.split(rng, 3)
        bound = 1.0 / math.sqrt(cin)
        params["fc_w"] = jax.random.uniform(k1, (cin, self.num_classes), dt,
                                            -bound, bound)
        params["fc_b"] = jax.random.uniform(k2, (self.num_classes,), dt,
                                            -bound, bound)
        return params, state

    # -- apply --------------------------------------------------------------
    def _block(self, p, st, x, *, cmid, stride, training):
        new_st = {}
        shortcut = x
        if "conv_proj" in p:
            shortcut = conv(p["conv_proj"], x, stride=stride)
            shortcut, new_st["bn_proj"] = self._bn(shortcut.shape[-1]).apply(
                p["bn_proj"], st["bn_proj"], shortcut, training=training)

        if self.bottleneck:
            h = conv(p["conv1"], x, stride=1)
            h, new_st["bn1"] = self._bn(cmid, fuse_relu=True).apply(
                p["bn1"], st["bn1"], h, training=training)
            h = conv(p["conv2"], h, stride=stride)
            h, new_st["bn2"] = self._bn(cmid, fuse_relu=True).apply(
                p["bn2"], st["bn2"], h, training=training)
            h = conv(p["conv3"], h, stride=1)
            # final BN fuses the residual add + relu (the groupbn
            # bn_add_relu pattern, contrib/csrc/groupbn/batch_norm_add_relu.cu)
            h, new_st["bn3"] = self._bn(h.shape[-1], fuse_relu=True).apply(
                p["bn3"], st["bn3"], h, z=shortcut, training=training)
        else:
            h = conv(p["conv1"], x, stride=stride)
            h, new_st["bn1"] = self._bn(cmid, fuse_relu=True).apply(
                p["bn1"], st["bn1"], h, training=training)
            h = conv(p["conv2"], h, stride=1)
            h, new_st["bn2"] = self._bn(h.shape[-1], fuse_relu=True).apply(
                p["bn2"], st["bn2"], h, z=shortcut, training=training)
        return h, new_st

    def _stem_conv(self, w, x):
        """The 7x7/s2 SAME stem conv, optionally as its space-to-depth
        rewrite. Derivation: with input padded lo=2/hi=4 per spatial dim
        (the extra hi column only meets the zero kernel row), y[oi] =
        sum_kh xe[2*oi + kh] * w8[kh] with w8 the kernel zero-padded
        7->8; substituting kh = 2u + a turns it into a VALID 4x4 stride-1
        conv between the 2x2 space-to-depth views of xe and w8."""
        n, hh, ww_, c = x.shape
        if self.stem == "conv" or hh % 2 or ww_ % 2:
            # odd sizes shift the even/odd phase the rewrite relies on
            # (SAME lo-padding becomes odd) — use the plain conv there
            return conv(w, x, stride=2)
        xe = jnp.pad(x, ((0, 0), (2, 4), (2, 4), (0, 0)))
        he, we = xe.shape[1] // 2, xe.shape[2] // 2
        xs = xe.reshape(n, he, 2, we, 2, c).transpose(0, 1, 3, 2, 4, 5) \
            .reshape(n, he, we, 4 * c)
        w8 = jnp.pad(w, ((0, 1), (0, 1), (0, 0), (0, 0)))
        cout = w.shape[-1]
        w2 = w8.reshape(4, 2, 4, 2, c, cout).transpose(0, 2, 1, 3, 4, 5) \
            .reshape(4, 4, 4 * c, cout)
        return jax.lax.conv_general_dilated(
            xs, w2.astype(xs.dtype), window_strides=(1, 1),
            padding="VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def apply(self, params: dict, state: dict, x: jax.Array,
              training: bool = True) -> tuple[jax.Array, dict]:
        """x: (N, H, W, 3) NHWC. Returns (logits fp32, new_state).

        Module boundaries (stem / stageN_blockM / head) are wrapped in
        ``jax.named_scope`` — metadata only (HLO op names, profiler
        timelines, and the per-module grouping of
        ``prof.coverage``/``tools/precision_audit.py``); the computation
        is unchanged."""
        new_state = {}
        with jax.named_scope("stem"):
            h = self._stem_conv(params["conv_stem"], x)
            h, new_state["bn_stem"] = self._bn(
                self.width, fuse_relu=True).apply(
                params["bn_stem"], state["bn_stem"], h, training=training)
            if self.stem_pool == "max":
                h = jax.lax.reduce_window(
                    h, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                    padding=((0, 0), (1, 1), (1, 1), (0, 0)))
            else:
                # fp32 operand + literal 0.0 init so this lowers to the
                # reduce_window_sum primitive (which has a transpose
                # rule); the generic reduce_window_p is not
                # reverse-differentiable
                h = jax.lax.reduce_window(
                    h.astype(jnp.float32), 0.0, jax.lax.add,
                    (1, 3, 3, 1), (1, 2, 2, 1),
                    padding=((0, 0), (1, 1), (1, 1), (0, 0)))
                h = (h / 9.0).astype(x.dtype)

        for s, nblocks in enumerate(self.block_sizes):
            cmid = self.width * (2 ** s)
            for b in range(nblocks):
                name = f"stage{s}_block{b}"
                stride = 2 if (s > 0 and b == 0) else 1
                with jax.named_scope(name):
                    h, new_state[name] = self._block(
                        params[name], state[name], h,
                        cmid=cmid, stride=stride, training=training)

        with jax.named_scope("head"):
            h = jnp.mean(h, axis=(1, 2))
            fc_w = params["fc_w"]
            if h.dtype == fc_w.dtype and h.dtype in (jnp.bfloat16,
                                                     jnp.float16):
                # O2/O3: run the fc dot in the storage half dtype with an
                # fp32 accumulator instead of upcasting both operands to
                # a (slower, convert-bounded) fp32 MXU pass. The half
                # operand values are exact and both shapes accumulate in
                # fp32, so this differs from the upcast dot only by
                # summation order — and it removes the last two
                # standalone activation/param converts in the head (r06
                # cast-coalescing audit).
                logits = jnp.matmul(h, fc_w,
                                    preferred_element_type=jnp.float32) \
                    + params["fc_b"].astype(jnp.float32)
            else:
                logits = h.astype(jnp.float32) @ fc_w.astype(jnp.float32) \
                    + params["fc_b"].astype(jnp.float32)
        return logits, new_state

    def __call__(self, params, state, x, training=True):
        return self.apply(params, state, x, training=training)


def analytic_flops(model: "ResNet", image: int) -> float:
    """Analytic forward FLOPs/img (2*K*K*Cin*Cout*Hout*Wout per conv + fc,
    2 flops per MAC). Training approx = 3x (bwd-wrt-input and
    bwd-wrt-weights each cost ~1 fwd). Used as the honest MFU numerator by
    tools/perf_probe.py (validated within 2% of XLA's cost
    analysis for RN50@224)."""
    def up(n, s):  # SAME-padding output size: ceil(n / s)
        return -(-n // s)

    flops = 0.0
    h = up(image, 2)  # 7x7/2 stem
    flops += 2 * 7 * 7 * 3 * model.width * h * h
    h = up(h, 2)      # stem pool
    cin = model.width
    for s, nblocks in enumerate(model.block_sizes):
        cmid = model.width * (2 ** s)
        cout = cmid * model.expansion
        for b in range(nblocks):
            stride = 2 if (s > 0 and b == 0) else 1
            hout = up(h, stride)
            if model.bottleneck:
                flops += 2 * 1 * 1 * cin * cmid * h * h
                flops += 2 * 3 * 3 * cmid * cmid * hout * hout
                flops += 2 * 1 * 1 * cmid * cout * hout * hout
            else:
                flops += 2 * 3 * 3 * cin * cmid * hout * hout
                flops += 2 * 3 * 3 * cmid * cout * hout * hout
            if b == 0 and (stride != 1 or cin != cout):
                flops += 2 * 1 * 1 * cin * cout * hout * hout
            cin = cout
            h = hout
    flops += 2 * cin * model.num_classes  # fc
    return flops


def resnet18(**kw) -> ResNet:
    return ResNet(block_sizes=(2, 2, 2, 2), bottleneck=False, **kw)


def resnet34(**kw) -> ResNet:
    return ResNet(block_sizes=(3, 4, 6, 3), bottleneck=False, **kw)


def resnet50(**kw) -> ResNet:
    return ResNet(block_sizes=(3, 4, 6, 3), bottleneck=True, **kw)


def resnet101(**kw) -> ResNet:
    return ResNet(block_sizes=(3, 4, 23, 3), bottleneck=True, **kw)


def resnet152(**kw) -> ResNet:
    return ResNet(block_sizes=(3, 8, 36, 3), bottleneck=True, **kw)
