"""Seeded weights, made by the benchmark and handed to BOTH sides.

The program gets the tree in its own layout and type; the plain
reference gets the same values. Nothing here imports the program. Every
leaf has a key of its own (the seed folded with a hash of the leaf's
path), so one leaf, one layer or the whole tree can be made alone and
comes out the same: the served model's reference regenerates a layer at
a time instead of holding 5 GB of fp32 weights.

A tree of *specs* describes a model: ``{name: subtree | (shape, kind)}``
with kind ``("normal", std)``, ``"ones"`` or ``"zeros"``.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def _leaf(key, path: str, spec, dtype):
    shape, kind = spec
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    name, std = kind
    if name != "normal":
        raise ValueError(f"unknown init kind {kind!r} at {path}")
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def build(specs, key, dtype, prefix: str = ""):
    """The tree of arrays for ``specs``. Trace it inside ``jax.jit``: the
    whole model is then one program on the device."""
    if _is_spec(specs):
        return _leaf(key, prefix, specs, dtype)
    return {name: build(sub, key, dtype, f"{prefix}/{name}")
            for name, sub in specs.items()}


def count(specs) -> int:
    if _is_spec(specs):
        n = 1
        for d in specs[0]:
            n *= d
        return n
    return sum(count(s) for s in specs.values())


def gpt2_layer_specs(cfg: dict) -> dict:
    e, f = cfg["n_embd"], cfg["n_inner"]
    std = cfg.get("initializer_range", 0.02)
    w = ("normal", std)
    ln = lambda: {"g": ((e,), "ones"), "b": ((e,), "zeros")}
    return {
        "ln1": ln(),
        "attn": {"in_proj": ((e, 3 * e), w),
                 "in_proj_bias": ((3 * e,), "zeros"),
                 "out_proj": ((e, e), w),
                 "out_proj_bias": ((e,), "zeros")},
        "ln2": ln(),
        "mlp": {"w1": ((e, f), w), "b1": ((f,), "zeros"),
                "w2": ((f, e), w), "b2": ((e,), "zeros")},
    }


def gpt2_specs(cfg: dict) -> dict:
    """GPT-2 (learned positions, pre-LN with biases, tied head) under the
    names ``apex_tpu.models.TransformerLM`` reads; GPT-2's own init:
    matrices N(0, initializer_range), biases 0, gains 1."""
    e = cfg["n_embd"]
    std = cfg.get("initializer_range", 0.02)
    specs = {
        "tok_emb": ((cfg["vocab_size"], e), ("normal", std)),
        "pos_emb": ((cfg["n_positions"], e), ("normal", std)),
        "ln_f": {"g": ((e,), "ones"), "b": ((e,), "zeros")},
    }
    for i in range(cfg["n_layer"]):
        specs[f"layer_{i}"] = gpt2_layer_specs(cfg)
    return specs


def _conv(kh, kw, cin, cout):
    # He normal, fan-out (torchvision's ResNet init)
    return ((kh, kw, cin, cout), ("normal", (2.0 / (kh * kw * cout)) ** 0.5))


def _bn(c):
    return {"weight": ((c,), "ones"), "bias": ((c,), "zeros")}


def resnet_specs(cfg: dict) -> dict:
    """Bottleneck ResNet v1.5 (stride on the 3x3) under the names
    ``apex_tpu.models.ResNet`` reads: NHWC, HWIO kernels. Convolutions He
    normal (fan-out), gains 1, biases 0; the classifier normal with the
    variance of torch's uniform(+-1/sqrt(fan_in))."""
    width, sizes = cfg["width"], cfg["block_sizes"]
    specs = {"conv_stem": _conv(7, 7, 3, width), "bn_stem": _bn(width)}
    cin = width
    for s, n in enumerate(sizes):
        cmid = width * 2 ** s
        cout = 4 * cmid
        for b in range(n):
            blk = {"conv1": _conv(1, 1, cin, cmid), "bn1": _bn(cmid),
                   "conv2": _conv(3, 3, cmid, cmid), "bn2": _bn(cmid),
                   "conv3": _conv(1, 1, cmid, cout), "bn3": _bn(cout)}
            if b == 0:
                blk["conv_proj"] = _conv(1, 1, cin, cout)
                blk["bn_proj"] = _bn(cout)
            specs[f"stage{s}_block{b}"] = blk
            cin = cout
    std = (1.0 / (3.0 * cin)) ** 0.5
    specs["fc_w"] = ((cin, cfg["num_classes"]), ("normal", std))
    specs["fc_b"] = ((cfg["num_classes"],), ("normal", std))
    return specs
