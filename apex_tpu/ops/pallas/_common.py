"""Shared helpers for the Pallas kernel modules."""

from __future__ import annotations

import jax

LANES = 128


def interpret_mode() -> bool:
    """Compiled on TPU; interpreter everywhere else (the CPU test path —
    the analog of the reference's Python-build execution axis)."""
    return jax.default_backend() != "tpu"


def vma(*arrays) -> frozenset:
    """Union of the varying-manual-axes of the inputs — required on
    pallas_call out_shapes under shard_map(check_vma=True)."""
    out = frozenset()
    for a in arrays:
        v = getattr(jax.typeof(a), "vma", None)
        if v:
            out = out | v
    return out


def round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m
