"""Backend dispatch — the single chokepoint for batched flat-buffer ops.

Plays the role of ``multi_tensor_applier`` in the reference
(apex/multi_tensor_apply/multi_tensor_apply.py:3-34): every optimizer and the
AMP scaler route their heavy ops through here. Instead of raising when the
native extension is missing (reference: multi_tensor_apply.py:20-22), this
layer selects between the Pallas kernels (TPU) and the pure-jnp reference
implementations (CPU / interpret / cross-check), keeping both paths
numerically interchangeable.
"""

from __future__ import annotations

import contextlib
import functools
import os

import jax

_VALID = ("auto", "reference", "pallas")

# "auto": pallas on TPU, reference elsewhere. Overridable for tests/benchmarks.
_backend = os.environ.get("APEX_TPU_BACKEND", "auto")
if _backend not in _VALID:
    raise ValueError(
        f"APEX_TPU_BACKEND must be one of {_VALID}, got {_backend!r}")


def set_backend(name: str) -> None:
    global _backend
    if name not in _VALID:
        raise ValueError(f"backend must be one of {_VALID}, got {name!r}")
    _backend = name


def get_backend() -> str:
    return _backend


@contextlib.contextmanager
def backend(name: str):
    """Temporarily force a backend (used by the bitwise cross-check tests)."""
    old = _backend
    set_backend(name)
    try:
        yield
    finally:
        set_backend(old)


@functools.cache
def _default_platform() -> str:
    return jax.default_backend()


def use_pallas() -> bool:
    if _backend == "pallas":
        return True
    if _backend == "reference":
        return False
    return _default_platform() == "tpu"


def resolve(reference_fn, pallas_fn):
    """Return the active implementation for an op pair."""
    if pallas_fn is not None and use_pallas():
        return pallas_fn
    return reference_fn


def resolve_crossover(reference_fn, pallas_fn, size: int, min_size: int):
    """:func:`resolve` with a measured crossover gate: route to the
    Pallas kernel only past ``min_size`` (flash_attention's
    ``S >= flash_min_s`` rule generalized — below the crossover XLA's
    composed program is the faster one even on TPU, docs/PERF.md r04).
    ``size`` is whatever dimension the kernel's win scales with."""
    if pallas_fn is not None and use_pallas() and size >= min_size:
        return pallas_fn
    return reference_fn
