"""Backend dispatch — the one rule for which implementation of an op runs.

Plays the role of ``multi_tensor_applier`` in the reference
(apex/multi_tensor_apply/multi_tensor_apply.py:3-34): every optimizer and the
AMP scaler route their heavy ops through here. Instead of raising when the
native extension is missing (reference: multi_tensor_apply.py:20-22), an op
with two sides takes its Pallas kernel where :func:`use_pallas` holds (the
platform is a TPU) AND the kernel's own module accepts the shapes
(``supported(...)`` / ``takes(...)``), and the pure-jnp implementation
everywhere else; the two stay numerically interchangeable. An op whose kernel
lost to XLA on the chip has one side, XLA's, and no switch reaches another.

The backend is a seam, not a feature: ``"pallas"`` runs the kernels off the
TPU (interpreted; the tests' way to reach them on the CPU), ``"reference"``
takes every kernel out (the oracle ``chip_smoke.py`` compares against on the
chip, and an operator's way to rule the kernels out of a fault).
"""

from __future__ import annotations

import contextlib
import functools
import os

import jax

_VALID = ("auto", "reference", "pallas")

# "auto": the kernels on a TPU, jnp elsewhere.
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


def resolve_crossover(reference_fn, pallas_fn, size: int, min_size: int):
    """The active implementation of an op pair behind a measured crossover:
    the Pallas kernel only where :func:`use_pallas` holds and ``size`` is
    past ``min_size`` (flash_attention's ``S >= flash_min_s`` rule
    generalized — below the crossover XLA's composed program is the faster
    one even on TPU, docs/PERF.md r04). ``size`` is whatever dimension the
    kernel's win scales with."""
    if pallas_fn is not None and use_pallas() and size >= min_size:
        return pallas_fn
    return reference_fn
