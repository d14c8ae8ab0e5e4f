"""Process start-up for every entry point: which device, which cache.

``setup_host_backend()`` is the one preamble ``chip_smoke.py``,
``benchmarks/run.py``, ``tools/*_bench.py`` and the examples call before any
other jax operation. It is strict about the device: the program runs on
the TPU, or on the CPU because the caller asked for the CPU
(``JAX_PLATFORMS=cpu``, or ``parallel.pin_cpu_devices``) — never on the
CPU because the chip failed to come up. With nothing pinned jax falls
back to the CPU with only a warning; ``require_accelerator`` turns that
into an error, so "ran on CPU" and "ran on the chip" cannot be confused
by exit code.

On the chip it also places the persistent compilation cache
(:func:`enable_compile_cache`): where ``JAX_COMPILATION_CACHE_DIR``
says, else at one fixed path inside the checkout.

``model.init`` + ``opt.init_state`` dispatch hundreds of small ops (one
per parameter leaf), each its own compile on the accelerator; building
state on the in-process CPU backend and placing it in one bulk transfer
keeps start-up to seconds:

    setup_host_backend()            # BEFORE the first jax operation
    with host_init():
        params = model.init(key)
        state = opt.init_state()
    state = ship(state)             # no-op when cpu IS the default

RNG results are backend-independent (threefry), so host init is
bit-identical to device init.
"""

from __future__ import annotations

import contextlib
import os
import sys

import jax

__all__ = ["host_init", "ship", "setup_host_backend",
           "require_accelerator", "enable_compile_cache"]

# <checkout>/.jax_cache — a fixed path, because the path is part of the
# cache key: a directory that moves (temp name, pid, timestamp) never hits.
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def setup_host_backend() -> str:
    """Armed XLA-knob flags (``utils.xla_flags`` — a no-op unless
    APEX_XLA_* env vars arm an A/B; they must land before backend
    init), then the strict device gate (which initializes the default
    backend), then — on the chip — the compilation cache. An explicit
    CPU run is a test or a rehearsal: nothing compiled there is worth
    keeping, and XLA:CPU's cache loader logs a page of machine-feature
    warnings per hit. Returns the platform."""
    from apex_tpu.utils import xla_flags
    applied = xla_flags.apply()
    if applied:
        sys.stderr.write("setup_host_backend: xla_flags armed: "
                         + " ".join(applied) + "\n")
    platform = require_accelerator()
    if platform == "tpu":
        enable_compile_cache()
    return platform


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache and return its
    directory. ``JAX_COMPILATION_CACHE_DIR`` wins when set (jax reads it
    itself; no other directory is set in code). The thresholds drop to
    zero so the 0.1-1 s kernel programs are kept as well as the
    minutes-long step programs."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _CHECKOUT_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def _platforms() -> str:
    """The effective jax platform list (config wins over env)."""
    cfg = getattr(jax.config, "jax_platforms", None)
    return cfg if cfg else os.environ.get("JAX_PLATFORMS", "")


def require_accelerator() -> str:
    """Initialize the default backend and return its platform: ``tpu``,
    or ``cpu`` when the platform list was pinned to exactly ``cpu``.
    Anything else — above all jax's own fall-back to the CPU when the
    chip fails to initialize — raises."""
    platform = jax.default_backend()
    if platform == "tpu":
        return platform
    if platform == "cpu" and _platforms() == "cpu":
        return platform
    raise RuntimeError(
        f"no accelerator: the default backend is {platform!r} and the "
        f"platform list is {_platforms()!r} — refusing to pass a host "
        f"run off as a device run (set JAX_PLATFORMS=cpu to ask for the "
        f"CPU)")


@contextlib.contextmanager
def host_init():
    """Context under which jax ops run on the host CPU backend."""
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        yield


def ship(tree, device=None):
    """``device_put`` a pytree to ``device`` (default: the default
    backend's first device) and wait for the transfer to finish."""
    dev = device if device is not None else jax.devices()[0]
    return jax.block_until_ready(jax.device_put(tree, dev))
