"""Device-mesh helpers — the process-group layer of the framework.

The reference's distributed substrate is ``torch.distributed`` process
groups over NCCL (reference: apex/parallel/distributed.py:235-237 asserts
NCCL; apex/parallel/__init__.py:58-95 builds sub-groups for SyncBN). The
TPU-native substrate is a ``jax.sharding.Mesh`` whose named axes play the
role of process groups: collectives ride ICI within an axis, and sub-groups
become ``axis_index_groups``.

Axis-name conventions used across the framework:

- ``"data"`` — data parallel (DDP / ZeRO sharding axis)
- ``"model"`` — tensor/model parallel
- ``"seq"``  — sequence/context parallel (ring attention)
- ``"pipe"`` — pipeline parallel
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
PIPE_AXIS = "pipe"


def pin_cpu_devices(n: int) -> None:
    """Force the CPU platform with ``n`` virtual devices — the explicit
    CPU request of tests, dry runs and examples that want a virtual
    mesh. Best called before any backend-touching call (a process that
    has initialized the TPU holds the chip until it exits); re-pins
    cleanly if a backend already initialized."""
    from jax._src import xla_bridge as _xb
    if _xb.backends_are_initialized():
        from jax.extend.backend import clear_backends
        clear_backends()
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", int(n))


def make_mesh(axis_sizes: dict[str, int] | None = None,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a mesh from ``{axis_name: size}``.

    ``make_mesh()`` -> 1-D data mesh over all local devices.
    A size of -1 (at most one) absorbs the remaining devices, so
    ``make_mesh({"data": -1, "model": 2})`` scales with the slice.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if axis_sizes is None:
        axis_sizes = {DATA_AXIS: n}
    names = list(axis_sizes.keys())
    sizes = list(axis_sizes.values())
    if sizes.count(-1) > 1:
        raise ValueError("at most one axis may be -1")
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        if n % known:
            raise ValueError(f"{n} devices not divisible by {known}")
        sizes[sizes.index(-1)] = n // known
    total = int(np.prod(sizes))
    if total != n:
        raise ValueError(f"mesh {dict(zip(names, sizes))} needs {total} "
                         f"devices, have {n}")
    dev_array = np.asarray(devices).reshape(sizes)
    mesh = Mesh(dev_array, tuple(names))
    try:
        # announce the topology to any active telemetry logger (r07):
        # a sidecar from a distributed run must say what mesh it ran on
        # for its collective-bytes records to mean anything
        from apex_tpu.prof import metrics as _telemetry
        _telemetry.note("mesh_created",
                        axes=dict(zip(names, (int(s) for s in sizes))),
                        devices=n,
                        platform=getattr(devices[0], "platform", None))
    except Exception:
        pass
    return mesh


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharded(mesh: Mesh, axis: str = DATA_AXIS) -> NamedSharding:
    """Shard the leading (batch) dim over ``axis``."""
    return NamedSharding(mesh, P(axis))


def subgroups(axis_size: int, group_size: int) -> list[list[int]]:
    """Partition an axis into contiguous groups of ``group_size`` — the
    ``axis_index_groups`` analog of ``create_syncbn_process_group``
    (reference: apex/parallel/__init__.py:58-95, which asserts
    world_size % group_size == 0 and builds contiguous rank groups)."""
    if group_size <= 0 or axis_size % group_size:
        raise ValueError(
            f"axis size {axis_size} not divisible by group_size {group_size}")
    return [list(range(i, i + group_size))
            for i in range(0, axis_size, group_size)]


def local_device_count() -> int:
    return jax.local_device_count()
