"""Distributed training over device meshes (the apex.parallel equivalent).

Public surface (reference: apex/parallel/__init__.py:10-21):
- ``DistributedDataParallel`` / ``Reducer`` — gradient averaging policies
- ``SyncBatchNorm`` — cross-replica batch norm (+ fused add/ReLU)
- ``convert_syncbn_model`` / ``create_syncbn_process_group`` — BN
  conversion + stat-sync sub-groups
- ``LARC`` (re-exported from optimizers, where it lives here)
- mesh helpers (``make_mesh``, shardings) — the process-group layer
- ``Plan`` / ``compile_step_with_plan`` — the sharding-plan layer: specs
  live in a Plan object, ONE compile entry point for every distributed
  step (pjit when global-view shardings are given, shard_map for
  per-device bodies with named-axis collectives)
- ``launch.initialize`` / ``launch.multiproc`` — multi-host / local spawn
"""

from apex_tpu.parallel.mesh import (  # noqa: F401
    DATA_AXIS, MODEL_AXIS, PIPE_AXIS, SEQ_AXIS,
    batch_sharded, local_device_count, make_mesh, pin_cpu_devices,
    replicated, subgroups,
)
from apex_tpu.parallel.plan import (  # noqa: F401
    Plan, PlanCompilationError, compile_step_with_plan, place_with_specs,
)
from apex_tpu.parallel.distributed import (  # noqa: F401
    DistributedDataParallel, Reducer, broadcast_params, flat_dist_call,
)
from apex_tpu.parallel.sync_batchnorm import SyncBatchNorm  # noqa: F401
from apex_tpu.parallel.ring_attention import (  # noqa: F401
    merge_partials, ring_attention, ulysses_attention)
from apex_tpu.parallel import launch  # noqa: F401
from apex_tpu.parallel.tensor_parallel import (  # noqa: F401
    transformer_tp_specs, vit_tp_specs, seq2seq_tp_specs, shard_params)
from apex_tpu.parallel.pipeline import (  # noqa: F401
    gpipe, stack_layers, unstack_layers)
from apex_tpu.optimizers.larc import LARC  # noqa: F401


def convert_syncbn_model(model, process_group=None, channel_last=False,
                         *, axis_name: str = "data",
                         axis_index_groups=None):
    """Return a copy of ``model`` with every BatchNorm flipped to
    cross-replica SyncBatchNorm (reference: ``convert_syncbn_model``
    recursively replaces BN modules, apex/parallel/__init__.py:21-56 —
    same positional order: (module, process_group, channel_last)).

    Functional models carry BN config rather than BN module objects, so
    conversion is a config rebuild: the model must expose
    ``replace(bn_axis_name=..., bn_axis_index_groups=...)``
    (apex_tpu.models.ResNet does). ``process_group`` is the
    create_syncbn_process_group result — our ``axis_index_groups``.
    ``channel_last`` is accepted for signature parity and ignored: it
    selects a CUDA memory-format kernel; TPU models here are
    channels-last throughout.
    """
    del channel_last
    if isinstance(process_group, str):
        # the 2nd positional used to be axis_name — fail loudly
        raise TypeError(
            f"process_group must be a sequence of rank groups, got "
            f"{process_group!r}; axis_name is keyword-only")
    groups = axis_index_groups if axis_index_groups is not None \
        else process_group
    if hasattr(model, "replace"):
        return model.replace(bn_axis_name=axis_name,
                             bn_axis_index_groups=groups)
    raise TypeError(
        f"{type(model).__name__} does not expose .replace(...); give your "
        f"model a config-rebuild method or construct it with "
        f"bn_axis_name={axis_name!r} directly")


def create_syncbn_process_group(group_size: int, axis_size: int = None):
    """Build ``axis_index_groups`` for SyncBatchNorm sub-groups (reference:
    apex/parallel/__init__.py:58-95 — contiguous rank groups, asserts
    divisibility). Pass the result as ``axis_index_groups``."""
    import jax
    if axis_size is None:
        axis_size = jax.device_count()
    if group_size == 0:
        return None
    return subgroups(axis_size, group_size)
