"""Utilities: native host runtime bindings + checkpoint/resume."""

from apex_tpu.utils import native  # noqa: F401
from apex_tpu.utils.checkpoint import (  # noqa: F401
    AsyncCheckpoint, save_checkpoint, load_checkpoint, verify_checkpoint,
)
from apex_tpu.utils.host_init import (  # noqa: F401
    host_init, ship, setup_host_backend, require_accelerator,
    enable_compile_cache,
)
from apex_tpu.utils import xla_flags  # noqa: F401
