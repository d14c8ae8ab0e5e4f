"""apex_tpu — a TPU-native mixed-precision + distributed-training toolkit.

A ground-up JAX/XLA/Pallas re-design of the capability surface of NVIDIA Apex
(reference: /root/reference, ``guanyonglai/apex``): automatic mixed precision
(``apex_tpu.amp``), fused optimizers (``apex_tpu.optimizers``), distributed
data parallelism and synchronized batch-norm (``apex_tpu.parallel``), and
fused layers (``apex_tpu.normalization``, ``apex_tpu.mlp``,
``apex_tpu.contrib``).

Where Apex is shaped by PyTorch eager mutability (op monkey-patching,
``_amp_stash`` bolted onto optimizers, hand-rolled CUDA streams, tensor-list
kernels), this framework inverts the design for XLA:

- a **flat parameter store** (one HBM buffer per role/dtype + static segment
  table) instead of tensor lists (``apex_tpu.ops.flat``);
- a **declarative precision policy** (O0-O3) instead of namespace patching
  (``apex_tpu.amp.policy``);
- **loss scaling as jittable pytree state** with on-device overflow handling
  (``lax.cond`` step-skip) instead of a host sync per step
  (``apex_tpu.amp.scaler``);
- **mesh collectives** (psum/all_gather/psum_scatter under shard_map) instead
  of NCCL process groups and streams (``apex_tpu.parallel``).

Compute-path kernels are Pallas (``apex_tpu.ops.pallas``) with pure-jnp
reference implementations (``apex_tpu.ops.reference``) used for CPU execution
and bitwise cross-checking, mirroring Apex's Python-build-vs-CUDA-build L1
test axis (reference: tests/L1/common/run_test.sh).
"""

__version__ = "0.1.0"

from apex_tpu import amp  # noqa: F401
from apex_tpu import ops  # noqa: F401
from apex_tpu import optimizers  # noqa: F401
from apex_tpu import parallel  # noqa: F401
from apex_tpu import normalization  # noqa: F401
from apex_tpu import mlp  # noqa: F401
from apex_tpu import fp16_utils  # noqa: F401
from apex_tpu import RNN  # noqa: F401
from apex_tpu import reparameterization  # noqa: F401
from apex_tpu import prof  # noqa: F401
from apex_tpu import data  # noqa: F401
from apex_tpu import utils  # noqa: F401
from apex_tpu import models  # noqa: F401
from apex_tpu import train_step  # noqa: F401
# contrib is intentionally NOT imported eagerly (reference apex/__init__.py
# leaves contrib opt-in); import apex_tpu.contrib.<pkg> directly.
