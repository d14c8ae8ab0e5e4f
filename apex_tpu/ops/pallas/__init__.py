"""Pallas TPU kernels — the native-kernel tier of the framework.

The analog of the reference's CUDA extension modules (amp_C, the attention
extensions, …; reference: setup.py:60-373), built as Pallas kernels over the
flat-buffer data model instead of tensor-list CUDA launches, for the ops
where a kernel beats XLA's fusion on the chip (fused_layer_norm_cuda,
xentropy_cuda, the syncbn kernels and LAMB have no kernel here: XLA's side
won each, docs/PERF.md). ``apex_tpu.ops.kernels`` is the dispatching facade; import
from here only to reach a specific kernel implementation directly.
"""

from apex_tpu.ops.pallas import multi_tensor  # noqa: F401

# decode_attn (the serve decode step's single-query slot attention) is
# imported lazily by its dispatch layer
# (contrib.multihead_attn.decode_attention) to keep pallas imports off
# the training-path critical import chain.
