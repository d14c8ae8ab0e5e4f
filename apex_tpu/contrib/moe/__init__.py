"""Mixture-of-Experts with expert parallelism (the ``ep`` axis).

Beyond-reference capability (the reference has none; SURVEY §2.3's
parallelism inventory is data-parallel + stat-sync). Switch-Transformer
style top-1 routing with capacity:

- router logits -> softmax -> argmax expert + gate prob;
- per-expert token queues of capacity ``ceil(tokens/num_experts * cf)``;
  overflow tokens are dropped (pass through with zero expert output),
  the standard Switch behavior;
- dispatch/combine are scatter/gather over a [num_experts * capacity]
  buffer — static shapes, no host sync, jit/vjp-clean.

Expert parallelism (``expert_axis``): call inside ``shard_map`` with the
stacked expert weights sharded ``P(axis)`` on their leading expert dim.
Every rank computes the (cheap, replicated) routing; each rank runs ONLY
its local experts' FFNs; one ``psum`` over the expert axis combines the
per-token outputs (each token's value is produced by exactly one rank).
Composes with a data axis outside (tokens sharded on batch).

``ExpertLayer`` (``expert_layer.py``) is the many-expert layer of today's
models as one expert-parallel rank computes it: routing over every expert,
dropless sorted dispatch to the experts held here (``experts_held``),
grouped matmuls, a gated shared expert, pairs past the one static bound
counted.
"""

from apex_tpu.contrib.moe.expert_layer import ExpertLayer  # noqa: F401
from apex_tpu.contrib.moe.moe import MoEMLP  # noqa: F401

__all__ = ["ExpertLayer", "MoEMLP"]
