"""Weight specs of SDAR-30B-A3B-Chat's block, for
``benchmarks.weights.build``: the tree both sides share, under the names
``apex_tpu.models.hybrid_lm.HybridLM`` reads and
``benchmarks/reference/sdar.py`` reads. Imports nothing of the program.

Leaf for leaf the tree of ``weights_mellum2.specs``, whose keys this
configuration's are (the Qwen3-MoE convention's): matrices ``[in, out]``
and N(0, ``initializer_range``), the embedding's rows N(0,
``embedding_initializer_range``), plain norms that start at 1 (``norm1``,
``norm2``, ``norm_f``, a layer's ``q_norm`` and ``k_norm``), every
layer's ``attn`` and ``moe`` (the router over all ``num_experts x
expert_chips`` experts, the ``num_experts`` held here, no shared expert),
a head of its own. Block diffusion adds no leaf: the mask token is the
embedding's last row, ``vocab_size - 1``, drawn like every other.
"""

from __future__ import annotations

from benchmarks.weights_mellum2 import specs  # noqa: F401
