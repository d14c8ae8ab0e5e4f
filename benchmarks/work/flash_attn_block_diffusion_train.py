"""FLOPs grouped-query flash attention under the block-diffusion mask
needs on the first device in the traced window, forward and backward,
from the configuration's keys alone (``num_attention_heads`` query heads
of ``head_dim``, ``block_length``, ``input.seq``, ``num_hidden_layers``).

A sequence of ``L`` positions runs as ``2 L`` rows (its noised copy beside
its clean one) and the mask shows a query head ``L^2 + B L`` pairs: ``B L``
noised by noised (a block sees itself), ``(L^2 - B L) / 2`` noised by clean
(the blocks before it) and ``(L^2 + B L) / 2`` clean by clean (the blocks
up to its own). A matmul over them is ``2 x pairs x hd`` FLOPs; the forward
has two such matmuls and the backward four. This is the mask's own count,
whatever grid or kernel computes it. Not counted: the backward's recomputed
``Q K^T``, and what a tile computes for the pairs the mask hides (the
noised-by-noised diagonal's tiles hold ``B`` visible keys a row of 512,
the two block-causal diagonals' tiles are half empty): work the program
chose, not work the result needs. Fewer key/value heads change the bytes,
not the FLOPs.
"""


def visible_pairs(cfg: dict) -> int:
    """Score elements one query head of one sequence needs."""
    length = cfg["input"]["seq"]
    return length * length + cfg["block_length"] * length


def step_flops(cfg: dict, rows: int) -> int:
    matmul = 2 * rows * cfg["num_attention_heads"] * visible_pairs(cfg) \
        * cfg["head_dim"]
    return (2 + 4) * matmul * cfg["num_hidden_layers"]


def total(run) -> dict:
    return {"flops": float(step_flops(run.ctx.config,
                                      run.ctx.traffic["per_chip"])
                           * run.rec["steps"])}
