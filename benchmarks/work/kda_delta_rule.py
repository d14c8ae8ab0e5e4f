"""Work the delta rule under a decay a channel (Kimi Delta Attention)
needs on the first device in the traced window, forward and backward,
from the configuration's shapes.

As ``gdn_delta_rule`` counts the scalar rule: a token of one head needs
three products with the ``d x d`` state (``S^T k``, the write ``k u^T``,
``S^T q``), ``2 d d`` FLOPs each, and the backward twice that. It reads
``q``, ``k``, ``v`` (bf16), ``g`` (**``d`` floats**, one a key channel)
and ``beta`` (one float) and writes ``o`` (bf16); the backward reads and
writes as much twice over (the same arrays, and their cotangents). What
the chunked form adds (the decayed operands, the products inside a chunk
level by level, the inverse) and what recomputation repeats is work the
algorithm chose, not work the result needs, and is not counted: the same
work whatever implements it, kernel or XLA. The layers are those of the
cut that ``linear_attn_config``'s ``kda_layers`` names (1-indexed). A
chip sees ``per_chip`` rows a step.
"""


def kda_layers(cfg: dict) -> int:
    return sum(i <= cfg["num_hidden_layers"]
               for i in cfg["linear_attn_config"]["kda_layers"])


def token_head_flops(cfg: dict) -> int:
    """Forward and backward, one token of one head."""
    d = cfg["linear_attn_config"]["head_dim"]
    return 3 * 3 * 2 * d * d


def token_head_bytes(cfg: dict) -> int:
    d = cfg["linear_attn_config"]["head_dim"]
    return 3 * (2 * 4 * d + 4 * d + 4)


def step_work(cfg: dict, rows: int) -> dict:
    n = rows * cfg["input"]["seq"] \
        * cfg["linear_attn_config"]["num_heads"] * kda_layers(cfg)
    return {"flops": n * token_head_flops(cfg),
            "bytes": n * token_head_bytes(cfg)}


def total(run) -> dict:
    return {k: float(v * run.rec["steps"]) for k, v in step_work(
        run.ctx.config, run.ctx.traffic["per_chip"]).items()}
