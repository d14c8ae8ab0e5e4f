"""Work the gated delta rule needs on the first device in the traced
window, forward and backward, from the configuration's shapes.

A token of one value head needs three products with the ``dk x dv``
state (``S^T k``, the write ``k d^T``, ``S^T q``), ``2 dk dv`` FLOPs
each, and the backward twice that. It reads ``q``, ``k``, ``v`` (bf16)
and ``g``, ``beta`` (float32) and writes ``o`` (bf16); the backward
reads and writes as much twice over (the same arrays, and their
cotangents). What the chunked form adds (the products inside a chunk,
the inverse) and what recomputation repeats is work the algorithm
chose, not work the result needs, and is not counted. A chip sees
``per_chip`` rows a step.
"""


def linear_layers(cfg: dict) -> int:
    every = cfg["full_attention_interval"]
    return sum((i + 1) % every != 0 for i in range(cfg["num_hidden_layers"]))


def token_head_flops(cfg: dict) -> int:
    """Forward and backward, one token of one value head."""
    return 3 * 3 * 2 * cfg["linear_key_head_dim"] \
        * cfg["linear_value_head_dim"]


def token_head_bytes(cfg: dict) -> int:
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return 3 * (2 * (2 * dk + 2 * dv) + 2 * 4)


def step_work(cfg: dict, rows: int) -> dict:
    n = rows * cfg["input"]["seq"] * cfg["linear_num_value_heads"] \
        * linear_layers(cfg)
    return {"flops": n * token_head_flops(cfg),
            "bytes": n * token_head_bytes(cfg)}


def total(run) -> dict:
    return {k: float(v * run.rec["steps"]) for k, v in step_work(
        run.ctx.config, run.ctx.traffic["per_chip"]).items()}
