"""FLOPs causal grouped-query flash attention needs on the first device
in the traced window, forward and backward, for a model whose layers
attend over a sliding window or over the whole causal past, by
``layer_types`` (the source's keys: ``num_attention_heads`` query heads of
``head_dim``, ``sliding_window`` keys visible in a "sliding_attention"
layer).

As ``flash_attn_layer_types_train``: a matmul over the scores one query
head needs is ``2 x (visible pairs) x hd`` FLOPs; a full layer's visible
pairs are ``S^2 / 2``, a window layer's ``S W - W^2 / 2`` (the triangle
of the first ``W`` queries and ``W`` a query after them); the forward
has two such matmuls and the backward four. Not counted: the backward's
recomputed ``Q K^T``, and what a block computes outside the window or
above the diagonal (at a window of 1024 in blocks of 1024 about half of
what the kernels' matmuls do): work the program chose, not work the
result needs. Fewer key/value heads change the bytes, not the FLOPs.
"""


def visible_pairs(cfg: dict, kind: str) -> int:
    """Score elements one query head of a ``kind`` layer needs."""
    s, w = cfg["input"]["seq"], min(cfg["sliding_window"],
                                    cfg["input"]["seq"])
    return s * w - w * w // 2 if kind == "sliding_attention" else s * s // 2


def layer_flops(cfg: dict, rows: int, kind: str) -> int:
    matmul = 2 * rows * cfg["num_attention_heads"] \
        * visible_pairs(cfg, kind) * cfg["head_dim"]
    return (2 + 4) * matmul


def step_flops(cfg: dict, rows: int, kinds=("sliding_attention",
                                            "full_attention")) -> int:
    return sum(layer_flops(cfg, rows, kind) for kind in cfg["layer_types"]
               if kind in kinds)


def total(run) -> dict:
    return {"flops": float(step_flops(run.ctx.config,
                                      run.ctx.traffic["per_chip"])
                           * run.rec["steps"])}
