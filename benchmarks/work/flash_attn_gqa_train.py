"""FLOPs causal grouped-query flash attention needs on the first device
in the traced window, forward and backward, from the configuration's
shapes (the source's keys: ``num_attention_heads`` query heads of
``head_dim`` in the full-attention layers, one in
``full_attention_interval``).

As ``flash_attn_train``: a matmul over the whole ``S x S`` score matrix
of one query head is ``2 S^2 hd`` FLOPs, causal attention needs the
lower half, the forward has two and the backward four; the backward's
recomputed ``Q K^T`` and the forward a recomputed block repeats are work
the program chose and are not counted. Fewer key/value heads change the
bytes, not the FLOPs: every query head still meets every key.
"""


def full_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] // cfg["full_attention_interval"]


def step_flops(cfg: dict, rows: int) -> int:
    seq = cfg["input"]["seq"]
    matmul = 2 * rows * cfg["num_attention_heads"] * seq * seq \
        * cfg["head_dim"] // 2
    return (2 + 4) * matmul * full_layers(cfg)


def total(run) -> dict:
    return {"flops": float(step_flops(run.ctx.config,
                                      run.ctx.traffic["per_chip"])
                           * run.rec["steps"])}
