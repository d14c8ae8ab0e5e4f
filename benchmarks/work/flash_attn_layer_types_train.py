"""FLOPs causal grouped-query flash attention needs on the first device
in the traced window, forward and backward, for a model whose attention
layers are named in ``layer_types`` (the source's keys:
``num_attention_heads`` query heads of ``hidden_size /
num_attention_heads`` in each "full_attention" layer).

As ``flash_attn_gqa_train``: a matmul over the whole ``S x S`` score
matrix of one query head is ``2 S^2 hd`` FLOPs, causal attention needs
the lower half, the forward has two and the backward four. Not counted:
the backward's recomputed ``Q K^T``, the forward a recomputed block
repeats, and what the kernels compute on the padding of a head narrower
than a lane tile to 128 (a head of 64: half of the kernels' matmul work):
work the program chose, not work the result needs. Fewer key/value heads
change the bytes, not the FLOPs.
"""


def full_layers(cfg: dict) -> int:
    return sum(kind == "full_attention" for kind in cfg["layer_types"])


def step_flops(cfg: dict, rows: int) -> int:
    seq = cfg["input"]["seq"]
    head = cfg["hidden_size"] // cfg["num_attention_heads"]
    matmul = 2 * rows * cfg["num_attention_heads"] * seq * seq * head // 2
    return (2 + 4) * matmul * full_layers(cfg)


def total(run) -> dict:
    return {"flops": float(step_flops(run.ctx.config,
                                      run.ctx.traffic["per_chip"])
                           * run.rec["steps"])}
