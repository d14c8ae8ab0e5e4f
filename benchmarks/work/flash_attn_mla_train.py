"""FLOPs causal latent attention (MLA) needs on the first device in the
traced window, forward and backward, from the configuration's shapes (the
source's keys: ``num_attention_heads`` heads in every layer, queries and
keys ``qk_nope_head_dim + qk_rope_head_dim`` wide over values
``v_head_dim`` wide).

As ``flash_attn_train``, with the two widths apart: over the whole
``S x S`` score matrix of one head a matmul against the keys (``Q K^T``,
and ``dQ``, ``dK`` backward) is ``2 S^2 (qk_nope + qk_rope)`` FLOPs, one
against the values (``P V``, and ``dV``, ``dP`` backward) ``2 S^2
v_head_dim``; causal attention needs the lower half; the forward has one
of each, the backward two of each. Not counted, as there: the backward's
recomputed ``Q K^T``, the forward a recomputed block repeats, and what
the kernels compute on the padding of both widths to one multiple of 128
lanes (256 here): work the program chose, not work the result needs.
"""


def step_flops(cfg: dict, rows: int) -> int:
    seq = cfg["input"]["seq"]
    wide = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] \
        + cfg["v_head_dim"]
    pair = 2 * rows * cfg["num_attention_heads"] * seq * seq * wide // 2
    return (1 + 2) * pair * cfg["num_hidden_layers"]


def total(run) -> dict:
    return {"flops": float(step_flops(run.ctx.config,
                                      run.ctx.traffic["per_chip"])
                           * run.rec["steps"])}
