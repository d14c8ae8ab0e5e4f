"""Weight specs of Keye-VL-2.0-30B-A3B's language block, for
``benchmarks.weights.build``: the tree both sides share, under the names
``apex_tpu.models.hybrid_lm.HybridLM`` reads and
``benchmarks/reference/keye_vl.py`` reads. Imports nothing of the program.

Matrices are ``[in, out]`` and N(0, ``initializer_range``), the
embedding's rows N(0, ``embedding_initializer_range``) (unit scale: the
configuration's ``assumed`` says why); the norms' weights (``norm1``,
``norm2``, ``norm_f``, a layer's ``q_norm`` and ``k_norm``, the indexer key
norm's ``w``) are plain and start at 1, the indexer key norm's bias at 0.
Every layer holds the attention mixer's leaves (``attn``), the indexer's
(``index``: ``w_q`` to ``indexer_num_heads`` heads of ``indexer_head_dim``,
``w_k`` to the one shared key head and ``w_w`` to a weight a head, these two
``[out, in]``; the key's LayerNorm) and the expert layer's (``moe``): the router over all
``num_experts x expert_chips`` experts and the ``num_experts`` held here,
no shared expert. The head is a matrix of its own.
"""

from __future__ import annotations


def specs(cfg: dict) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    w = ("normal", cfg.get("initializer_range", 0.02))
    nh, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    sa = cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    held, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    attn = {"w_q": ((d, nh * hd), w), "w_k": ((d, kv * hd), w),
            "w_v": ((d, kv * hd), w), "q_norm": ((hd,), "ones"),
            "k_norm": ((hd,), "ones"), "w_o": ((nh * hd, d), w)}
    index = {"w_q": ((d, hi * di), w), "w_k": ((di, d), w),
             "w_w": ((hi, d), w),
             "k_norm": {"w": ((di,), "ones"), "b": ((di,), "zeros")}}
    moe = {"router": ((d, held * cfg["expert_chips"]), w),
           "w_gate": ((held, d, f), w), "w_up": ((held, d, f), w),
           "w_down": ((held, f, d), w)}
    rows = ("normal", cfg.get("embedding_initializer_range", w[1]))
    out = {"embed": ((v, d), rows), "head": ((v, d), w),
           "norm_f": ((d,), "ones")}
    for i in range(cfg["num_hidden_layers"]):
        out[f"layer_{i}"] = {"norm1": ((d,), "ones"),
                             "norm2": ((d,), "ones"), "attn": attn,
                             "index": index, "moe": moe}
    return out
