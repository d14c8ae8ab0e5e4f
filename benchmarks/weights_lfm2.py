"""Weight specs of LFM2-24B-A2B's block, for ``benchmarks.weights.build``:
the tree both sides share, under the names
``apex_tpu.models.hybrid_lm.HybridLM`` reads and
``benchmarks/reference/lfm2.py`` reads. Imports nothing of the program.

Matrices are ``[in, out]`` and N(0, ``initializer_range``), the
convolution's taps ``[taps, channels]`` too; the norms' weights (``norm1``,
``norm2``, ``norm_f``, an attention layer's ``q_norm`` and ``k_norm``) are
plain and start at 1. Column order inside ``w_in``: B | C | u, the three
streams of the double-gated short convolution (random weights: any fixed
order). A layer has a ``conv`` or an ``attn`` mixer by ``layer_types``;
the first ``num_dense_layers`` layers a dense SwiGLU (``mlp``), the others
the expert layer (``moe``): the router over all ``num_experts x
expert_chips`` experts and the ``num_experts`` held here, no shared
expert. There is no ``head``: it is the embedding. The routers' selection
biases are no weights: state beside the master, zero at the start.
"""

from __future__ import annotations


def specs(cfg: dict) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    w = ("normal", cfg.get("initializer_range", 0.02))
    nh, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // nh
    held, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    fd = cfg["intermediate_size"]
    conv = {"w_in": ((d, 3 * d), w), "taps": ((cfg["conv_L_cache"], d), w),
            "w_out": ((d, d), w)}
    attn = {"w_q": ((d, nh * hd), w), "w_k": ((d, kv * hd), w),
            "w_v": ((d, kv * hd), w), "q_norm": ((hd,), "ones"),
            "k_norm": ((hd,), "ones"), "w_o": ((nh * hd, d), w)}
    mlp = {"w_gate": ((d, fd), w), "w_up": ((d, fd), w),
           "w_down": ((fd, d), w)}
    moe = {"router": ((d, held * cfg["expert_chips"]), w),
           "w_gate": ((held, d, f), w), "w_up": ((held, d, f), w),
           "w_down": ((held, f, d), w)}
    out = {"embed": ((v, d), w), "norm_f": ((d,), "ones")}
    for i, kind in enumerate(cfg["layer_types"]):
        out[f"layer_{i}"] = {
            "norm1": ((d,), "ones"), "norm2": ((d,), "ones"),
            **({"conv": conv} if kind == "conv" else {"attn": attn}),
            **({"mlp": mlp} if i < cfg["num_dense_layers"]
               else {"moe": moe})}
    return out
