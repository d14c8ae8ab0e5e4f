"""Weight specs of Kimi-VL-A3B's language model (a DeepSeek-V3-shaped
decoder), for ``benchmarks.weights.build``: the tree both sides share,
under the names ``apex_tpu.models.hybrid_lm.HybridLM`` reads and
``benchmarks/reference/kimi_vl.py`` reads. Imports nothing of the program.

Matrices are ``[in, out]`` and N(0, ``initializer_range``); the norms'
weights (``norm1``, ``norm2``, ``norm_f``, the latent's ``kv_norm``) are
plain and start at 1. Column order: inside a head of ``w_q`` the 128
without position, then the 64 rotary; inside ``w_kva`` the 512-wide
latent, then the one rotary key head; inside a head of ``w_kvb`` the 128
key columns, then the 128 value columns (random weights: any fixed
order). The first ``first_k_dense_replace`` layers have a dense SwiGLU
(``mlp``), the others the expert layer (``moe``): the router over all
``n_routed_experts x expert_chips`` experts, the ``n_routed_experts`` held
here, and the ``n_shared_experts`` shared experts as one SwiGLU of their
summed width. The routers' selection biases are no weights: state beside
the master, zero at the start.
"""

from __future__ import annotations


def specs(cfg: dict) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    w = ("normal", cfg.get("initializer_range", 0.02))
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    held, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs, fd = cfg["n_shared_experts"] * f, cfg["intermediate_size"]
    latent = {
        "w_q": ((d, h * (dn + dr)), w), "w_kva": ((d, r + dr), w),
        "kv_norm": ((r,), "ones"), "w_kvb": ((r, h * (dn + dv)), w),
        "w_o": ((h * dv, d), w)}
    mlp = {"w_gate": ((d, fd), w), "w_up": ((d, fd), w),
           "w_down": ((fd, d), w)}
    moe = {
        "router": ((d, held * cfg["expert_chips"]), w),
        "w_gate": ((held, d, f), w), "w_up": ((held, d, f), w),
        "w_down": ((held, f, d), w),
        "shared": {"w_gate": ((d, fs), w), "w_up": ((d, fs), w),
                   "w_down": ((fs, d), w)}}
    out = {"embed": ((v, d), w), "head": ((v, d), w),
           "norm_f": ((d,), "ones")}
    for i in range(cfg["num_hidden_layers"]):
        dense = i < cfg["first_k_dense_replace"]
        out[f"layer_{i}"] = {
            "norm1": ((d,), "ones"), "norm2": ((d,), "ones"),
            "latent": latent, **({"mlp": mlp} if dense else {"moe": moe})}
    return out
