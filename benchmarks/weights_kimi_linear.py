"""Weight specs of Kimi-Linear-48B-A3B's block, for
``benchmarks.weights.build``: the tree both sides share, under the names
``apex_tpu.models.hybrid_lm.HybridLM`` reads and
``benchmarks/reference/kimi_linear.py`` reads. Imports nothing of the
program.

Matrices are ``[in, out]`` and N(0, ``initializer_range``), the
convolutions' taps ``[taps, channels]`` among them; one matrix lies
``[out, in]``: a Kimi Delta Attention layer's ``w_b [heads, hidden]`` (32
wide the other way: the program's text says why). The norms' weights
(``norm1``, ``norm2``, ``norm_f``, the latent's ``kv_norm``, the delta
rule's output ``norm``) are plain and start at 1, ``A_log`` at 0 and
``dt_bias`` at 1 (the configuration's ``assumed``), the output gate's bias
``b_g`` at 0. A layer's mixer is ``kda`` or ``latent`` as
``linear_attn_config`` names it (1-indexed), its FFN a dense SwiGLU
(``mlp``) for the first ``first_k_dense_replace`` layers and the expert
layer (``moe``) after: the router over all ``num_experts x expert_chips``
experts, the ``num_experts`` held here, the ``num_shared_experts`` shared
experts as one SwiGLU of their summed width. The routers' selection
biases are no weights: state beside the master, zero at the start.
"""

from __future__ import annotations


def layer_kinds(cfg: dict) -> list:
    """``"kda"`` or ``"latent"`` for each layer of the cut, from the
    published (1-indexed) lists."""
    lin = cfg["linear_attn_config"]
    kinds = []
    for i in range(1, cfg["num_hidden_layers"] + 1):
        assert (i in lin["kda_layers"]) != (i in lin["full_attn_layers"]), i
        kinds.append("kda" if i in lin["kda_layers"] else "latent")
    return kinds


def specs(cfg: dict) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    w = ("normal", cfg.get("initializer_range", 0.02))
    lin = cfg["linear_attn_config"]
    hk, dk, taps = (lin["num_heads"], lin["head_dim"],
                    lin["short_conv_kernel_size"])
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    held, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    fs, fd = cfg["num_shared_experts"] * f, cfg["intermediate_size"]
    kda = {
        "w_q": ((d, hk * dk), w), "w_k": ((d, hk * dk), w),
        "w_v": ((d, hk * dk), w), "conv_q": ((taps, hk * dk), w),
        "conv_k": ((taps, hk * dk), w), "conv_v": ((taps, hk * dk), w),
        "w_f1": ((d, dk), w), "w_f2": ((dk, hk * dk), w),
        "A_log": ((hk,), "zeros"), "dt_bias": ((hk * dk,), "ones"),
        "w_b": ((hk, d), w), "w_g1": ((d, dk), w),
        "w_g2": ((dk, hk * dk), w), "b_g": ((hk * dk,), "zeros"),
        "norm": ((dk,), "ones"), "w_out": ((hk * dk, d), w)}
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
    out = {"embed": ((v, d), ("normal", cfg.get(
               "embedding_initializer_range",
               cfg.get("initializer_range", 0.02)))),
           "head": ((v, d), w), "norm_f": ((d,), "ones")}
    for i, kind in enumerate(layer_kinds(cfg)):
        dense = i < cfg["first_k_dense_replace"]
        out[f"layer_{i}"] = {
            "norm1": ((d,), "ones"), "norm2": ((d,), "ones"),
            kind: kda if kind == "kda" else latent,
            **({"mlp": mlp} if dense else {"moe": moe})}
    return out
