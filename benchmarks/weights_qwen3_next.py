"""Weight specs of the Qwen3-Next block, for ``benchmarks.weights.build``:
the tree both sides share, under the names
``apex_tpu.models.hybrid_lm.HybridLM`` reads and
``benchmarks/reference/qwen3_next.py`` reads. Imports nothing of the
program.

Matrices are ``[in, out]`` and N(0, ``initializer_range``). The
zero-centred norms (``norm1``, ``norm2``, ``norm_f``, ``q_norm``,
``k_norm``) start at 0, the Gated DeltaNet's output norm at 1, ``dt_bias``
at 1 (the source's init), ``A_log`` at 0 (``A`` = 1 for every head: the
source draws ``A`` from U(0, 16); the configuration's ``assumed``). The
column order inside ``w_qkvz`` is q | k | v | z, inside ``w_ba`` b | a,
inside a query head of ``w_q`` query | gate (random weights: any fixed
order). The experts held are ``num_experts`` of the router's
``num_experts x expert_chips``.
"""

from __future__ import annotations


def specs(cfg: dict) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    w = ("normal", cfg.get("initializer_range", 0.02))
    kd = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    hv = cfg["linear_num_value_heads"]
    vd = hv * cfg["linear_value_head_dim"]
    nh, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    held, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    fs = cfg["shared_expert_intermediate_size"]
    linear = {
        "w_qkvz": ((d, 2 * kd + 2 * vd), w), "w_ba": ((d, 2 * hv), w),
        "conv": ((cfg["linear_conv_kernel_dim"], 2 * kd + vd), w),
        "A_log": ((hv,), "zeros"), "dt_bias": ((hv,), "ones"),
        "norm": ((cfg["linear_value_head_dim"],), "ones"),
        "w_out": ((vd, d), w)}
    attn = {
        "w_q": ((d, nh * 2 * hd), w), "w_k": ((d, kv * hd), w),
        "w_v": ((d, kv * hd), w), "q_norm": ((hd,), "zeros"),
        "k_norm": ((hd,), "zeros"), "w_o": ((nh * hd, d), w)}
    moe = {
        "router": ((d, held * cfg["expert_chips"]), w),
        "w_gate": ((held, d, f), w), "w_up": ((held, d, f), w),
        "w_down": ((held, f, d), w),
        "shared": {"w_gate": ((d, fs), w), "w_up": ((d, fs), w),
                   "w_down": ((fs, d), w), "gate": ((d, 1), w)}}
    out = {"embed": ((v, d), w), "head": ((v, d), w),
           "norm_f": ((d,), "zeros")}
    for i in range(cfg["num_hidden_layers"]):
        full = (i + 1) % cfg["full_attention_interval"] == 0
        out[f"layer_{i}"] = {
            "norm1": ((d,), "zeros"), "norm2": ((d,), "zeros"),
            **({"attn": attn} if full else {"linear": linear}), "moe": moe}
    return out
