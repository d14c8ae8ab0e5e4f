"""FLOPs causal latent attention (MLA) needs on the first device in the
traced window, forward and backward, for a model only some of whose
layers are latent attention: ``flash_attn_mla_train``'s count a layer,
over the layers of the cut that ``linear_attn_config``'s
``full_attn_layers`` names (1-indexed), not over ``num_hidden_layers``.
What is and is not counted is said there.
"""

from benchmarks.work import flash_attn_mla_train


def latent_layers(cfg: dict) -> int:
    return sum(i <= cfg["num_hidden_layers"]
               for i in cfg["linear_attn_config"]["full_attn_layers"])


def step_flops(cfg: dict, rows: int) -> int:
    return flash_attn_mla_train.step_flops(
        {**cfg, "num_hidden_layers": latent_layers(cfg)}, rows)


def total(run) -> dict:
    return {"flops": float(step_flops(run.ctx.config,
                                      run.ctx.traffic["per_chip"])
                           * run.rec["steps"])}
