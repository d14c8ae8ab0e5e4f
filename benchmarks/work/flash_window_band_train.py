"""FLOPs the sliding-window layers' flash kernels alone need on the first
device in the traced window: ``flash_attn_window_train``'s count for the
"sliding_attention" layers of ``layer_types`` (``S W - W^2 / 2`` visible
pairs a query head), for the share of its roofline that the band's grid
(``apex_flash_win_*``) reaches.
"""

from benchmarks.work import flash_attn_window_train as both


def step_flops(cfg: dict, rows: int) -> int:
    return both.step_flops(cfg, rows, kinds=("sliding_attention",))


def total(run) -> dict:
    return {"flops": float(step_flops(run.ctx.config,
                                      run.ctx.traffic["per_chip"])
                           * run.rec["steps"])}
