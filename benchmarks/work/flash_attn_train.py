"""FLOPs causal flash attention needs on the first device in the traced
window, forward and backward, from the configuration's shapes.

A matmul over the whole ``S x S`` score matrix of one head is
``2 S^2 hd`` FLOPs; causal attention needs the lower half. The forward
has two (``Q K^T``, ``P V``), the backward four (``dV``, ``dP``, ``dQ``,
``dK``). The backward kernels also recompute ``Q K^T``: work the
algorithm chose, not work the result needs, so it is not counted
(PERF.md's 1.44 TFLOP a step of PR 23 counted it as a seventh matmul;
this is 6/7 of that). A chip sees ``per_chip`` rows a step.
"""


def step_flops(cfg: dict, rows: int) -> int:
    seq, heads = cfg["input"]["seq"], cfg["n_head"]
    matmul = 2 * rows * heads * seq * seq * (cfg["n_embd"] // heads) // 2
    return (2 + 4) * matmul * cfg["n_layer"]


def total(run) -> dict:
    return {"flops": float(step_flops(run.ctx.config,
                                      run.ctx.traffic["per_chip"])
                           * run.rec["steps"])}
