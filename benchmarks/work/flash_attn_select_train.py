"""FLOPs grouped-query flash attention over a learned per-query key set
needs on the first device in the traced window, forward and backward
(the source's keys: ``num_attention_heads`` query heads of ``head_dim``,
``sa_config.topk`` keys a query).

A matmul over the scores one query head needs is ``2 x (selected pairs) x
hd`` FLOPs; a row of ``S`` tokens selects ``sum_t min(t + 1, k) = S k -
k^2 / 2 + k / 2`` pairs (the triangle of the first ``k`` queries and ``k`` a
query after them: 31,458,304 at 16,384 and 2,048, 23.4% of the causal
pairs); the forward has two such matmuls and the backward four. Not
counted: the backward's recomputed ``Q K^T``, and what a kernel computes
on pairs that were not selected: work the program chose, not work the
result needs. So kernels that skip nothing read at most 23.4% of what
they read on dense work, and no implementation can read over 100%. Fewer
key/value heads change the bytes, not the FLOPs.
"""


def selected_pairs(cfg: dict) -> int:
    """Score elements one query head of a row needs."""
    s = cfg["input"]["seq"]
    k = min(cfg["sa_config"]["topk"], s)
    return s * k - k * (k - 1) // 2


def step_flops(cfg: dict, rows: int) -> int:
    matmul = 2 * rows * cfg["num_attention_heads"] * selected_pairs(cfg) \
        * cfg["head_dim"]
    return (2 + 4) * matmul * cfg["num_hidden_layers"]


def total(run) -> dict:
    return {"flops": float(step_flops(run.ctx.config,
                                      run.ctx.traffic["per_chip"])
                           * run.rec["steps"])}
