"""Work the lightning indexers need on the first device in the traced
window, forward and backward, from the configuration's shapes (the
source's keys: ``sa_config``'s ``indexer_num_heads`` heads of
``indexer_head_dim`` over one shared key head).

The index scores ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` are
needed for every causal pair (``S (S + 1) / 2`` a row: the choice is over
all of them), a matmul of ``2 x pairs x heads x dim`` FLOPs; the backward
of the indexer's loss has two such matmuls (the queries' and the key's
cotangents). The bytes are ``qI``, ``kI`` (bfloat16), ``w`` (float32) and
the packed set (a bit a pair of ``S x S``), once each way. Not counted:
the indexer's three projections, the search for each query's ``topk``-th
score, the head-mean attention probabilities the loss reads (``apex_idx_
probs``: 32 heads of 128, twice the scores' FLOPs), the KL terms, and
whatever an implementation computes twice (the scores are made once for
the choice and once more for the loss): the same work whatever implements
it, kernel or XLA, so 100% is far out of reach and the share says how far.
"""


def step_work(cfg: dict, rows: int) -> dict:
    s, sa = cfg["input"]["seq"], cfg["sa_config"]
    heads, dim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    n = rows * cfg["num_hidden_layers"]
    pairs = s * (s + 1) // 2
    operands = s * (heads * dim * 2 + dim * 2 + heads * 4) + s * s // 8
    return {"flops": n * (1 + 2) * 2 * pairs * heads * dim,
            "bytes": n * 2 * operands}


def total(run) -> dict:
    return {k: float(v * run.rec["steps"]) for k, v in step_work(
        run.ctx.config, run.ctx.traffic["per_chip"]).items()}
