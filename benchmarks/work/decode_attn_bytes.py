"""Bytes the paged decode-attention kernel has to read in the traced
window, rebuilt from the benchmark's own request records.

A decode step gives each live request one token, whose attention reads
that request's whole KV: prompt + tokens so far. A token's K and V are
2 * layers * n_embd values of the served type (196,608 B for
Cerebras-GPT-1.3B in bf16). The first token of a request comes from the
commit, not from a decode step. Queries, outputs and the page table are
left out: the number is a floor on the traffic.
"""


def kv_token_bytes(cfg: dict, itemsize: int = 2) -> int:
    return 2 * cfg["n_layer"] * cfg["n_embd"] * itemsize


def total(run) -> dict:
    t0, t1 = run.rec["span"]
    live = 0
    for r in run.rec["every_request"]:
        for k, t in enumerate(r["token_times"]):
            if k >= 1 and t0 <= t < t1:
                live += r["prompt_len"] + k
    return {"bytes": float(live) * kv_token_bytes(run.ctx.config)}
