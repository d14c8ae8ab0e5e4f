"""A percentile (nearest rank), over the window's finished requests, of
``ttft`` (first token less due time) or ``tpot`` ((finish less first
token) / (tokens - 1)), in milliseconds, from the benchmark's own request
records."""

from benchmarks import common


def values(rows, what: str) -> list:
    done = [r for r in rows if r["ok"]]
    if what == "ttft":
        return [(r["first_token_s"] - r["arrival_s"]) * 1e3 for r in done]
    if what == "tpot":
        return [(r["finish_s"] - r["first_token_s"]) * 1e3
                / (len(r["tokens"]) - 1) for r in done
                if len(r["tokens"]) > 1]
    raise ValueError(f"what must be ttft or tpot, not {what!r}")


def read(run, what: str, q: float):
    vals = values(run.rec.get("requests") or [], what)
    return common.percentile(vals, q) if vals else None
