"""Serving traffic: an open loop of requests due at fixed times.

Parameters (the traffic file): ``rate`` requests a second, ``arrivals``
(``poisson``), ``ramp_s`` of the same traffic before the window,
``system_prompt`` tokens shared by every request, and ``prompt`` /
``output`` length laws (``lognormal`` with ``median``, ``sigma``,
``min``, ``max``). A mix that needs another law brings it with it.

The *set* of arrival times and of (prompt, output) sizes comes from the
file's own ``law_seed`` and is the same for every ``--seed``; the seed
gives the order of the sizes (shuffled within the ramp and within the
window, so both keep their work) and the tokens. Runs then differ by
what a run may differ by, and not by how much work they were given.
"""

from __future__ import annotations

import numpy as np


def _lengths(law: dict, n: int, rng) -> np.ndarray:
    if law["law"] != "lognormal":
        raise ValueError(f"unknown length law {law['law']!r}")
    x = rng.lognormal(np.log(law["median"]), law["sigma"], n)
    return np.clip(np.rint(x), law["min"], law["max"]).astype(np.int64)


def _gaps(traffic: dict, n: int, rng) -> np.ndarray:
    if traffic["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival law {traffic['arrivals']!r}")
    return rng.exponential(1.0 / traffic["rate"], n)


def generate(traffic: dict, config: dict, seed: int, seconds: float) -> dict:
    """Requests due in ``[0, ramp_s + seconds)``: ``{"id", "arrival_s",
    "prompt" (int32, system prompt first), "max_new"}``, by due time."""
    law = np.random.default_rng(traffic["law_seed"])
    ramp = float(traffic["ramp_s"])
    horizon = ramp + seconds
    n = int(horizon * traffic["rate"] * 2) + 64
    due = np.cumsum(_gaps(traffic, n, law))
    due = due[due < horizon]
    own = _lengths(traffic["prompt"], len(due), law)
    out = _lengths(traffic["output"], len(due), law)
    sys_len = traffic["system_prompt"]
    if sys_len + int(own.max()) + int(out.max()) > traffic["engine"]["max_len"]:
        raise ValueError("length laws pass the engine's max_len")

    rng = np.random.default_rng(seed)
    order = np.arange(len(due))
    in_ramp = due < ramp
    for part in (in_ramp, ~in_ramp):
        idx = order[part]
        order[part] = rng.permutation(idx)
    own, out = own[order], out[order]
    vocab = config["vocab_size"]
    system = rng.integers(0, vocab, sys_len, dtype=np.int32)
    requests = [{
        "id": i, "arrival_s": float(t),
        "prompt": np.concatenate(
            [system, rng.integers(0, vocab, int(p), dtype=np.int32)]),
        "max_new": int(o),
    } for i, (t, p, o) in enumerate(zip(due, own, out))]
    return {"requests": requests, "ramp_s": ramp, "end_s": horizon}
