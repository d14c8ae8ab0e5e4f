"""Training traffic for a model trained by block diffusion: a fixed batch
shape, new sequences every step, each with the positions that are masked
and the probability they were drawn with.

Parameters (the traffic file): ``per_chip`` sequences a chip a step,
``distinct`` different batches made before the window and fed in turn,
``steps_checked`` first steps the reference follows, ``noise_eps``. A
sequence is ``seq`` (the configuration's ``input``) tokens uniform over
the data ids ``0 .. vocab_size - 2`` (the slice's last row stands for the
mask token and is never data). The ``distinct x rows`` sequences' noise
levels are stratified over [0, 1): ``t_j = (u + perm(j) / n) mod 1`` with
one ``u`` and one permutation from the seed, so each ``t`` is uniform by
itself and every seed's feed covers the schedule evenly; ``p = (1 -
noise_eps) t + noise_eps``, and each position is masked independently with
probability ``p``.

``units_per_step`` counts the data's tokens, once each: the second copy
the layers see and the positions that carry no loss are the method's cost.
"""

from __future__ import annotations

import numpy as np


def generate(traffic: dict, config: dict, seed: int, chips: int) -> dict:
    rng = np.random.default_rng(seed)
    n, rows = traffic["distinct"], traffic["per_chip"] * chips
    seq, eps = config["input"]["seq"], traffic["noise_eps"]
    x = rng.integers(0, config["vocab_size"] - 1, (n, rows, seq),
                     dtype=np.int32)
    t = (rng.random() + rng.permutation(n * rows) / (n * rows)) % 1.0
    p = ((1.0 - eps) * t + eps).astype(np.float32).reshape(n, rows)
    return {"x": x, "masked": rng.random((n, rows, seq)) < p[..., None],
            "p": p, "units_per_step": rows * seq}
