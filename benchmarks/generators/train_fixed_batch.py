"""Training traffic: a fixed batch shape, new rows every step.

Parameters (the traffic file): ``per_chip`` rows a chip a step,
``distinct`` different batches made before the window and fed in turn,
``steps_checked`` first steps the reference follows. What a row is comes
from the configuration's ``input``: ``tokens`` (``seq + 1`` uniform
tokens, so the model sees ``seq`` positions and predicts ``seq``) or
``images`` (standard-normal pixels, uniform labels).
"""

from __future__ import annotations

import numpy as np


def generate(traffic: dict, config: dict, seed: int, chips: int) -> dict:
    rng = np.random.default_rng(seed)
    n, rows = traffic["distinct"], traffic["per_chip"] * chips
    kind = config["input"]["kind"]
    if kind == "tokens":
        seq = config["input"]["seq"]
        return {"x": rng.integers(0, config["vocab_size"],
                                  (n, rows, seq + 1), dtype=np.int32),
                "units_per_step": rows * seq}
    if kind == "images":
        s = config["input"]["size"]
        return {"x": rng.standard_normal((n, rows, s, s, 3),
                                         dtype=np.float32),
                "y": rng.integers(0, config["num_classes"], (n, rows),
                                  dtype=np.int32),
                "units_per_step": rows}
    raise ValueError(f"unknown input kind {kind!r}")
