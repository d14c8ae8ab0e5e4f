"""Work the double-gated short-convolution mixers need on the first
device in the traced window, forward and backward, from the
configuration's shapes (the source's keys: a layer of ``layer_types``
"conv" has ``W_in``, ``hidden_size`` to three streams of it, and
``W_out``, ``hidden_size`` square).

A token of a conv layer needs the two projections, ``2 d (3 d + d)``
FLOPs, and the backward twice that (the weights' and the input's
gradients). It reads the mixer's input and writes its output, ``d``
bfloat16 each, and the backward their cotangents. The forward a
recomputed block repeats and the elementwise passes between the
projections (the two products, the ``conv_L_cache`` taps) are what the
program chose and are not counted, as ``gdn_delta_rule`` does not count
what the chunked form adds. A chip sees ``per_chip`` rows a step.
"""


def conv_layers(cfg: dict) -> int:
    return sum(kind == "conv" for kind in cfg["layer_types"])


def step_work(cfg: dict, rows: int) -> dict:
    d = cfg["hidden_size"]
    n = rows * cfg["input"]["seq"] * conv_layers(cfg)
    return {"flops": n * 3 * 2 * d * (3 * d + d),
            "bytes": n * 2 * 2 * d * 2}


def total(run) -> dict:
    return {k: float(v * run.rec["steps"]) for k, v in step_work(
        run.ctx.config, run.ctx.traffic["per_chip"]).items()}
