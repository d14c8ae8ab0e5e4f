"""Bytes the fused Adam kernel has to move on the first device in the
traced window: a step reads the fp32 master, ``m``, ``v`` and the fp32
gradient and writes master, ``m`` and ``v``, 7 x 4 = 28 B a parameter.
Under data parallelism every chip updates every parameter. The padding
of the flat buffers and the scalars are left out: a floor.
"""

BYTES_PER_PARAMETER = 28


def total(run) -> dict:
    return {"bytes": float(BYTES_PER_PARAMETER
                           * run.ctx.config["parameters"]
                           * run.rec["steps"])}
