"""A kernel's share of its roofline: the least time the chip could take
for the work the traced window gave it (``work.<work>.total``: FLOPs and
bytes on the first device, from shapes and the benchmark's own records)
over the device time of the operations whose name matches ``pattern``
(with ``module``, only those inside runs of the jitted program of that
name). Nothing matching in the trace: nothing to read.

The v5e's traces name a Pallas kernel by an HLO instruction whose name
comes from the enclosing function or transformation, not from the kernel
(PERF.md, Open questions); what is stable is its
``custom_call_target="tpu_custom_call"`` and the program it runs in."""

from benchmarks import xplane
from benchmarks.spec import plugin


def read(run, pattern: str, work: str, module: str | None = None):
    dev = min(run.ops)
    ops = run.ops[dev]
    if module:
        ops = xplane.within(ops, run.modules.get(dev, []), module)
    seconds = xplane.op_seconds(ops, pattern)
    if seconds <= 0.0:
        return None
    need = plugin("work", work).total(run)
    least = max(need.get("flops", 0.0) / run.ctx.peaks["bf16_flops_per_s"],
                need.get("bytes", 0.0) / run.ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
