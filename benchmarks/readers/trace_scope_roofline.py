"""A scope's share of its roofline, for an op that is no single kernel:
the least time the chip could take for the work the traced window gave
it (``work.<work>.total``: FLOPs and bytes on the first device, from
shapes) over the first device's own time under the scopes matching
``scope`` (``trace_scope``, both directions, a recomputation in the
backward included). A Pallas kernel's share is read by
``trace_kernel_roofline`` from its instruction's name instead. No such
scope in the trace, as in a program that lacks the op: nothing to read.
"""

from benchmarks.readers import trace_scope


def read(run, scope: str, work: str):
    ms = trace_scope.read(run, "ms_per_step", scope)
    if not ms:
        return None
    need = run.ctx.plugin("work", work).total(run)
    least = max(need.get("flops", 0.0) / run.ctx.peaks["bf16_flops_per_s"],
                need.get("bytes", 0.0) / run.ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (1e-3 * ms * run.rec["steps"])
