"""Collective operations on the first device, from the trace: the
operations whose opcode matches ``pattern``, on the ``XLA Ops`` line and
(where the compiler made them asynchronous) the ``Async XLA Ops`` line.

``ms_per_step``: their time (the union of their intervals) over the
steps of the traced window. ``exposed_pct``: the share of that time
during which no other operation runs on the device. No collective in the
trace (a one-chip cell): nothing to read."""

from benchmarks import xplane


def read(run, pattern: str, what: str):
    dev = min(run.ops)
    # the opcode follows "= <shape> " in the instruction the event is named by
    rx = rf" ({pattern})(-start)?\("
    events = run.ops[dev] + run.async_ops.get(dev, [])
    total = xplane.op_seconds(events, rx)
    if total <= 0.0:
        return None
    if what == "ms_per_step":
        return 1e3 * total / run.rec["steps"]
    if what == "exposed_pct":
        return 100.0 * xplane.exposed_seconds(events, rx) / total
    raise ValueError(f"what must be ms_per_step or exposed_pct, not {what!r}")
