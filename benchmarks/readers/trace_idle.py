"""Share of the traced window in which no operation ran on the first
device: 100 * (1 - union of its operations' intervals / window)."""

from benchmarks import xplane


def read(run):
    ops = run.ops[min(run.ops)]
    return 100.0 * (1.0 - xplane.busy_seconds(ops) / run.rec["window_s"])
