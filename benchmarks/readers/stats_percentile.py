"""A percentile (nearest rank) of one of the program's own per-step
lists, as it returns them (the engine's ``stats["step_ms"]``: host
clock, dispatch to sync, every decode step of the run)."""

from benchmarks import common


def read(run, key: str, q: float):
    values = run.rec["stats"].get(key)
    return common.percentile(values, q) if values else None
