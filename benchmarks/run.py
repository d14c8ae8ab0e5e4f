"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s>
                              --trace <0|1> [--rehearse]

One process, no child. It fails, and prints no result, without a TPU or
with fewer chips than the cell asks for. ``--rehearse`` walks the same
code at the configuration's tiny ``rehearsal`` sizes on the CPU
(``JAX_PLATFORMS=cpu``), names the CPU as its device and prints no
metric. Every line is JSON and names the device; the last is the result.
"""

from __future__ import annotations

import time

_T0 = time.time()       # as near to the start of the process as Python gets

import argparse         # noqa: E402
import json             # noqa: E402
import os               # noqa: E402
import sys              # noqa: E402
import types            # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None, root=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks import common
    from benchmarks.spec import Spec, plugin
    spec = Spec(root) if root else Spec()
    cell = spec.cell(args.workload)
    ctx = context(spec, cell, args.seed, rehearse=args.rehearse)

    ctx.mark("backend_up")
    ctx.say(attach_s=ctx.attach_s, before_attach_s=ctx.before_attach_s)
    driver = plugin("drivers", ctx.config["driver"]).Driver(ctx)
    counter = common.CompileCounter.install()
    driver.setup()
    ctx.mark("set_up")
    seconds = args.seconds
    trace_dir = None
    if args.trace:
        seconds = min(seconds, ctx.traffic.get("trace_seconds", 5.0))
        trace_dir = common.out_dir(cell["name"], "trace")
    # process start to here, less the seconds the TPU runtime took to
    # attach to the chip (the machine's: PERF.md section 2)
    setup_s = time.time() - _T0 - ctx.attach_s
    before = counter.n
    rec = driver.window(seconds, trace_dir)
    rec["counters"]["compiles_in_window"] = counter.n - before
    # the allocator's peak leaves a program's temporaries out (PERF.md):
    # the step's own footprint, as its memory analysis gives it, counts
    peak = max([common.peak_bytes(ctx.devices)] + ctx.program_bytes)
    ctx.say(window_s=rec["window_s"], attempted=rec["attempted"],
            failed=rec["failed"], **rec["counters"], **rec.get("notes", {}))

    driver.release()
    t = time.time()
    checks = driver.check(rec)
    # on the CPU the engine's donated programs recompile on new layouts
    # (its own warmup() says so); on the chip nothing may compile here
    correct = ctx.rehearse or rec["counters"]["compiles_in_window"] == 0
    for c in checks:
        c["ok"] = bool(c["value"] <= c["limit"])
        correct = correct and c["ok"]
        ctx.say(compared=c["name"], value=c["value"], limit=c["limit"],
                ok=c["ok"])
    ctx.say(reference_s=time.time() - t)

    device = {**ctx.device, "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": {}, "device": device}
    # rehearsing: a CPU number is never written under a device metric's name
    if args.trace and not ctx.rehearse:
        result.update(traced(spec, ctx, driver, rec, trace_dir))
        result["device"]["memory_peak_bytes"] = peak
    elif not ctx.rehearse:
        units = {m["name"]: m["unit"] for m in spec.end_to_end(cell)}
        values = {**driver.end_to_end(rec),
                  "setup_s": setup_s + rec.get("setup_extra_s", 0.0)}
        result["metrics"] = {k: {"value": values[k], "unit": u}
                             for k, u in units.items()}
    print(json.dumps(result), flush=True)
    return 0


def context(spec, cell: dict, seed: int, *, rehearse: bool = False):
    """Bring the backend up the way the program's entry points do, see
    that it is the chip (or, rehearsing, the CPU that was asked for),
    and gather what a driver needs."""
    from benchmarks import common
    config, traffic = spec.config(cell), spec.traffic(cell)
    limits = spec.limits(cell)
    if rehearse:        # tiny sizes, and the limits read at them
        config = {**config, **config.get("rehearsal", {})}
        traffic = {**traffic, **traffic.get("rehearsal", {})}
        limits = {**limits, **limits.get("rehearsal", {})}
    import jax

    from apex_tpu.utils import setup_host_backend
    # the one call in which the runtime attaches to the chip, timed apart:
    # seconds that nothing in the repo moves, and what made setup_s wander
    # (PERF.md section 2)
    t = time.time()
    platform = setup_host_backend()     # raises on a silent CPU
    attach_s = time.time() - t
    if (platform == "tpu") == rehearse:
        raise common.NoAccelerator(
            f"platform is {platform!r}: the benchmark measures on the TPU "
            f"and rehearses on the CPU (JAX_PLATFORMS=cpu --rehearse)")
    if len(jax.devices()) < cell["chips"]:
        raise common.NoAccelerator(
            f"{cell['name']} needs {cell['chips']} chips, jax reports "
            f"{len(jax.devices())}")
    devices = jax.devices()[:cell["chips"]]
    device = common.device_line(devices)
    return types.SimpleNamespace(
        cell=cell, config=config, traffic=traffic, limits=limits,
        seed=seed, devices=devices, device=device, rehearse=rehearse,
        on_tpu=platform == "tpu", attach_s=attach_s,
        before_attach_s=t - _T0,
        peaks=None if rehearse else common.peaks(device["kind"]),
        program_bytes=[],
        # where set-up's seconds go: a line a stage, seconds since start
        mark=lambda name: common.say(device, mark=name,
                                     t=time.time() - _T0),
        say=lambda **kw: common.say(device, **kw))


def traced(spec, ctx, driver, rec: dict, trace_dir: str) -> dict:
    """The per-layer metrics of a traced run, the device's busy time and
    the breakdown."""
    from benchmarks import xplane
    from benchmarks.spec import plugin
    path = xplane.find(trace_dir)
    by_device = xplane.load(path)
    if not by_device:
        raise RuntimeError("no device plane with an 'XLA Ops' line in the "
                           "trace")
    run = types.SimpleNamespace(
        ctx=ctx, rec=rec, ops=by_device, e2e=driver.end_to_end(rec),
        async_ops=xplane.load(path, line=xplane.ASYNC_LINE),
        modules=xplane.load(path, line=xplane.MODULES_LINE))
    metrics = {}
    for m in spec.per_layer(ctx.cell):
        value = plugin("readers", m["reader"]).read(run, **m.get("args", {}))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    busy = [xplane.busy_seconds(ev) for ev in by_device.values()]
    first = by_device[min(by_device)]
    return {
        "metrics": metrics,
        "device": {**ctx.device,
                   "busy_s": sum(busy) / len(busy),
                   "window_s": rec["window_s"]},
        "breakdown": {"device_ops": xplane.top_ops(first),
                      "idle_gaps": xplane.idle_gaps(first)},
    }


if __name__ == "__main__":
    sys.exit(main())
