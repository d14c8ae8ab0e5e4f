"""Read, in one process, what a cell's limits are set from.

    python3 benchmarks/calibrate.py --workload <cell> --seeds 101,102,...
                                    --control 3 [--rehearse]

For each seed the program's numbers as ``correct`` compares them, and for
the first ``--control`` seeds the same numbers of the *control*: the
plain reference put in the program's place and computed in the precision
below the one the configuration states. The last line gives, for each
number, the largest the sound runs read and the smallest the control
read: a limit goes between them (PERF.md has the readings and limits).
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    from benchmarks.run import context
    from benchmarks.spec import Spec, plugin
    spec = Spec()
    cell = spec.cell(args.workload)
    ctx = context(spec, cell, seeds[0], rehearse=args.rehearse)
    driver = plugin("drivers", ctx.config["driver"]).Driver(ctx)
    driver.setup()
    sound, control = {}, {}
    for i, seed in enumerate(seeds):
        got = driver.calibrate(seed, control=i < args.control)
        ctx.say(seed=seed, **got)
        for k, v in got["program"].items():
            sound.setdefault(k, []).append(v)
        for k, v in got.get("control", {}).items():
            control.setdefault(k, []).append(v)
    ctx.say(summary={k: {"sound_max": max(v), "sound_all": v,
                         "control_min": min(control[k]) if k in control
                         else None, "control_all": control.get(k)}
                     for k, v in sound.items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
