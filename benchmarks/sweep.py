"""Find a serving cell's knee, once, on the chip: the same engine and
traffic laws at a ladder of fixed rates, one process.

    python3 benchmarks/sweep.py --workload <cell> --rates 0.3,0.4,0.5
                                --seconds 200 --ramp 100 --seed <n>

``--seconds`` and ``--ramp`` are several times a request's stay in the
engine (53 s for chat on PR 23's engine): a shorter window never sees
the slots fill and calls every rate sustained, as PR 23's 40 s windows
did (PERF.md).

A rate is sustained if the queue (requests due and still without a first
token) is no longer at the window's end than at its middle and at least
98% of the requests due in the window finish within ``--drain`` seconds
of its end. The knee is the highest sustained rate; the cell's traffic
file carries 0.8 x knee (rounded down to 0.05) as a number. The ladder stops at the first rate
whose queue ends beyond four times the engine's slots. The benchmark's
own runs never run this.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def queue_at(rows, t: float) -> int:
    return sum(1 for r in rows if r["arrival_s"] <= t
               and (r["first_token_s"] is None or r["first_token_s"] > t))


def main(argv=None, root=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=200.0)
    ap.add_argument("--ramp", type=float,
                    help="seconds of the same traffic before each window "
                         "(default: the traffic file's ramp_s)")
    ap.add_argument("--drain", type=float, default=200.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks import common
    from benchmarks.readers.request_percentile import values
    from benchmarks.run import context
    from benchmarks.spec import Spec, plugin
    spec = Spec(root) if root else Spec()
    cell = spec.cell(args.workload)
    ctx = context(spec, cell, args.seed, rehearse=args.rehearse)
    driver = plugin("drivers", ctx.config["driver"]).Driver(ctx)
    driver.setup()
    knee = None
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = {**ctx.traffic, "rate": rate}
        if args.ramp is not None:
            traffic["ramp_s"] = args.ramp
        rec = driver.window(args.seconds, traffic=traffic)
        t0, t1 = rec["span"]
        rows = rec["every_request"]
        due = rec["requests"]
        in_time = sum(1 for r in due if r["ok"]
                      and r["finish_s"] <= t1 + args.drain)
        q_mid, q_end = queue_at(rows, (t0 + t1) / 2), queue_at(rows, t1)
        sustained = q_end <= q_mid and in_time >= 0.98 * len(due)
        if sustained:
            knee = rate
        e2e = driver.end_to_end(rec)
        ctx.say(rate=rate, due=len(due), finished_in_drain=in_time,
                queue_mid=q_mid, queue_end=q_end, sustained=sustained,
                drain_s=rec["drain_s"],
                ttft_p50_ms=common.percentile(values(due, "ttft"), 50),
                **e2e,
                step_p50_ms=common.percentile(rec["stats"]["step_ms"], 50),
                tokens_per_s=sum(len(r["tokens"]) for r in due)
                / args.seconds)
        if q_end > 4 * ctx.traffic["engine"]["slots"]:
            break
    ctx.say(knee=knee,
            rate_at_0_8=None if knee is None
            else int(0.8 * knee * 20 + 1e-9) / 20.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
