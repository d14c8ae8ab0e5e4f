"""Thin CLI over ``apex_tpu.prof`` — print a trace's top-N op table and
its GAPS (inter-op dead time) attribution as markdown.

The reference's pyprof pipeline (apex/pyprof/parse + prof) reads nvprof's
SQLite kernel records and computes per-op FLOP/byte tables; the library
API here does both over an xprof capture (see apex_tpu/prof/__init__.py).
The GAPS table is the r06 addition (apex_tpu/prof/gaps.py): every
inter-op gap on the device lane, binned and attributed to its bounding
ops — the 66 ms IDLE row of TRACE_TOP_OPS_r05b.md, made addressable.
Use with ``tools/perf_probe.py --trace /tmp/trace`` (or any
``prof.trace`` / ``jax.profiler`` capture) and commit the table to
PERF_r{N}.md; feed ``--gaps-json`` output to ``tools/hlo_audit.py
--gaps`` to cross-reference gap sites against the optimized HLO.

Usage:
    python tools/trace_top_ops.py /tmp/trace [--top 15]
        [--min-gap-us 5] [--gaps-json GAPS.json]
        [--strict [--max-unattributed-pct 10]]

``--strict`` is the chip-window gate for the classifier itself: the
GAPS footer always states the unattributed fraction of dead time (plus
the seam names to extend the ``_RULES`` table from), and strict mode
exits 1 when that fraction exceeds the threshold (2 when attribution
failed entirely) — a capture whose gaps mostly dodge the rule table
must read as "extend the table", not as a clean attribution.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("logdir")
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--min-gap-us", type=float, default=5.0,
                    help="ignore inter-op gaps shorter than this "
                         "(emitter latency noise)")
    ap.add_argument("--gaps-json", default=None,
                    help="also write machine-readable gap sites here "
                         "(input for hlo_audit.py --gaps)")
    ap.add_argument("--strict", action="store_true",
                    help="exit non-zero when the unattributed gap "
                         "fraction exceeds --max-unattributed-pct (or "
                         "when gap attribution fails entirely) — for "
                         "chip-window scripts that must not record a "
                         "GAPS table whose classifier went blind")
    ap.add_argument("--max-unattributed-pct", type=float, default=10.0,
                    help="--strict threshold: max %% of dead time the "
                         "classifier may leave unattributed (default 10)")
    ap.add_argument("--device-kind", default=None,
                    help="device_kind of the chip that made the capture "
                         "(prof.peaks table key, e.g. 'TPU v5 lite'); "
                         "default: the attached device")
    args = ap.parse_args()

    from apex_tpu import prof
    stats = prof.top_ops(args.logdir)   # parse once; slice for display
    if stats and not stats[0].on_device:
        sys.stderr.write("no Device rows; showing Host rows\n")
    print(prof.format_top_ops(stats[:args.top]))
    try:
        r = prof.roofline(stats=stats, device_kind=args.device_kind)
        print(f"\nroofline: busy {r.busy_us / 1e3:.1f} ms "
              f"(idle {r.idle_us / 1e3:.1f}), "
              f"{r.achieved_bytes_per_s / 1e9:.0f} GB/s "
              f"({r.bandwidth_util:.0%} of HBM peak), "
              f"{r.achieved_flops_per_s / 1e12:.1f} TF/s "
              f"(MFU {r.mfu:.3f}) -> bound by {r.bound_by} "
              f"({r.hbm_bound_pct:.0f}% of busy time HBM-bound)")
    except ValueError as e:
        sys.stderr.write(f"roofline skipped: {e}\n")

    # GAPS: where the IDLE time actually lives, attributed. Never let a
    # gap-analysis failure cost the per-op table above (older captures,
    # exotic plane layouts) — unless --strict, where a silent skip would
    # defeat the gate.
    report = None
    try:
        report = prof.attribute_gaps(args.logdir,
                                     min_gap_us=args.min_gap_us)
        print("\n## GAPS\n")
        print(prof.format_gaps(report, top=args.top))
        if args.gaps_json:
            with open(args.gaps_json, "w") as f:
                f.write(report.to_json() + "\n")
            sys.stderr.write(f"gap sites written to {args.gaps_json}\n")
    except Exception as e:
        sys.stderr.write(f"gap attribution skipped: "
                         f"{type(e).__name__}: {e}\n")
        if args.strict:
            sys.stderr.write("--strict: no gap attribution -> exit 2\n")
            sys.exit(2)
    if args.strict and report is not None and report.gaps and \
            report.unattributed_pct > args.max_unattributed_pct:
        sys.stderr.write(
            f"--strict: {report.unattributed_pct:.1f}% of dead time "
            f"unattributed (> {args.max_unattributed_pct:g}%); extend "
            f"prof/gaps.py _RULES from the footer's seam names -> "
            f"exit 1\n")
        sys.exit(1)


if __name__ == "__main__":
    main()
