"""apex_lint — rule-based static audit of the repo's compiled programs.

Runs the ``apex_tpu.analysis`` rule registry (docs/ANALYSIS.md) over

- the CANONICAL PROGRAM SET (``apex_tpu/analysis/programs.py``): the
  two benchmark training steps, built through ``apex_tpu.train_step``
  (the ResNet step; the dense-LM step, plan-compiled: the DDP
  shard_map arm when >1 device is visible — this tool forces a
  2-device CPU mesh for exactly that), the serve engine's
  prefill/commit/decode trio (fused, serialized AND paged — r20),
  and both examples' train-step replicas; and
- the HOST-SIDE SOURCE SET: ``apex_tpu/serve/engine.py``,
  ``tools/*.py``, ``examples/**/*.py`` (the AST rules).

Nothing executes: programs are traced abstractly, so the whole audit
runs in seconds on any host.

Usage:
    python tools/apex_lint.py                       # human findings
    python tools/apex_lint.py --strict              # exit 1 on any
                                                    # unsuppressed error
    python tools/apex_lint.py --json [PATH]         # machine findings
    python tools/apex_lint.py --programs lm,serve_fused --rules donation-miss
    python tools/apex_lint.py --write-baseline      # accept current
                                                    # findings (reasons
                                                    # must be filled in
                                                    # by hand)

Suppressions (both REQUIRE a reason — a reasonless suppression is
itself an error):
    inline   ``# apex-lint: disable=<rule> -- <reason>``
    baseline ``apex_lint_baseline.json`` (``--baseline`` to point
             elsewhere), entries ``{"fingerprint": ..., "reason": ...}``

Exit codes: 0 clean (or findings without --strict), 1 unsuppressed
errors under --strict, 2 usage/internal error.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DEFAULT_BASELINE = os.path.join(REPO, "apex_lint_baseline.json")

# the host-side hazard surface (ISSUE r15): the serve engine's
# scheduler loop, every perf tool, both examples. r16 adds repo-root
# bench.py — a measurement tool that predates tools/ (the
# bare-json-line rule and host-sync warnings apply to it like any
# other tool; rules._TOOL_PATH_RX knows the path).
# r18 adds apex_tpu/prof/live.py: the LiveEmitter's non-blocking
# producer contract is exactly what blocking-emit-on-step-path guards,
# so the module that defines the contract is audited against it.
# r19 adds apex_tpu/serve/router.py: the routing hot loop is audited
# by blocking-emit-on-step-path / host-sync-in-hot-loop, and the
# module that books sheds is audited by its own unattributed-shed
# contract.
SOURCE_GLOBS = ("apex_tpu/serve/engine.py", "apex_tpu/serve/router.py",
                "apex_tpu/prof/live.py",
                "tools/*.py", "bench.py",
                "examples/*/*.py", "examples/*.py")


def _source_views():
    from apex_tpu.analysis.core import SourceView
    seen = set()
    views = []
    for g in SOURCE_GLOBS:
        for path in sorted(glob.glob(os.path.join(REPO, g))):
            if path in seen or os.path.basename(path).startswith("_"):
                continue
            seen.add(path)
            try:
                views.append(SourceView.from_file(path, root=REPO))
            except SyntaxError as e:
                print(f"apex_lint: skipping unparseable {path}: {e}",
                      file=sys.stderr)
    return views


def main() -> int:
    ap = argparse.ArgumentParser(
        description="rule-based static audit of compiled step programs")
    ap.add_argument("--programs", default=None,
                    help="comma list from the canonical registry "
                         "(default: all canonical; 'none' skips "
                         "program rules)")
    ap.add_argument("--rules", default=None,
                    help="comma list of rule names (default: all)")
    ap.add_argument("--json", nargs="?", const="-", default=None,
                    metavar="PATH",
                    help="emit machine-readable findings (to PATH, or "
                         "stdout with no argument)")
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 when any unsuppressed error remains")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help=f"baseline file (default "
                         f"{os.path.relpath(DEFAULT_BASELINE, REPO)})")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write every unsuppressed finding into the "
                         "baseline with reason 'TODO: justify' — fill "
                         "the reasons in before committing (a TODO "
                         "reason still lints, but reviewers see it)")
    ap.add_argument("--no-source", action="store_true",
                    help="skip the AST (source) rules")
    ap.add_argument("--devices", type=int, default=2,
                    help="forced CPU device count (exercises the DDP "
                         "shard_map lowering of the lm program; only "
                         "honored when jax is not yet initialized)")
    args = ap.parse_args()

    # a multi-device CPU mesh must be requested BEFORE jax initializes:
    # the lm program's DDP arm (shard_map + psum over 'data') is the
    # collective-misuse rule's real-world subject
    if "jax" not in sys.modules and args.devices > 1:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.devices}").strip()

    from apex_tpu import analysis
    from apex_tpu.analysis import programs as registry

    targets = []
    if args.programs != "none":
        names = args.programs.split(",") if args.programs else None
        try:
            targets.extend(registry.build_programs(names))
        except KeyError as e:
            ap.error(str(e))
    if not args.no_source:
        targets.extend(_source_views())

    rules = args.rules.split(",") if args.rules else None
    try:
        report = analysis.lint(targets, rules=rules,
                               baseline_path=args.baseline)
    except KeyError as e:
        ap.error(str(e))

    if args.write_baseline:
        entries = [{"fingerprint": f.fingerprint,
                    "rule": f.rule, "target": f.target,
                    "reason": "TODO: justify"}
                   for f in report.findings if not f.suppressed]
        with open(args.baseline, "w") as fh:
            json.dump({"version": 1, "suppressions": entries}, fh,
                      indent=2)
            fh.write("\n")
        print(f"wrote {len(entries)} entr(ies) to {args.baseline} — "
              f"replace every 'TODO: justify' before committing")
        return 0

    payload = report.to_json(
        programs=[t.name for t in targets
                  if hasattr(t, "example_args")],
        sources=[t.path for t in targets if hasattr(t, "tree")])
    if args.json == "-":
        print(json.dumps(payload))
    else:
        if args.json:
            with open(args.json, "w") as fh:
                json.dump(payload, fh, indent=2)
                fh.write("\n")
        print(report.format_human())

    errors = report.errors()
    if args.strict and errors:
        print(f"apex_lint --strict: {len(errors)} unsuppressed "
              f"error(s)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
