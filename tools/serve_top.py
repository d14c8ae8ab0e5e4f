"""serve_top — a refresh-in-place terminal dashboard over the live plane.

``top`` for the serving fleet (r18): polls a
``apex_tpu.prof.live.LiveCollector``'s ``/snapshot`` endpoint and
renders one row per replica — occupancy, queue depth, decode-step p50,
TTFT / token-latency p95 over that replica's rolling window, samples,
drops, alerts, stream age — plus the fleet header (merged-stream
percentiles, fleet-scope SLO rules and violations, total drops) and,
when the snapshot carries one (``fleet_smoke --serve --router`` /
``serve_bench --router``), the r19 ROUTER line (policy,
routed/completed/shed/redirected counts, routed balance, scale
events) and the r21 SPEC line (per-replica draft k and accepted-length
mean when speculative decoding is on). The
collector is armed by ``serve_bench.py --live``, ``fleet_smoke.py
--live``; point this tool at the /metrics
port it prints.

Usage:
    python tools/serve_top.py http://127.0.0.1:PORT [--interval 1.0]
    python tools/serve_top.py --from SNAPSHOT.json --once
    python tools/serve_top.py URL --once [--json]

``--once`` prints a single frame and exits (the CI shape); ``--from``
renders a dumped snapshot file (``fleet_smoke --live`` writes
``<out>.snapshot.json``) with no collector needed. Rendering is
in-place via ANSI home+clear — no curses dependency, works in any
terminal and in a pipe (where the escape codes are suppressed).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.request

_CLEAR = "\x1b[H\x1b[2J"


def _fmt(v, pat="{:.2f}", na="-") -> str:
    if v is None:
        return na
    try:
        return pat.format(v)
    except (TypeError, ValueError):
        return str(v)


def render_frame(snap: dict, *, clock: "float | None" = None) -> str:
    """One dashboard frame from a collector snapshot dict — pure
    function (unit-tested without sockets; ``--from`` uses it on a
    dumped file)."""
    fleet = snap.get("fleet") or {}
    rows = snap.get("replicas") or []
    when = time.strftime("%H:%M:%S",
                         time.localtime(clock or snap.get("t")
                                        or time.time()))
    head = (f"apex_tpu serve_top — {fleet.get('processes', 0)} "
            f"replica(s) | fleet alerts {fleet.get('alerts', 0)}"
            + (f" ({', '.join(fleet['violated'])})"
               if fleet.get("violated") else "")
            + f" | drops {fleet.get('drops_total', 0)} | {when}")
    lines = [head]
    occ = fleet.get("occupancy")
    tt = fleet.get("ttft_ms")
    tl = fleet.get("token_lat_ms")
    agg = []
    if occ:
        agg.append(f"occupancy min/mean {occ['min']:.2f}/"
                   f"{occ['mean']:.2f}")
    if tt:
        agg.append(f"TTFT p95 {tt['p95']} ms")
    if tl:
        agg.append(f"token-lat p95 {tl['p95']} ms")
    if fleet.get("rules"):
        agg.append(f"rules: {', '.join(fleet['rules'])}")
    if agg:
        lines.append("fleet: " + " | ".join(agg))
    rt = snap.get("router")
    if rt:
        shed = rt.get("shed", 0)
        row = (f"router: policy {rt.get('policy')} | "
               f"routed {rt.get('routed', 0)} | "
               f"completed {rt.get('completed', 0)} | "
               f"shed {shed} | redirected {rt.get('redirected', 0)}")
        if rt.get("routed_balance") is not None:
            row += f" | balance {rt['routed_balance']:.2f}"
        if rt.get("scale_events"):
            row += f" | scale events {len(rt['scale_events'])}"
        lines.append(row)
    # r21: one spec line when any replica runs speculative decoding —
    # the accept mean IS the lossless tokens/s multiple's free variable
    spec_rows = [r for r in rows if r.get("spec_k")]
    if spec_rows:
        parts = [f"p{r['process']} k={r['spec_k']} accept "
                 f"{_fmt(r.get('spec_accept_mean'))}"
                 for r in spec_rows]
        lines.append("spec: " + " | ".join(parts))
    lines.append("")
    hdr = (f"{'proc':<6}{'run':<14}{'occ':>6}{'queue':>7}"
           f"{'step p50':>10}{'ttft p95':>10}{'tok p95':>9}"
           f"{'done':>7}{'samples':>9}{'drops':>7}{'alerts':>7}"
           f"{'age s':>7}")
    lines.append(hdr)
    lines.append("-" * len(hdr))
    for r in rows:
        done = (f"{r['completed']}/{r['offered']}"
                if r.get("completed") is not None
                and r.get("offered") is not None else "-")
        mark = " " if not r.get("closed") else "*"   # * = stream closed
        lines.append(
            f"p{r['process']:<4}{mark}{(r.get('run') or '-'):<14}"
            f"{_fmt(r.get('occupancy')):>6}"
            f"{_fmt(r.get('queue_depth'), '{:.0f}'):>7}"
            f"{_fmt(r.get('step_p50_ms')):>10}"
            f"{_fmt(r.get('ttft_p95_ms'), '{:.1f}'):>10}"
            f"{_fmt(r.get('token_lat_p95_ms'), '{:.1f}'):>9}"
            f"{done:>7}{r.get('samples', 0):>9}"
            f"{r.get('drops', 0):>7}{r.get('alerts', 0):>7}"
            f"{_fmt(r.get('age_s'), '{:.1f}'):>7}")
    if not rows:
        lines.append("(no replicas connected yet)")
    return "\n".join(lines)


def _fetch(url: str) -> dict:
    if not url.endswith("/snapshot"):
        url = url.rstrip("/")
        if url.endswith("/metrics"):
            url = url[: -len("/metrics")]
        url += "/snapshot"
    with urllib.request.urlopen(url, timeout=5) as resp:
        return json.loads(resp.read().decode())


def main() -> int:
    ap = argparse.ArgumentParser(
        description="live terminal dashboard over a LiveCollector")
    ap.add_argument("url", nargs="?", default=None,
                    help="collector base URL (the /metrics URL the "
                         "armed tool prints works as-is)")
    ap.add_argument("--from", dest="snapshot_file", default=None,
                    help="render a dumped /snapshot JSON file instead "
                         "of polling (fleet_smoke --live writes "
                         "<out>.snapshot.json)")
    ap.add_argument("--interval", type=float, default=1.0)
    ap.add_argument("--once", action="store_true",
                    help="print one frame and exit (no ANSI refresh)")
    ap.add_argument("--frames", type=int, default=0,
                    help="stop after N frames (0 = until ^C)")
    ap.add_argument("--json", action="store_true",
                    help="print the raw snapshot JSON instead of the "
                         "table")
    args = ap.parse_args()
    if (args.url is None) == (args.snapshot_file is None):
        ap.error("pass a collector URL or --from SNAPSHOT.json")

    inplace = (not args.once and args.snapshot_file is None
               and sys.stdout.isatty())
    n = 0
    while True:
        try:
            snap = (json.load(open(args.snapshot_file))
                    if args.snapshot_file else _fetch(args.url))
        except Exception as e:
            print(f"serve_top: {type(e).__name__}: {e}",
                  file=sys.stderr)
            return 1
        if args.json:
            print(json.dumps(snap))
        else:
            frame = render_frame(snap)
            if inplace:
                sys.stdout.write(_CLEAR + frame + "\n")
                sys.stdout.flush()
            else:
                print(frame)
        n += 1
        if args.once or args.snapshot_file or \
                (args.frames and n >= args.frames):
            return 0
        try:
            time.sleep(args.interval)
        except KeyboardInterrupt:
            return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:      # `serve_top ... | head` is fine
        os.close(sys.stdout.fileno())
        sys.exit(0)
