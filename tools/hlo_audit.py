"""Static audit of the compiled headline train step's optimized HLO.

The one perf check that needs no run: compile the HEAD RN50
O2+FusedLAMB step and inspect what XLA actually produced. This answers
the regression question VERDICT r3 raised about unmeasured commits —
the step-glue wins of r03 (docs/PERF.md: ONE flat-buffer convert
instead of 161 per-leaf casts, no per-leaf flatten chains, no
double-moments BN) are all visible as structure in the optimized
module:

* instruction histogram outside fusions (converts/copies/transposes
  that XLA could not fuse are real HBM passes),
* fusion count and the largest fusions by operand bytes,
* convolution/custom-call inventory (53 BNs should NOT appear as 53
  standalone reduce chains),
* peak memory + argument/output/temp sizes from compiled memory
  analysis where the backend exposes it.

Usage:
    python tools/hlo_audit.py [--out HLO_AUDIT_r04.md] [--batch 256]
        [--image 224] [--s2d] [--json]

Works on CPU too under an explicit CPU request (different backend,
same report shape) — that is what the test tier drives.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
import time
import os
# repo root importable without PYTHONPATH
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from collections import Counter, defaultdict


_feed = lambda: None  # rebound by arm_watchdog in main()


def _note(m):
    _feed()
    sys.stderr.write(f"hlo[{time.strftime('%H:%M:%S')}]: {m}\n")
    sys.stderr.flush()


_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*[a-z0-9[\],{}/ ]*?\s*"
    r"([a-z][a-z0-9\-]*)\(")
_NAMED_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*[a-z0-9[\],{}/ ]*?\s*"
    r"([a-z][a-z0-9\-]*)\(")
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e5m2": 1, "f8e4m3fn": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "s4": 1, "u4": 1,
}


def shape_bytes(text: str) -> int:
    """Sum the byte sizes of every shape literal in an HLO line."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def audit_hlo_text(hlo: str) -> dict:
    """Parse an optimized HLO module dump into the audit summary.

    Top-level = instructions inside ENTRY and while-body computations
    (the per-step program); instructions inside `fused_computation`s are
    counted separately — an op inside a fusion is free-ish (registers),
    the same op at top level is its own HBM pass.
    """
    top = Counter()
    fused = Counter()
    fusion_bytes = []   # (bytes-in-line, name) per fusion instruction
    top_convert_bytes = 0
    in_fused_computation = False
    cur_computation = None

    for line in hlo.splitlines():
        stripped = line.strip()
        if stripped.startswith(("ENTRY", "%fused_computation",
                                "fused_computation")) or \
                (stripped and not line.startswith(" ") and "{" in stripped):
            name = stripped.split("(")[0].split("=")[-1].strip()
            in_fused_computation = "fused_computation" in stripped
            cur_computation = name
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        op = m.group(1)
        if op in ("parameter", "constant", "tuple", "get-tuple-element",
                  "bitcast"):
            continue
        if in_fused_computation:
            fused[op] += 1
            continue
        top[op] += 1
        if op == "fusion":
            fusion_bytes.append((shape_bytes(line), line.strip()[:120]))
        if op == "convert":
            top_convert_bytes += shape_bytes(line)

    fusion_bytes.sort(reverse=True)
    return {
        "top_level_histogram": dict(top.most_common()),
        "inside_fusions_histogram": dict(fused.most_common(25)),
        "n_fusions": top.get("fusion", 0),
        "n_top_level_converts": top.get("convert", 0),
        "top_level_convert_bytes": top_convert_bytes,
        "n_top_level_copies": top.get("copy", 0),
        "n_top_level_transposes": top.get("transpose", 0),
        "n_convolutions": top.get("convolution", 0)
        + fused.get("convolution", 0),
        "n_custom_calls": top.get("custom-call", 0),
        "largest_fusions": [
            {"bytes": b, "instr": s} for b, s in fusion_bytes[:10]],
    }


# Donation parsing lives in apex_tpu.analysis.donation (r15): ONE code
# path shared with the apex_lint donation-miss rule — same table
# output here, same contract ("only stream inputs may show up
# undonated") checked per-aval over every canonical program there.
from apex_tpu.analysis.donation import audit_donation  # noqa: E402,F401


def _index_instructions(hlo: str) -> tuple[dict, dict]:
    """(instr name -> {"op", "calls", "line"},
    computation name -> set of op kinds inside). The instruction names
    are what xprof's 'XLA Ops' lane reports as event names, so this is
    the join key between a trace-gap site and the compiled module."""
    instrs: dict = {}
    comp_ops: dict = {}
    cur_computation = None
    for line in hlo.splitlines():
        stripped = line.strip()
        if stripped.startswith(("ENTRY", "%fused_computation",
                                "fused_computation")) or \
                (stripped and not line.startswith(" ") and "{" in stripped):
            cur_computation = stripped.split("(")[0].split("=")[-1] \
                .strip().lstrip("%")
            continue
        m = _NAMED_INSTR_RE.match(line)
        if not m:
            continue
        name, op = m.group(1), m.group(2)
        if cur_computation is not None:
            comp_ops.setdefault(cur_computation, set()).add(op)
        calls = _CALLS_RE.search(line)
        instrs[name] = {"op": op,
                        "calls": calls.group(1) if calls else None,
                        "line": stripped[:160]}
    return instrs, comp_ops


def cross_reference_gaps(hlo: str, gap_sites: list) -> list:
    """Join trace-gap sites (prof.gaps ``to_json()["gaps"]`` rows)
    against the optimized HLO: which instruction/fusion ended at the
    gap, which began, and was a ``convert`` at the seam (either bounding
    op IS a convert, or a bounding fusion's computation contains one) —
    the question the cast-coalescing work needs answered per gap site.
    """
    instrs, comp_ops = _index_instructions(hlo)

    def describe(name: str) -> dict:
        name = name.lstrip("%")
        info = instrs.get(name)
        if info is None:
            return {"name": name, "op": None, "has_convert": False,
                    "in_hlo": False}
        ops = comp_ops.get(info["calls"], set()) if info["calls"] else set()
        return {"name": name, "op": info["op"], "calls": info["calls"],
                "has_convert": info["op"] == "convert" or "convert" in ops,
                "in_hlo": True}

    out = []
    for site in gap_sites:
        before = describe(str(site.get("before", "")))
        after = describe(str(site.get("after", "")))
        out.append({
            "dur_us": site.get("dur_us"),
            "category": site.get("category"),
            "before": before,
            "after": after,
            "convert_at_seam": bool(before["has_convert"]
                                    or after["has_convert"]),
            "resolved": before["in_hlo"] or after["in_hlo"],
        })
    return out


def main():
    # Stall watchdog: bound a hung compile instead of burning the
    # caller's time limit.
    global _feed
    from _perf_common import arm_watchdog
    _feed = arm_watchdog("hlo_audit")
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--image", type=int, default=None)
    ap.add_argument("--s2d", action="store_true")
    ap.add_argument("--out", default=None, help="markdown report path")
    ap.add_argument("--json", action="store_true",
                    help="print the summary as one JSON line")
    ap.add_argument("--gaps", default=None,
                    help="gap-sites JSON from trace_top_ops.py "
                         "--gaps-json: cross-reference each gap site "
                         "against the compiled HLO")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu import amp
    from apex_tpu.models import ResNet, resnet50
    from apex_tpu.optimizers import FusedLAMB
    from apex_tpu.ops import flat as F

    from apex_tpu.utils import setup_host_backend
    backend = setup_host_backend()
    on_tpu = backend == "tpu"
    batch = args.batch or (256 if on_tpu else 8)
    image = args.image or (224 if on_tpu else 32)
    _note(f"backend={backend} batch={batch} image={image}")

    stem = "space_to_depth" if args.s2d else "conv"
    model = resnet50(stem=stem) if on_tpu else ResNet(
        block_sizes=(1, 1), bottleneck=True, num_classes=10, width=8,
        stem=stem)
    params, bn_state = model.init(jax.random.key(0))
    _, handle = amp.initialize(opt_level="O2", verbosity=0)
    amp_state = handle.init_state()
    half = handle.policy.cast_model_dtype
    opt = FusedLAMB(params, lr=1e-3)
    table = opt._tables[0]
    opt_state = opt.init_state()

    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(batch, image, image, 3), half)
    y = jnp.asarray(rs.randint(0, model.num_classes, batch), jnp.int32)

    def step(opt_state, bn_state, amp_state, x, y):
        # the bench.py train step verbatim (flat-master differentiation)
        def loss_fn(master):
            p_half = F.unflatten(master, table, dtype=half)
            logits, new_st = model.apply(p_half, bn_state, x,
                                         training=True)
            logits = logits.astype(jnp.float32)
            logp = jax.nn.log_softmax(logits)
            loss = -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))
            return handle.scale_loss(loss, amp_state), (loss, new_st)

        fg, (loss, new_bn) = jax.grad(loss_fn, has_aux=True)(
            opt_state[0].master)
        fg, found_inf = handle.unscale(fg, amp_state)
        new_opt = opt.apply_update(opt_state, [fg], found_inf=found_inf)
        new_amp = handle.update(amp_state, found_inf)
        return new_opt, new_bn, new_amp, loss

    jstep = jax.jit(step, donate_argnums=(0, 1, 2))
    _note("lowering")
    lowered = jstep.lower(opt_state, bn_state, amp_state, x, y)
    try:
        donation = audit_donation(lowered.as_text())
        _note(f"donation: {donation['n_donated']}/{donation['n_args']} "
              f"args donated, "
              f"{donation['undonated_bytes'] / 1e6:.1f} MB undonated")
    except Exception as e:
        donation = None
        _note(f"donation audit unavailable: {type(e).__name__}: {e}")
    _note("compiling")
    _feed(allow=2400.0)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    _note(f"compiled in {time.perf_counter() - t0:.0f}s")

    # as_text() can come back empty (r04: cost/memory analysis worked,
    # text didn't — the md showed all-zero structure counts); fall back
    # to the runtime executable's HLO modules, and flag honestly if
    # neither works so a zero reads as "unavailable", not "no fusions".
    hlo = ""
    for what, getter in (
            ("as_text", lambda: compiled.as_text()),
            ("runtime_executable", lambda: "\n".join(
                m.to_string()
                for m in compiled.runtime_executable().hlo_modules()))):
        try:
            hlo = getter() or ""
        except Exception as e:
            _note(f"{what} unavailable: {type(e).__name__}: {e}")
        if hlo.strip():
            break
    summary = audit_hlo_text(hlo)
    summary["hlo_text_chars"] = len(hlo)
    if not hlo.strip():
        summary["hlo_text_unavailable"] = True
    summary["backend"] = backend
    summary["batch"], summary["image"], summary["stem"] = batch, image, stem
    summary["hlo_lines"] = hlo.count("\n")
    if donation is not None:
        summary["donation"] = donation

    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if ca:
            summary["cost_flops"] = float(ca.get("flops", 0.0))
            summary["cost_bytes_accessed"] = float(
                ca.get("bytes accessed", 0.0))
    except Exception as e:  # backend may not expose it
        _note(f"cost_analysis unavailable: {e}")
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                # apex-lint: disable=host-sync-in-hot-loop -- memory_analysis returns host ints, not device buffers
                summary[k] = int(v)
    except Exception as e:
        _note(f"memory_analysis unavailable: {e}")

    if args.gaps:
        try:
            with open(args.gaps) as f:
                sites = json.load(f).get("gaps", [])
            summary["gap_xref"] = cross_reference_gaps(hlo, sites)
            n_conv = sum(1 for g in summary["gap_xref"]
                         if g["convert_at_seam"])
            n_res = sum(1 for g in summary["gap_xref"] if g["resolved"])
            _note(f"gap xref: {len(sites)} sites, {n_res} resolved in "
                  f"this HLO, {n_conv} with a convert at the seam")
        except Exception as e:
            _note(f"gap xref failed: {type(e).__name__}: {e}")

    if args.json:
        print(json.dumps(summary))
    if args.out:
        lines = [f"# HLO audit — backend={backend} batch={batch} "
                 f"image={image} stem={stem}", ""]
        lines.append("## Headline structure")
        if summary.get("hlo_text_unavailable"):
            lines.append("- **hlo text unavailable through this backend "
                         "— structure counts below are meaningless; "
                         "cost/memory numbers are real**")
        for k in ("hlo_text_chars", "n_fusions", "n_convolutions",
                  "n_custom_calls",
                  "n_top_level_converts", "top_level_convert_bytes",
                  "n_top_level_copies", "n_top_level_transposes",
                  "cost_flops", "cost_bytes_accessed",
                  "argument_size_in_bytes", "temp_size_in_bytes"):
            if k in summary:
                lines.append(f"- {k}: {summary[k]}")
        lines.append("")
        lines.append("## Top-level instruction histogram")
        for op, n in summary["top_level_histogram"].items():
            lines.append(f"- {op}: {n}")
        lines.append("")
        lines.append("## Largest fusions (by shape bytes on the line)")
        for f in summary["largest_fusions"]:
            lines.append(f"- {f['bytes']}: `{f['instr']}`")
        if "donation" in summary:
            d = summary["donation"]
            lines.append("")
            lines.append("## Donation audit (entry-arg aliasing)")
            lines.append(f"- donated: {d['n_donated']}/{d['n_args']} "
                         f"args ({d['donated_bytes']} bytes)")
            lines.append(f"- undonated: {d['undonated_bytes']} bytes")
            for a in d["undonated"]:
                lines.append(f"  - arg{a['arg']} tensor<{a['type']}> "
                             f"({a['bytes']} bytes)")
        if "gap_xref" in summary:
            lines.append("")
            lines.append("## Gap cross-reference (trace gap sites vs "
                         "this HLO)")
            lines.append("| gap us | category | before | after | "
                         "convert at seam |")
            lines.append("|---|---|---|---|---|")
            for g in summary["gap_xref"]:
                b, a = g["before"], g["after"]
                bd = f"`{b['name']}` ({b['op'] or '?'})"
                ad = f"`{a['name']}` ({a['op'] or '?'})"
                dur = g["dur_us"]
                lines.append(
                    f"| {dur:.0f} | {g['category']} | {bd} | {ad} | "
                    f"{'YES' if g['convert_at_seam'] else 'no'} |"
                    if isinstance(dur, (int, float)) else
                    f"| ? | {g['category']} | {bd} | {ad} | "
                    f"{'YES' if g['convert_at_seam'] else 'no'} |")
        with open(args.out, "w") as fh:
            fh.write("\n".join(lines) + "\n")
        _note(f"wrote {args.out}")
    if not args.json and not args.out:
        print(json.dumps({k: v for k, v in summary.items()
                          if not isinstance(v, (dict, list))}, indent=2))


if __name__ == "__main__":
    main()
