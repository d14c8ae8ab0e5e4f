"""Shared helpers for the perf tools (perf_probe, decode_bench, serve_bench)."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the ``format: "<tool>@<ver>"`` tag every tool JSON line carries (r16)
# — bump per tool when its line shape changes incompatibly; the
# perf_history ingester accepts untagged legacy lines unchanged
RESULT_FORMAT_VERSION = 1


def _git_rev() -> "str | None":
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short=12", "HEAD"], cwd=_REPO,
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or None
    except Exception:
        return None


def run_meta(tool: str) -> dict:
    """The self-description block stamped into every tool JSON line
    (r16): git rev, jax version, backend platform, device count,
    telemetry schema — the fields that turn a committed artifact into
    a trajectory point someone can still interpret ten rounds later.
    Consults jax ONLY when the tool already imported it (stamping must
    never force a backend init)."""
    meta: dict = {"tool": tool, "git": _git_rev(),
                  "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                       time.gmtime())}
    jax = sys.modules.get("jax")
    if jax is not None:
        meta["jax"] = getattr(jax, "__version__", None)
        try:
            from jax._src import xla_bridge as _xb
            if _xb.backends_are_initialized():
                meta["platform"] = jax.default_backend()
                meta["devices"] = jax.device_count()
        except Exception:
            pass
    try:
        from apex_tpu.prof.metrics import SCHEMA_VERSION
        meta["telemetry_schema"] = SCHEMA_VERSION
    except Exception:
        pass
    return meta


def stamp_result(line: dict, tool: str, *,
                 version: int = RESULT_FORMAT_VERSION) -> dict:
    """Make a tool's JSON result line self-describing: a ``format:
    "<tool>@<ver>"`` tag plus the :func:`run_meta` block. Returns the
    line (mutated in place) so ``print(json.dumps(stamp_result(out,
    "x")))`` reads naturally. ``APEX_RUN_META=0`` disables (the
    overhead-A/B knob; the perf_history ingester accepts untagged
    lines either way). Happens once per emission, OUTSIDE any timed
    region — measured overhead on the CPU bench loop: within run
    noise, <1% (docs/PERF.md r16)."""
    if os.environ.get("APEX_RUN_META", "1") in ("0", "false"):
        return line
    line.setdefault("format", f"{tool}@{version}")
    line.setdefault("run_meta", run_meta(tool))
    return line


def append_trajectory(line: dict, *, tool: str,
                      arg: "str | None" = None,
                      round: "int | None" = None) -> "str | None":
    """The r16 trajectory hook: canonicalize a just-emitted result line
    into PerfPoints and append them to the committed store. Armed by
    ``arg`` or ``APEX_TRAJECTORY`` (path, or "1" for the repo-root
    ``BENCH_TRAJECTORY.json``); the round comes from ``APEX_ROUND``
    else continues the store's max round. Returns the store path, or
    None when unarmed; never raises — losing a bench's JSON line to a
    bookkeeping failure would invert the tool's one-line contract."""
    arg = arg or os.environ.get("APEX_TRAJECTORY")
    if not arg:
        return None
    try:
        from apex_tpu.prof import history as H
        path = (os.path.join(_REPO, H.DEFAULT_BASENAME)
                if arg in ("1", "true") else arg)
        traj = H.Trajectory.load(path)
        if round is None:
            env_round = os.environ.get("APEX_ROUND")
            round = int(env_round) if env_round else \
                max(traj.max_round(), 1)
        pts = H.points_from_result_line(line, tool=tool, round=round,
                                        provenance="live")
        if traj.append(pts):
            traj.save(path)
        return path
    except Exception as e:
        sys.stderr.write(f"append_trajectory: {type(e).__name__}: {e} "
                         f"(line emitted; trajectory not updated)\n")
        return None


def emit_result(line: dict, tool: str) -> dict:
    """THE result-line funnel: stamp (:func:`stamp_result`), print the
    one JSON line, flush, and run the :func:`append_trajectory` hook.
    The apex_lint ``bare-json-line`` rule flags tools that print
    metric/value lines any other way."""
    stamp_result(line, tool)
    print(json.dumps(line))
    sys.stdout.flush()
    append_trajectory(line, tool=tool)
    return line


def arm_watchdog(label: str, seconds: "float | None" = None):
    """Stall watchdog for the measurement tools.

    A device call that never returns blocks in an uninterruptible C
    call; without a watchdog the tool burns its caller's whole time
    limit. Returns ``feed()`` — call it at every progress point. If no
    progress for ``seconds`` (default: PROBE_DEADMAN env var, else
    1200) the process writes a stall note to stderr and hard-exits 3
    (``os._exit``; a hung C call cannot be unwound by exceptions).
    Results already printed/written before the stall survive."""
    if seconds is None:
        seconds = float(os.environ.get("PROBE_DEADMAN", 1200.0))
    deadline = [time.monotonic() + seconds]

    def feed(allow: "float | None" = None) -> None:
        """Mark progress. ``allow`` grants a one-shot larger budget for
        the NEXT gap (e.g. a single long XLA compile that legitimately
        exceeds the default window); the following feed() resets to the
        tight default."""
        deadline[0] = time.monotonic() + (seconds if allow is None
                                          else allow)

    def _watch() -> None:
        while True:
            time.sleep(min(seconds / 4.0, 30.0))
            over = time.monotonic() - deadline[0]
            if over > 0:
                sys.stderr.write(
                    f"{label}: WATCHDOG no progress past deadline "
                    f"(+{over:.0f}s); exiting 3\n")
                sys.stderr.flush()
                os._exit(3)

    threading.Thread(target=_watch, daemon=True).start()
    return feed


def make_decoder_lm(*, vocab: int, dim: int, heads: int, layers: int,
                    max_seq_len: int, dtype: str = "bf16",
                    attn_impl: str = "auto", seed: int = 0,
                    host_extras=None):
    """The model-build + ship preamble shared by the inference-side
    tools (decode_bench, serve_bench): construct a TransformerLM, init
    its params on the HOST cpu backend in the tool dtype, and ship them
    to the default device in ONE bulk transfer (utils.host_init).

    ``host_extras``: optional thunk run under the same ``host_init()``
    (e.g. building a prompt batch) so its arrays ride the same ship.
    Returns ``(lm, params, extras)`` (extras None when not requested).
    """
    import jax
    import jax.numpy as jnp

    from apex_tpu.models import TransformerLM
    from apex_tpu.utils import host_init, ship

    half = jnp.bfloat16 if dtype == "bf16" else jnp.float32
    lm = TransformerLM(vocab_size=vocab, max_seq_len=max_seq_len,
                       embed_dim=dim, num_heads=heads,
                       num_layers=layers, attn_impl=attn_impl)
    with host_init():
        params = lm.init(jax.random.key(seed))
        params = jax.tree.map(
            lambda t: t.astype(half) if t.dtype == jnp.float32 else t,
            params)
        extras = host_extras() if host_extras is not None else None
    params, extras = ship((params, extras))
    return lm, params, extras


def open_telemetry(arg, *, tag: str, run: str, meta=None, feed=None,
                   min_interval_s: float = 600.0, tracer=None):
    """The ``--telemetry`` boilerplate shared by the perf tools: resolve
    the sidecar path (``"1"`` auto-names next to the BENCH_* artifacts),
    open the MetricsLogger + stall Watchdog, and wrap ``feed`` so every
    tool progress note also heartbeats the watchdog.

    ``tracer`` (r13): an optional ``prof.SpanTracer`` handed to the
    Watchdog so a stall snapshot names the spans that were in flight.

    Returns ``(telem, watchdog, feed)`` — all pass-through (telem None,
    feed unchanged) when ``arg`` is falsy, so call sites stay
    unconditional."""
    if not arg:
        return None, None, (feed or (lambda allow=None: None))
    from apex_tpu import prof
    path = (arg if arg != "1" else
            prof.metrics.default_sidecar_path(
                tag, os.path.join(os.path.dirname(__file__), "..")))
    telem = prof.MetricsLogger(path, run=run, meta=meta)
    wd = prof.Watchdog(telem, min_interval_s=min_interval_s,
                       label=run, tracer=tracer).start()
    prev = feed or (lambda allow=None: None)

    def feed_and_beat(allow=None):
        wd.heartbeat()
        prev(allow)

    return telem, wd, feed_and_beat
