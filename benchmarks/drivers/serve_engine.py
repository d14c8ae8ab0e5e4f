"""Driver: the paged continuous-batching engine, as ``chip_smoke.py``
builds it: ``ContinuousBatchingEngine(fused, paged, prefix_share)`` +
``warmup()``, greedy, no EOS.

One ``engine.run`` serves the ramp, the window and the drain. A request
counts if it was *due* in the window. Its time to first token runs from
its due time, so a late generator or a queue counts.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import common, weights as W
from benchmarks.spec import plugin


class _Feed:
    """Hands the engine every request at its first poll (their due times
    do the pacing) and, at later polls, runs what is due on the run's
    clock: the profiler starts and stops from the engine's own thread."""

    def __init__(self, requests, hooks, t0):
        self.requests, self.hooks, self.t0 = list(requests), list(hooks), t0

    def poll(self):
        now = time.perf_counter() - self.t0
        while self.hooks and now >= self.hooks[0][0]:
            self.hooks.pop(0)[1]()
        out, self.requests = self.requests, []
        return out

    @property
    def closed(self):
        return not self.requests and not self.hooks


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.specs = W.gpt2_specs(ctx.config)

    def setup(self):
        import jax
        import jax.numpy as jnp

        from apex_tpu.models import TransformerLM
        from apex_tpu.serve import ContinuousBatchingEngine

        ctx, cfg, eng = self.ctx, self.ctx.config, self.ctx.traffic["engine"]
        lm = TransformerLM(
            vocab_size=cfg["vocab_size"], max_seq_len=cfg["n_positions"],
            embed_dim=cfg["n_embd"], num_heads=cfg["n_head"],
            num_layers=cfg["n_layer"],
            ffn_mult=cfg["n_inner"] // cfg["n_embd"])
        # everything on the default device (the first), with no
        # default_device context: a context is part of every jit cache's
        # key, and the window's calls would miss what warmup() compiled
        params = jax.jit(lambda k: W.build(self.specs, k, jnp.bfloat16))(
            W.seed_key(ctx.seed))
        engine = ContinuousBatchingEngine(
            lm, params, slots=eng["slots"], max_len=eng["max_len"],
            prefill_chunk=eng["prefill_chunk"], fused=True, paged=True,
            page_size=eng["page_size"], kv_pages=eng["kv_pages"],
            prefix_share=True, seed=ctx.seed & 0x7FFFFFFF)
        if ctx.on_tpu:
            # lint_programs() would make a second arena; lower the decode
            # step on the shapes of one instead
            state = jax.eval_shape(engine._init_state)
            table = np.zeros((engine.slots, engine.max_pages), np.int32)
            if "tpu_custom_call" not in engine._decode_fn.lower(
                    params, state, table).as_text():
                raise AssertionError(
                    "no tpu_custom_call in the decode step: the dispatch "
                    "took the jnp reference")
        ctx.mark("engine_built")
        engine.warmup()
        self.engine = engine

    def reseed(self, seed: int):
        """Other weights and traffic on the compiled engine."""
        import jax
        import jax.numpy as jnp
        self.ctx.seed = seed
        self.engine.params = None
        self.engine.params = jax.jit(
            lambda k: W.build(self.specs, k, jnp.bfloat16))(W.seed_key(seed))

    # -- the window --------------------------------------------------------
    def window(self, seconds: float, trace_dir=None, traffic=None) -> dict:
        import jax

        from apex_tpu.serve import Request

        ctx = self.ctx
        traffic = traffic or ctx.traffic
        feed = plugin("generators", traffic["kind"]).generate(
            traffic, ctx.config, ctx.seed, seconds)
        reqs = [Request(id=r["id"], prompt=r["prompt"], max_new=r["max_new"],
                        arrival_s=r["arrival_s"]) for r in feed["requests"]]
        ramp, end = feed["ramp_s"], feed["end_s"]
        traced = {}
        hooks = []
        if trace_dir:
            def start():
                jax.profiler.start_trace(trace_dir)
                traced["t0"] = time.perf_counter() - t_run

            def stop():
                traced["t1"] = time.perf_counter() - t_run
                jax.profiler.stop_trace()
            hooks = [(ramp, start), (end, stop)]
        t_run = time.perf_counter()
        results, stats = self.engine.run(_Feed(reqs, hooks, t_run), t0=t_run)

        due = [(q, r) for q, r in zip(feed["requests"], results)
               if ramp <= q["arrival_s"] < end]
        rows, failed = [], 0
        for q, r in due:
            ok = (r.finish_s is not None and len(r.tokens) == q["max_new"]
                  and all(0 <= t < ctx.config["vocab_size"]
                          for t in r.tokens))
            failed += not ok
            rows.append({
                "id": q["id"], "ok": ok, "arrival_s": q["arrival_s"],
                "prompt_len": len(q["prompt"]), "prompt": q["prompt"],
                "prefix_tokens": r.prefix_tokens,
                "first_token_s": r.first_token_s, "finish_s": r.finish_s,
                "tokens": list(r.tokens), "token_times": list(r.token_times),
            })
        # every request of the run (ramp too): the kernels' work and the
        # queue are made of all of them
        every = [{"prompt_len": len(q["prompt"]),
                  "arrival_s": q["arrival_s"],
                  "first_token_s": r.first_token_s,
                  "token_times": list(r.token_times)}
                 for q, r in zip(feed["requests"], results)]
        late = max((r.admit_s or end) - q["arrival_s"] for q, r in due) \
            if due else 0.0
        return {
            "window_s": (traced["t1"] - traced["t0"]) if trace_dir
            else seconds,
            "span": (traced["t0"], traced["t1"]) if trace_dir
            else (ramp, end),
            "setup_extra_s": ramp, "attempted": len(due), "failed": failed,
            "requests": rows, "every_request": every,
            "stats": stats, "counters": {},
            "drain_s": stats["duration_s"] - end,
            "notes": {"queue_wait_max_s": late,
                      "drain_s": stats["duration_s"] - end},
        }

    def end_to_end(self, rec: dict) -> dict:
        from benchmarks.readers.request_percentile import values
        # a request that failed misses every limit: it stands in the tail
        # with the whole run's length
        miss = [rec["stats"]["duration_s"] * 1e3] * rec["failed"]
        return {f"{what}_p95_ms": common.percentile(
            values(rec["requests"], what) + miss, 95)
            for what in ("ttft", "tpot")}

    def release(self):
        self.engine = None

    # -- the plain reference ----------------------------------------------
    def sample(self, rec: dict) -> list:
        """Finished requests drawn from the seed, the longest among them."""
        done = [r for r in rec["requests"] if r["ok"]]
        if not done:
            return []
        n = min(self.ctx.traffic["sample"], len(done))
        longest = max(done, key=lambda r: r["prompt_len"] + len(r["tokens"]))
        rng = np.random.default_rng(self.ctx.seed)
        rest = [r for r in done if r is not longest]
        pick = [rest[i] for i in rng.permutation(len(rest))[:n - 1]]
        return [longest] + pick

    def reference_gaps(self, sampled: list, control: bool = False) -> dict:
        """The widest gap by which a served token's logit lies below the
        reference's best, over every served token of ``sampled``; with
        ``control``, also that of the tokens the lower precision puts
        first at the same positions."""
        ref = plugin("reference", self.ctx.config["reference"])
        return ref.served_gaps(
            self.ctx.config, self.specs, W.seed_key(self.ctx.seed),
            [(np.asarray(r["prompt"]), np.asarray(r["tokens"]))
             for r in sampled],
            pad_to=self.ctx.traffic["engine"]["max_len"],
            control="fp8" if control else None)

    def check(self, rec: dict) -> list:
        sampled = self.sample(rec)
        out = [{"name": "requests_failed", "value": rec["failed"],
                "limit": self.ctx.limits["requests_failed"]}]
        if sampled:
            g = self.reference_gaps(sampled)
            self.ctx.say(sampled_requests=len(sampled),
                         sampled_tokens=g["tokens"])
            out.append({"name": "served_logit_gap", "value": g["served"],
                        "limit": self.ctx.limits["served_logit_gap"]})
        return out

    def calibrate(self, seed: int, control: bool) -> dict:
        """One seed's numbers as ``check`` compares them, from a short
        window at the cell's own load, and with ``control`` the gap of
        the tokens the reference in fp8 puts first."""
        if seed != self.ctx.seed:
            self.reseed(seed)
        rec = self.window(self.ctx.traffic["calibrate_seconds"])
        g = self.reference_gaps(self.sample(rec), control=control)
        out = {"program": {"requests_failed": rec["failed"],
                           "served_logit_gap": g["served"]},
               "sampled_tokens": g["tokens"], "due": rec["attempted"],
               "drain_s": rec["drain_s"], **self.end_to_end(rec)}
        if control:
            out["control"] = {"served_logit_gap": g["control"]}
        return out
