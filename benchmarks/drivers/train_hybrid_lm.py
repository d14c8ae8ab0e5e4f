"""Driver: the hybrid linear-attention mixture-of-experts LM's training
step, built as the dense LM's is (``train_lm.Driver``:
``tools/lm_bench.build_train_step``, bf16 over one flat fp32 master,
FusedAdam, one chip plain jit).

What differs: the model (``apex_tpu.models.hybrid_lm.HybridLM`` from the
source's own keys), the weights' specs, the reference, and the step's
counters: the model hands ``moe_overflow_pairs``, ``moe_held_pairs_max``
and ``expert_load_max_over_mean`` out beside the loss, the driver keeps
them for every step since the seed's first and holds the first at 0.
``correct`` also compares the **median** leaf's gap in the first
gradient's norm (``grad_norm_mid_gap``): the worst leaf's is one of the
few small leaves that sum rounding noise over 16,384 tokens and swings
five-fold by seed, the median leaf's does not.
"""

from __future__ import annotations

import statistics

from benchmarks import weights as W, weights_qwen3_next
from benchmarks.drivers import train_lm
from benchmarks.training import TrainDriver, same_tree


def mid_gap(got: dict, ref: dict) -> float:
    """The median over the leaves of what ``training.gaps`` takes the
    worst of: the gap between the two norms of a leaf's first gradient,
    against the reference's norm of that leaf or of the median leaf."""
    import jax
    a, b = (jax.tree.leaves(x["grad_norms"]) for x in (got, ref))
    floor = statistics.median(b)
    return statistics.median(abs(x - y) / max(y, floor)
                             for x, y in zip(a, b))


class Driver(train_lm.Driver):
    def __init__(self, ctx):
        # not train_lm.Driver's own: that one reads GPT-2's keys
        TrainDriver.__init__(self, ctx)
        self.kept = {}
        self.specs = weights_qwen3_next.specs(ctx.config)
        self.reference = ctx.plugin("reference", ctx.config["reference"])
        self.beta1 = self.reference.ADAM["beta1"]

    def model(self):
        import jax
        import jax.numpy as jnp

        from apex_tpu.models.hybrid_lm import HybridLM

        cfg, prog = self.ctx.config, self.ctx.config["program"]
        lm = HybridLM(
            vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
            layer_types=tuple(self.reference.layer_kinds(cfg)),
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"],
            rotary_dim=int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
            rope_theta=float(cfg["rope_theta"]),
            linear_k_heads=cfg["linear_num_key_heads"],
            linear_v_heads=cfg["linear_num_value_heads"],
            linear_k_dim=cfg["linear_key_head_dim"],
            linear_v_dim=cfg["linear_value_head_dim"],
            conv_kernel=cfg["linear_conv_kernel_dim"],
            delta_chunk=prog["delta_chunk"],
            num_experts=cfg["num_experts"] * cfg["expert_chips"],
            top_k=cfg["num_experts_per_tok"],
            expert_ffn=cfg["moe_intermediate_size"],
            shared_ffn=cfg["shared_expert_intermediate_size"],
            experts_held=self.reference.held(cfg),
            dispatch_bound=prog["dispatch_bound"],
            aux_coef=cfg["router_aux_loss_coef"],
            rms_eps=cfg["rms_norm_eps"], attn_impl=prog["attn_impl"],
            head_chunk=prog["head_chunk"],
            remat=prog["remat"].startswith("block"))
        shapes = jax.eval_shape(lm.init, jax.random.key(0))
        mine = jax.eval_shape(lambda: W.build(self.specs, W.seed_key(0),
                                              jnp.float32))
        same_tree(mine, shapes, "Qwen3-Next")
        return lm, mine

    def first_steps(self, state):
        self.seen = []
        super().first_steps(state)

    def advance(self, state, i: int):
        state, (loss, counters) = self.step(
            state, self.batches[i % len(self.batches)])
        self.seen.append(counters)
        return state, loss

    def counters(self) -> dict:
        import jax
        seen = jax.device_get(self.seen)
        return {"moe_overflow_pairs": int(sum(
                    c["moe_overflow_pairs"] for c in seen)),
                "moe_held_pairs_max": int(max(
                    c["moe_held_pairs_max"] for c in seen)),
                "expert_load_max_over_mean": float(max(
                    c["expert_load_max_over_mean"] for c in seen))}

    def check(self, rec: dict) -> list:
        return super().check(rec) + [{
            "name": "grad_norm_mid_gap",
            "limit": self.ctx.limits["grad_norm_mid_gap"],
            "value": mid_gap(self.readings, self.reference_readings())}, {
            "name": "moe_overflow_pairs", "limit": 0,
            "value": rec["counters"]["moe_overflow_pairs"]}]

    def calibrate(self, seed: int, control: bool) -> dict:
        out = super().calibrate(seed, control)
        ref = self.reference_readings()
        out["program"]["grad_norm_mid_gap"] = mid_gap(self.readings, ref)
        if control:
            out["control"]["grad_norm_mid_gap"] = mid_gap(
                self.reference_readings("fp8"), ref)
        out["counters"] = self.counters()
        return out

    def reference_readings(self, precision: str = "float32") -> dict:
        """The reference's readings for the seed at hand, kept while the
        seed stays: ``check`` and ``calibrate`` read them twice."""
        if self.kept.get("seed") != self.ctx.seed:
            self.kept = {"seed": self.ctx.seed}
        if precision not in self.kept:
            self.kept[precision] = self._reference_readings(precision)
        return self.kept[precision]

    def _reference_readings(self, precision: str) -> dict:
        import jax
        import jax.numpy as jnp
        params = jax.jit(lambda k: W.build(self.specs, k, jnp.float32))(
            W.seed_key(self.ctx.seed))
        first = [jnp.asarray(b) for b in self.feed["x"][:self.n_checked]]
        return self.reference.train_steps(
            params, first, self.ctx.config, precision,
            lr=self.ctx.traffic["lr"])
