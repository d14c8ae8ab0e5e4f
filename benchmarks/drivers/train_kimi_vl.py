"""Driver: the training step of Kimi-VL-A3B's language model (latent
attention, a leading dense layer, a sigmoid router balanced by a bias),
built as the hybrid LM's is (``train_hybrid_lm.Driver``: ``HybridLM``
through ``tools/lm_bench.build_train_step``, bf16 over one flat fp32
master, FusedAdam, one chip plain jit).

What differs: the model's keys, the weights' specs, the reference, and
the routers' selection biases: state that no gradient reaches, so the
step's state is ``(optimizer state, biases)`` and a new seed starts them
at zero. ``correct`` also compares the first step's pairs an expert, a
vector an expert layer (``training.gaps``' ``forward_stat_gap`` /
``forward_stat_mid_gap``: a bias compared sign by sign would flip on
rounding for an expert at the mean load, the counts do not), and the
biases after the checked steps against the reference's
(``router_bias_gap``, their distance in the reference's norm: biases left
where they were read 1). The counters gain ``router_bias_abs_max``, the
largest bias of any layer after the window.
"""

from __future__ import annotations

import numpy as np

from benchmarks import weights as W, weights_kimi_vl
from benchmarks.drivers import train_hybrid_lm
from benchmarks.training import TrainDriver, fresh_state, same_tree


def bias_gap(got, ref) -> float:
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


class Driver(train_hybrid_lm.Driver):
    def __init__(self, ctx):
        # not train_hybrid_lm.Driver's own: that one reads Qwen3-Next's specs
        TrainDriver.__init__(self, ctx)
        self.kept = {}
        self.specs = weights_kimi_vl.specs(ctx.config)
        self.reference = ctx.plugin("reference", ctx.config["reference"])
        self.beta1 = self.reference.ADAM["beta1"]

    def model(self):
        import jax
        import jax.numpy as jnp

        from apex_tpu.models.hybrid_lm import HybridLM

        cfg, prog = self.ctx.config, self.ctx.config["program"]
        layers = cfg["num_hidden_layers"]
        lm = HybridLM(
            vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
            layer_types=("latent",) * layers,
            ffn_types=tuple(self.reference.ffn_kinds(cfg)),
            num_heads=cfg["num_attention_heads"],
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_dim=cfg["qk_nope_head_dim"],
            qk_rope_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
            rope_theta=float(cfg["rope_theta"]),
            num_experts=self.reference.width(cfg),
            top_k=cfg["num_experts_per_tok"],
            expert_ffn=cfg["moe_intermediate_size"],
            shared_ffn=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            experts_held=self.reference.held(cfg),
            dispatch_bound=prog["dispatch_bound"],
            router=cfg["scoring_func"],
            routed_scale=cfg["routed_scaling_factor"],
            bias_rate=cfg["bias_update_speed"],
            dense_ffn=cfg["intermediate_size"],
            aux_coef=cfg["aux_loss_alpha"], rms_eps=cfg["rms_norm_eps"],
            zero_centred_norm=False, attn_impl=prog["attn_impl"],
            head_chunk=prog["head_chunk"],
            remat=prog["remat"].startswith("block"))
        shapes = jax.eval_shape(lm.init, jax.random.key(0))
        mine = jax.eval_shape(lambda: W.build(self.specs, W.seed_key(0),
                                              jnp.float32))
        same_tree(mine, shapes, "Kimi-VL")
        return lm, mine

    def make_state(self, opt, seed_key):
        import jax.numpy as jnp
        lm, _ = self.model()
        return (fresh_state(opt, W.build(self.specs, seed_key, jnp.float32)),
                lm.router_state())

    def group(self, state):
        return state[0][0]

    def advance(self, state, i: int):
        state, loss = super().advance(state, i)
        if i == self.n_checked - 1:     # a copy: the next step donates it
            self.readings_bias = np.asarray(state[1])
        return state, loss

    def first_step_extras(self, state) -> dict:
        return {"vectors": list(np.asarray(self.seen[0]["expert_pairs"],
                                           np.float64))}

    def counters(self) -> dict:
        import jax

        from benchmarks import common
        # the allocator's own peak, set-up and window: the result's
        # memory_peak_bytes is the larger of it and the step's footprint
        self.ctx.say(allocator_peak_bytes=common.peak_bytes(
            self.ctx.devices))
        return {**super().counters(), "router_bias_abs_max": float(
            jax.device_get(self.seen[-1]["router_bias_abs_max"]))}

    def check(self, rec: dict) -> list:
        return super().check(rec) + [{
            "name": "router_bias_gap",
            "limit": self.ctx.limits["router_bias_gap"],
            "value": bias_gap(self.readings_bias,
                              self.reference_readings()["router_biases"])}]

    def _reference_readings(self, precision: str) -> dict:
        """As the hybrid driver's, with the seed's weights handed over on
        the host: beside the reference's own copy, Adam's ``m`` and ``v``
        and the gradient, a second copy on the device does not fit."""
        import jax
        import jax.numpy as jnp
        params = jax.device_get(jax.jit(
            lambda k: W.build(self.specs, k, jnp.float32))(
                W.seed_key(self.ctx.seed)))
        first = [jnp.asarray(b) for b in self.feed["x"][:self.n_checked]]
        return self.reference.train_steps(
            params, first, self.ctx.config, precision,
            lr=self.ctx.traffic["lr"])

    def calibrate(self, seed: int, control: bool) -> dict:
        out = super().calibrate(seed, control)
        ref = self.reference_readings()["router_biases"]
        out["program"]["router_bias_gap"] = bias_gap(self.readings_bias, ref)
        if control:
            out["control"]["router_bias_gap"] = bias_gap(
                self.reference_readings("fp8")["router_biases"], ref)
        return out
