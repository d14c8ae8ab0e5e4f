"""Driver: the training step of Kimi-Linear-48B-A3B's block (Kimi Delta
Attention layers 3:1 with latent attention without positions, a leading
dense layer, a sigmoid router balanced by a bias), built as Kimi-VL's is
(``train_kimi_vl.Driver``, whose state beside the master, checks and
counters it needs as they are: ``HybridLM`` through
``tools/lm_bench.build_train_step``, bf16 over one flat fp32 master,
FusedAdam, one chip plain jit).

What differs: the model's keys (the kinds of the cut's layers come from
``linear_attn_config``'s published lists), the weights' specs, the
reference, and one more counter from the mixers:
``kda_chunk_decay_nats_max``, how far the fastest channel of any Kimi
Delta Attention layer decays inside one chunk, the window's largest. The
result line states the first checked step's under ``checks``, beside the
limits file's bound: what a channel that decays evenly may lose over a
chunk before a 16-token sub-block of the chunked form leaves float32's
range (``ops/gated_delta_rule.py``). Past float32's largest exponent
(88.7 nats) a form that divided by ``exp(G)`` over a whole chunk would be
wrong; the number says how near this cell stands.
"""

from __future__ import annotations

from benchmarks import weights as W, weights_kimi_linear
from benchmarks.drivers import train_kimi_vl
from benchmarks.training import TrainDriver, same_tree


class Driver(train_kimi_vl.Driver):
    def __init__(self, ctx):
        # not train_kimi_vl.Driver's own: that one reads Kimi-VL's specs
        TrainDriver.__init__(self, ctx)
        self.kept = {}
        self.specs = weights_kimi_linear.specs(ctx.config)
        self.reference = ctx.plugin("reference", ctx.config["reference"])
        self.beta1 = self.reference.ADAM["beta1"]

    def model(self):
        import jax
        import jax.numpy as jnp

        from apex_tpu.models.hybrid_lm import HybridLM

        cfg, prog = self.ctx.config, self.ctx.config["program"]
        lin = cfg["linear_attn_config"]
        assert cfg["mla_use_nope"] and cfg["q_lora_rank"] is None \
            and cfg["moe_router_activation_func"] == "sigmoid" \
            and cfg["moe_renormalize"] and cfg["num_expert_group"] == 1 \
            and cfg["moe_layer_freq"] == 1 \
            and cfg["num_nextn_predict_layers"] == 0, cfg
        lm = HybridLM(
            vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
            layer_types=tuple(weights_kimi_linear.layer_kinds(cfg)),
            ffn_types=tuple(self.reference.ffn_kinds(cfg)),
            kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
            conv_kernel=lin["short_conv_kernel_size"],
            delta_chunk=prog["delta_chunk"],
            num_heads=cfg["num_attention_heads"],
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_dim=cfg["qk_nope_head_dim"],
            qk_rope_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"], latent_rotary=False,
            num_experts=self.reference.width(cfg),
            top_k=cfg["num_experts_per_token"],
            expert_ffn=cfg["moe_intermediate_size"],
            shared_ffn=cfg["num_shared_experts"]
            * cfg["moe_intermediate_size"],
            experts_held=self.reference.held(cfg),
            dispatch_bound=prog["dispatch_bound"],
            router=cfg["moe_router_activation_func"],
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
        same_tree(mine, shapes, "Kimi-Linear")
        return lm, mine

    def counters(self) -> dict:
        import jax
        seen = jax.device_get(self.seen)
        # how the routers move through the run: every eighth step's
        # fullest layer (the dispatch bound is sized from these)
        self.ctx.say(routers_every_8th_step={
            "moe_held_pairs_max": [int(c["moe_held_pairs_max"])
                                   for c in seen[::8]],
            "expert_load_max_over_mean": [round(float(
                c["expert_load_max_over_mean"]), 2) for c in seen[::8]]})
        nats = [float(c["kda_chunk_decay_nats_max"]) for c in seen]
        return {**super().counters(),
                "kda_chunk_decay_nats_max": max(nats),
                "kda_chunk_decay_nats_first": nats[0]}

    def check(self, rec: dict) -> list:
        return super().check(rec) + [{
            "name": "kda_chunk_decay_nats_max",
            "limit": self.ctx.limits["kda_chunk_decay_nats_max"],
            "value": rec["counters"]["kda_chunk_decay_nats_first"]}]
