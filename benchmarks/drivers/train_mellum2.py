"""Driver: the training step of Mellum2-12B-A2.5B's block (sliding-window
grouped-query attention layers 3:1 with full-attention layers under YaRN,
a softmax router with no shared expert), built as the hybrid LM's is
(``train_hybrid_lm.Driver``, whose checks and counters it needs as they
are: ``HybridLM`` through ``tools/lm_bench.build_train_step``, bf16 over
one flat fp32 master, FusedAdam, one chip plain jit; a softmax router has
no state beside the master).

What differs: the model's keys, the weights' specs, the reference (which
takes its weights from the host, as Kimi-VL's does: beside its own copy,
Adam's ``m`` and ``v`` and the gradient, a second copy on the device does
not fit), and what the result line states of the program: the flash
kernels' ``block_census`` of a window layer and of the full layer, for
the forward's and the backward's blocks.
"""

from __future__ import annotations

from benchmarks import weights as W, weights_mellum2
from benchmarks.drivers import train_hybrid_lm, train_kimi_vl
from benchmarks.training import TrainDriver, same_tree


class Driver(train_hybrid_lm.Driver):
    def __init__(self, ctx):
        # not train_hybrid_lm.Driver's own: that one reads Qwen3-Next's specs
        TrainDriver.__init__(self, ctx)
        self.kept = {}
        self.specs = weights_mellum2.specs(ctx.config)
        self.reference = ctx.plugin("reference", ctx.config["reference"])
        self.beta1 = self.reference.ADAM["beta1"]

    def model(self):
        import jax
        import jax.numpy as jnp

        from apex_tpu.models.hybrid_lm import HybridLM, Yarn

        cfg, prog = self.ctx.config, self.ctx.config["program"]
        rope = cfg["rope_parameters"]
        full, sliding = rope["full_attention"], rope["sliding_attention"]
        assert sliding["rope_type"] == "default" \
            and full["rope_type"] == "yarn" \
            and sliding["rope_theta"] == full["rope_theta"], rope
        lm = HybridLM(
            vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
            layer_types=tuple(self.reference.layer_kinds(cfg)),
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], rotary_dim=cfg["head_dim"],
            attn_gate=False, window=cfg["sliding_window"],
            rope_theta=float(full["rope_theta"]),
            rope_yarn=Yarn(
                factor=float(full["factor"]),
                positions=full["original_max_position_embeddings"],
                beta_fast=float(full["beta_fast"]),
                beta_slow=float(full["beta_slow"]),
                attention_factor=float(full["attention_factor"])),
            num_experts=self.reference.width(cfg),
            top_k=cfg["num_experts_per_tok"],
            expert_ffn=cfg["moe_intermediate_size"], shared_ffn=0,
            experts_held=self.reference.held(cfg),
            dispatch_bound=prog["dispatch_bound"], router="softmax",
            aux_coef=cfg["router_aux_loss_coef"],
            rms_eps=cfg["rms_norm_eps"], zero_centred_norm=False,
            attn_impl=prog["attn_impl"], head_chunk=prog["head_chunk"],
            remat=prog["remat"].startswith("block"))
        shapes = jax.eval_shape(lm.init, jax.random.key(0))
        mine = jax.eval_shape(lambda: W.build(self.specs, W.seed_key(0),
                                              jnp.float32))
        same_tree(mine, shapes, "Mellum2")
        return lm, mine

    def setup(self):
        super().setup()
        self.ctx.say(block_census=self.census())

    def census(self) -> dict:
        """Facts of the program: the flash grids' blocks by kind (dead,
        interior, edge) for a batch-head of a window layer and of the full
        layer, the forward's blocks and the backward's."""
        import importlib
        fa = importlib.import_module(
            "apex_tpu.contrib.multihead_attn.flash_attention")
        cfg = self.ctx.config
        s, out = cfg["input"]["seq"], {}
        for kind, window in (("window", cfg["sliding_window"]),
                             ("full", None)):
            fq, fk, bq, bk = fa.block_sizes(s, s, window=window,
                                            d=cfg["head_dim"])
            for which, (q, k) in (("forward", (fq, fk)),
                                  ("backward", (bq, bk))):
                out[f"{kind}_{which}"] = {
                    "blocks": [q, k], **fa.block_census(
                        s, s, q, k, True, window=window)}
        return out

    # the seed's weights handed over on the host
    _reference_readings = train_kimi_vl.Driver._reference_readings
