"""Driver: SDAR-30B-A3B-Chat's block-diffusion training step (every
sequence twice through the layers, its noised copy beside its clean one,
grouped-query attention under the block-diffusion mask, a masked loss
weighted by 1 / p, a softmax router with no shared expert), built as the
hybrid LM's is (``train_hybrid_lm.Driver``, whose checks and counters it
needs as they are: ``HybridLM`` through ``tools/lm_bench.build_train_step``,
bf16 over one flat fp32 master, FusedAdam, one chip plain jit; a softmax
router has no state beside the master).

What differs: the model's keys, the weights' specs, the reference (which
takes its weights from the host, as Kimi-VL's does), **the batch, a triple
``(tokens, masked, p)``** that rides through the step builder as one
pytree argument where the other cells' is one array, two counters of the
loss (``diffusion_masked_tokens``, the positions that carried loss, a
step's mean; ``diffusion_weight_max``, the run's largest ``1 / p``) and
what the result line states of the program: the flash grids'
``block_census`` under the block-diffusion mask, the forward's blocks and
the backward's, and the routers' loads step by step beside each step's
``p`` (the masked rows enter layer 0 as one embedding row and route
alike).

``correct`` also compares a forward reading, as ``vectors`` (the training
drivers' ``forward_stat_gap``): **what layer 0's heads made for the first
``PROBE_ROWS`` noised rows, before ``W_o``** (the step's counter
``diffusion_probe``, kept from the seed's first step alone). The masked
rows reach a router with nearly one vector, so a rounding that tips one of
them between its eighth and ninth expert tips hundreds, and every
gradient's norm reads the tipped rows, in the program and in the fp8
control alike (the three norms' limits stand at three times their sound
maxima, against a causal mask, a loss without its ``1 / p`` and a state
left unchanged); layer 0's attention is made before any router has read
anything, and reads the arithmetic, the block length and the positions
(PERF.md section 6, PR 50).
"""

from __future__ import annotations

from benchmarks import weights as W, weights_sdar
from benchmarks.drivers import train_hybrid_lm
from benchmarks.training import TrainDriver, same_tree


class Driver(train_hybrid_lm.Driver):
    def __init__(self, ctx):
        # not train_hybrid_lm.Driver's own: that one reads Qwen3-Next's specs
        TrainDriver.__init__(self, ctx)
        self.kept = {}
        self.specs = weights_sdar.specs(ctx.config)
        self.reference = ctx.plugin("reference", ctx.config["reference"])
        self.beta1 = self.reference.ADAM["beta1"]

    def new_feed(self, seed: int):
        """The feed's ``x`` as the batches the step takes: triples."""
        super().new_feed(seed)
        self.feed["x"] = list(zip(self.feed["x"], self.feed["masked"],
                                  self.feed["p"]))

    def model(self):
        import jax
        import jax.numpy as jnp

        from apex_tpu.models.hybrid_lm import HybridLM

        cfg, prog = self.ctx.config, self.ctx.config["program"]
        assert cfg["rope_scaling"] is None and not cfg["use_sliding_window"] \
            and not cfg["mlp_only_layers"] and cfg["norm_topk_prob"] \
            and cfg["decoder_sparse_step"] == 1 \
            and not cfg["tie_word_embeddings"], cfg
        lm = HybridLM(
            vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
            layer_types=tuple(self.reference.layer_kinds(cfg)),
            block_diffusion=cfg["block_length"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], rotary_dim=cfg["head_dim"],
            attn_gate=False, rope_theta=float(cfg["rope_theta"]),
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
        same_tree(mine, shapes, "SDAR")
        return lm, mine

    def setup(self):
        super().setup()
        self.ctx.say(block_census=self.census())

    def census(self) -> dict:
        """Facts of the program: the flash grids' blocks by kind (dead,
        interior, edge) for a batch-head under the block-diffusion mask,
        the forward's blocks and the backward's."""
        import importlib
        fa = importlib.import_module(
            "apex_tpu.contrib.multihead_attn.flash_attention")
        cfg = self.ctx.config
        length, out = cfg["input"]["seq"], {}
        fq, fk, bq, bk = fa.block_sizes(2 * length, 2 * length)
        for which, (q, k) in (("forward", (fq, fk)), ("backward", (bq, bk))):
            out[which] = {"blocks": [q, k], **fa.block_census(
                2 * length, 2 * length, q, k, False,
                block_diffusion=(cfg["block_length"], length))}
        return out

    def advance(self, state, i: int):
        """The step's probe rows stay with the seed's first step alone."""
        state, loss = super().advance(state, i)
        probe = self.seen[-1].pop("diffusion_probe")
        if len(self.seen) == 1:
            self.first_probe = probe
        return state, loss

    def first_step_extras(self, state) -> dict:
        import numpy as np
        return {"vectors": [np.asarray(self.first_probe,
                                       np.float64).ravel()]}

    def counters(self) -> dict:
        import jax
        seen = jax.device_get(self.seen)
        # the routers step by step beside the step's masking probability
        # (row 0's): the masked rows route alike in layer 0
        p = [float(b[2][0]) for b in self.feed["x"]]
        self.ctx.say(routers_by_step={
            "p": [round(p[i % len(p)], 4) for i in range(len(seen))],
            "moe_held_pairs_max": [int(c["moe_held_pairs_max"])
                                   for c in seen],
            "expert_load_max_over_mean": [round(float(
                c["expert_load_max_over_mean"]), 2) for c in seen]})
        return {**super().counters(),
                "diffusion_masked_tokens": float(sum(
                    c["diffusion_masked_tokens"] for c in seen)) / len(seen),
                "diffusion_weight_max": float(max(
                    c["diffusion_weight_max"] for c in seen))}

    def _reference_readings(self, precision: str) -> dict:
        """As the hybrid driver's, with the seed's weights handed over on
        the host (beside the reference's own copy, Adam's ``m`` and ``v``
        and the gradient, a second copy on the device does not fit) and
        the batches as triples."""
        import jax
        import jax.numpy as jnp
        params = jax.device_get(jax.jit(
            lambda k: W.build(self.specs, k, jnp.float32))(
                W.seed_key(self.ctx.seed)))
        first = [tuple(jnp.asarray(a) for a in b)
                 for b in self.feed["x"][:self.n_checked]]
        return self.reference.train_steps(
            params, first, self.ctx.config, precision,
            lr=self.ctx.traffic["lr"])
