"""Driver: the training step of LFM2-24B-A2B's block (double-gated
short-convolution mixers 3:1 with ungated grouped-query attention, a
leading dense layer, a sigmoid router balanced by a bias alone, a tied
head), built as Kimi-VL's is (``train_kimi_vl.Driver``, whose bias state
beside the master and whose checks it needs: ``HybridLM`` through
``tools/lm_bench.build_train_step``, bf16 over one flat fp32 master,
FusedAdam, one chip plain jit).

What differs: the model's keys, the weights' specs, the reference, and
**the leaves whose gradient is zero in the reference**. A share holds the
tokens' weights constant in the backward and this model has no auxiliary
loss, so the routers' matrices have no gradient at all, on either side.
Such a leaf must read zero in the program too (``zero_grad_leaf_norm``,
the largest norm the program's first gradient has on those leaves, held
at the limit 0), and is then left out of the ratios: out of the worst and
the median leaf's gap in the gradient's and the update's norm, where a
norm of zero on both sides would read as a perfect leaf and move the
median that the small leaves are held against.
"""

from __future__ import annotations

from benchmarks import weights as W, weights_lfm2
from benchmarks.drivers import train_kimi_vl
from benchmarks.training import TrainDriver, same_tree


def by_path(tree: dict) -> dict:
    """A tree of norms (nested dicts of floats) as one dict, a leaf a
    path: what ``training.gaps`` and ``mid_gap`` take as well, and a leaf
    can be left out of."""
    import jax
    return {jax.tree_util.keystr(p): x
            for p, x in jax.tree_util.tree_leaves_with_path(tree)}


class Driver(train_kimi_vl.Driver):
    def __init__(self, ctx):
        # not train_kimi_vl.Driver's own: that one reads Kimi-VL's specs
        TrainDriver.__init__(self, ctx)
        self.kept = {}
        self.specs = weights_lfm2.specs(ctx.config)
        self.reference = ctx.plugin("reference", ctx.config["reference"])
        self.beta1 = self.reference.ADAM["beta1"]

    def model(self):
        import jax
        import jax.numpy as jnp

        from apex_tpu.models.hybrid_lm import HybridLM

        cfg, prog = self.ctx.config, self.ctx.config["program"]
        mixers, ffns = zip(*self.reference.layer_kinds(cfg))
        hd = self.reference.head_dim(cfg)
        lm = HybridLM(
            vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
            layer_types=mixers, ffn_types=ffns,
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=hd, rotary_dim=hd, attn_gate=False,
            rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
            conv_kernel=cfg["conv_L_cache"],
            num_experts=self.reference.width(cfg),
            top_k=cfg["num_experts_per_tok"],
            expert_ffn=cfg["moe_intermediate_size"], shared_ffn=0,
            experts_held=self.reference.held(cfg),
            dispatch_bound=prog["dispatch_bound"], router="sigmoid",
            routed_scale=cfg["routed_scaling_factor"],
            bias_rate=cfg["bias_update_speed"],
            dense_ffn=cfg["intermediate_size"], aux_coef=0.0,
            rms_eps=cfg["norm_eps"], zero_centred_norm=False,
            tied_head=cfg["tie_embedding"], attn_impl=prog["attn_impl"],
            head_chunk=prog["head_chunk"],
            remat=prog["remat"].startswith("block"))
        shapes = jax.eval_shape(lm.init, jax.random.key(0))
        mine = jax.eval_shape(lambda: W.build(self.specs, W.seed_key(0),
                                              jnp.float32))
        same_tree(mine, shapes, "LFM2")
        return lm, mine

    def _reference_readings(self, precision: str) -> dict:
        """As Kimi-VL's, less the leaves whose gradient is zero in the
        float32 reference (``check`` and ``calibrate`` read that one
        first), which leave the program's readings with them; what the
        program's first gradient read there is kept for
        ``zero_grad_leaf_norm``."""
        ref = super()._reference_readings(precision)
        sides = [ref]
        if precision == "float32":
            grads = by_path(self.readings["grad_norms"])
            self.zero = sorted(k for k, x in by_path(
                ref["grad_norms"]).items() if x == 0.0)
            self.zero_read = max((grads[k] for k in self.zero), default=0.0)
            sides.append(self.readings)
        for side in sides:
            for which in ("grad_norms", "delta_norms"):
                side[which] = {k: x for k, x in by_path(side[which]).items()
                               if k not in self.zero}
        return ref

    def check(self, rec: dict) -> list:
        return super().check(rec) + [{
            "name": "zero_grad_leaf_norm",
            "limit": self.ctx.limits["zero_grad_leaf_norm"],
            "value": self.zero_read}]

    def calibrate(self, seed: int, control: bool) -> dict:
        out = super().calibrate(seed, control)
        out["program"]["zero_grad_leaf_norm"] = self.zero_read
        out["zero_grad_leaves"] = self.zero
        return out
