"""Driver: the training step of Keye-VL-2.0-30B-A3B's language block
(grouped-query attention over a learned per-query key set, a lightning
indexer with a loss of its own, a softmax router with no shared expert),
built as the hybrid LM's is (``train_hybrid_lm.Driver``, whose checks and
counters it needs as they are: ``HybridLM`` through
``tools/lm_bench.build_train_step``, bf16 over one flat fp32 master,
FusedAdam, one chip plain jit).

What differs: the model's keys, the weights' specs, the reference (which
takes its weights from the host, as Kimi-VL's does), the step's counters
(``index_loss``, ``select_pairs``, ``select_live_tile_pct`` beside the
expert layer's), and three more numbers that ``correct`` compares:

- ``select_pairs_off``: every step of the run selected exactly ``layers x
  rows x sum_t min(t + 1, topk)`` pairs (held at 0);
- ``select_disagreement``: 1 - ``select_agreement``, the share of the
  reference's selected pairs of layer 0, row 0, first step, that the
  program's selection lacks: bf16 products move the marginal keys, a
  wrong indexer or a wrong count moves many. **It is a side program's
  number, not the timed step's**: the step hands no key set out, so
  ``side_selection`` jits ``HybridLM.first_selection`` (the methods
  layer 0 of the step runs, at the step's size) on the seed's weights
  built again in bf16, after the window, outside ``setup_s`` and the
  rate. What holds the timed step's own selection is ``select_pairs_off``
  (its count, every step) and ``index_loss_gap`` (a loss over its set);
- ``index_loss_gap``: the checked steps' worst gap between the program's
  ``index_loss`` and the reference's, against the reference's.

The result line also states the selected-key flash kernels' grids
(``block_census``) and the first and last checked step's ``index_loss``.
"""

from __future__ import annotations

from benchmarks import weights as W, weights_keye_vl
from benchmarks.drivers import train_hybrid_lm, train_kimi_vl
from benchmarks.training import TrainDriver, same_tree


class Driver(train_hybrid_lm.Driver):
    def __init__(self, ctx):
        # not train_hybrid_lm.Driver's own: that one reads Qwen3-Next's specs
        TrainDriver.__init__(self, ctx)
        self.kept = {}
        self.specs = weights_keye_vl.specs(ctx.config)
        self.reference = ctx.plugin("reference", ctx.config["reference"])
        self.beta1 = self.reference.ADAM["beta1"]

    def model(self):
        import jax
        import jax.numpy as jnp

        from apex_tpu.models.hybrid_lm import HybridLM

        cfg, prog, sa = (self.ctx.config, self.ctx.config["program"],
                         self.ctx.config["sa_config"])
        assert cfg["rope_scaling"]["rope_type"] == "default" \
            and sa["indexer_num_kv_heads"] == 1 \
            and not cfg["mlp_only_layers"] \
            and cfg["decoder_sparse_step"] == 1, cfg
        lm = HybridLM(
            vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
            layer_types=tuple(self.reference.layer_kinds(cfg)),
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], rotary_dim=cfg["head_dim"],
            attn_gate=False, rope_theta=float(cfg["rope_theta"]),
            index_heads=sa["indexer_num_heads"],
            index_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
            index_coef=cfg["indexer_loss_coef"],
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
        same_tree(mine, shapes, "Keye-VL")
        return lm, mine

    def expected_pairs(self) -> int:
        """The pairs a step selects: layers x rows x sum_t min(t + 1,
        topk)."""
        cfg = self.ctx.config
        return cfg["num_hidden_layers"] * self.feed["x"].shape[1] \
            * self.reference.selected_pairs(cfg["input"]["seq"],
                                            cfg["sa_config"]["topk"])

    def setup(self):
        super().setup()
        self.ctx.say(block_census=self.census(),
                     select_pairs_a_step=self.expected_pairs())

    def census(self) -> dict:
        """Facts of the program: the selected-key flash kernels' grids,
        the forward's blocks and the backward's, by kind (dead, interior,
        edge) for a batch-head: the causal grids, since which live tiles
        hold a selected key is known only on the device
        (``select_live_tile_pct``)."""
        import importlib
        fa = importlib.import_module(
            "apex_tpu.contrib.multihead_attn.flash_attention")
        s = self.ctx.config["input"]["seq"]
        fq, fk, bq, bk = fa.block_sizes(s, s, d=self.ctx.config["head_dim"])
        return {which: {"blocks": [q, k],
                        **fa.block_census(s, s, q, k, True)}
                for which, (q, k) in (("forward", (fq, fk)),
                                      ("backward", (bq, bk)))}

    def first_steps(self, state):
        self.first_select = None        # this seed's: made when compared
        super().first_steps(state)

    def side_selection(self):
        """Layer 0's packed key sets for row 0 of the first batch on the
        seed's weights, from a program of its own (see the module's
        text)."""
        import jax
        import jax.numpy as jnp
        lm, _ = self.model()
        # the leaves layer 0's indexer reads, as the step casts them: a
        # leaf's values go by the seed and its path (weights.build)
        sub = {"embed": self.specs["embed"], "layer_0": {
            k: self.specs["layer_0"][k] for k in ("norm1", "index")}}
        return jax.jit(lambda key, toks: lm.first_selection(
            W.build(sub, key, jnp.bfloat16), toks))(
                W.seed_key(self.ctx.seed), self.feed["x"][0][:1, :-1])

    def counters(self) -> dict:
        import jax
        seen = jax.device_get(self.seen)
        # how the routers and the selection move through the run: every
        # eighth step's fullest layer (the bound is sized from these)
        self.ctx.say(routers_every_8th_step={
            "moe_held_pairs_max": [int(c["moe_held_pairs_max"])
                                   for c in seen[::8]],
            "expert_load_max_over_mean": [round(float(
                c["expert_load_max_over_mean"]), 2) for c in seen[::8]],
            "select_live_tile_pct": [round(float(
                c["select_live_tile_pct"]), 2) for c in seen[::8]]})
        return {**super().counters(),
                "select_live_tile_pct": float(max(
                    c["select_live_tile_pct"] for c in seen)),
                "select_pairs_off": int(max(
                    abs(int(c["select_pairs"]) - self.expected_pairs())
                    for c in seen)),
                "index_loss_first": float(seen[0]["index_loss"]),
                "index_loss_last_checked": float(
                    seen[self.n_checked - 1]["index_loss"])}

    def chosen(self, bits):
        """A reference's ``select_bits`` as bool ``[T, T]``."""
        import jax.numpy as jnp
        return jnp.unpackbits(jnp.asarray(bits), axis=-1, count=self.ctx
                              .config["input"]["seq"]).astype(bool)

    def own_gaps(self, ref: dict, low: dict | None = None) -> dict:
        """This cell's own numbers against the readings ``ref``: the
        program's, or those of the readings ``low`` (the control's)."""
        import jax
        import jax.numpy as jnp

        from apex_tpu.ops.key_set import unpack_select
        if low is None:
            if self.first_select is None:
                self.first_select = self.side_selection()
            mine = unpack_select(self.first_select, self.ctx.config[
                "input"]["seq"])[0]
            losses = [float(c["index_loss"]) for c in jax.device_get(
                self.seen[:self.n_checked])]
        else:
            mine, losses = self.chosen(low["select_bits"]), \
                low["index_losses"]
        theirs = self.chosen(ref["select_bits"])
        return {
            "select_disagreement": 1.0 - float(
                jnp.sum(mine & theirs) / jnp.sum(theirs)),
            "index_loss_gap": max(abs(got - want) / abs(want) for got, want
                                  in zip(losses, ref["index_losses"]))}

    def check(self, rec: dict) -> list:
        own = self.own_gaps(self.reference_readings())
        return super().check(rec) + [
            {"name": k, "value": v, "limit": self.ctx.limits[k]}
            for k, v in own.items()] + [{
                "name": "select_pairs_off", "limit": 0,
                "value": rec["counters"]["select_pairs_off"]}]

    def calibrate(self, seed: int, control: bool) -> dict:
        out = super().calibrate(seed, control)
        ref = self.reference_readings()
        out["program"].update(self.own_gaps(ref))
        if control:
            out["control"].update(self.own_gaps(
                ref, self.reference_readings("fp8")))
        return out

    # the seed's weights handed over on the host
    _reference_readings = train_kimi_vl.Driver._reference_readings
