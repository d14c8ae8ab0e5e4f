"""Driver: the dense-LM training step, as ``tools/lm_bench.py`` and
``chip_smoke.py`` build it.

``TransformerLM`` at the configuration's widths, bf16 over one flat fp32
master, FusedAdam, flash attention, over one chip or (``chips`` 4) under
``Plan`` DDP on a data mesh. The benchmark makes the weights (one jitted
call on the device, from the seed) and the token batches; the program
contributes the step and the layout of its state.
"""

from __future__ import annotations

import os
import sys

from benchmarks import common, weights as W
from benchmarks.spec import ROOT, plugin
from benchmarks.training import TrainDriver, fresh_state, same_tree


def _tools():
    """``tools/lm_bench.py`` importable: the step's builder lives there."""
    for p in (ROOT, os.path.join(ROOT, "tools")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import lm_bench
    return lm_bench


class Driver(TrainDriver):
    def __init__(self, ctx):
        super().__init__(ctx)
        self.specs = W.gpt2_specs(ctx.config)
        self.beta1 = plugin("reference", ctx.config["reference"]).ADAM["beta1"]

    # -- the program -------------------------------------------------------
    def model(self):
        """The program's model at the configuration's sizes, checked
        against the tree the benchmark's weights come in."""
        import jax
        import jax.numpy as jnp

        from apex_tpu.models import TransformerLM

        cfg = self.ctx.config
        lm = TransformerLM(
            vocab_size=cfg["vocab_size"], max_seq_len=cfg["n_positions"],
            embed_dim=cfg["n_embd"], num_heads=cfg["n_head"],
            num_layers=cfg["n_layer"],
            ffn_mult=cfg["n_inner"] // cfg["n_embd"],
            attn_impl=cfg["program"]["attn_impl"],
            head_chunk=cfg["program"]["head_chunk"])
        shapes = jax.eval_shape(lm.init, jax.random.key(0))
        mine = jax.eval_shape(lambda: W.build(self.specs, W.seed_key(0),
                                              jnp.float32))
        same_tree(mine, shapes, "GPT-2")
        return lm, mine

    def program(self, devices, params):
        """``tools/lm_bench.build_train_step`` over ``devices`` from the
        weights ``params``: (opt, state, step body, plan)."""
        import jax.numpy as jnp

        from apex_tpu.parallel import make_mesh

        lm, _ = self.model()
        mesh = make_mesh({"data": len(devices)}, devices=list(devices))
        opt, state, step, plan = _tools().build_train_step(
            lm, params, mesh, half=jnp.bfloat16, lr=self.ctx.traffic["lr"])
        opt.state = ()      # the caller's copy is the one that lives on
        return opt, state, step, plan

    def make_state(self, opt, seed_key):
        """The optimizer state with the seed's weights as its master."""
        import jax.numpy as jnp
        return fresh_state(opt, W.build(self.specs, seed_key, jnp.float32))

    def setup(self):
        """Build the step as the program's tools do, but with the
        optimizer made on the device from weights made there (one jitted
        call from the seed): nothing crosses from the host. Then place,
        compile, and drive the first steps."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import (NamedSharding, PartitionSpec as P,
                                  SingleDeviceSharding)

        from apex_tpu.parallel import compile_step_with_plan

        ctx = self.ctx
        params = jax.jit(lambda k: W.build(self.specs, k, jnp.float32))(
            W.seed_key(ctx.seed))
        ctx.mark("weights")
        opt, state, step, plan = self.program(ctx.devices, params)
        del params
        ctx.mark("optimizer")
        self.opt = opt
        one = SingleDeviceSharding(ctx.devices[0])
        many = len(ctx.devices) > 1
        self.rep = NamedSharding(plan.mesh, P()) if many else one
        self.rows = NamedSharding(plan.mesh, P("data")) if many else one
        self.init = jax.jit(lambda k: self.make_state(opt, k),
                            out_shardings=self.rep)
        state, _ = _tools().place_for_plan(state, self.feed["x"][0], plan)
        self.batches = [jax.device_put(b, self.rows) for b in self.feed["x"]]
        compiled = compile_step_with_plan(step, plan).lower(
            state, self.batches[0]).compile()
        if ctx.on_tpu and "tpu_custom_call" not in compiled.as_text():
            raise AssertionError("no tpu_custom_call in the train step: "
                                 "the dispatch took the jnp reference")
        common.note_program(ctx, compiled)
        ctx.mark("compiled")
        self.step = compiled
        self.first_steps(state)

    def reseed(self, seed: int):
        """Another seed's weights and batches on the compiled step."""
        import jax
        self.release()
        self.new_feed(seed)
        self.batches = [jax.device_put(b, self.rows) for b in self.feed["x"]]
        self.first_steps(self.init(W.seed_key(seed)))

    def group(self, state):
        return state[0]

    def advance(self, state, i: int):
        return self.step(state, self.batches[i % len(self.batches)])

    def release(self):
        """Free the state; the compiled step stays for another seed."""
        self.state = self.batches = None

    # -- the plain reference ----------------------------------------------
    def reference_readings(self, precision: str = "float32") -> dict:
        import jax
        import jax.numpy as jnp
        gpt2 = plugin("reference", self.ctx.config["reference"])
        params = jax.jit(lambda k: W.build(self.specs, k, jnp.float32))(
            W.seed_key(self.ctx.seed))
        first = [jnp.asarray(b) for b in self.feed["x"][:self.n_checked]]
        return gpt2.train_steps(params, first, self.ctx.config["n_head"],
                                precision, lr=self.ctx.traffic["lr"])
