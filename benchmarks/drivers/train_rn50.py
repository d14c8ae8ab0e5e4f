"""Driver: the ResNet-50 O2 + FusedLAMB step, as ``bench.py`` and
``chip_smoke.py`` build it: ``bench.build_train_step`` over
``apex_tpu.models.ResNet`` with the committed ``BENCH_DEFAULTS.json``
stem, NHWC, bf16 over fp32 masters, dynamic loss scale.

The weights are the benchmark's (one jitted call from the seed, on the
host backend: 25.6M parameters, and the optimizer flattens them there as
the program's tools do, then one transfer). The images are bf16 and
resident on the device; the input pipeline is not in this cell.
"""

from __future__ import annotations

import sys

import numpy as np

from benchmarks import common, weights as W
from benchmarks.spec import ROOT, plugin
from benchmarks.training import TrainDriver, fresh_state, same_tree


class Driver(TrainDriver):
    def __init__(self, ctx):
        super().__init__(ctx)
        self.specs = W.resnet_specs(ctx.config)
        self.beta1 = plugin("reference", ctx.config["reference"]).LAMB["beta1"]

    def host_state(self, seed: int):
        """(opt_state, bn_state, amp_state) on the host backend."""
        import jax
        import jax.numpy as jnp

        from apex_tpu.utils import host_init
        with host_init():
            params = jax.jit(lambda k: W.build(self.specs, k, jnp.float32))(
                W.seed_key(seed))
            if self.opt is None:
                if ROOT not in sys.path:
                    sys.path.insert(0, ROOT)
                from bench import build_train_step
                self.opt, _, self.body = build_train_step(
                    self.model, params, self.handle,
                    lr=self.ctx.traffic["lr"])
                opt_state = self.opt.init_state()
                self.opt.state = ()
            else:
                opt_state = fresh_state(self.opt, params)
            bn = jax.tree_util.tree_map_with_path(
                lambda path, s: (jnp.ones if "running_var" in str(path[-1])
                                 else jnp.zeros)(s.shape, s.dtype),
                jax.eval_shape(self.model.init, jax.random.key(0))[1])
            return opt_state, bn, self.handle.init_state()

    def place(self, seed: int):
        import jax
        import jax.numpy as jnp

        from apex_tpu.utils import ship
        dev = self.ctx.devices[0]
        half = self.handle.policy.cast_model_dtype
        # the pixels both sides see are the bf16-rounded ones
        self.x = [jax.device_put(jnp.asarray(x, half), dev)
                  for x in self.feed["x"]]
        self.y = [jax.device_put(y, dev) for y in self.feed["y"]]
        return ship(self.host_state(seed), dev)

    def setup(self):
        import jax

        from apex_tpu import amp
        from apex_tpu.models import ResNet

        cfg = self.ctx.config
        self.model = ResNet(
            block_sizes=tuple(cfg["block_sizes"]), bottleneck=True,
            num_classes=cfg["num_classes"], width=cfg["width"],
            stem=cfg["program"]["stem"])
        shapes = jax.eval_shape(self.model.init, jax.random.key(0))[0]
        mine = jax.eval_shape(lambda: W.build(self.specs, W.seed_key(0),
                                              np.float32))
        same_tree(mine, shapes, "ResNet")
        _, self.handle = amp.initialize(
            opt_level=cfg["program"]["opt_level"],
            loss_scale=cfg["program"]["loss_scale"], verbosity=0)
        self.opt = None
        state = self.place(self.ctx.seed)
        self.ctx.mark("state_placed")
        self.step = jax.jit(self.body, donate_argnums=(0, 1, 2)).lower(
            *state, self.x[0], self.y[0]).compile()
        common.note_program(self.ctx, self.step)
        self.ctx.mark("compiled")
        self.first_steps(state)

    def reseed(self, seed: int):
        self.release()
        self.new_feed(seed)
        self.first_steps(self.place(seed))

    def advance(self, state, i: int):
        k = i % len(self.x)
        *state, loss = self.step(*state, self.x[k], self.y[k])
        return tuple(state), loss

    def group(self, state):
        return state[0][0]

    def first_step_extras(self, state) -> dict:
        """Each BatchNorm's batch variance, out of the running variance
        the first step left: 0.9 x 1 + 0.1 x unbiased variance, in the
        order the layers run (stem, then each block's projection first)."""
        bn = state[1]
        n = self.feed["x"].shape[1]
        order = ["bn_proj", "bn1", "bn2", "bn3"]

        def var(layer, positions):
            unbiased = (np.asarray(layer["running_var"]) - 0.9) / 0.1
            return unbiased * (positions - 1) / positions
        size = self.ctx.config["input"]["size"] // 2     # after the stem
        out = [var(bn["bn_stem"], n * size * size)]
        size //= 2                                       # after the pool
        for s, blocks in enumerate(self.ctx.config["block_sizes"]):
            for b in range(blocks):
                blk = bn[f"stage{s}_block{b}"]
                down = 2 if (s > 0 and b == 0) else 1
                for name in order:
                    if name in blk:
                        here = size if name == "bn1" else size // down
                        out.append(var(blk[name], n * here * here))
                size //= down
        return {"vectors": out}

    def counters(self) -> dict:
        scaler = self.handle.scalers[0].state_dict(self.state[2][0])
        return {"amp_overflow_skips": int(scaler["overflow_count"])}

    def release(self):
        self.state = self.x = self.y = None

    def reference_readings(self, precision: str = "float32") -> dict:
        import jax
        import jax.numpy as jnp
        ref = plugin("reference", self.ctx.config["reference"])
        params = jax.jit(lambda k: W.build(self.specs, k, jnp.float32))(
            W.seed_key(self.ctx.seed))
        first = [(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32),
                  jnp.asarray(y))
                 for x, y in zip(self.feed["x"][:self.n_checked],
                                 self.feed["y"][:self.n_checked])]
        return ref.train_steps(params, first, precision,
                               lr=self.ctx.traffic["lr"])
