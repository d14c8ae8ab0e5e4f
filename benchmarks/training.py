"""What the training drivers share: the window, the numbers ``correct``
compares, and the calibration of their limits.

A driver gives ``setup``, ``reseed``, ``advance(state, i) -> (state,
loss)`` (one optimizer step on batch ``i``, dispatched and not waited
for) and ``reference_readings(precision)``. Steps are dispatched from the
host one by one; the host waits only on the loss ``in_flight`` steps
back. A step is dispatched only while the queue ahead of it lets it land
inside ``--seconds`` (at the step time the window's own completions
show), so the window closes within a step or two of ``--seconds``. The rate is all steps over
all that time.

``in_flight`` is ``QUEUE_SECONDS`` of work at the step time the warm-up
steps showed (12 to 64 steps): on the chip's one-chip machines the host
now and then stalls for 2 to 9 seconds (PERF.md: with 2 steps in flight
2 of 12 runs lost 2-5% to it, with 12 in flight 3 of 12 lost 2-13%), and
a queue that long rides it out. The window line gives the longest gap
between two steps' completions, so that a stall can be told from a slow
step.
"""

from __future__ import annotations

import collections
import math
import time

import numpy as np

from benchmarks.spec import plugin

QUEUE_SECONDS = 12.0


def gaps(got: dict, ref: dict) -> dict:
    """Each step's loss gap, and the worst leaf's gap in the first
    gradient's norm and in the update's norm: the gap between the two
    norms, against the reference's norm of that leaf or of the median
    leaf, whichever is larger (some gradients are all but zero). Where
    the reference gives ``vectors`` too (a vector a layer), the worst
    layer's relative distance and the middle layer's."""
    import jax

    def worst(a, b):
        a, b = jax.tree.leaves(a), jax.tree.leaves(b)
        floor = float(np.median(b))
        return max(abs(x - y) / max(y, floor) for x, y in zip(a, b))
    out = {f"loss_gap.step{i}": abs(x - y) for i, (x, y) in
           enumerate(zip(got["losses"], ref["losses"]))}
    out["grad_norm_gap"] = worst(got["grad_norms"], ref["grad_norms"])
    out["update_norm_gap"] = worst(got["delta_norms"], ref["delta_norms"])
    if "vectors" in ref:
        # forward statistics a step leaves in its state (BatchNorm's
        # batch variances): each layer's distance in its own norm; the
        # worst layer, and the middle one
        layers = sorted(float(np.linalg.norm(a - b) / np.linalg.norm(b))
                        for a, b in zip(got["vectors"], ref["vectors"]))
        out["forward_stat_gap"] = layers[-1]
        out["forward_stat_mid_gap"] = layers[len(layers) // 2]
    return out


def same_tree(mine, theirs, what: str) -> None:
    """The benchmark's weights come in the tree the program's own ``init``
    makes (both as shapes): same structure, same leaf shapes."""
    import jax
    if jax.tree.structure(mine) != jax.tree.structure(theirs) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(mine),
                                               jax.tree.leaves(theirs))):
        raise AssertionError(f"the benchmark's {what} tree is not the "
                             f"program's")


def fresh_state(opt, params):
    """A fused optimizer's state (one group) at step 0 with ``params`` as
    its master: what ``opt.init_state()`` gives right after construction,
    for another seed's weights."""
    import jax.numpy as jnp

    from apex_tpu.ops import flat as F
    from apex_tpu.optimizers.base import GroupState
    master, _ = F.flatten(params, table=opt._tables[0], dtype=jnp.float32)
    return (GroupState(
        master=master,
        slots={k: jnp.zeros_like(master) for k in opt._slot_names},
        step=jnp.asarray(0, jnp.int32)),)


def worst_leaves(got: dict, ref: dict, which: str, n: int = 4) -> list:
    """The ``n`` leaves with the widest gap in ``which`` norms: [path,
    gap, program's norm, reference's norm]. For reading, not for
    judging."""
    import jax
    a = jax.tree_util.tree_leaves_with_path(got[which])
    b = jax.tree.leaves(ref[which])
    floor = float(np.median(b))
    rows = [(jax.tree_util.keystr(p), abs(x - y) / max(y, floor), x, y)
            for (p, x), y in zip(a, b)]
    return [list(r) for r in sorted(rows, key=lambda r: -r[1])[:n]]


class TrainDriver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.n_checked = ctx.traffic["steps_checked"]
        self.state = None
        self.new_feed(ctx.seed)

    def new_feed(self, seed: int):
        self.ctx.seed = seed
        self.feed = plugin("generators", self.ctx.traffic["kind"]).generate(
            self.ctx.traffic, self.ctx.config, seed, len(self.ctx.devices))

    def first_steps(self, state):
        """The first steps, through the window's own call and feed: each
        step's loss, the first gradient's norm leaf by leaf as the
        optimizer gets it (its first moment is (1 - beta1) times it) and
        the parameters' change; then two more steps, so that the donated
        state is in its steady layout when the window opens.

        ``self.group(state)`` is the optimizer's ``GroupState`` within
        ``state``; ``self.opt``, ``self.specs`` and ``self.beta1`` are the
        driver's."""
        import jax
        import jax.numpy as jnp

        from apex_tpu.ops import flat as F
        from benchmarks import weights as W

        table = self.opt._tables[0]

        def norms(tree):
            return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))),
                                tree)
        first_grad = jax.jit(lambda m: norms(jax.tree.map(
            lambda x: x / (1.0 - self.beta1), F.unflatten(m, table))))
        moved = jax.jit(lambda master, k: norms(jax.tree.map(
            jnp.subtract, F.unflatten(master, table),
            W.build(self.specs, k, jnp.float32))))
        got = {"losses": []}
        for i in range(self.n_checked):
            state, loss = self.advance(state, i)
            got["losses"].append(float(loss))
            if i == 0:
                got["grad_norms"] = jax.tree.map(float, first_grad(
                    self.group(state).slots["exp_avg"]))
                got.update(self.first_step_extras(state))
        got["delta_norms"] = jax.tree.map(float, moved(
            self.group(state).master, W.seed_key(self.ctx.seed)))
        self.readings = got
        self.next = self.n_checked
        t = time.perf_counter()
        for _ in range(2):
            state, loss = self.advance(state, self.next)
            self.next += 1
        loss.block_until_ready()
        self.step_s = (time.perf_counter() - t) / 2
        self.in_flight = min(64, max(12, math.ceil(QUEUE_SECONDS
                                                   / self.step_s)))
        self.state = state

    def first_step_extras(self, state) -> dict:
        """More of what the first step left in ``state``, to compare."""
        return {}

    def counters(self) -> dict:
        """Counts read from the program's state after the window."""
        return {}

    def window(self, seconds: float, trace_dir=None) -> dict:
        import jax
        state = self.state
        losses, waiting, done = [], collections.deque(), []

        def land():
            waiting.popleft().block_until_ready()
            done.append(time.perf_counter())

        def step_s():
            """The step time the window's own completions show (all that
            landed over all the time so far), and the warm-up's until
            one has landed."""
            return (done[-1] - t0) / len(done) if done else self.step_s
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
        n, t0 = 0, time.perf_counter()
        # a step dispatched now lands behind the queue: dispatch while
        # that is inside the window (and once at the least); where the
        # queue reaches the window's end, wait for a step and look again.
        # What is done is landed at once, so that ``waiting`` is the
        # queue and a gap between two landings is the device's or a
        # stalled host's.
        while True:
            while waiting and waiting[0].is_ready():
                land()
            room = seconds - (time.perf_counter() - t0)
            if n and room <= (len(waiting) + 1) * step_s():
                if not waiting:
                    break
                land()
                continue
            state, loss = self.advance(state, self.next + n)
            losses.append(loss)
            waiting.append(loss)
            n += 1
            # until a step of the window has landed its time is the
            # warm-up's guess: queue no more than two on a guess
            if len(waiting) > (self.in_flight if done else 1):
                land()
        jax.block_until_ready(state)
        elapsed = time.perf_counter() - t0
        if trace_dir:
            jax.profiler.stop_trace()
        self.state = state
        losses = np.asarray([float(x) for x in losses])
        return {"window_s": elapsed, "steps": n,
                "units_per_step": self.feed["units_per_step"],
                "attempted": n,
                "failed": int((~np.isfinite(losses)).sum()),
                "stats": {}, "counters": self.counters(),
                "notes": {"in_flight": self.in_flight,
                          "step_gap_max_ms": 1e3 * max(
                    (b - a for a, b in zip(done, done[1:])), default=0.0)}}

    def end_to_end(self, rec: dict) -> dict:
        return {self.ctx.config["rate_metric"]:
                rec["steps"] * rec["units_per_step"] / rec["window_s"]}

    def check(self, rec: dict) -> list:
        got = gaps(self.readings, self.reference_readings())
        return [{"name": "steps_failed", "value": rec["failed"], "limit": 0}] \
            + [{"name": k, "value": v,
                "limit": self.ctx.limits[k.split(".")[0]]}
               for k, v in got.items()]

    def calibrate(self, seed: int, control: bool) -> dict:
        """One seed's numbers as ``check`` compares them, and with
        ``control`` the same numbers of the reference in fp8."""
        if seed != self.ctx.seed or self.state is None:
            self.reseed(seed)
        self.release()
        ref = self.reference_readings()
        out = {"program": gaps(self.readings, ref),
               "worst_grad_leaves": worst_leaves(self.readings, ref,
                                                 "grad_norms")}
        if control:
            low = self.reference_readings("fp8")
            out["control"] = gaps(low, ref)
            out["control_worst_grad_leaves"] = worst_leaves(
                low, ref, "grad_norms")
        return out
