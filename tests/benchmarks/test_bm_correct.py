"""``correct`` comes out false where it has to.

The timed path broken underneath (a step that returns its state
unchanged; a served token altered where it is produced) fails a whole
rehearsal run, and the control (the plain reference in the precision
below the configuration's, fp8 for bf16) fails the limits read at the
rehearsal size, while the sound program passes them. The limits of the
real cells were read on the chip the same way (PERF.md)."""

import json

import jax
import jax.numpy as jnp
import pytest

from benchmarks import run, spec as S, training


def rehearse(capsys, cell, root=None):
    assert run.main(["--workload", cell, "--seed", "5", "--seconds", "1",
                     "--trace", "0", "--rehearse"], root=root) == 0
    lines = [json.loads(x) for x in
             capsys.readouterr().out.strip().splitlines()]
    return lines[-1], {x["compared"]: x for x in lines if "compared" in x}


@pytest.mark.parametrize("cell, driver", [
    ("cgpt_train_s2048", "train_lm"), ("rn50_train_b384", "train_rn50")])
def test_a_step_that_returns_its_state_unchanged(monkeypatch, capsys, cell,
                                                 driver):
    mod = S.plugin("drivers", driver)

    class Broken(mod.Driver):
        def advance(self, state, i):
            keep = jax.tree.map(jnp.copy, state)
            _, loss = super().advance(state, i)
            return keep, loss
    monkeypatch.setattr(mod, "Driver", Broken)
    result, compared = rehearse(capsys, cell)
    assert result["correct"] is False
    assert not compared["update_norm_gap"]["ok"]
    assert compared["update_norm_gap"]["value"] == pytest.approx(1.0)
    assert not compared["grad_norm_gap"]["ok"]


def test_part_of_the_batch_left_out(monkeypatch, capsys):
    """The loss is there to catch this: half of the rows repeated."""
    mod = S.plugin("drivers", "train_lm")

    class Broken(mod.Driver):
        def advance(self, state, i):
            b = self.batches[i % len(self.batches)]
            half = b.shape[0] // 2
            return self.step(state, jnp.concatenate([b[:half], b[:half]]))
    monkeypatch.setattr(mod, "Driver", Broken)
    result, compared = rehearse(capsys, "cgpt_train_s2048")
    assert result["correct"] is False
    assert not compared["loss_gap.step0"]["ok"]


def test_a_served_token_altered_where_it_is_produced(monkeypatch, capsys,
                                                     every_cell_root):
    from apex_tpu.serve import ContinuousBatchingEngine
    real = ContinuousBatchingEngine.run

    def altered(self, requests, **kw):
        results, stats = real(self, requests, **kw)
        for r in results:
            r.tokens[len(r.tokens) // 2] = \
                (r.tokens[len(r.tokens) // 2] + 1) % self.model.vocab_size
        return results, stats
    monkeypatch.setattr(ContinuousBatchingEngine, "run", altered)
    result, compared = rehearse(capsys, "cgpt_serve_chat", every_cell_root)
    assert result["correct"] is False
    assert not compared["served_logit_gap"]["ok"]


@pytest.mark.parametrize("cell, number", [
    ("cgpt_train_s2048", "grad_norm_gap"),
    ("rn50_train_b384", "forward_stat_mid_gap"),
    ("cgpt_serve_chat", "served_logit_gap"),
])
def test_the_control_fails_and_the_program_passes(every_cell_root, cell,
                                                  number):
    spec = S.Spec(every_cell_root)
    ctx = run.context(spec, spec.cell(cell), 5, rehearse=True)
    driver = S.plugin("drivers", ctx.config["driver"]).Driver(ctx)
    driver.setup()
    got = driver.calibrate(5, control=True)
    limit = ctx.limits[number]
    assert got["program"][number] <= limit < got["control"][number]
    for name, value in got["program"].items():
        assert value <= ctx.limits[name.split(".")[0]]


def test_gaps_take_the_worst_leaf_against_the_median_leaf():
    ref = {"losses": [2.0], "grad_norms": {"a": 1.0, "b": 1e-9, "c": 2.0},
           "delta_norms": {"a": 1.0, "b": 1.0, "c": 1.0}}
    got = {"losses": [2.5], "grad_norms": {"a": 1.1, "b": 1e-3, "c": 2.0},
           "delta_norms": {"a": 0.0, "b": 1.0, "c": 1.0}}
    g = training.gaps(got, ref)
    assert g["loss_gap.step0"] == 0.5
    # leaf b is all but zero: held against the median leaf's norm (1.0)
    assert g["grad_norm_gap"] == pytest.approx(0.1)
    assert g["update_norm_gap"] == pytest.approx(1.0)
