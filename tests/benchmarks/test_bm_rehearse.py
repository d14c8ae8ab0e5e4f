"""Each driver walks a whole run at its configuration's tiny
``rehearsal`` sizes on the CPU: the last line has exactly the contract's
keys, names the CPU, and carries no metric (a CPU number is never written
under a device metric's name). Without ``--rehearse`` the CPU is refused."""

import json

import pytest

from benchmarks import common, run, spec as S, sweep

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def last_line(capsys, argv, root=None):
    assert run.main(argv, root=root) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    for line in lines:      # every line is JSON and names its device
        assert json.loads(line)["device"]["platform"] == "cpu"
    return json.loads(lines[-1]), [json.loads(x) for x in lines[:-1]]


@pytest.mark.parametrize("cell, trace", [
    ("cgpt_train_s2048", 0), ("cgpt_train_s2048", 1),
    ("cgpt_serve_chat", 0), ("cgpt_serve_chat", 1), ("rn50_train_b384", 0),
])
def test_rehearsal_prints_the_contracts_line_and_no_metric(
        capsys, every_cell_root, cell, trace):
    result, earlier = last_line(capsys, [
        "--workload", cell, "--seed", str(2 ** 31 + 77), "--seconds", "1.5",
        "--trace", str(trace), "--rehearse"], every_cell_root)
    assert set(result) == KEYS
    assert result["metrics"] == {}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    compared = [x for x in earlier if "compared" in x]
    assert compared and all("limit" in x and "value" in x for x in compared)


def test_the_cpu_is_refused_without_rehearse(capsys):
    with pytest.raises(common.NoAccelerator):
        run.main(["--workload", "cgpt_train_s2048", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert capsys.readouterr().out.strip() == ""    # and prints no result


def test_an_unknown_cell_is_refused():
    with pytest.raises(S.SpecError):
        run.main(["--workload", "no_such_cell", "--seed", "1", "--seconds",
                  "1", "--trace", "0", "--rehearse"])


def test_a_four_chip_cell_rehearses_on_virtual_devices(every_cell_root,
                                                       capsys):
    """The data-parallel cell that the budget of PR 23 did not reach
    (PERF.md, Open questions) is entries plus the files that are there:
    with ``unproved.json``'s entries beside the committed ones, it walks a
    whole run on four of the suite's virtual CPU devices."""
    spec = S.Spec(every_cell_root)
    assert [m["reader"] for m in spec.per_layer(spec.cell("cgpt_train_ddp4"))
            if m["name"].startswith("collective_")] == ["trace_collective"] * 2
    result, _ = last_line(capsys, [
        "--workload", "cgpt_train_ddp4", "--seed", "3", "--seconds", "1",
        "--trace", "0", "--rehearse"], every_cell_root)
    assert result["device"]["count"] == 4 and result["correct"] is True


class _Device:
    """A device that runs one step at a time on a clock of its own: a
    step dispatched at ``now`` starts when the one before it is done, and
    the runtime holds ``depth`` steps at most (the v5e's blocked the
    host in dispatch at about thirty: PR 23, call 19)."""

    def __init__(self, step_s, depth=31):
        self.now, self.step_s, self.depth, self.ends = 0.0, step_s, depth, []

    def dispatch(self):
        self.now += 1e-3                    # the host's own time a step
        if len(self.ends) >= self.depth:    # wait for room in the runtime
            self.now = max(self.now, self.ends[-self.depth])
        self.ends.append(max(self.now, self.ends[-1] if self.ends else 0.0)
                         + self.step_s)
        return _Loss(self, self.ends[-1])


class _Loss:
    def __init__(self, device, done_at):
        self.device, self.done_at = device, done_at

    def block_until_ready(self):
        self.device.now = max(self.device.now, self.done_at)

    def is_ready(self):
        return self.device.now >= self.done_at

    def __float__(self):
        return 1.0


@pytest.mark.parametrize("warm_up_step_s", [0.05, 0.2, 0.6])
@pytest.mark.parametrize("seconds, in_flight", [(51.0, 56), (5.0, 56),
                                                (5.0, 12)])
def test_the_window_closes_at_its_seconds(monkeypatch, seconds, in_flight,
                                          warm_up_step_s):
    """No step is dispatched that the queue ahead of it would carry past
    ``--seconds``, whatever the warm-up made of the step time: the window
    closes within two steps of it and the device never waits."""
    import types

    from benchmarks import training
    device = _Device(step_s=0.2)
    monkeypatch.setattr(training, "time", types.SimpleNamespace(
        perf_counter=lambda: device.now))

    class Driver(training.TrainDriver):
        def __init__(self):
            self.state, self.next, self.feed = None, 0, {"units_per_step": 1}
            self.step_s, self.in_flight = warm_up_step_s, in_flight

        def advance(self, state, i):
            return state, device.dispatch()
    rec = Driver().window(seconds)
    assert seconds - 0.4 <= rec["window_s"] <= seconds + 0.4
    # the device ran back to back: the steps are the window's length of them
    assert rec["steps"] == pytest.approx(rec["window_s"] / 0.2, abs=1.01)
    assert rec["failed"] == 0
    # the longest gap between two landings is a step: no burst, no fill
    assert rec["notes"]["step_gap_max_ms"] == pytest.approx(200.0, abs=2.0)


def test_the_sweep_walks_a_ladder_of_rates(capsys, every_cell_root):
    assert sweep.main(["--workload", "cgpt_serve_chat", "--rates", "4,8",
                       "--seconds", "2", "--ramp", "1.5", "--rehearse"],
                      root=every_cell_root) == 0
    lines = [json.loads(x) for x in
             capsys.readouterr().out.strip().splitlines()]
    rates = [x for x in lines if "rate" in x]
    assert [x["rate"] for x in rates] == [4.0, 8.0]
    assert all(x["due"] > 0 and "ttft_p95_ms" in x and "tpot_p95_ms" in x
               for x in rates)
    assert "knee" in lines[-1]
