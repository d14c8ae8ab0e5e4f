"""The trace reducer on a small recorded xplane (text proto beside this
file, names as the v5e's traces have them) and on events fed by hand."""

import os

import pytest

from benchmarks import common, xplane

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def events(tmp_path_factory):
    from jax.profiler import ProfileData
    with open(os.path.join(HERE, "data", "bm_small_xplane.txt")) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(raw)
    path = xplane.find(str(d.parent.parent.parent))
    by_device = xplane.load(path)
    assert list(by_device) == [0]       # the host plane is no device
    return by_device[0]


@pytest.fixture(scope="module")
def modules(events, tmp_path_factory):
    path = xplane.find(str(tmp_path_factory.getbasetemp() / "trace0"))
    return xplane.load(path, line=xplane.MODULES_LINE)[0]


def test_busy_is_the_union_of_intervals(events):
    # [0,4] holds [1,3]; [5,7] overlaps [6,9]; [10,11]: 4 + 4 + 1 ms
    assert xplane.busy_seconds(events) == pytest.approx(9e-3)


def test_kernel_time_by_name_pattern(events):
    assert xplane.op_seconds(events, r"decode_kernel") == pytest.approx(3e-3)
    assert xplane.op_seconds(events, r"no_such_kernel") == 0.0


def test_kernel_time_inside_one_program(events, modules):
    # the decode program runs 0.5..4.5 ms: one of the two kernel events
    inside = xplane.within(events, modules, r"^jit__decode_fused_paged\(")
    assert xplane.op_seconds(
        inside, 'custom_call_target="tpu_custom_call"') == pytest.approx(2e-3)
    assert len(xplane.within(events, modules, r"^jit_step\(")) == len(events)
    assert xplane.within(events, modules, r"no_such_program") == []


def test_exposed_collective_time(events):
    # the all-reduce runs 6..9 ms, a fusion covers 6..7 of it
    assert xplane.op_seconds(events, r"all-reduce") == pytest.approx(3e-3)
    assert xplane.exposed_seconds(events, r"all-reduce") == \
        pytest.approx(2e-3)


def test_top_ops_are_self_times_by_family(events):
    top = dict(xplane.top_ops(events))
    assert top["%decode_kernel custom-call:tpu_custom_call"] == \
        pytest.approx(3e-3)                                 # both events
    assert top["%while while"] == pytest.approx(2e-3)       # 4 less its child
    assert top["%fusion fusion"] == pytest.approx(2e-3)     # overlap: no child
    assert xplane.family("%fusion.12 = f32[8] fusion(f32[8] %x), kind=kLoop") \
        == xplane.family("%fusion.13 = f32[8] fusion(f32[8] %y), kind=kLoop")


def test_idle_gaps_name_what_ended_them(events):
    gaps = xplane.idle_gaps(events)
    assert [round(g * 1e3, 6) for _, g in gaps] == [1.0, 1.0]
    assert {n for n, _ in gaps} == {
        "before %decode_kernel.7 custom-call:tpu_custom_call",
        "before %fusion.9 fusion"}


@pytest.mark.parametrize("values, q, want", [
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 50, 5),
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 95, 10),
    (list(range(1, 101)), 95, 95),
    ([7.0], 95, 7.0),
    ([3, 1, 2], 0, 1),
])
def test_percentile_is_nearest_rank(values, q, want):
    assert common.percentile(values, q) == want


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        common.percentile([], 95)


def test_peaks_are_keyed_by_device_kind():
    v5e = common.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        common.peaks("cpu")


# -- an operation's own time: nest, abut, never count twice ------------------

def _loop(start: float, n: int, each: float, tail: float = 0.0):
    """A ``while`` from ``start`` whose body is ``n`` operations of
    ``each`` seconds that abut to the picosecond, the last one ``tail``
    short of the loop's end, as a trace has them: whole picoseconds,
    handed over in float seconds."""
    ps = [round(start * 1e12) + k * round(each * 1e12) for k in range(n + 1)]
    body = [(f"%fusion.{k} = f32[8] fusion()", ps[k] * 1e-12,
             (ps[k + 1] - ps[k]) * 1e-12) for k in range(n)]
    whole = ps[-1] - ps[0] + round(tail * 1e12)
    return [("%while.1 = (s32[]) while()", ps[0] * 1e-12, whole * 1e-12)] \
        + body


@pytest.mark.parametrize("start, n, each, tail", [
    (0.1, 1000, 1e-7, 0.0),         # float-hostile: 0.1 + k * 1e-7
    (0.1, 1000, 1e-7, 3e-9),        # the loop's own 3 ns after its body
    (0.3, 777, 3.3e-8, 1e-12),      # a picosecond of its own
    (4.642527310, 50, 1.6e-8, 0.0),  # late in a 5 s trace, 16 ns each
    (0.7, 3, 2.5e-3, 1e-4),
])
def test_a_loops_own_time_is_its_duration_less_its_abutting_body(
        start, n, each, tail):
    """In float seconds ``s + d`` of one operation reads an ulp past the
    start of the next (1,121 times in one ``kvl_train_s8192`` trace): the
    sibling stayed open, and the next operation was not taken off the
    ``while``. Compared in whole picoseconds the loop's own time is what
    its body leaves, and the own times sum to the line's busy time."""
    events = _loop(start, n, each, tail)
    # the hazard is in these values: a float end past the next start
    floats = sum(a[1] + a[2] > b[1]
                 for a, b in zip(events[1:], events[2:]))
    assert n < 10 or floats > 0
    own = xplane.self_times(events)
    assert own["%while.1 = (s32[]) while()"] == pytest.approx(
        tail, abs=1e-15)
    assert all(own[name] == pytest.approx(d, abs=1e-15)
               for name, _, d in events[1:])
    assert sum(own.values()) == pytest.approx(
        xplane.busy_seconds(events), rel=1e-12)
    # two loops one after the other, the second's start the first's end
    twice = events + _loop(events[0][1] + events[0][2], n, each, tail)
    assert sum(xplane.self_times(twice).values()) == pytest.approx(
        xplane.busy_seconds(twice), rel=1e-12)


def test_a_loop_in_a_loop_keeps_only_its_own():
    inner = _loop(0.1, 100, 1e-7, 2e-9)
    end = inner[0][1] + inner[0][2]
    outer = [("%while.9 = (s32[]) while()", 0.1, end - 0.1 + 5e-9)] + inner \
        + [("%copy.3 = f32[8] copy()", end, 4e-9)]
    own = xplane.self_times(outer)
    assert own["%while.9 = (s32[]) while()"] == pytest.approx(1e-9, abs=1e-15)
    assert own["%while.1 = (s32[]) while()"] == pytest.approx(2e-9, abs=1e-15)
    assert sum(own.values()) == pytest.approx(xplane.busy_seconds(outer),
                                              rel=1e-12)
    assert dict(xplane.top_ops(outer))["%fusion fusion"] == pytest.approx(
        100 * 1e-7)


@pytest.mark.parametrize("data, partly_overlapping_ms", [
    ("bm_region_xplane.txt", 0.0),
    # [5, 7] and [6, 9] partly overlap: neither is the other's, both keep
    # their whole duration, and the sum passes the union by the overlap
    ("bm_small_xplane.txt", 1.0),
])
def test_own_times_sum_to_the_busy_time_of_a_hand_written_line(
        tmp_path, data, partly_overlapping_ms):
    from jax.profiler import ProfileData
    with open(os.path.join(HERE, "data", data)) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(raw)
    line = xplane.load(str(path))[0]
    assert sum(xplane.self_times(line).values()) == pytest.approx(
        xplane.busy_seconds(line) + 1e-3 * partly_overlapping_ms, rel=1e-12)
