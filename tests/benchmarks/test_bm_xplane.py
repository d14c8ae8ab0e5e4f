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
