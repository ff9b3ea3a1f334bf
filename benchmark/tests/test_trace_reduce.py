"""The .xplane.pb reducer against the small trace recorded on a v5e by
``record_trace.py`` (two traced rounds of a 200k x 28, 31-leaf training
with the fused histogram kernel), and against hand-made planes."""
import pytest

from benchmark.lib import trace_reduce as tr
from benchmark.lib.lookup import REPO

TRACE = REPO / "benchmark/tests/data/trace_small.xplane.pb.gz"


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_trace(TRACE)


def test_recorded_trace_has_one_chip_and_the_kernel(reduced):
    assert len(reduced["devices"]) == 1
    dev = reduced["devices"][0]
    assert dev["plane"] == "/device:TPU:0"
    # 13 frontier passes of the Mosaic accumulate kernel in two trees
    assert dev["kernel_calls"] == 13
    assert 0.040 < dev["kernel_s"] < 0.050
    assert dev["collective_s"] == 0.0


def test_recorded_trace_busy_and_idle(reduced):
    dev = reduced["devices"][0]
    assert 0 < dev["busy_s"] <= reduced["window_s"]
    assert reduced["busy_s"] == pytest.approx(dev["busy_s"])
    # own times partition the busy union: nested whiles are not counted
    # twice
    assert sum(dev["ops"].values()) == pytest.approx(dev["busy_s"], rel=1e-6)
    assert dev["kernel_s"] < dev["busy_s"]
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(s for _, s in reduced["idle_gaps"]) == pytest.approx(
        idle, rel=1e-3)


def test_recorded_trace_names_and_annotations(reduced):
    top = reduced["device_ops"][0][0]
    assert top.startswith("custom-call:tpu_custom_call %")
    assert all(len(n) <= 120 for n, _ in reduced["device_ops"])
    ann = reduced["annotations"]
    assert ann["bench.update"][0] == 2 and ann["bench.sync"][0] == 2
    assert ann["bench.pull_trees"][0] == 1
    assert {n for n, _ in reduced["idle_gaps"]} <= set(ann) | {
        "outside_harness_spans"}


def test_short_name():
    text = ('%body.17 = f32[96,2048]{1,0:T(8,128)S(1)} custom-call(u8[4,8,'
            '262144]{2,1,0:T(8,128)(4,1)S(1)} %p), custom_call_target='
            '"tpu_custom_call", operand_layout_constraints={}')
    assert tr.short_name(text) == "custom-call:tpu_custom_call %body.17"
    loop = ("%while.3 = (s32[]{:T(128)}, f32[1,8]{1,0:T(1,128)}) "
            "while((s32[]{:T(128)}, f32[1,8]{1,0:T(1,128)}) %t), body=%b")
    assert tr.short_name(loop) == "while %while.3"
    assert tr.short_name("%all-reduce.1 = f32[4]{0} all-reduce(f32[4]{0} "
                         "%x), replica_groups={}") == "all-reduce %all-reduce.1"
    assert tr.short_name("bench.update") == "bench.update"


def test_union_overlap_complement():
    total, merged = tr._union([(0, 10), (5, 20), (30, 40)])
    assert total == 30 and merged == [[0, 20], [30, 40]]
    assert tr._overlap(merged, [[15, 35]]) == 10
    assert tr._complement(merged, 0, 50) == [[20, 30], [40, 50]]


def test_self_times_nested():
    events = [("while", 0.0, 100.0), ("a", 10.0, 20.0), ("b", 40.0, 50.0),
              ("b.inner", 45.0, 10.0), ("c", 200.0, 5.0)]
    own = dict(tr.self_times(events))
    assert own == {"while": 30.0, "a": 20.0, "b": 40.0, "b.inner": 10.0,
                   "c": 5.0}


def test_hand_made_planes_collectives_and_gaps():
    planes = {
        "/device:TPU:0": {"XLA Ops": [
            ("fusion %f", 100.0, 100.0),
            ("all-reduce %ar", 150.0, 100.0),     # 50 hidden, 50 exposed
            ("custom-call:tpu_custom_call %k", 400.0, 100.0)]},
        "/device:TPU:1": {"XLA Ops": [("fusion %f", 100.0, 400.0)]},
        "/host:CPU": {"python3": [("bench.update", 0.0, 300.0),
                                  ("bench.sync", 300.0, 300.0),
                                  ("other", 0.0, 1000.0)]},
    }
    r = tr.reduce_planes(planes)
    d0, d1 = r["devices"]
    assert r["window_s"] == pytest.approx(600e-9)
    assert d0["busy_s"] == pytest.approx(250e-9)
    assert d0["collective_s"] == pytest.approx(100e-9)
    assert d0["collective_exposed_s"] == pytest.approx(50e-9)
    assert d0["kernel_calls"] == 1 and d0["kernel_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx((250e-9 + 400e-9) / 2)
    gaps = dict(r["idle_gaps"])
    # chip 0 idles 0-100 (update), 250-300 (update), 300-400 and 500-600
    # (sync)
    assert gaps["bench.update"] == pytest.approx(150e-9)
    assert gaps["bench.sync"] == pytest.approx(200e-9)


def test_trace_without_device_plane_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_planes({"/host:CPU": {"t": [("bench.x", 0.0, 1.0)]}})
