"""The bytes/ops functions against hand-worked values at the three
configurations' sizes, and no share over 100% for a kernel that moved only
the necessary bytes at the peak bandwidth."""
import json
from types import SimpleNamespace

import pytest

from benchmark.lib import lookup

MANIFEST = lookup.load_manifest()
PEAKS = lookup.peaks_for(MANIFEST, "TPU v5 lite")
hist = lookup.load_module(lookup.find(MANIFEST, "rooflines/hist.py"))
trav = lookup.load_module(lookup.find(MANIFEST, "rooflines/traverse.py"))


def config(name):
    return json.loads(
        (lookup.REPO / f"benchmark/configs/{name}.json").read_text())


def test_peaks_table():
    assert PEAKS["bf16_flops_per_s"] == 197e12
    assert PEAKS["int8_ops_per_s"] == 393e12
    assert PEAKS["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        lookup.peaks_for(MANIFEST, "TPU v9")


@pytest.mark.parametrize("name, rows, per_row, hist_bytes", [
    # 28 one-byte bins + 8 B of f32 grad/hess + 4 B leaf id; 3x28x64 cells
    ("higgs-dense", 10_500_000, 40, 3 * 28 * 64 * 4),
    # 28 + 2 B of int8 grad/hess + 4; 2x28x256 cells
    ("higgs-quant", 10_500_000, 34, 2 * 28 * 256 * 4),
    # 67 + 2 B of int8 grad/hess + 4; 2x67x64 cells
    ("criteo-quant", 25_165_824, 73, 2 * 67 * 64 * 4),
    # a chip's quarter of 33,554,432 rows; 67 + 8 + 4; 3x67x64 cells
    ("criteo-dp", 8_388_608, 79, 3 * 67 * 64 * 4),
])
def test_pass_bytes_by_hand(name, rows, per_row, hist_bytes):
    c = config(name)
    chips = 4 if name == "criteo-dp" else 1
    assert int(c["rows"]) // chips == rows
    h = c["hist"]
    got = hist.pass_bytes(rows, c["features"], h["bin_itemsize"],
                          h["value_bytes_per_row"],
                          c["params"]["max_bin"] + 1, h["channels"])
    assert got == rows * per_row + hist_bytes


def test_tree_min_bytes_and_ops_by_hand():
    assert hist.tree_min_bytes(21_000_000, 28, 1) == 21_000_000 * 44
    assert hist.pass_ops(21_000_000, 28, 3) == 21_000_000 * 84
    sec, bound = hist.least_seconds(21_000_000 * 40, 21_000_000 * 84, PEAKS,
                                    "bf16_flops_per_s")
    assert bound == "hbm"
    assert sec == pytest.approx(840e6 / 819e9)


def test_traverse_bytes_by_hand():
    assert trav.forest_bytes(500, 255) == 500 * (254 * 16 + 255 * 8)
    assert trav.request_bytes(524_288, 28, 500, 255) == (
        524_288 * (112 + 4) + 500 * (254 * 16 + 255 * 8))
    assert trav.score_min_bytes(524_288, 28) == 524_288 * 120


def _ctx(cfg, trace, run, e2e=None, traffic=None):
    return {"trace": trace, "config": cfg, "peaks": PEAKS, "chips": 1,
            "run": run, "e2e": e2e or {}, "traffic": traffic or {},
            "spans": {}, "samples": {},
            "roofline": lambda n: {"hist": hist, "traverse": trav}[n]}


def reader(name):
    return lookup.load_module(lookup.find(MANIFEST, f"metrics/{name}.py"))


@pytest.mark.parametrize("name", ["higgs-dense", "higgs-quant",
                                  "criteo-quant"])
def test_hist_share_is_100_at_the_roofline_and_never_more(name):
    """A kernel that made 13 passes and moved exactly the necessary bytes
    at 819 GB/s reads 100%; any real kernel is slower, so reads less."""
    c = config(name)
    h = c["hist"]
    nbytes = hist.pass_bytes(c["rows"], c["features"], h["bin_itemsize"],
                             h["value_bytes_per_row"],
                             c["params"]["max_bin"] + 1, h["channels"])
    least = 13 * nbytes / 819e9
    for slowdown, want in ((1.0, 100.0), (2.0, 50.0), (1000.0, 0.1)):
        trace = {"devices": [{"kernel_calls": 13,
                              "kernel_s": least * slowdown}]}
        got = reader("hist_roofline").read(
            _ctx(c, trace, SimpleNamespace(trees=1)))
        assert got == pytest.approx(want)
        assert got <= 100.0 + 1e-9


@pytest.mark.parametrize("name", ["higgs-dense", "criteo-quant"])
def test_step_mfu_is_100_at_the_roofline(name):
    c = config(name)
    least = hist.tree_min_bytes(c["rows"], c["features"], 1) / 819e9
    got = reader("train_step_mfu").read(_ctx(
        c, None, SimpleNamespace(trees=3), {"train_s_per_tree": least}))
    assert got == pytest.approx(100.0)
    run = SimpleNamespace(features=28)
    rate = 819e9 / trav.score_min_bytes(1, 28)
    assert reader("score_step_mfu").read(_ctx(
        c, None, run, {"score_rows_per_s": rate})) == pytest.approx(100.0)


def test_traverse_share_is_100_at_the_roofline():
    c = config("higgs-dense")
    traffic = {"forest_trees": 500}
    run = SimpleNamespace(requests=4, request_rows=524_288, features=28,
                          rows=4 * 524_288)
    busy = 4 * trav.request_bytes(524_288, 28, 500, 255) / 819e9
    trace = {"devices": [{"busy_s": busy}], "busy_s": busy}
    assert reader("traverse_roofline").read(
        _ctx(c, trace, run, traffic=traffic)) == pytest.approx(100.0)


def test_readers_return_nothing_when_there_is_nothing_to_read():
    c = config("higgs-dense")
    empty = {"devices": [{"kernel_calls": 0, "kernel_s": 0.0, "busy_s": 0.0,
                          "ops": {}, "collective_s": 0.0,
                          "collective_exposed_s": 0.0}],
             "busy_s": 0.0, "window_s": 0.0}
    run = SimpleNamespace(trees=0, requests=0, rows=0, kind="train_loop")
    for name in ("hist_roofline", "hist_kernel_ms_per_tree",
                 "grower_xla_ms_per_tree", "traverse_ms_per_mrow",
                 "traverse_roofline", "allreduce_ms_per_tree",
                 "allreduce_exposed_ms_per_tree", "train_step_mfu",
                 "score_step_mfu", "device_idle_share.train",
                 "warm_compile_s", "ingest_s"):
        assert reader(name).read(_ctx(c, empty, run)) is None, name
