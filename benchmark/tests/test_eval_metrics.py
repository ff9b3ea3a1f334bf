"""The evaluation layer's readers (``eval_device_ms_per_tree``,
``eval_roofline``) and what ``grower_xla_ms_per_tree`` takes out for them:
values on a hand-made reduction, ``None`` (never 0) where no
validation-update program ran, and the line ``XLA Modules`` of the recorded
trace."""
import json
from types import SimpleNamespace

import pytest

from benchmark.lib import lookup, trace_reduce

MANIFEST = lookup.load_manifest()
PEAKS = lookup.peaks_for(MANIFEST, "TPU v5 lite")
CONFIG = json.loads(
    (lookup.REPO / "benchmark/configs/criteo-quant.json").read_text())
VALID_ROWS = 2_796_202


def reader(name):
    return lookup.load_module(lookup.find(MANIFEST, f"metrics/{name}.py"))


def ctx(modules, valid_rows=VALID_ROWS, trees=7):
    dev = {"modules": modules, "kernel_s": 20.0, "collective_s": 0.0,
           "ops": {"custom-call:tpu_custom_call %k": 20.0, "fusion %a": 9.0,
                   "fusion %b": 21.0}}
    return {"trace": {"devices": [dev]}, "config": CONFIG, "peaks": PEAKS,
            "run": SimpleNamespace(trees=trees, valid_rows=valid_rows),
            "roofline": lambda name: lookup.load_module(
                lookup.find(MANIFEST, f"rooflines/{name}.py"))}


WITH = {"jit_chunk": [7, 29.0], "jit_upd": [7, 21.0], "jit_copy": [13, 0.004]}
WITHOUT = {"jit_chunk": [14, 50.0], "jit_copy": [27, 0.008]}


def test_eval_device_ms_per_tree():
    assert reader("eval_device_ms_per_tree").read(ctx(WITH)) == 3000.0
    assert reader("eval_device_ms_per_tree").read(ctx(WITHOUT)) is None
    per_iteration = {"jit_valid_update_full": [3, 6.0]}
    assert reader("eval_device_ms_per_tree").read(
        ctx(per_iteration, trees=3)) == 2000.0


def test_eval_roofline_by_hand():
    # 7 walks over 2,796,202 rows x (67 one-byte bins + a 4 B score)
    least = 7 * VALID_ROWS * 71 / 819e9
    got = reader("eval_roofline").read(ctx(WITH))
    assert got == pytest.approx(100.0 * least / 21.0)
    assert 0 < got < 100
    assert reader("eval_roofline").read(ctx(WITHOUT)) is None
    assert reader("eval_roofline").read(ctx(WITH, valid_rows=0)) is None


def test_roofline_cannot_pass_100_percent():
    """A walk that moved only the necessary bytes at the peak bandwidth."""
    roof = lookup.load_module(
        lookup.find(MANIFEST, "rooflines/valid_update.py"))
    seconds = 7 * roof.tree_bytes(VALID_ROWS, 67) / PEAKS["hbm_bytes_per_s"]
    got = reader("eval_roofline").read(ctx({"jit_upd": [7, seconds]}))
    assert got == pytest.approx(100.0)


def test_grower_xla_leaves_the_walk_out():
    read = reader("grower_xla_ms_per_tree").read
    # 50 s of ops less 20 s of kernel, less the walk's 21 s, over 7 trees
    assert read(ctx(WITH)) == pytest.approx(1e3 * 9.0 / 7)
    assert read(ctx(WITHOUT, trees=14)) == pytest.approx(1e3 * 30.0 / 14)


def test_recorded_trace_has_the_round_program():
    reduced = trace_reduce.reduce_trace(
        lookup.REPO / "benchmark/tests/data/trace_small.xplane.pb.gz")
    modules = reduced["devices"][0]["modules"]
    assert modules["jit_chunk"][0] == 2
    assert 0 < modules["jit_chunk"][1] <= reduced["busy_s"] * 1.001
    assert all("(" not in name for name in modules)
    assert list(reduced["modules"])[0] == "jit_chunk"
    # a plain training trace: nothing for the evaluation readers to read
    c = ctx(modules)
    assert reader("eval_device_ms_per_tree").read(c) is None
