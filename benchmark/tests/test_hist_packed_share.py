"""``hist_packed_share``: the accumulate passes whose kernel built its
operands packed over all the passes counted, from the program's two
counters; ``None`` for a program that made neither."""
import json

import pytest

from benchmark import check_manifest
from benchmark.lib import lookup

MANIFEST = lookup.load_manifest()
NAME = "hist_packed_share"
CELLS = ["criteo-quant.train", "criteo-quant.monitored",
         "istella-rank.train", "criteo-cat.train"]


def read():
    reader = lookup.load_module(lookup.find(MANIFEST, f"metrics/{NAME}.py"))
    return reader.read({})


@pytest.fixture
def registry(monkeypatch):
    from lightgbm_tpu.obs import metrics
    reg = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "global_registry", reg)
    return reg


def test_none_without_the_counters(registry):
    """The parent program: it counts no passes."""
    registry.counter("grower_rounds_routed_total").inc(40)
    assert read() is None


def test_share_of_the_counted_passes(registry):
    registry.counter("hist_passes_compared_total").inc(25)
    assert read() == 0.0            # a reading: the program counted
    registry.counter("hist_passes_packed_total").inc(75)
    assert read() == 75.0


def test_all_packed_reads_100(registry):
    registry.counter("hist_passes_packed_total").inc(81)
    assert read() == 100.0


def test_manifest_holds_the_metric():
    """Held to the entry's name, not to its place in ``per_layer``: the
    next PR appends."""
    assert check_manifest.check(MANIFEST) == []
    (m,) = [m for m in MANIFEST["per_layer"] if m["name"] == NAME]
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "program_counter", "layer": "hist_kernel",
                 "moves": "train_s_per_tree", "workloads": CELLS}
    assert [w["name"] for w in MANIFEST["workloads"]
            if w["name"] in CELLS] == CELLS


def test_on_the_cpu_twin(capsys):
    """One run of the twin cell on the CPU, then the reader on the
    program's own registry: whichever grower and histogram family the CPU
    elects, a share of what was counted, or nothing where no kernel pass
    ever ran in this process."""
    from benchmark import run as bench_run
    from lightgbm_tpu.obs.metrics import global_registry
    rc = bench_run.main(["--workload", "criteo-quant.train", "--seed", "13",
                         "--seconds", "0.3", "--trace", "0", "--manifest",
                         "benchmark/tests/data/BENCHMARK.json"])
    assert rc == 0
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    counters = global_registry.to_dict().get("counters", {})
    counted = sum(counters.get(n, 0) for n in ("hist_passes_packed_total",
                                               "hist_passes_compared_total"))
    share = read()
    assert (share is None) if not counted else (0.0 <= share <= 100.0)
