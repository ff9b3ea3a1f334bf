"""``eval_device_metric_share``: the metric evaluations the device form
computed over all counted, from the program's two counters; ``None`` for a
program that made neither."""
import json

import pytest

from benchmark import check_manifest
from benchmark.lib import lookup

MANIFEST = lookup.load_manifest()
NAME = "eval_device_metric_share"


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
    """The parent program: it counts no evaluations by form."""
    registry.counter("valid_update_trees_routed_total").inc(40)
    assert read() is None


def test_share_of_the_counted_evaluations(registry):
    registry.counter("eval_metrics_host_total").inc(6)
    assert read() == 0.0            # a reading: the program counted
    registry.counter("eval_metrics_device_total").inc(18)
    assert read() == 75.0


def test_all_on_the_device_reads_100(registry):
    registry.counter("eval_metrics_device_total").inc(40)
    assert read() == 100.0


def test_manifest_holds_the_metric():
    """Held to the entry's name, not to its place in ``per_layer``."""
    assert check_manifest.check(MANIFEST) == []
    (m,) = [m for m in MANIFEST["per_layer"] if m["name"] == NAME]
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "program_counter", "layer": "eval",
                 "moves": "train_s_per_tree",
                 "workloads": ["criteo-quant.monitored"]}


def test_on_the_cpu_twin(capsys):
    """One run of the monitored twin cell on the CPU (AUC and logloss on
    an unweighted validation set, every round), then the reader on the
    program's own registry: every evaluation of the run took the device
    form, and the reader reads the whole process's counts."""
    from benchmark import run as bench_run
    from lightgbm_tpu.obs.metrics import global_registry
    before = global_registry.to_dict().get("counters", {})
    rc = bench_run.main(["--workload", "criteo-quant.monitored", "--seed",
                         "2147483901", "--seconds", "0.3", "--trace", "0",
                         "--manifest", "benchmark/tests/data/BENCHMARK.json"])
    assert rc == 0
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    after = global_registry.to_dict().get("counters", {})
    grew = {n: after.get(n, 0) - before.get(n, 0)
            for n in ("eval_metrics_device_total", "eval_metrics_host_total")}
    assert grew["eval_metrics_device_total"] > 0
    assert grew["eval_metrics_host_total"] == 0
    device = after["eval_metrics_device_total"]
    assert read() == 100.0 * device / (
        device + after.get("eval_metrics_host_total", 0))
