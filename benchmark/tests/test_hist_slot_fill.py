"""``hist_slot_fill``: live candidates over the slot widths the histogram
passes ran at, from the ``grower.tree`` records; ``None`` for a program
whose records carry no ``slots``."""
from types import SimpleNamespace

import pytest

from benchmark import check_manifest
from benchmark.lib import lookup

MANIFEST = lookup.load_manifest()


def read(trees):
    reader = lookup.load_module(
        lookup.find(MANIFEST, "metrics/hist_slot_fill.py"))
    return reader.read({"run": SimpleNamespace(trees=trees,
                                               kind="train_loop")})


@pytest.fixture
def ring(monkeypatch):
    from lightgbm_tpu.obs import flight
    ring = flight.FlightRecorder(max_events=64, enabled=True, max_dumps=0)
    monkeypatch.setattr(flight, "global_flight", ring)
    return ring


def test_reads_offered_over_slots_of_the_window(ring):
    # a warm round's tree, then the window's two
    ring.note("grower.tree", it=0, k=0, rounds=30, offered=900,
              applied=254, slots=3856)
    ring.note("grower.tree", it=1, k=0, rounds=20, offered=600,
              applied=254, slots=1040)
    ring.note("grower.tree", it=2, k=0, rounds=22, offered=670,
              applied=254, slots=1500)
    assert read(2) == pytest.approx(100.0 * 1270 / 2540)
    assert read(3) == pytest.approx(100.0 * 2170 / 6396)
    assert read(4) is None          # fewer records than trees


def test_none_on_records_without_slots(ring):
    """The parent program: every pass at the round cap, nothing counted."""
    ring.note("grower.tree", it=0, k=0, rounds=20, offered=600, applied=254)
    ring.note("grower.tree", it=1, k=0, rounds=22, offered=670, applied=254,
              slots=1500)
    assert read(2) is None
    assert read(1) == pytest.approx(100.0 * 670 / 1500)
    ring.enabled = False
    assert read(1) is None


def test_none_without_records_or_slots_run(ring):
    assert read(1) is None
    ring.note("grower.tree", it=0, k=0, rounds=0, offered=0, applied=0,
              slots=0)
    assert read(1) is None


def test_manifest_holds_the_metric():
    assert check_manifest.check(MANIFEST) == []
    (m,) = [m for m in MANIFEST["per_layer"] if m["name"] == "hist_slot_fill"]
    assert m == {"name": "hist_slot_fill", "unit": "%", "better": "higher",
                 "source": "program_counter", "layer": "hist_kernel",
                 "moves": "train_s_per_tree",
                 "workloads": ["criteo-quant.train",
                               "criteo-quant.monitored"]}


def test_on_the_cpu_twin(capsys):
    """One run of the twin cell on the CPU (the staged family: every pass
    at the round cap), then the reader on what the program left."""
    import json

    from benchmark import run as bench_run
    from lightgbm_tpu.obs.flight import global_flight
    global_flight._ring.clear()
    rc = bench_run.main(["--workload", "criteo-quant.train", "--seed", "13",
                         "--seconds", "0.3", "--trace", "0", "--manifest",
                         "benchmark/tests/data/BENCHMARK.json"])
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert 0 < read(result["attempted"]) <= 100.0
