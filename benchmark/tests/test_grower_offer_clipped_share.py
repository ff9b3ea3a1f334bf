"""``grower_offer_clipped_share``: rounds the offer clipped over rounds run,
from the ``grower.tree`` records; ``None`` for a program whose records carry
no ``clipped``."""
from types import SimpleNamespace

import pytest

from benchmark import check_manifest
from benchmark.lib import lookup

MANIFEST = lookup.load_manifest()
NAME = "grower_offer_clipped_share"


def read(trees):
    reader = lookup.load_module(lookup.find(MANIFEST, f"metrics/{NAME}.py"))
    return reader.read({"run": SimpleNamespace(trees=trees,
                                               kind="train_loop")})


@pytest.fixture
def ring(monkeypatch):
    from lightgbm_tpu.obs import flight
    ring = flight.FlightRecorder(max_events=64, enabled=True, max_dumps=0)
    monkeypatch.setattr(flight, "global_flight", ring)
    return ring


def test_reads_clipped_over_rounds_of_the_window(ring):
    # a warm round's tree, then the window's two
    ring.note("grower.tree", it=0, k=0, rounds=30, offered=300,
              applied=254, slots=700, clipped=6)
    ring.note("grower.tree", it=1, k=0, rounds=25, offered=280,
              applied=254, slots=640, clipped=2)
    ring.note("grower.tree", it=2, k=0, rounds=75, offered=900,
              applied=254, slots=1300, clipped=0)
    assert read(2) == pytest.approx(100.0 * 2 / 100)
    assert read(3) == pytest.approx(100.0 * 8 / 130)
    assert read(4) is None          # fewer records than trees


def test_zero_is_a_reading(ring):
    """No round clipped is 0%, not absent: the program counted."""
    ring.note("grower.tree", it=0, k=0, rounds=75, offered=900,
              applied=254, slots=1300, clipped=0)
    assert read(1) == 0.0


def test_none_on_records_without_clipped(ring):
    """The parent program: every round offers up to the cap, no count."""
    ring.note("grower.tree", it=0, k=0, rounds=20, offered=600, applied=254,
              slots=1500)
    ring.note("grower.tree", it=1, k=0, rounds=22, offered=300, applied=254,
              slots=700, clipped=3)
    assert read(2) is None
    assert read(1) == pytest.approx(100.0 * 3 / 22)
    ring.enabled = False
    assert read(1) is None


def test_none_without_records_or_rounds(ring):
    assert read(1) is None
    ring.note("grower.tree", it=0, k=0, rounds=0, offered=0, applied=0,
              slots=0, clipped=0)
    assert read(1) is None


def test_manifest_holds_the_metric():
    assert check_manifest.check(MANIFEST) == []
    (m,) = [m for m in MANIFEST["per_layer"] if m["name"] == NAME]
    assert m["workloads"][:3] == ["criteo-quant.train",
                                  "criteo-quant.monitored",
                                  "istella-rank.train"]
    assert {k: v for k, v in m.items() if k != "workloads"} == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "grower",
        "moves": "train_s_per_tree"}


def test_on_the_cpu_twin(capsys):
    """One run of the twin cell on the CPU, then the reader on what the
    program left: the serial or the rounds grower, either counts."""
    import json

    from benchmark import run as bench_run
    from lightgbm_tpu.obs.flight import global_flight
    global_flight._ring.clear()
    rc = bench_run.main(["--workload", "criteo-quant.train", "--seed", "13",
                         "--seconds", "0.3", "--trace", "0", "--manifest",
                         "benchmark/tests/data/BENCHMARK.json"])
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert 0.0 <= read(result["attempted"]) <= 100.0
