"""``grower_route_lanes_per_round``: the lanes the route compared rows
with over the rounds run, from the ``grower.tree`` records; ``None`` for a
program whose records carry no ``lanes``."""
from types import SimpleNamespace

import pytest

from benchmark import check_manifest
from benchmark.lib import lookup

MANIFEST = lookup.load_manifest()
NAME = "grower_route_lanes_per_round"
CELLS = ["criteo-quant.train", "criteo-quant.monitored", "istella-rank.train",
         "criteo-cat.train", "criteo-job.resume"]


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


def test_reads_lanes_over_rounds_of_the_window(ring):
    # a warm round's tree, then the window's two
    ring.note("grower.tree", it=0, k=0, rounds=30, offered=300,
              applied=254, slots=700, clipped=6, lanes=684)
    ring.note("grower.tree", it=1, k=0, rounds=25, offered=280,
              applied=254, slots=640, clipped=2, lanes=624)
    ring.note("grower.tree", it=2, k=0, rounds=75, offered=900,
              applied=254, slots=1300, clipped=0, lanes=1284)
    assert read(2) == pytest.approx((624 + 1284) / 100)
    assert read(3) == pytest.approx((684 + 624 + 1284) / 130)
    assert read(4) is None          # fewer records than trees


def test_none_on_records_without_lanes(ring):
    """The parent program: its router compared every row with every leaf
    and counted nothing."""
    ring.note("grower.tree", it=0, k=0, rounds=20, offered=600, applied=254,
              slots=1500, clipped=0)
    ring.note("grower.tree", it=1, k=0, rounds=22, offered=300, applied=254,
              slots=700, clipped=3, lanes=400)
    assert read(2) is None
    assert read(1) == pytest.approx(400 / 22)
    ring.enabled = False
    assert read(1) is None


def test_none_without_records_or_rounds(ring):
    assert read(1) is None
    ring.note("grower.tree", it=0, k=0, rounds=0, offered=0, applied=0,
              slots=0, clipped=0, lanes=0)
    assert read(1) is None


def test_manifest_holds_the_metric():
    """Held to what this metric needs, not to equality: the cells are
    among its workloads."""
    assert check_manifest.check(MANIFEST) == []
    (m,) = [m for m in MANIFEST["per_layer"] if m["name"] == NAME]
    assert set(CELLS) <= set(m["workloads"])
    assert {k: v for k, v in m.items() if k != "workloads"} == {
        "name": NAME, "unit": "lanes/round", "better": "lower",
        "source": "program_counter", "layer": "grower",
        "moves": "train_s_per_tree"}


def test_on_the_cpu_twin(capsys):
    """One run of the twin cell on the CPU, then the reader on what the
    program left: the candidate scan's live lanes, at most the cap a
    round."""
    import json

    from benchmark import run as bench_run
    from lightgbm_tpu.obs.flight import global_flight
    global_flight._ring.clear()
    rc = bench_run.main(["--workload", "criteo-quant.train", "--seed", "17",
                         "--seconds", "0.3", "--trace", "0", "--manifest",
                         "benchmark/tests/data/BENCHMARK.json"])
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert 1.0 <= read(result["attempted"]) <= 254.0
