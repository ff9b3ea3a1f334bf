"""The readers of what the program records of itself (flight-ring records
and registry counters): the right value on planted records, ``None`` when
the source is missing, and values from a real run of the CPU twin."""
import json
from types import SimpleNamespace

import pytest

from benchmark import check_manifest, run as bench_run
from benchmark.lib import lookup

MANIFEST = lookup.load_manifest()
TWIN = "benchmark/tests/data/BENCHMARK.json"
NEW = ["grower_rounds_per_tree", "grower_batch_fill",
       "engine_host_ms_per_tree", "compile_trace_lower_s",
       "compile_cache_read_s", "compile_backend_s", "compile_programs",
       "ingest_put_wait_s", "ingest_bin_s", "ingest_edges_s"]


def reader(name):
    return lookup.load_module(lookup.find(MANIFEST, f"metrics/{name}.py"))


def ctx(trees):
    return {"run": SimpleNamespace(trees=trees, kind="train_loop")}


@pytest.fixture
def program(monkeypatch):
    """A fresh ring and registry in the program's place."""
    from lightgbm_tpu.obs import flight, metrics
    ring = flight.FlightRecorder(max_events=256, enabled=True, max_dumps=0)
    registry = metrics.MetricsRegistry()
    monkeypatch.setattr(flight, "global_flight", ring)
    monkeypatch.setattr(metrics, "global_registry", registry)
    return ring, registry


def plant(ring, name, ts_ms, dur_ms, **args):
    ring.feed({"name": name, "ph": "X", "pid": 1, "tid": 7,
               "ts": ts_ms * 1e3, "dur": dur_ms * 1e3, "args": args})


def plant_round(ring, it, t0):
    plant(ring, "macro.host_inputs", t0, 4.0, it=it)
    plant(ring, "macro.dispatch", t0 + 5, 10.0, it=it)
    # the seam that waits for the device: not the engine's, not entered
    plant(ring, "macro.host_fetch", t0 + 16, 8000.0, it=it)
    plant(ring, "gbdt.drain_pending", t0 + 8020, 3.0, it=it)


PLANTED = {
    "grower_rounds_per_tree": 21.0,          # (20 + 22) / 2
    "grower_batch_fill": 100.0 * 508 / 1270,
    "engine_host_ms_per_tree": 4.0 + 10.0 + 3.0,
    "compile_trace_lower_s": 5.5,
    "compile_cache_read_s": 7.25,
    "compile_backend_s": 40.0,
    "compile_programs": 181.0,
    "ingest_put_wait_s": 1.5 + 0.25 + 6.0,
    "ingest_bin_s": 20.0 + 1.0,
    "ingest_edges_s": 3.0,
}


@pytest.mark.parametrize("name", NEW)
def test_reader_on_planted_records(program, name):
    ring, registry = program
    # set-up's records, then three rounds of which the window holds two
    plant(ring, "ingest.edges", 0, 3000.0, rows=10)
    plant(ring, "ingest.device_bin", 3000, 21500.0, wait_put_s=1.5,
          bin_s=20.0)
    plant(ring, "ingest.device_bin", 25000, 1300.0, wait_put_s=0.25,
          bin_s=1.0)
    plant(ring, "ingest.to_device", 27000, 6000.0)
    for it, rounds, offered in ((0, 30, 900), (1, 20, 600), (2, 22, 670)):
        plant_round(ring, it, 40000 + 9000 * it)
        ring.note("grower.tree", it=it, k=0, rounds=rounds,
                  offered=offered, applied=254)
    for cname, v in (("compile_trace_seconds", 3.5),
                     ("compile_lower_seconds", 2.0),
                     ("compile_cache_read_seconds", 7.25),
                     ("compile_backend_seconds", 40.0),
                     ("compile_programs_total", 181)):
        registry.counter(cname).inc(v)
    assert reader(name).read(ctx(2)) == pytest.approx(PLANTED[name])


@pytest.mark.parametrize("name", NEW)
def test_reader_is_none_without_its_source(program, name):
    """An older program: the ring holds other things, the registry other
    counters.  And a ring that is disarmed."""
    ring, registry = program
    ring.note("engine.step", i=0, c=1, dur_us=5.0)
    registry.counter("train_iterations_total").inc(3)
    assert reader(name).read(ctx(2)) is None
    ring.enabled = False
    if not name.startswith("compile_"):
        assert reader(name).read(ctx(2)) is None


WINDOW = ["grower_rounds_per_tree", "grower_batch_fill",
          "engine_host_ms_per_tree"]


@pytest.mark.parametrize("name", WINDOW + [n for n in NEW
                                           if n.startswith("ingest_")])
def test_reader_is_none_once_the_ring_overflowed(program, name):
    """A run of more rounds than the ring holds: set-up's records are
    pushed out first, then the window's early rounds.  A sum over what is
    left would be a wrong number, so the reader gives none."""
    ring, _ = program
    plant(ring, "ingest.edges", 0, 3000.0)
    plant(ring, "ingest.device_bin", 3000, 21500.0, wait_put_s=1.5,
          bin_s=20.0)
    plant(ring, "ingest.to_device", 27000, 6000.0)
    rounds = ring._ring.maxlen // 4      # 4 records a round + 1 a tree
    for it in range(rounds):
        plant_round(ring, it, 40000 + 9000 * it)
        ring.note("grower.tree", it=it, k=0, rounds=3, offered=5,
                  applied=4)
    assert ring.dropped > 0
    assert reader(name).read(ctx(rounds)) is None
    if name in WINDOW:       # the last rounds are whole: a short window reads
        assert reader(name).read(ctx(2)) is not None


def test_grower_reader_is_none_without_trees(program):
    ring, _ = program
    ring.note("grower.tree", it=0, k=0, rounds=3, offered=5, applied=4)
    assert reader("grower_rounds_per_tree").read(ctx(0)) is None
    assert reader("grower_batch_fill").read(ctx(0)) is None
    assert reader("engine_host_ms_per_tree").read(ctx(0)) is None


def test_readers_on_the_cpu_twin(capsys):
    """One run of the twin cell on the CPU, then every reader on what the
    program left in its ring and registry.  The twin bins on the host, so
    the kernel's ``ingest_bin_s`` has no source here."""
    from lightgbm_tpu.obs.flight import global_flight
    global_flight._ring.clear()
    rc = bench_run.main(["--workload", "criteo-quant.train", "--seed", "12",
                         "--seconds", "0.3", "--trace", "0",
                         "--manifest", TWIN])
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    trees = result["attempted"]
    assert trees > 0
    got = {name: reader(name).read(ctx(trees)) for name in NEW}
    num_leaves = json.loads((lookup.REPO / "benchmark/tests/data/configs/"
                             "criteo-quant.json").read_text()
                            )["params"]["num_leaves"]
    assert 1 <= got["grower_rounds_per_tree"] <= num_leaves - 1
    assert 0 < got["grower_batch_fill"] <= 100.0
    assert got["engine_host_ms_per_tree"] > 0
    assert got["compile_programs"] >= 1
    for name in ("compile_trace_lower_s", "compile_cache_read_s",
                 "compile_backend_s", "ingest_put_wait_s",
                 "ingest_edges_s"):
        assert got[name] is not None and got[name] >= 0, (name, got)
    assert got["ingest_bin_s"] is None


def test_manifest_holds_the_new_metrics():
    assert check_manifest.check(MANIFEST) == []
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == ["criteo-quant.train",
                                              "criteo-quant.monitored"]
        assert by_name[name]["source"] in ("program_counter",
                                           "program_span")
