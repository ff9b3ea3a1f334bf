"""The readers of the engine loop's whole turns, ``eval_host_ms_per_tree``
and ``engine_unnamed_ms_per_tree``: the right value on planted turns,
``None`` on a ring without the records (the program before the seams, a
cell that bypasses the loop), values from real runs of the CPU twins of
``criteo-quant.monitored`` and ``criteo-job.resume``, and the manifest's
entries."""
import json
from types import SimpleNamespace

import pytest

from benchmark import check_manifest
from benchmark import run as bench_run
from benchmark.lib import lookup

NEW = ("eval_host_ms_per_tree", "engine_unnamed_ms_per_tree")
CELLS = {"eval_host_ms_per_tree": ["criteo-quant.monitored"],
         "engine_unnamed_ms_per_tree": ["criteo-quant.monitored",
                                        "criteo-job.resume"]}


def reader(name):
    manifest = lookup.load_manifest("BENCHMARK.json")
    return lookup.load_module(lookup.find(manifest, f"metrics/{name}.py"))


@pytest.fixture
def ring(monkeypatch):
    from lightgbm_tpu.obs import flight
    r = flight.FlightRecorder(max_events=256, enabled=True, max_dumps=0)
    monkeypatch.setattr(flight, "global_flight", r)
    return r


def plant(ring, name, ts_ms, dur_ms, tid=7, **args):
    ring.feed({"name": name, "ph": "X", "pid": 1, "tid": tid,
               "ts": ts_ms * 1e3, "dur": dur_ms * 1e3, "args": args})


def plant_turn(ring, it, t0, save=False):
    """One turn of 3,000 ms: 2,000 of update seams, 900 of evaluation,
    100 named by nothing; a turn with a save is 80 ms longer, 60 of them
    the save (a part under it)."""
    plant(ring, "macro.host_inputs", t0 + 5, 10.0, it=it,
          parent="engine.step")
    plant(ring, "macro.dispatch", t0 + 15, 20.0, it=it, parent="engine.step")
    plant(ring, "macro.host_fetch", t0 + 35, 1960.0, it=it,
          parent="engine.step")
    plant(ring, "gbdt.drain_pending", t0 + 1995, 10.0, it=it,
          parent="engine.step")
    ring.note("grower.tree", it=it, rounds=20)
    plant(ring, "engine.eval", t0 + 2010, 900.0, it=it, iteration=it,
          parent="engine.step")
    # another thread's record inside the turn is not the turn's
    plant(ring, "ingest.put", t0 + 2950, 40.0, tid=9)
    if save:
        plant(ring, "checkpoint.save", t0 + 2920, 60.0, it=it + 1,
              parent="engine.step")
        plant(ring, "checkpoint.encode", t0 + 2930, 30.0, it=it + 1,
              parent="checkpoint.save")
    dur = 3000.0 + (80.0 if save else 0.0)
    plant(ring, "engine.step", t0, dur, it=it, c=1, parent="engine.train")
    return dur


def test_readers_on_planted_turns(ring):
    for it in range(5):             # two warm turns, then three in the window
        plant_turn(ring, it, 4000.0 * it, save=(it == 4))
    ctx = {"run": SimpleNamespace(trees=3)}
    assert reader("eval_host_ms_per_tree").read(ctx) == pytest.approx(900.0)
    # 100 ms a turn named by nothing, 120 in the turn with the save
    assert reader("engine_unnamed_ms_per_tree").read(ctx) \
        == pytest.approx((100.0 + 100.0 + 120.0) / 3)
    # more trees in the window than the ring holds turns: nothing, not a
    # guess
    for name in NEW:
        assert reader(name).read({"run": SimpleNamespace(trees=9)}) is None


def test_a_job_window_ends_with_its_last_save(ring):
    """``train_job``: the round past the close is grown in a turn of its
    own, which in a traced run holds the profiler's stop; the window's
    turns end with the one that holds the window's last save."""
    for it in range(4):
        plant_turn(ring, it, 4000.0 * it, save=(it in (1, 3)))
    plant(ring, "macro.dispatch", 16005.0, 20.0, it=4, parent="engine.step")
    plant(ring, "engine.step", 16000.0, 45000.0, it=4, c=1,
          parent="engine.train")
    ctx = {"run": SimpleNamespace(trees=2, saves=1)}
    assert reader("engine_unnamed_ms_per_tree").read(ctx) \
        == pytest.approx((100.0 + 120.0) / 2)
    # a job window whose saves the ring does not hold reads nothing
    ring._ring.clear()
    plant_turn(ring, 0, 0.0)
    assert reader("engine_unnamed_ms_per_tree").read(ctx) is None


def test_a_chunked_turn_counts_for_its_trees(ring):
    plant(ring, "engine.eval", 100.0, 600.0, it=0, parent="engine.step")
    plant(ring, "engine.step", 0.0, 1000.0, it=0, c=4,
          parent="engine.train")
    ctx = {"run": SimpleNamespace(trees=4)}
    assert reader("eval_host_ms_per_tree").read(ctx) == pytest.approx(150.0)
    assert reader("engine_unnamed_ms_per_tree").read(ctx) \
        == pytest.approx(100.0)


def test_readers_return_nothing_without_the_records(ring):
    """The program before these seams: ``engine.step`` held the update,
    the evaluation had no ring record, the save ran under
    ``engine.train``.  And a cell that never enters the loop."""
    ctx = {"run": SimpleNamespace(trees=2)}
    for name in NEW:
        assert reader(name).read(ctx) is None, name        # empty ring
    for it in range(3):
        t0 = 3000.0 * it
        plant(ring, "macro.dispatch", t0 + 5, 20.0, it=it,
              parent="engine.step")
        plant(ring, "engine.step", t0, 2000.0, it=it, c=1,
              parent="engine.train")
        plant(ring, "checkpoint.save", t0 + 2500, 60.0, it=it + 1,
              parent="engine.train")
    for name in NEW:
        assert reader(name).read(ctx) is None, name


def drive(capsys, manifest, cell, seed):
    rc = bench_run.main(["--workload", cell, "--seed", str(seed),
                         "--seconds", "0.3", "--trace", "0",
                         "--manifest", manifest])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    window = next(json.loads(line) for line in out
                  if '"phase": "window"' in line)
    return result, window


def test_readers_find_the_programs_own_turns_monitored(capsys):
    result, _ = drive(capsys, "benchmark/tests/data/BENCHMARK.json",
                      "criteo-quant.monitored", 23)
    ctx = {"run": SimpleNamespace(trees=result["attempted"])}
    ev = reader("eval_host_ms_per_tree").read(ctx)
    unnamed = reader("engine_unnamed_ms_per_tree").read(ctx)
    assert ev is not None and ev > 0
    assert unnamed is not None and unnamed >= 0
    from benchmark.metrics._turns import window_turns
    turns, trees = window_turns(ctx)
    assert trees == result["attempted"]
    per_turn = sum(t["dur"] for t in turns) / 1e3 / trees
    assert ev + unnamed < per_turn


def test_readers_find_the_programs_own_turns_job(capsys):
    result, window = drive(capsys, "benchmark/tests/data/job/BENCHMARK.json",
                           "criteo-job.resume", 31)
    ctx = {"run": SimpleNamespace(
        trees=result["attempted"],
        saves=window["info"]["after_close"]["periods"])}
    assert reader("eval_host_ms_per_tree").read(ctx) is None  # no valid set
    unnamed = reader("engine_unnamed_ms_per_tree").read(ctx)
    assert unnamed is not None and unnamed >= 0
    from benchmark.metrics._turns import window_turns
    turns, trees = window_turns(ctx)
    assert trees == result["attempted"]
    # the last turn of the window holds the window's last save
    from benchmark.metrics._program import records
    last_save = records("checkpoint.save")[-1]
    assert turns[-1]["ts"] <= last_save["ts"] \
        <= turns[-1]["ts"] + turns[-1]["dur"]


@pytest.mark.parametrize("name", NEW)
def test_manifest_holds_the_metric(name):
    m = lookup.load_manifest("BENCHMARK.json")
    assert check_manifest.check(m) == []
    (entry,) = [e for e in m["per_layer"] if e["name"] == name]
    assert entry["source"] == "program_span"
    assert entry["moves"] == "train_s_per_tree"
    assert set(CELLS[name]) <= set(entry["workloads"])
    cells = {w["name"] for w in m["workloads"]}
    assert set(entry["workloads"]) <= cells
