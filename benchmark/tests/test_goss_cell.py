"""The GOSS deployment's benchmark files on the CPU: the tiny twin of
``criteo-goss.train`` (correct; not correct under the control and under
every planted fault of its kind), a program without row weights ending the
run at once, the sample check of ``lib/reference_goss.py`` on planted
samples, the two new readers on planted records and on the program's own,
and the manifest's new entries."""
import json

import numpy as np
import pytest

from benchmark import check_manifest
from benchmark import run as bench_run
from benchmark.lib import lookup
from benchmark.lib import reference_goss as ref_goss
from benchmark.objectives import binary

TWIN = "benchmark/tests/data/goss/BENCHMARK.json"
CELL = "criteo-goss.train"
NEW = ("goss_kept_share", "hist_weighted_row_share")
SAMPLE = ("goss_top_missing", "goss_top_extra", "goss_rest_count_gap",
          "goss_weight_gap", "goss_rest_bias_z")


def kind():
    return lookup.load_module(
        lookup.REPO / "benchmark/kinds/train_loop_goss.py")


def _reader(name):
    manifest = lookup.load_manifest("BENCHMARK.json")
    return lookup.load_module(lookup.find(manifest, f"metrics/{name}.py"))


def drive(capsys, fault=None, seed=11, monkeypatch=None, overlay=None):
    if overlay:
        inner = lookup.cell_files

        def laid_over(manifest, workload):
            cell, centry, config, traffic, cell_file = inner(manifest,
                                                             workload)
            config = dict(config, params=dict(config["params"], **overlay))
            return cell, centry, config, traffic, cell_file
        monkeypatch.setattr(lookup, "cell_files", laid_over)
    rc = bench_run.main(["--workload", CELL, "--seed", str(seed),
                         "--seconds", "0.3", "--trace", "0",
                         "--manifest", TWIN], fault=fault)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    assert result["metrics"] == {} and result["device"]["platform"] == "cpu"
    numbers = next(json.loads(line) for line in out
                   if '"phase": "compare"' in line)["numbers"]
    return result, numbers


def over(result):
    return {k for k, (v, lim) in result["compared"].items() if v > lim}


# ---- the tiny twin of the cell --------------------------------------------

@pytest.mark.parametrize("seed", [11, 2147484012])
def test_twin_is_correct(capsys, seed):
    result, numbers = drive(capsys, seed=seed)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    for name in SAMPLE:
        assert name in result["compared"]
    assert numbers["goss_rounds_missing"] == 0


@pytest.mark.parametrize("fault", sorted(kind().FAULTS))
def test_twin_fault_is_not_correct(capsys, fault):
    result, _ = drive(capsys, kind().FAULTS[fault]())
    assert result["correct"] is False and over(result), result["compared"]


@pytest.mark.parametrize("fault, fails", [
    ("goss_unamplified", {"goss_rest_count_gap", "goss_top_extra"}),
    ("goss_top_random", {"goss_top_missing", "goss_top_extra"}),
    ("goss_never", {"goss_rest_count_gap", "goss_top_extra"}),
    ("half_batch", {"goss_top_missing", "goss_top_extra"})])
def test_a_wrong_sample_fails_the_sample_check(capsys, fault, fails):
    result, _ = drive(capsys, kind().FAULTS[fault]())
    assert fails <= over(result), result["compared"]


def test_twin_control_is_not_correct(capsys, monkeypatch):
    control = lookup.load_json(
        lookup.REPO / "benchmark/controls/criteo-goss.json")
    assert control["params"] == {"stochastic_rounding": False}
    result, _ = drive(capsys, seed=21, monkeypatch=monkeypatch,
                      overlay=control["params"])
    assert result["correct"] is False
    assert {"hess_gap", "grad_noise"} <= over(result), result["compared"]


def test_a_program_without_row_weights_ends_at_once(capsys, monkeypatch):
    from lightgbm_tpu.boosting import goss
    monkeypatch.delattr(goss.GOSS, "last_row_weights", raising=False)
    real_init = goss.GOSS.__init__

    def without(self, *a, **k):
        real_init(self, *a, **k)
        del self.last_row_weights
    monkeypatch.setattr(goss.GOSS, "__init__", without)
    with pytest.raises(SystemExit) as done:
        drive(capsys)
    assert done.value.code == 5
    assert "no row weights" in capsys.readouterr().err


def test_twin_states_what_the_cell_states():
    full = lookup.load_json(lookup.REPO / "benchmark/configs/criteo-goss.json")
    quant = lookup.load_json(
        lookup.REPO / "benchmark/configs/criteo-quant.json")
    twin = lookup.load_json(
        lookup.REPO / "benchmark/tests/data/goss/configs/criteo-goss.json")
    differ = {k for k in full["params"]
              if full["params"][k] != twin["params"][k]}
    assert differ == {"num_leaves", "min_sum_hessian_in_leaf"}
    # criteo-quant's deployment, sampled
    assert {k: v for k, v in full["params"].items()
            if k not in ("boosting", "top_rate", "other_rate")} \
        == quant["params"]
    assert (full["params"]["boosting"], full["params"]["top_rate"],
            full["params"]["other_rate"]) == ("goss", 0.2, 0.1)
    for key in ("rows", "features", "data", "hist", "env", "reduced"):
        assert full[key] == quant[key], key
    for key in ("boosting", "top_rate", "other_rate"):
        assert full["published"][key] == full["params"][key]
    cells = [lookup.load_json(lookup.REPO / p) for p in (
        "benchmark/cells/criteo-goss.train.json",
        "benchmark/tests/data/goss/cells/criteo-goss.train.json")]
    assert cells[0]["reference_trees"] == cells[1]["reference_trees"] == 1
    assert set(cells[1]["limits"]) <= set(cells[0]["limits"]) | set(
        kind().LIMITS)
    traffic = lookup.load_json(
        lookup.REPO / "benchmark/traffic/train_loop_goss.json")
    assert traffic == dict(traffic, kind="train_loop_goss", warm_rounds=11)
    # the last warm round is the first GOSS samples: 1 / learning_rate
    assert traffic["warm_rounds"] - 1 == round(
        1 / full["params"]["learning_rate"])


# ---- the sample check -------------------------------------------------------

def _planted(seed=3, n=40000):
    """A sound sample by the published rule from a score, and its parts."""
    rng = np.random.RandomState(seed)
    y = (rng.rand(n) < 0.25).astype(np.float64)
    before = rng.standard_normal(n) * 0.7 - 1.0
    g, h = binary.gradients(before, y)
    s = np.abs(g * h)
    top_k, other_k, amp = ref_goss.sizes(n, 0.2, 0.1)
    thr = np.sort(s)[n - top_k]
    w = np.where(s >= thr, 1.0, 0.0)
    rest = np.flatnonzero(w == 0)
    w[rng.choice(rest, other_k, replace=False)] = np.float32(amp)
    return before, y, w, s, thr


def _sample(before, y, w):
    return ref_goss.sample_numbers(before, w, y, binary, 0.2, 0.1)


def test_a_sound_sample_reads_zero():
    before, y, w, _, _ = _planted()
    got = _sample(before, y, w)
    assert got["goss_top_missing"] == got["goss_top_extra"] == 0
    assert got["goss_rest_count_gap"] == 0
    assert got["goss_weight_gap"] < 1e-6
    assert got["goss_rest_bias_z"] < 5


def test_an_ordered_or_biased_draw_fails_the_z():
    before, y, w, s, _ = _planted()
    rest = np.flatnonzero(w != 1.0)
    k = int((w > 1).sum())
    amp = w[w > 1][0]
    for pick in (rest[:k],                                 # in row order
                 rest[np.argsort(s[rest])[:k]]):           # smallest |g*h|
        bad = np.where(w == 1.0, 1.0, 0.0)
        bad[pick] = amp
        assert _sample(before, y, bad)["goss_rest_bias_z"] > 20


def test_a_wrong_multiplier_fails_the_weight_gap():
    before, y, w, _, _ = _planted()
    w = np.where(w > 1, 7.0, w)
    assert _sample(before, y, w)["goss_weight_gap"] > 0.1


# ---- the readers ------------------------------------------------------------

class _Run:
    rows = 1000
    trees = 2
    info = {"n_pad": 1024}


def test_readers_on_planted_records(monkeypatch):
    from lightgbm_tpu.obs import flight
    ring = flight.FlightRecorder(max_events=64, enabled=True, max_dumps=0)
    monkeypatch.setattr(flight, "global_flight", ring)
    ctx = {"run": _Run()}
    ring.note("grower.tree", it=0, k=0, rounds=9, offered=20, applied=14,
              slots=30, clipped=0, lanes=20)
    ring.note("grower.tree", it=1, k=0, rounds=9, offered=20, applied=14,
              slots=30, clipped=0, lanes=20)
    for name in NEW:                        # records without the counts
        assert _reader(name).read(ctx) is None
    ring.note("grower.tree", it=2, k=0, rounds=9, offered=20, applied=14,
              slots=30, clipped=0, lanes=20, goss_kept=300, goss_top=200)
    ring.note("grower.tree", it=3, k=0, rounds=19, offered=20, applied=14,
              slots=30, clipped=0, lanes=20, goss_kept=320, goss_top=220)
    assert _reader("goss_kept_share").read(ctx) == pytest.approx(31.0)
    # passes 10 and 20: (300 x 10 + 320 x 20) / (1024 x 30)
    assert _reader("hist_weighted_row_share").read(ctx) == pytest.approx(
        100.0 * 9400 / 30720)


def test_readers_find_the_programs_own_records(capsys):
    """After a run of the twin: the window's trees all sampled, the kept
    share near 30 (ties at the threshold are all kept, so at or above)."""
    result, numbers = drive(capsys, seed=31)
    kept = _reader("goss_kept_share")
    rows = _reader("hist_weighted_row_share")

    class Run:
        pass
    run = Run()
    twin = lookup.load_json(
        lookup.REPO / "benchmark/tests/data/goss/configs/criteo-goss.json")
    run.rows = twin["rows"]
    run.trees = result["attempted"]
    run.info = {"n_pad": twin["rows"]}
    share = kept.read({"run": run})
    assert share is not None and 30.0 <= share < 31.0
    assert rows.read({"run": run}) == pytest.approx(share)


# ---- the manifest -----------------------------------------------------------

@pytest.mark.parametrize("rel", ["BENCHMARK.json", TWIN])
def test_manifest_passes(rel):
    manifest = lookup.load_manifest(rel)
    assert check_manifest.check(manifest, allow_extra=("rehearsal",)) == []


def test_new_entries():
    """Held by what each list contains, so that later PRs may append."""
    m = lookup.load_manifest("BENCHMARK.json")
    config = {c["name"]: c for c in m["configs"]}["criteo-goss"]
    assert config["reduced"] == ["rows", "num_iterations"] \
        and config["file"] == "benchmark/configs/criteo-goss.json"
    cell = {w["name"]: w for w in m["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("criteo-goss", "train_loop_goss", 1)
    metrics = {e["name"]: e for e in m["per_layer"]}
    for name in NEW:
        e = metrics[name]
        assert CELL in e["workloads"]
        assert (e["layer"], e["moves"], e["source"], e["unit"]) == (
            "sampling", "train_s_per_tree", "program_counter", "%")
    # the cell reports what criteo-quant.train reports, and its own two
    mine = {e["name"] for e in m["per_layer"] if CELL in e["workloads"]}
    theirs = {e["name"] for e in m["per_layer"]
              if "criteo-quant.train" in e["workloads"]}
    assert theirs <= mine and set(NEW) <= mine
    assert CELL in m["end_to_end"][0]["workloads"]
