"""The categorical deployment's benchmark files on the CPU: the generator's
laws, the tiny twin of ``criteo-cat.train`` (correct; not correct under the
control and under every planted fault of its kind, ``cat_as_numeric`` among
them), the three new readers on the program's counters, and the manifest's
new entries."""
import json
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmark import check_manifest
from benchmark import run as bench_run
from benchmark.datagen import criteo_cat_like as gen
from benchmark.lib import lookup

TWIN = "benchmark/tests/data/cat/BENCHMARK.json"
CELL = "criteo-cat.train"
NEW = ("grower_routed_share", "cat_split_share", "cat_set_codes_per_split")
COUNTERS = ("grower_rounds_routed_total", "grower_rounds_scanned_total",
            "tree_splits_total", "tree_splits_categorical_total",
            "tree_cat_set_codes_total")


def kind():
    return lookup.load_module(
        lookup.REPO / "benchmark/kinds/train_loop_cat.py")


# ---- the generator --------------------------------------------------------

@pytest.fixture(scope="module")
def rows():
    return gen.generate(2147484001, (1 << 20) + 70000, 39, threads=4)


def test_schema_is_the_click_logs(rows):
    X, y, fields = rows
    assert X.shape == ((1 << 20) + 70000, 39) and X.dtype == np.float32
    assert fields == {"categorical_feature": list(range(13, 39))}
    assert len(gen.CARDINALITIES) == 26 and max(gen.CARDINALITIES) < 2 ** 24
    assert 0.24 < y.mean() < 0.26
    seen = X[~np.isnan(X)]
    assert (seen == np.floor(seen)).all() and seen.min() == 0


def test_codes_are_frequency_ranks_over_the_published_cardinalities(rows):
    X = rows[0]
    for c, card in enumerate(gen.CARDINALITIES):
        col = X[:, 13 + c]
        col = col[~np.isnan(col)]
        assert col.max() <= card - 1
        if card <= 24:
            share = np.bincount(col.astype(int), minlength=card) / len(col)
            assert (np.diff(share) < 0).all() and share[-1] > 0
            want = np.log((np.arange(card) + 2) / (np.arange(card) + 1)) \
                / np.log(card + 1)
            assert np.abs(share - want).max() < 0.004
    # an id column: most codes are rare, the first few are not
    ids = X[:, 13 + 2]
    assert np.nanmax(ids) > 5e6 and (ids == 0).mean() > 0.03


def test_some_cells_are_missing_in_columns_of_each_kind(rows):
    X = rows[0]
    share = np.isnan(X).mean(axis=0)
    for col, want in gen.MISSING.items():
        assert abs(share[col] - want) < 0.004
    assert (share[[c for c in range(39) if c not in gen.MISSING]] == 0).all()
    assert any(c < 13 for c in gen.MISSING) \
        and any(c >= 13 for c in gen.MISSING)
    # C20 (four codes, one-hot mode) has no missing cell: a bin for them
    # would take it out of that mode
    assert 13 + 19 not in gen.MISSING


def test_label_follows_counts_and_categories(rows):
    X, y, _ = rows
    for col in (13 + 8, 13 + 19, 13 + 13, 13 + 2):       # C9 C20 C14 C3
        codes = np.nan_to_num(X[:, col], nan=-1)
        rates = [y[codes == k].mean() for k in range(3)]
        assert max(rates) - min(rates) > 0.02, col
    counts = np.log1p(np.nan_to_num(X[:, :13]))
    corr = [abs(np.corrcoef(counts[:, j], y)[0, 1]) for j in range(13)]
    assert max(corr) > 0.05


def test_rows_follow_the_seed_and_not_the_threads(rows):
    X, y, _ = rows
    X2, y2, _ = gen.generate(2147484001, (1 << 20) + 70000, 39, threads=2)
    assert np.array_equal(X, X2, equal_nan=True) and (y == y2).all()
    X3, _, _ = gen.generate(2147484002, 70000, 39)
    assert not np.array_equal(X[:70000], X3, equal_nan=True)
    with pytest.raises(ValueError):
        gen.generate(1, 100, 67)


# ---- the tiny twin of the cell --------------------------------------------

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


@pytest.mark.parametrize("seed", [11, 2147484012])
def test_twin_is_correct(capsys, seed):
    result, numbers = drive(capsys, seed=seed)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert numbers["cat_splits_followed"] >= 3
    assert result["compared"]["trees_without_cat_split"] == [0.0, 0]


@pytest.mark.parametrize("fault", sorted(kind().FAULTS))
def test_twin_fault_is_not_correct(capsys, fault):
    result, _ = drive(capsys, kind().FAULTS[fault]())
    assert result["correct"] is False and over(result), result["compared"]


def test_a_categorical_node_read_as_numeric_fails_the_sums(capsys):
    """``cat_as_numeric`` is not only seen by the count of categorical
    nodes: rows sent by ``code <= threshold`` land in other leaves, and the
    leaves' sums say so."""
    assert "cat_as_numeric" in kind().FAULTS
    result, numbers = drive(capsys, kind().FAULTS["cat_as_numeric"]())
    assert {"hess_gap", "loss_gap", "trees_without_cat_split"} <= over(result)
    assert numbers["cat_splits_followed"] == 0


def test_twin_control_is_not_correct(capsys, monkeypatch):
    control = lookup.load_json(
        lookup.REPO / "benchmark/controls/criteo-cat.json")
    assert control["params"] == {"stochastic_rounding": False}
    result, _ = drive(capsys, seed=21, monkeypatch=monkeypatch,
                      overlay=control["params"])
    assert result["correct"] is False
    assert {"hess_gap", "grad_noise"} <= over(result), result["compared"]


def test_twin_states_what_the_cell_states():
    full = lookup.load_json(lookup.REPO / "benchmark/configs/criteo-cat.json")
    twin = lookup.load_json(
        lookup.REPO / "benchmark/tests/data/cat/configs/criteo-cat.json")
    differ = {k for k in full["params"]
              if full["params"][k] != twin["params"][k]}
    assert differ == {"num_leaves", "min_sum_hessian_in_leaf"}
    assert full["reduced"] == ["rows", "num_iterations"]
    assert full["published"]["rows"] == 41256555
    assert full["rows"] == 1 << 25              # a rung, filled exactly
    assert full["features"] == 39
    assert tuple(full["published"]["cardinalities"]) == gen.CARDINALITIES
    for key in ("max_cat_threshold", "cat_l2", "cat_smooth",
                "max_cat_to_onehot", "min_data_per_group"):
        assert full["params"][key] == full["published"][key]
    cells = [lookup.load_json(lookup.REPO / p) for p in (
        "benchmark/cells/criteo-cat.train.json",
        "benchmark/tests/data/cat/cells/criteo-cat.train.json")]
    assert cells[0]["reference_trees"] == cells[1]["reference_trees"] == 2
    assert set(cells[1]["limits"]) <= set(cells[0]["limits"])
    traffic = lookup.load_json(
        lookup.REPO / "benchmark/traffic/train_loop_cat.json")
    assert traffic == dict(traffic, kind="train_loop_cat", warm_rounds=2)


# ---- the readers ------------------------------------------------------------

def _reader(name):
    manifest = lookup.load_manifest("BENCHMARK.json")
    return lookup.load_module(lookup.find(manifest, f"metrics/{name}.py"))


def test_readers_on_planted_counters(monkeypatch):
    from lightgbm_tpu.obs import metrics
    reg = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "global_registry", reg)
    for name in NEW:
        assert _reader(name).read({}) is None     # no counter was made
    reg.counter("grower_rounds_scanned_total").inc(30)
    assert _reader("grower_routed_share").read({}) == 0.0
    reg.counter("grower_rounds_routed_total").inc(90)
    assert _reader("grower_routed_share").read({}) == 75.0
    reg.counter("tree_splits_total").inc(254)
    assert _reader("cat_split_share").read({}) == 0.0
    assert _reader("cat_set_codes_per_split").read({}) is None
    reg.counter("tree_splits_categorical_total").inc(127)
    reg.counter("tree_cat_set_codes_total").inc(635)
    assert _reader("cat_split_share").read({}) == 50.0
    assert _reader("cat_set_codes_per_split").read({}) == 5.0


def test_readers_find_the_programs_own_counters(capsys, monkeypatch):
    """After a run of the twin on a fresh registry: the CPU scans, so the
    routed share reads 0; the trees' counters are this run's."""
    from lightgbm_tpu.obs import metrics
    reg = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "global_registry", reg)
    for mod in ("lightgbm_tpu.tree", "lightgbm_tpu.boosting.gbdt"):
        m = sys.modules.get(mod)
        if m is not None and hasattr(m, "_obs_registry"):
            monkeypatch.setattr(m, "_obs_registry", reg)
    _, numbers = drive(capsys, seed=31)
    assert _reader("grower_routed_share").read({}) == 0.0
    share = _reader("cat_split_share").read({})
    codes = _reader("cat_set_codes_per_split").read({})
    assert 0 < share < 100 and 1 <= codes <= 32
    counters = reg.to_dict()["counters"]
    assert set(COUNTERS) - {"grower_rounds_routed_total"} <= set(counters)
    assert counters["tree_splits_categorical_total"] \
        >= numbers["cat_splits_followed"]


# ---- the manifest -----------------------------------------------------------

@pytest.mark.parametrize("rel", ["BENCHMARK.json", TWIN])
def test_manifest_passes(rel):
    manifest = lookup.load_manifest(rel)
    assert check_manifest.check(manifest, allow_extra=("rehearsal",)) == []


def test_new_entries():
    m = lookup.load_manifest("BENCHMARK.json")
    config = m["configs"][-1]
    assert config["name"] == "criteo-cat" \
        and config["reduced"] == ["rows", "num_iterations"] \
        and len(config["source"]) <= 200
    cell = m["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, "criteo-cat", "train_loop_cat", 1)
    new = {e["name"]: e for e in m["per_layer"][-3:]}
    assert tuple(new) == NEW
    for e in new.values():
        assert (e["workloads"], e["layer"], e["moves"], e["source"]) == (
            [CELL], "grower", "train_s_per_tree", "program_counter")
    # the cell reports what criteo-quant.train reports, and its own three
    mine = {e["name"] for e in m["per_layer"] if CELL in e["workloads"]}
    theirs = {e["name"] for e in m["per_layer"]
              if "criteo-quant.train" in e["workloads"]}
    assert mine == theirs | set(NEW)
    assert m["end_to_end"][0]["workloads"][-1] == CELL


def test_run_without_the_system_exits_4(tmp_path):
    """A directory holding only ``BENCHMARK.json`` and ``benchmark/``: the
    run says that the system is not importable and exits 4, for the new
    cell as for the others."""
    shutil.copy(lookup.REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(lookup.REPO / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    done = subprocess.run(
        [sys.executable, "-S", "-c",
         "import sys; sys.path[:0] = [p for p in %r]; "
         "sys.argv = ['run.py', '--workload', %r, '--seed', '1', "
         "'--seconds', '1']; import runpy; "
         "runpy.run_path('benchmark/run.py', run_name='__main__')"
         % ([p for p in sys.path if "site-packages" in p], CELL)],
        cwd=tmp_path, capture_output=True, text=True)
    assert done.returncode == 4, done.stderr[-2000:]
    assert "not importable" in done.stderr
