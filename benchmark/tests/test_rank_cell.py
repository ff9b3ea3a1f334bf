"""The ranking deployment's benchmark files on the CPU: the generator's
laws, the reference objective against a literal per-pair loop, the tiny twin
of ``istella-rank.train`` (correct; not correct under the control, every
planted fault and a departure in the objective's arithmetic), the three
``rank_*`` readers on a recorded ring, and the manifest's new entries."""
import json

import numpy as np
import pytest

from benchmark import check_manifest
from benchmark import run as bench_run
from benchmark.datagen import istella_like
from benchmark.lib import faults, lookup
from benchmark.objectives import lambdarank as ref

TWIN = "benchmark/tests/data/rank/BENCHMARK.json"
CELL = "istella-rank.train"
RANK_KEYS = ("sigmoid", "lambdarank_norm", "lambdarank_truncation_level",
             "label_gain")


# ---- the generator --------------------------------------------------------

@pytest.fixture(scope="module")
def rows():
    return istella_like.generate(2147484001, 60000, 220, queries=190)


def test_lengths_are_whole_queries_that_sum_to_rows(rows):
    X, y, fields = rows
    sizes = fields["group"]
    assert X.shape == (60000, 220) and X.dtype == np.float32
    assert len(sizes) == 190 and sizes.sum() == 60000
    assert sizes.min() >= 10 and sizes.max() <= 1200
    assert sizes.std() > 0.5 * sizes.mean()      # very uneven, as real logs


def test_lengths_fit_at_the_cells_own_size():
    sizes = istella_like.query_lengths(2147484002, 7325625, 23219)
    assert sizes.sum() == 7325625 and len(sizes) == 23219
    assert sizes.min() >= 10 and sizes.max() <= 1200
    with pytest.raises(ValueError):
        istella_like.query_lengths(1, 100, 20)   # 20 x 10 > 100


def test_grade_law(rows):
    _, y, fields = rows
    sizes = fields["group"]
    share = np.bincount(y.astype(int), minlength=5) / len(y)
    assert 0.94 < share[0] < 0.975
    assert share[1] > share[2] > share[3] > share[4] > 0
    starts = np.concatenate([[0], np.cumsum(sizes)])
    relevant = np.array([y[a:b].max() > 0
                         for a, b in zip(starts[:-1], starts[1:])])
    assert 0.9 <= relevant.mean() < 1.0          # some queries hold none


def test_column_laws(rows):
    X = rows[0]
    counts, dense, sparse = X[:, :59], X[:, 59:160], X[:, 160:]
    assert (counts == np.floor(counts)).all() and counts.max() > 100
    assert 0.4 < dense.mean() < 0.6 and dense.max() <= 1.0
    assert 0.75 < (sparse == 0).mean() < 0.85


def test_rows_follow_the_seed_and_not_the_threads(rows):
    X, y, fields = rows
    X2, y2, f2 = istella_like.generate(2147484001, 60000, 220, queries=190,
                                       threads=2)
    assert (X == X2).all() and (y == y2).all() \
        and (fields["group"] == f2["group"]).all()
    X3, y3, f3 = istella_like.generate(2147484002, 60000, 220, queries=190)
    assert not (X == X3).all() and not (fields["group"] == f3["group"]).all()


# ---- the reference objective ----------------------------------------------

def literal_lambdarank(score, label, sizes, sigmoid, norm, level, gains):
    """LightGBM's ``GetGradientsForOneQuery`` written out pair by pair."""
    lam, hes = np.zeros(len(score)), np.zeros(len(score))
    start = 0
    for cnt in sizes:
        s, lab = score[start:start + cnt], label[start:start + cnt]
        best = sorted((gains[int(v)] for v in lab), reverse=True)[:level]
        max_dcg = sum(g / np.log2(2 + r) for r, g in enumerate(best))
        inv = 1.0 / max_dcg if max_dcg > 0 else 0.0
        order = sorted(range(cnt), key=lambda a: -s[a])     # stable
        total = 0.0
        for i in range(min(cnt - 1, level)):
            for j in range(i + 1, cnt):
                a, b = order[i], order[j]
                if lab[a] == lab[b]:
                    continue
                (hi, hr), (lo, lr) = ((a, i), (b, j)) if lab[a] > lab[b] \
                    else ((b, j), (a, i))
                ds = s[hi] - s[lo]
                delta = ((gains[int(lab[hi])] - gains[int(lab[lo])])
                         * abs(1 / np.log2(2 + hr) - 1 / np.log2(2 + lr))
                         * inv)
                if norm and s[order[0]] != s[order[-1]]:
                    delta /= 0.01 + abs(ds)
                rho = 1.0 / (1.0 + np.exp(sigmoid * ds))
                p_lambda = -sigmoid * delta * rho
                p_hess = sigmoid * sigmoid * delta * rho * (1.0 - rho)
                lam[start + lo] -= p_lambda
                lam[start + hi] += p_lambda
                hes[start + lo] += p_hess
                hes[start + hi] += p_hess
                total -= 2 * p_lambda
        if norm and total > 0:
            factor = np.log2(1 + total) / total
            lam[start:start + cnt] *= factor
            hes[start:start + cnt] *= factor
        start += cnt
    return lam, hes


@pytest.fixture
def reference_parameters():
    stated = dict(ref.PARAMS)
    yield ref
    ref.configure(**stated)


@pytest.mark.parametrize("norm", [True, False])
@pytest.mark.parametrize("level", [20, 2])
def test_reference_is_the_literal_loop(reference_parameters, norm, level):
    rng = np.random.default_rng(17)
    sizes = np.array([1, 2, 9, 30, 9, 44, 3])
    n = sizes.sum()
    label = rng.choice(5, n, p=[.6, .15, .1, .1, .05]).astype(np.float32)
    label[3:12] = 1.0
    ref.configure(lambdarank_norm=norm, lambdarank_truncation_level=level,
                  sigmoid=1.5)
    for score in (np.zeros(n), rng.normal(size=n),
                  np.round(rng.normal(size=n), 1)):
        lam, hes = ref.gradients(score, label, {"group": sizes})
        lam_l, hes_l = literal_lambdarank(
            score, label, sizes, 1.5, norm, level, ref.PARAMS["label_gain"])
        np.testing.assert_allclose(lam, lam_l, rtol=1e-11, atol=1e-14)
        np.testing.assert_allclose(hes, hes_l, rtol=1e-11, atol=1e-14)


def test_loss_is_one_minus_mean_ndcg_at_10():
    sizes = np.array([3, 2, 12])
    label = np.array([0, 2, 1, 0, 0] + [0] * 11 + [4], np.float32)
    score = np.array([.1, .9, .5, .3, .2] + list(np.arange(12) * -1.0))
    # query 0 ranked ideally: 1.0; query 1 has no relevant document: 1.0;
    # query 2 ranks its one relevant document 12th, outside the top 10: 0.0
    assert ref.loss(score, label, {"group": sizes}) == pytest.approx(1 / 3)
    assert ref.init_score(label) == 0.0


def test_reference_needs_the_groups():
    with pytest.raises(ValueError):
        ref.gradients(np.zeros(3), np.zeros(3), {})
    with pytest.raises(ValueError):
        ref.gradients(np.zeros(3), np.zeros(3), {"group": [2]})


def test_reference_reads_the_configurations_parameters():
    full = lookup.load_json(lookup.REPO / "benchmark/configs/istella-rank.json")
    twin = lookup.load_json(
        lookup.REPO / "benchmark/tests/data/rank/configs/istella-rank.json")
    for key in RANK_KEYS:
        assert ref.PARAMS[key] == full["params"][key] == twin["params"][key]
    assert full["reduced"] == ["num_iterations"]
    assert full["rows"] == full["published"]["rows"] == 7325625
    assert full["features"] == full["published"]["features"] == 220
    assert full["data"]["args"]["queries"] == full["published"]["queries"]


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
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["metrics"] == {} and result["device"]["platform"] == "cpu"
    return result


def over(result):
    return {k for k, (v, lim) in result["compared"].items() if v > lim}


@pytest.mark.parametrize("seed", [11, 2147484012])
def test_twin_is_correct(capsys, seed):
    result = drive(capsys, seed=seed)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0


@pytest.mark.parametrize("fault", sorted(faults.TRAIN))
def test_twin_fault_is_not_correct(capsys, fault):
    result = drive(capsys, faults.TRAIN[fault]())
    assert result["correct"] is False and over(result), result["compared"]


def test_twin_control_is_not_correct(capsys, monkeypatch):
    """Leaf values left at the 4-level sums (controls/istella-rank.json)."""
    control = lookup.load_json(
        lookup.REPO / "benchmark/controls/istella-rank.json")
    assert control["params"] == {"quant_train_renew_leaf": False}
    result = drive(capsys, seed=21, monkeypatch=monkeypatch,
                   overlay=control["params"])
    assert result["correct"] is False
    assert "leaf_value_gap" in over(result), result["compared"]


def test_twin_sees_the_objectives_arithmetic(capsys, monkeypatch):
    """``lambdarank_norm=false`` in the program while the reference keeps
    the configuration's: the comparison follows the gradients, not only the
    histograms."""
    result = drive(capsys, seed=22, monkeypatch=monkeypatch,
                   overlay={"lambdarank_norm": False})
    assert result["correct"] is False
    assert "leaf_value_gap" in over(result), result["compared"]


def test_order_of_near_tied_scores_moves_the_gradients():
    """Why the cell follows one warm tree and not two: after the first tree
    the scores are its leaves' values, and the gradients depend on their
    ORDER.  Two leaves 1e-7 apart, in the other order on the other side
    (f32 sums against float64), give some rows another gradient by much
    more than any limit of the cell, and neither side is wrong.  (On the
    chip: seed 1561828543, PERF.md section 2b.)"""
    rng = np.random.default_rng(5)
    sizes = np.array([40, 33, 64])
    n = int(sizes.sum())
    label = (rng.random(n) < 0.2) * rng.integers(1, 5, n)
    leaf = rng.integers(0, 6, n)
    value = np.array([-0.2, -0.1, 0.05, 0.05 + 1e-7, 0.1, 0.2])
    other = value.copy()
    other[[2, 3]] = value[[3, 2]]           # the same two values, swapped
    g1, h1 = ref.gradients(value[leaf], label, {"group": sizes})
    g2, h2 = ref.gradients(other[leaf], label, {"group": sizes})
    assert np.abs(value[leaf] - other[leaf]).max() <= 1.0000001e-7
    assert np.abs(g1 - g2).max() > 1e-2 and np.abs(h1 - h2).max() > 1e-3
    # rows of neither leaf rank as before; what moves them is the scale
    # of their query's sums
    moved = np.abs(g1 - g2) > 1e-9
    assert moved[np.isin(leaf, [2, 3])].any()


def test_followed_trees_start_from_a_score_both_sides_share():
    """The first tree (both start from 0) and the window's last (followed
    from the program's own score): ``reference_trees`` 1 in the cell's file
    and in the twin's, under two warm rounds."""
    full = lookup.load_json(
        lookup.REPO / "benchmark/cells/istella-rank.train.json")
    twin = lookup.load_json(
        lookup.REPO / "benchmark/tests/data/rank/cells/istella-rank.train.json")
    traffic = lookup.load_json(lookup.REPO / "benchmark/traffic/train_loop.json")
    assert full["reference_trees"] == twin["reference_trees"] == 1
    assert traffic["warm_rounds"] == 2
    assert set(twin["limits"]) <= set(full["limits"])


# ---- the readers ------------------------------------------------------------

def _reader(name):
    manifest = lookup.load_manifest("BENCHMARK.json")
    return lookup.load_module(lookup.find(manifest, f"metrics/{name}.py"))


def test_readers_on_a_recorded_ring(monkeypatch):
    from benchmark.metrics import _program
    record = {"name": "rank.init", "ph": "X", "ts": 5.0, "dur": 612000.0,
              "args": {"rows": 7325625, "queries": 23219, "slots": 10814208,
                       "pair_slots": 216281088, "label_pairs": 92879081}}
    monkeypatch.setattr(
        _program, "records",
        lambda *names, whole_run=False: [record] if "rank.init" in names
        else None)
    from benchmark.metrics import _rank
    monkeypatch.setattr(_rank, "records", _program.records)
    assert _reader("rank_init_s").read({}) == pytest.approx(0.612)
    assert _reader("rank_pair_slots_per_tree").read({}) == 216281088.0
    assert _reader("rank_pair_fill").read({}) == pytest.approx(
        100 * 92879081 / 216281088)


def test_readers_return_none_without_the_record(monkeypatch):
    from benchmark.metrics import _rank
    monkeypatch.setattr(_rank, "records",
                        lambda *names, whole_run=False: None)
    for name in ("rank_init_s", "rank_pair_slots_per_tree", "rank_pair_fill"):
        assert _reader(name).read({}) is None


def test_host_bin_reader(monkeypatch):
    """The seconds of every ``ingest.host_bin`` record; ``None`` where the
    ring holds none (the kernel binned, or the parent's annotation-only
    span)."""
    reader = _reader("ingest_host_bin_s")
    ring = [{"name": "ingest.host_bin", "ph": "X", "ts": 1.0,
             "dur": 27500000.0, "args": {"rows": 7325625}},
            {"name": "ingest.host_bin", "ph": "X", "ts": 9.0,
             "dur": 500000.0, "args": {"rows": 1000}}]
    monkeypatch.setattr(reader, "records",
                        lambda *names, whole_run=False: ring)
    assert reader.read({}) == pytest.approx(28.0)
    monkeypatch.setattr(reader, "records",
                        lambda *names, whole_run=False: None)
    assert reader.read({}) is None


def test_readers_find_the_programs_own_record(capsys, monkeypatch):
    """After a run of the twin the ring holds the program's record and the
    readers read it."""
    from lightgbm_tpu.obs.flight import global_flight
    drive(capsys, seed=31)
    # what earlier tests of this process pushed out is not this run's
    monkeypatch.setattr(global_flight, "dropped", 0)
    fill = _reader("rank_pair_fill").read({})
    slots = _reader("rank_pair_slots_per_tree").read({})
    assert 0 < fill <= 100 and slots > 8192
    assert _reader("rank_init_s").read({}) > 0
    # off the accelerator the host bins, so its record is there too
    assert _reader("ingest_host_bin_s").read({}) > 0


# ---- the manifest -----------------------------------------------------------

@pytest.mark.parametrize("rel", ["BENCHMARK.json", TWIN])
def test_manifest_passes(rel):
    manifest = lookup.load_manifest(rel)
    assert check_manifest.check(manifest, allow_extra=("rehearsal",)) == []


def test_new_entries():
    m = lookup.load_manifest("BENCHMARK.json")
    config = m["configs"][-1]
    assert config["name"] == "istella-rank" \
        and config["reduced"] == ["num_iterations"]
    cell = m["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, "istella-rank", "train_loop", 1)
    new = {e["name"]: e for e in m["per_layer"][-4:]}
    assert set(new) == {"rank_pair_fill", "rank_pair_slots_per_tree",
                        "rank_init_s", "ingest_host_bin_s"}
    assert all(e["workloads"] == [CELL] for e in new.values())
    assert {n: e["layer"] for n, e in new.items()} == {
        "rank_pair_fill": "objective", "rank_pair_slots_per_tree": "objective",
        "rank_init_s": "objective", "ingest_host_bin_s": "ingest"}
    assert new["rank_init_s"]["moves"] == "setup_s" \
        and new["ingest_host_bin_s"]["moves"] == "setup_s"
    listed = {e["name"] for e in m["per_layer"] if CELL in e["workloads"]}
    # not read on this cell: no validation set; and at 220 columns the
    # planner elects host binning (``ingest_variant`` "host"), so the ring
    # holds no ``ingest.device_bin`` record and no ``bin_s``
    silent = {"eval_device_ms_per_tree", "eval_roofline", "ingest_bin_s"}
    assert silent.isdisjoint(listed)
    assert len(listed) == len(m["per_layer"]) - len(silent)
    assert CELL in m["end_to_end"][0]["workloads"]
