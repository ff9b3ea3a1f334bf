"""``correct`` on the tiny twins (CPU): the program as the configuration
states it is correct; the control one precision step down is not; and a
run driven end to end through ``run.main`` with the timed path broken
underneath comes out not correct, once for each fault a cell can have."""
import json

import pytest

from benchmark import run as bench_run
from benchmark.lib import correct, faults, lookup

TWIN = "benchmark/tests/data/BENCHMARK.json"
TRAIN_CELLS = ["higgs-dense.train", "higgs-quant.train",
               "criteo-quant.train", "criteo-quant.monitored"]
MONITORED = "criteo-quant.monitored"


def drive(capsys, workload, fault=None, seed=11):
    rc = bench_run.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", "0.3", "--trace", "0",
                         "--manifest", TWIN], fault=fault)
    assert rc == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    result = json.loads(last)
    assert list(result)[-1] == "compared"
    assert result["metrics"] == {} and result["device"]["platform"] == "cpu"
    return result


@pytest.mark.parametrize("workload", TRAIN_CELLS + ["higgs-dense.score",
                                                   "criteo-dp.train4"])
def test_program_is_correct(capsys, workload):
    result = drive(capsys, workload)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0


@pytest.mark.parametrize("workload", TRAIN_CELLS)
@pytest.mark.parametrize("fault", sorted(faults.TRAIN))
def test_training_fault_is_not_correct(capsys, workload, fault):
    result = drive(capsys, workload, faults.TRAIN[fault]())
    assert result["correct"] is False, result["compared"]
    over = [k for k, (v, lim) in result["compared"].items() if v > lim]
    assert over, result["compared"]


@pytest.mark.parametrize("fault", sorted(faults.EVAL))
def test_evaluation_fault_is_not_correct(capsys, fault):
    """An evaluation one round stale, and a validation score that skipped
    a tree: the recorded metrics of the last counted round part from the
    reference's, and nothing else does."""
    result = drive(capsys, MONITORED, faults.EVAL[fault]())
    assert result["correct"] is False, result["compared"]
    over = {k for k, (v, lim) in result["compared"].items() if v > lim}
    assert over and over <= {"eval_logloss_gap", "eval_auc_gap"}, over


def test_monitored_run_records_every_round(capsys):
    """One ``lgb.train`` call: the window's trees and the warm ones are
    all evaluated, and the numbers of both kinds of comparison are held."""
    result = drive(capsys, MONITORED)
    compared = result["compared"]
    assert compared["eval_rounds_missing"] == [0.0, 0]
    assert {"eval_logloss_gap", "eval_auc_gap", "loss_gap",
            "window_tree_missing"} <= set(compared)
    assert result["correct"] is True and result["attempted"] >= 1


@pytest.mark.parametrize("fault", sorted(faults.SCORE))
def test_scoring_fault_is_not_correct(capsys, fault):
    result = drive(capsys, "higgs-dense.score", faults.SCORE[fault]())
    assert result["correct"] is False, result["compared"]


@pytest.mark.parametrize("workload", TRAIN_CELLS)
@pytest.mark.parametrize("seed", [21, 22, 23])
def test_training_control_is_not_correct(capsys, monkeypatch, workload, seed):
    """The program with the parameters of controls/<config>.json laid over
    the configuration's (the nearest lower precision it has a path for)."""
    inner = lookup.cell_files

    def with_control(manifest, name):
        cell, centry, config, traffic, cell_file = inner(manifest, name)
        control = lookup.load_json(
            lookup.find(manifest, f"controls/{cell['config']}.json"))
        config = dict(config, params=dict(config["params"],
                                          **control["params"]))
        return cell, centry, config, traffic, cell_file

    monkeypatch.setattr(lookup, "cell_files", with_control)
    result = drive(capsys, workload, seed=seed)
    assert result["correct"] is False, result["compared"]


@pytest.mark.parametrize("seed", [31, 32, 33])
def test_scoring_control_is_not_correct(seed):
    """The plain reference put in the program's place with every node's
    comparison made in bfloat16, read at the same sampled rows."""
    import jax
    from benchmark.lib import traffic as traffic_lib
    from benchmark.lib.spans import CompileCounter, Spans
    manifest = lookup.load_manifest(TWIN)
    _, _, config, traffic, cell_file = lookup.cell_files(
        manifest, "higgs-dense.score")
    kind = traffic_lib.kind_module(manifest, traffic)
    run = kind.run(
        manifest, config, traffic, cell_file, seed, 0.05, Spans(),
        CompileCounter(), jax.devices())
    limit = cell_file["limits"]["score_gap"]
    assert kind.numbers(run)["score_gap"] <= limit
    assert kind.control_numbers(run)["score_gap"] > 3 * limit


@pytest.mark.parametrize("feature,wide", [(3, False), (5, True)])
def test_split_choice_reads_a_split_on_the_wrong_feature(feature, wide):
    """The reference's own best gain over every feature and threshold
    against the gain of the chosen split: a stump on the feature that
    carries the label reads nought, one on another feature nearly one."""
    import numpy as np
    from benchmark.lib import reference_gbdt as ref
    rng = np.random.default_rng(5)
    X = rng.random((40_000, 8), dtype=np.float32)
    y = (X[:, 3] + 0.1 * rng.standard_normal(40_000) > 0.5).astype(np.float32)
    stump = {"split_feature": np.array([feature]),
             "threshold": np.array([0.5]), "left_child": np.array([-1]),
             "right_child": np.array([-2]), "leaf_value": np.zeros(2)}
    binary = lookup.load_module(lookup.REPO / "benchmark/objectives/binary.py")
    r = next(ref.follow(X, y, [stump], 0.1, check_nodes=(0,), blocks=3,
                        objective=binary))
    got, best, runner = r["splits"][0]
    assert ((best - got) / best > 0.9) is wide
    assert (abs(best - got) / best < 0.01) is not wide
    assert runner < 0.1 * best or wide


def test_judge_needs_every_limited_number():
    ok, compared = correct.judge({"a": 1.0, "b": 0.0}, {"a": 2.0})
    assert ok and compared == {"a": [1.0, 2.0]}
    ok, _ = correct.judge({"a": float("nan")}, {"a": 2.0})
    assert not ok
    with pytest.raises(KeyError):
        correct.judge({"a": 1.0}, {"zz": 1.0})
