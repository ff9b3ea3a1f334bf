"""The three seams a later PR extends with new files only: a kind of
traffic, a reference objective and a generator that returns dataset fields,
all defined in the test's own temporary path, are found by name and used
with no edit to ``benchmark/lib``; and the refactor that made them seams
moved no number of the cell the benchmark had."""
import json
import sys
import textwrap

import pytest

from benchmark import check_manifest, run as bench_run
from benchmark.lib import lookup

TWIN = "benchmark/tests/data/BENCHMARK.json"

KIND = '''
"""A kind of the test's own: the closed training loop, counted in a unit
of its own and tagged."""
from benchmark.lib.traffic import kind_module

PRIMARY = "train_trees_per_s"
LIMITS = {"window_tree_missing": 0}
CALLS = []


def _inner(manifest):
    return kind_module(manifest, {"kind": "train_loop"})


def run(manifest, *args, **kw):
    out = _inner(manifest).run(manifest, *args, **kw)
    out.inner = _inner(manifest)
    CALLS.append("run")
    return out


def primary(run):
    CALLS.append("primary")
    return PRIMARY, run.trees / max(run.window_s, 1e-9)


def numbers(run, detail=None):
    CALLS.append("numbers")
    return run.inner.numbers(run, detail)
'''

OBJECTIVE = '''
"""Cross-entropy with row weights, float64: g = w (p - y), h = w p (1 - p),
the weighted mean loss, the log-odds of the weighted label mean."""
import numpy as np

CALLS = set()


def _w(aux, n):
    return np.asarray(aux["weight"], np.float64) if aux else np.ones(n)


def gradients(score, y, aux=None):
    CALLS.add("gradients")
    p = 1.0 / (1.0 + np.exp(-score))
    w = _w(aux, len(y))
    return w * (p - y), w * p * (1.0 - p)


def loss(score, y, aux=None):
    CALLS.add("loss")
    z = np.where(y > 0, score, -score)
    w = _w(aux, len(y))
    return float(np.sum(w * np.logaddexp(0.0, -z)) / np.sum(w))


def init_score(y, aux=None):
    CALLS.add("init_score")
    w = _w(aux, len(y))
    p = float(np.sum(w * y) / np.sum(w))
    return float(np.log(p / (1.0 - p)))
'''

GENERATOR = '''
"""HIGGS-like rows with a weight a row: three values."""
import numpy as np

from benchmark.lib import lookup

CALLS = []


def generate(seed, rows, features, **args):
    inner = lookup.load_module(lookup.REPO / "benchmark/datagen/higgs_like.py")
    X, y = inner.generate(seed, rows, features, **args)
    w = np.random.default_rng(seed).uniform(0.5, 1.5, rows).astype(np.float32)
    CALLS.append(rows)
    return X, y, {"weight": w}
'''


@pytest.fixture
def own_files(tmp_path):
    """A manifest whose first path is the temporary directory: a kind, an
    objective, a generator, a configuration, a traffic mix and a cell that
    no file of the benchmark knows of."""
    # lookup caches a module by its last two path parts: each test's own
    for name in ("bench_kinds_own_kind", "bench_objectives_cross_entropy",
                 "bench_datagen_own_rows"):
        sys.modules.pop(name, None)
    for rel, text in (("kinds/own_kind.py", KIND),
                      ("objectives/cross_entropy.py", OBJECTIVE),
                      ("datagen/own_rows.py", GENERATOR)):
        path = tmp_path / rel
        path.parent.mkdir(exist_ok=True)
        path.write_text(textwrap.dedent(text))
    config = lookup.load_json(
        lookup.REPO / "benchmark/tests/data/configs/higgs-dense.json")
    config["params"]["objective"] = "cross_entropy"
    config["data"] = {"generator": "own_rows", "args": {}}
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs/own.json").write_text(json.dumps(config))
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic/own_mix.json").write_text(json.dumps(
        {"kind": "own_kind", "warm_rounds": 2}))
    (tmp_path / "cells").mkdir()
    (tmp_path / "cells/own.cell.json").write_text(json.dumps(
        lookup.load_json(lookup.REPO / "benchmark/tests/data/cells/"
                         "higgs-dense.train.json")))
    manifest = {
        "command": ["python3", "benchmark/run.py"],
        "paths": [str(tmp_path), "benchmark/tests/data", "benchmark"],
        "run_seconds": 2, "rehearsal": True,
        "configs": [{"name": "own", "source": "the test", "reduced": [],
                     "file": str(tmp_path / "configs/own.json"),
                     "why": "x"}],
        "workloads": [{"name": "own.cell", "config": "own",
                       "traffic": "own_mix", "chips": 1, "why": "x"}],
        "end_to_end": [
            {"name": "train_trees_per_s", "unit": "trees/s",
             "better": "higher", "bound": 0.03, "source": "host_clock",
             "workloads": ["own.cell"]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.1, "source": "host_clock"}],
        "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return tmp_path, manifest


def test_new_files_are_found_and_used(capsys, own_files):
    tmp_path, manifest = own_files
    rc = bench_run.main(["--workload", "own.cell", "--seed", "5",
                         "--seconds", "0.2", "--trace", "0",
                         "--manifest", str(tmp_path / "BENCHMARK.json")])
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is True, result["compared"]
    kind = lookup.load_module(tmp_path / "kinds/own_kind.py")
    objective = lookup.load_module(tmp_path / "objectives/cross_entropy.py")
    generator = lookup.load_module(tmp_path / "datagen/own_rows.py")
    assert kind.CALLS == ["run", "primary", "numbers"]
    assert objective.CALLS == {"gradients", "loss", "init_score"}
    assert generator.CALLS == [6000]


def test_weights_reach_the_program_and_the_reference(capsys, own_files):
    """The same cell with the reference's objective blind to the weights
    comes out not correct: the fields went to the ``Dataset``, and the
    comparison needs them as ``aux``."""
    tmp_path, _ = own_files
    path = tmp_path / "objectives/cross_entropy.py"
    path.write_text(path.read_text().replace(
        'np.asarray(aux["weight"], np.float64) if aux else np.ones(n)',
        "np.ones(n)"))
    bench_run.main(["--workload", "own.cell", "--seed", "5", "--seconds",
                    "0.2", "--trace", "0",
                    "--manifest", str(tmp_path / "BENCHMARK.json")])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is False, result["compared"]


def test_kind_must_report_a_metric_of_the_cell(capsys, own_files):
    """A kind whose primary metric the manifest does not give the cell
    fails before the program is imported."""
    tmp_path, manifest = own_files
    manifest["end_to_end"][0]["workloads"] = []
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    with pytest.raises(SystemExit) as e:
        bench_run.main(["--workload", "own.cell", "--seed", "5",
                        "--seconds", "0.2", "--trace", "0",
                        "--manifest", str(tmp_path / "BENCHMARK.json")])
    assert e.value.code == 2
    assert "no end-to-end metric" in capsys.readouterr().err


@pytest.mark.parametrize("breakage, needle", [
    (lambda cfg, trf: trf.__setitem__("kind", "no_such_kind"),
     "no kind file"),
    (lambda cfg, trf: cfg["params"].__setitem__("objective", "no_such"),
     "no reference objective"),
])
def test_checker_resolves_kind_and_objective(tmp_path, breakage, needle):
    """check_manifest.py: every cell's kind and objective is a file under
    the paths."""
    cfg = lookup.load_json(
        lookup.REPO / "benchmark/tests/data/configs/criteo-quant.json")
    trf = lookup.load_json(lookup.REPO / "benchmark/traffic/train_loop.json")
    breakage(cfg, trf)
    errors = check_manifest.check_files(
        {"paths": ["benchmark"],
         "workloads": [{"name": "a.b", "config": "a", "traffic": "t"}]},
        configs={"a": cfg}, traffics={"t": trf})
    assert any(needle in e for e in errors), errors


# what the twin of criteo-quant.train printed before the seams were cut
# (commit c23a43f, seed 11, --seconds 0.001: two warm trees and exactly one
# from the window), every digit
BEFORE = {
    "count_mismatch": 30.0, "leaf_value_gap": 0.2732728743797009,
    "hess_gap": 0.028334144094293464, "gain_gap": 0.381513810348313,
    "loss_gap": 0.0016503198506674203, "score_gap": 0.1795345610474002,
    "count_gap": 0.22117400419287211, "hess_noise": 0.028848972396697366,
    "grad_noise": 0.3055360525331185,
    "split_choice_gap": 0.025621738450704312,
    "split_runner_up_gap": 0.8129183592054, "window_tree_missing": 0.0,
    "trees_missing": 0.0, "window_compiles": 0.0, "nothing_done": 0.0}


def test_refactor_moved_no_number(capsys):
    rc = bench_run.main(["--workload", "criteo-quant.train", "--seed", "11",
                         "--seconds", "0.001", "--trace", "0",
                         "--manifest", TWIN])
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    compare = [json.loads(ln) for ln in lines if '"phase": "compare"' in ln]
    assert compare[0]["numbers"] == BEFORE
    assert json.loads(lines[-1])["attempted"] == 1
