"""The job deployment's benchmark files on the CPU: the tiny twin of
``criteo-job.resume`` (a sound run is correct; the control, every planted
fault of ``lib/faults.TRAIN`` and the kind's own three are not), the
window's whole periods, the harness's own bundle reader, the six new
readers on the program's records and on a program without them, and the
manifest's new entries."""
import ast
import json
import shutil
import subprocess
import sys
import zipfile

import numpy as np
import pytest

from benchmark import check_manifest
from benchmark import run as bench_run
from benchmark.lib import lookup

TWIN = "benchmark/tests/data/job/BENCHMARK.json"
CELL = "criteo-job.resume"
NEW = ("checkpoint_stall_ms_per_tree", "checkpoint_capture_ms_per_save",
       "checkpoint_encode_ms_per_save", "checkpoint_mb_per_save",
       "resume_s", "resume_restore_s")
FROM_THE_PROGRAM = tuple(n for n in NEW if n != "resume_s")
JOB = ("resume_tree_mismatch", "resume_score_mismatch",
       "bundle_state_mismatch", "bundles_missing")


def kind():
    return lookup.load_module(lookup.REPO / "benchmark/kinds/train_job.py")


def _reader(name):
    manifest = lookup.load_manifest("BENCHMARK.json")
    return lookup.load_module(lookup.find(manifest, f"metrics/{name}.py"))


def drive(capsys, fault=None, seed=11, monkeypatch=None, overlay=None,
          seconds="0.3"):
    if overlay:
        inner = lookup.cell_files

        def laid_over(manifest, workload):
            cell, centry, config, traffic, cell_file = inner(manifest,
                                                             workload)
            config = dict(config, params=dict(config["params"], **overlay))
            return cell, centry, config, traffic, cell_file
        monkeypatch.setattr(lookup, "cell_files", laid_over)
    rc = bench_run.main(["--workload", CELL, "--seed", str(seed),
                         "--seconds", seconds, "--trace", "0",
                         "--manifest", TWIN], fault=fault)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    assert result["metrics"] == {} and result["device"]["platform"] == "cpu"
    window = next(json.loads(line) for line in out
                  if '"phase": "window"' in line)
    return result, window


def over(result):
    return {k for k, (v, lim) in result["compared"].items() if not v <= lim}


# ---- the tiny twin of the cell ----------------------------------------------

@pytest.mark.parametrize("seed", [11, 2147484012])
def test_twin_is_correct(capsys, seed):
    result, window = drive(capsys, seed=seed)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0
    for name in JOB:
        assert result["compared"][name] == [0.0, 0]
    info = window["info"]
    assert info["first_round_of_b"] == 6
    assert info["after_kill"]["found"] == info["after_kill"]["trees"] == 5
    # the kill left through the engine's forensic dump, beside the bundles
    assert info["after_kill"]["flight_dumps"] == 1
    assert not list(lookup.REPO.glob("flight_*.json"))


@pytest.mark.parametrize("seconds", ["0.01", "0.3"])
def test_window_holds_whole_periods(capsys, seconds):
    """``k`` periods of 5 rounds and one save, the smallest ``k`` for which
    the seconds have passed; the round past the close is not counted."""
    result, window = drive(capsys, seed=13, seconds=seconds)
    after = window["info"]["after_close"]
    k = after["periods"]
    assert k >= 1 and result["attempted"] == 5 * k == len(
        window["step_seconds"])
    assert window["window_s"] >= float(seconds)
    if k > 1:       # one period less would not have filled the seconds
        assert sum(window["step_seconds"][:5 * (k - 1)]) < float(seconds)
    assert after["found"] == after["trees"] == 10 + 5 * k
    assert after["written"] == k + 2
    assert after["left"] == [f"ckpt_iter_{10 + 5 * (k - j):08d}.lgbckpt"
                             for j in (2, 1, 0)][-min(3, k + 2):]
    # the extra round was grown and thrown away
    assert window["info"]["trees_held"] == 10 + 5 * k
    assert window["spans"]["resume"] > 0 and window["spans"]["kill"] > 0


@pytest.mark.parametrize("fault", sorted(kind().FAULTS))
def test_twin_fault_is_not_correct(capsys, fault):
    result, _ = drive(capsys, kind().FAULTS[fault]())
    assert result["correct"] is False and over(result), result["compared"]


@pytest.mark.parametrize("fault, caught_by", [
    ("restore_skipped", {"resume_tree_mismatch", "resume_score_mismatch"}),
    ("score_perturbed", {"resume_score_mismatch"}),
    ("save_dropped", {"bundle_state_mismatch", "bundles_missing"})])
def test_the_jobs_own_faults_fail_the_jobs_own_limits(capsys, fault,
                                                      caught_by):
    """Each is caught by a number of the job and leaves the training
    numbers sound: the limit 0 is what catches a restore that is skipped or
    a hair off, and a bundle that is not there."""
    result, _ = drive(capsys, kind().FAULTS[fault]())
    assert caught_by <= over(result) <= set(JOB), result["compared"]


def test_twin_control_is_not_correct(capsys, monkeypatch):
    control = lookup.load_json(
        lookup.REPO / "benchmark/controls/criteo-job.json")
    quant = lookup.load_json(
        lookup.REPO / "benchmark/controls/criteo-quant.json")
    assert control["params"] == quant["params"] \
        == {"stochastic_rounding": False}
    result, _ = drive(capsys, seed=21, monkeypatch=monkeypatch,
                      overlay=control["params"])
    assert result["correct"] is False
    assert "grad_noise" in over(result), result["compared"]
    # a lower precision is still resumed exactly: not the job's to catch
    assert not over(result) & set(JOB)


def test_twin_states_what_the_cell_states():
    full = lookup.load_json(lookup.REPO / "benchmark/configs/criteo-job.json")
    quant = lookup.load_json(
        lookup.REPO / "benchmark/configs/criteo-quant.json")
    twin = lookup.load_json(
        lookup.REPO / "benchmark/tests/data/job/configs/criteo-job.json")
    for key in ("published", "rows", "features", "params", "data", "hist",
                "env", "reduced"):
        assert full[key] == quant[key], key      # criteo-quant, unchanged
    assert full["job"] == twin["job"] == {"snapshot_freq": 5,
                                          "snapshot_keep": 3}
    assert full["guarantees"] == twin["guarantees"]
    assert "bit for bit" in full["guarantees"]
    assert {"snapshot_freq", "snapshot_keep", "the kill"} \
        <= set(full["assumed"])
    differ = {k for k in full["params"]
              if full["params"][k] != twin["params"][k]}
    assert differ == {"num_leaves", "min_sum_hessian_in_leaf"}
    cells = [lookup.load_json(lookup.REPO / p) for p in (
        "benchmark/cells/criteo-job.resume.json",
        "benchmark/tests/data/job/cells/criteo-job.resume.json")]
    for cell in cells:
        # one warm tree and the window's last: the parent's traced run
        # took 300.8 s with two (PERF.md section 4)
        assert cell["reference_trees"] == 1
        assert all(cell["limits"][name] == 0 for name in JOB)
    assert set(cells[1]["limits"]) <= set(cells[0]["limits"])
    traffic = lookup.load_json(
        lookup.REPO / "benchmark/traffic/train_job.json")
    assert traffic == dict(traffic, kind="train_job", warm_rounds=2,
                           kill_after_round=6, open_round=11,
                           num_boost_round=10000)


# ---- the harness's own reader -------------------------------------------------

def test_the_bundle_reader_imports_nothing_of_resilience():
    """Durability is checked by code that shares nothing with the code
    that promises it: no file the kind is made of names the package."""
    path = lookup.REPO / "benchmark/kinds/train_job.py"
    tree = ast.parse(path.read_text())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add(node.module or "")
    assert not [n for n in names if "resilience" in n], names
    assert {"zipfile", "hashlib", "pickle"} <= names
    before = set(sys.modules)
    kind()
    assert not [m for m in set(sys.modules) - before if "resilience" in m]


def test_the_bundle_reader_trusts_no_bundle_that_fails(tmp_path):
    """The newest bundle that verifies is the one read; a flipped byte in a
    member, a truncated file and a wrong size in the manifest are each
    passed over for the bundle before."""
    import lightgbm_tpu as lgb
    k = kind()
    rng = np.random.RandomState(0)
    X = rng.rand(500, 5).astype(np.float32)
    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1},
              lgb.Dataset(X, label=(X[:, 0] > 0.5)), 9, verbose_eval=False,
              snapshot_freq=3, snapshot_out=str(tmp_path / "m.txt"))
    d = tmp_path / "m.txt.ckpt"
    assert k.bundles_left(d) == [f"ckpt_iter_{i:08d}.lgbckpt"
                                 for i in (3, 6, 9)]
    got, skipped = k.newest_verified(d)
    assert (got["iteration"], got["trees"], skipped) == (9, 9, 0)
    assert got["train_score"].dtype == np.float32 \
        and got["train_score"].shape[-1] >= 500
    newest = d / "ckpt_iter_00000009.lgbckpt"
    blob = newest.read_bytes()
    with zipfile.ZipFile(newest) as zf:
        info = zf.getinfo("state.pkl")
    at = info.header_offset + 30 + len("state.pkl") + info.file_size // 2
    for broken in (blob[:at] + bytes([blob[at] ^ 1]) + blob[at + 1:],
                   blob[:len(blob) // 2], b""):
        newest.write_bytes(broken)
        got, skipped = k.newest_verified(d)
        assert (got["iteration"], skipped) == (6, 1)
    seen = k.read_back(d, 9, None)
    assert seen["wrong"] == 3.0 and seen["found"] == 6
    score = got["train_score"]
    assert k.read_back(d, 6, score)["wrong"] == 0.0
    assert k.read_back(d, 6, np.nextafter(score, np.float32(9)))["wrong"] \
        == score.size


# ---- the readers -----------------------------------------------------------------

class _Run:
    trees, saves = 10, 2


def _ring(monkeypatch, events):
    from lightgbm_tpu.obs import flight
    rec = flight.FlightRecorder()
    for ev in events:
        rec.feed(dict(ev, ph="X"))
    monkeypatch.setattr(flight, "global_flight", rec)


def _save(ts, dur, parts):
    out = [{"name": "checkpoint.save", "ts": ts, "dur": dur,
            "args": {"it": 5, "bytes": 100}}]
    at = ts
    for name, d in parts:
        out.append({"name": f"checkpoint.{name}", "ts": at, "dur": d,
                    "args": {"parent": "checkpoint.save"}})
        at += d
    return out


def test_readers_on_planted_records(monkeypatch):
    from lightgbm_tpu.obs import metrics
    reg = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "global_registry", reg)
    parts = lambda c, e, w: [("capture", c), ("encode", e), ("write", w)]
    _ring(monkeypatch,
          _save(0.0, 9e6, parts(1e6, 7e6, 1e6))                 # set-up's
          + [{"name": "engine.resume", "ts": 1e7, "dur": 2.5e6,
              "args": {"it": 5, "bytes": 100}}]
          + _save(2e7, 1.0e6, parts(2e5, 5e5, 3e5))
          + _save(4e7, 2.0e6, parts(4e5, 1.1e6, 5e5)))
    ctx = {"run": _Run(), "spans": {"resume": 7.5}}
    # the window's two saves, not the set-up's: 3 s over 10 trees
    assert _reader("checkpoint_stall_ms_per_tree").read(ctx) \
        == pytest.approx(300.0)
    assert _reader("checkpoint_capture_ms_per_save").read(ctx) \
        == pytest.approx(300.0)
    assert _reader("checkpoint_encode_ms_per_save").read(ctx) \
        == pytest.approx(800.0)
    assert _reader("resume_restore_s").read(ctx) == pytest.approx(2.5)
    assert _reader("resume_s").read(ctx) == 7.5
    assert _reader("checkpoint_mb_per_save").read(ctx) is None
    reg.counter("checkpoint_saves_total").inc(4)
    reg.counter("checkpoint_bytes_total").inc(400_000_000)
    assert _reader("checkpoint_mb_per_save").read(ctx) == 100.0


def test_readers_return_nothing_on_a_program_without_the_records(
        monkeypatch):
    """The parent keeps ``checkpoint.save`` off the ring, has no parts, no
    ``engine.resume`` and no counters: five readers return ``None`` and
    raise nothing; the harness's own span is there either way."""
    from lightgbm_tpu.obs import metrics
    monkeypatch.setattr(metrics, "global_registry",
                        metrics.MetricsRegistry())
    _ring(monkeypatch, [{"name": "engine.step", "ts": 0.0, "dur": 1e6,
                         "args": {"it": 3}}])
    ctx = {"run": _Run(), "spans": {"resume": 7.5}}
    for name in FROM_THE_PROGRAM:
        assert _reader(name).read(ctx) is None, name
    assert _reader("resume_s").read(ctx) == 7.5
    # fewer saves on the ring than the window made: nothing, not a guess
    _ring(monkeypatch, _save(0.0, 1e6, [("capture", 1e5)]))
    assert _reader("checkpoint_stall_ms_per_tree").read(ctx) is None
    # and a run that is no job has nothing to read
    assert _reader("resume_s").read({"spans": {}}) is None


def test_readers_find_the_programs_own_records(capsys):
    """After a run of the twin: the program's ring holds the window's
    saves with their parts, the resume, and the counters."""
    from benchmark.metrics import _program
    from lightgbm_tpu.obs.flight import global_flight
    from lightgbm_tpu.obs.metrics import global_registry
    c0 = dict(global_registry.to_dict()["counters"])
    marks = [e["ts"] for e in global_flight.ring_events() if "ts" in e]
    since = max(marks) if marks else 0.0
    result, window = drive(capsys, seed=31)
    k = window["info"]["after_close"]["periods"]

    class Ran:
        trees, saves = result["attempted"], k
    ctx = {"run": Ran(), "spans": window["spans"]}
    values = {name: _reader(name).read(ctx) for name in NEW}
    assert all(v is not None and v > 0 for v in values.values()), values
    saves = [e for e in _program.records("checkpoint.save")
             if e["ts"] > since]
    assert [e["args"]["it"] for e in saves] \
        == [5 * (j + 1) for j in range(k + 2)]
    in_window = sum(e["dur"] for e in saves[-k:]) / 1e3
    assert values["checkpoint_stall_ms_per_tree"] \
        == pytest.approx(in_window / Ran.trees)
    assert values["checkpoint_capture_ms_per_save"] \
        + values["checkpoint_encode_ms_per_save"] <= in_window / k
    assert values["resume_restore_s"] < values["resume_s"]
    c1 = global_registry.to_dict()["counters"]
    assert c1["checkpoint_saves_total"] \
        - c0.get("checkpoint_saves_total", 0) == k + 2
    assert c1["checkpoint_resumes_total"] \
        - c0.get("checkpoint_resumes_total", 0) == 1
    assert c1["checkpoint_bytes_total"] \
        - c0.get("checkpoint_bytes_total", 0) == sum(
            e["args"]["bytes"] for e in saves)


# ---- the manifest ------------------------------------------------------------------

@pytest.mark.parametrize("rel", ["BENCHMARK.json", TWIN])
def test_manifest_passes(rel):
    manifest = lookup.load_manifest(rel)
    assert check_manifest.check(manifest, allow_extra=("rehearsal",)) == []


def test_new_entries():
    m = lookup.load_manifest("BENCHMARK.json")
    config = {c["name"]: c for c in m["configs"]}["criteo-job"]
    assert config["reduced"] == ["rows", "num_iterations"] \
        and len(config["source"]) <= 200 \
        and "snapshot_freq" in config["source"] \
        and "2207.09682" in config["source"]
    assert config["source"] == lookup.load_json(
        lookup.REPO / config["file"])["source"]
    cell = {w["name"]: w for w in m["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("criteo-job", "train_job", 1)
    new = {e["name"]: e for e in m["per_layer"] if e["name"] in NEW}
    assert tuple(new) == NEW
    for e in new.values():
        assert (e["workloads"], e["layer"], e["better"]) \
            == ([CELL], "checkpoint", "lower")
    assert {n for n, e in new.items() if e["moves"] == "setup_s"} \
        == {"resume_s", "resume_restore_s"}
    assert new["resume_s"]["source"] == "host_clock"
    assert new["checkpoint_mb_per_save"]["source"] == "program_counter"
    # the cell reports what criteo-quant.train reports, and its own six
    mine = {e["name"] for e in m["per_layer"] if CELL in e["workloads"]}
    theirs = {e["name"] for e in m["per_layer"]
              if "criteo-quant.train" in e["workloads"]}
    assert mine == theirs | set(NEW)
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert CELL in e2e["train_s_per_tree"]["workloads"]
    # no cell asks for four chips, and the cell count fits the run budget
    assert all(w["chips"] == 1 for w in m["workloads"])


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
