"""The bench contract: ``python bench.py`` is ONE process.  It refuses to
measure without a TPU (non-zero exit, no result under a device metric's
name); with ``BENCH_WORKER_ALLOW_CPU=1`` CI walks the same stage pipeline
on the CPU, and every stage line says which device it ran on."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(env_extra, timeout=240):
    env = dict(os.environ)
    env.update({
        "BENCH_ROWS": "5000",
        "BENCH_TREES": "3",
        "BENCH_LEAVES": "15",
        "BENCH_BIN": "63",
        "JAX_PLATFORMS": "cpu",
    })
    env.update(env_extra)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)
    stages = []
    for ln in proc.stdout.strip().splitlines():
        try:
            obj = json.loads(ln)
        except ValueError:
            continue
        if isinstance(obj, dict) and obj.get("stage"):
            stages.append(obj)
    return proc, stages


def _run_worker(env_extra, timeout=240):
    return _run_bench(dict(env_extra, BENCH_WORKER_ALLOW_CPU="1"),
                      timeout)[1]


def test_bench_without_tpu_refuses_to_measure():
    """No TPU and no BENCH_WORKER_ALLOW_CPU: exit non-zero after the init
    line, which names the platform it found and says ok=false — there is
    no CPU continuation, and nothing is printed under a metric's name."""
    proc, stages = _run_bench({"BENCH_JOURNAL": "0"})
    assert proc.returncode == 3, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert [s["stage"] for s in stages] == ["init"]
    assert stages[0]["ok"] is False and stages[0]["platform"] == "cpu"
    assert "sec_per_tree" not in proc.stdout
    assert "metric" not in proc.stdout
    assert "refusing to measure" in proc.stderr


def test_bench_cpu_pipeline_stamps_every_stage_with_its_device(tmp_path):
    """The CI walk (ALLOW_CPU) runs the smoke stage end to end in the one
    process, and every stage line carries platform / device_kind /
    n_devices — a CPU number can never pass for a device's."""
    proc, stages = _run_bench({
        "BENCH_WORKER_ALLOW_CPU": "1",
        "BENCH_JOURNAL": str(tmp_path / "journal.json"),
        "BENCH_ONLY": "smoke", "BENCH_SMOKE_ROWS": "5000",
        "BENCH_SKIP_OBS": "1"})
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert [s["stage"] for s in stages] == ["init", "smoke"]
    for s in stages:
        assert s["platform"] == "cpu" and s["n_devices"] >= 1, s
        assert s["device_kind"], s
    smoke = stages[1]
    assert "error" not in smoke and smoke["sec_per_tree"] > 0, smoke
    # a CPU run has no device utilization to report
    assert "mfu_histogram_lower_bound" not in smoke


def test_bench_journal_resume_after_crash(tmp_path):
    """Stage-journal contract: a run that dies after banking a stage must
    NOT re-execute it on rerun — the journal replays it and only the
    missing stages run (round 5 lost ranking+epsilon to exactly this)."""
    journal = str(tmp_path / "journal.json")
    # first run "crashes" after kernel_probe (only that stage selected)
    s1 = _run_worker({"BENCH_JOURNAL": journal,
                      "BENCH_ONLY": "kernel_probe"})
    assert any(s["stage"] == "kernel_probe" and "error" not in s
               for s in s1), s1
    d = json.load(open(journal))
    assert "kernel_probe" in d["stages"]

    # rerun wants kernel_probe + hist_probe: the first must come from the
    # journal (no re-execution), the second runs fresh and is banked
    s2 = _run_worker({"BENCH_JOURNAL": journal,
                      "BENCH_ONLY": "kernel_probe,hist_probe"})
    kp = [s for s in s2 if s["stage"] == "kernel_probe"]
    hp = [s for s in s2 if s["stage"] == "hist_probe"]
    assert kp and kp[0].get("journal") is True, kp
    assert hp and "error" not in hp[0] and "journal" not in hp[0], hp
    d = json.load(open(journal))
    assert set(d["stages"]) == {"kernel_probe", "hist_probe"}


def test_bench_collective_probe_stage(tmp_path):
    """The pod-scale collective micro-bench rides the stage journal like
    every probe: BENCH_ONLY selects it, the journaled result carries the
    per-tier byte fields, and the acceptance signal (voting DCN bytes
    strictly below data-parallel at equal trees) holds."""
    journal = str(tmp_path / "journal.json")
    stages = _run_worker({"BENCH_JOURNAL": journal,
                          "BENCH_ONLY": "collective_probe"})
    cp = [s for s in stages
          if s["stage"] == "collective_probe" and "error" not in s]
    assert cp, stages
    out = cp[0]
    assert {"mesh_shape", "ici_bytes", "dcn_bytes", "hierarchy_elected",
            "voting_k", "measured_ms"} <= out.keys(), sorted(out)
    for payload in ("f32", "quant"):
        assert out[payload]["voting_dcn_below_data"], out[payload]
        assert out[payload]["voting_parallel"]["dcn_bytes"] \
            < out[payload]["data_parallel"]["dcn_bytes"]
    d = json.load(open(journal))
    assert "collective_probe" in d["stages"]


def test_bench_diff_gate(tmp_path):
    """tools/bench_diff.py is the perf gate: an unchanged journal passes
    (exit 0), a synthetic 2x sec_per_tree regression is flagged by name
    with a nonzero exit, and the last stdout line is one JSON verdict."""
    base = {"fingerprint": "fp", "stages": {
        "full@200000": {"sec_per_tree": 0.5, "value": 25.0,
                        "holdout_auc": 0.965, "iters_per_sec": 2.0,
                        "compile_seconds": 8.0,
                        "compile_cache": {"entries_after": 4}},
        "serving": {"p99_ms": 12.0, "qps": 900.0}}}
    a = tmp_path / "old.json"
    b = tmp_path / "new.json"
    a.write_text(json.dumps(base))
    b.write_text(json.dumps(base))

    def run(old, new, *extra):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "bench_diff.py"),
             str(old), str(new), *extra],
            capture_output=True, text=True, timeout=60)

    proc = run(a, b)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    verdict = json.loads(proc.stdout.strip().splitlines()[-1])
    assert verdict["ok"] is True and verdict["regressions"] == []
    assert verdict["stages_compared"] == 2

    worse = json.loads(json.dumps(base))
    worse["stages"]["full@200000"]["sec_per_tree"] = 1.0     # 2x slower
    c = tmp_path / "regressed.json"
    c.write_text(json.dumps(worse))
    proc = run(a, c)
    assert proc.returncode == 1, proc.stdout
    verdict = json.loads(proc.stdout.strip().splitlines()[-1])
    assert verdict["ok"] is False
    regressed = {r["metric"] for r in verdict["regressions"]}
    assert regressed == {"sec_per_tree"}
    assert "REGRESSION" in proc.stdout

    # per-metric threshold override loosens the gate
    proc = run(a, c, "--threshold", "sec_per_tree=2.5")
    assert proc.returncode == 0, proc.stdout

    # a higher-is-better metric collapsing to ZERO must not slip through
    # the sub-noise-floor branch (qps=0 IS the regression)
    dead = json.loads(json.dumps(base))
    dead["stages"]["serving"]["qps"] = 0.0
    e = tmp_path / "collapsed.json"
    e.write_text(json.dumps(dead))
    proc = run(a, e)
    assert proc.returncode == 1, proc.stdout
    verdict = json.loads(proc.stdout.strip().splitlines()[-1])
    assert {r["metric"] for r in verdict["regressions"]} == {"qps"}

    # a driver-format file ({"n", "rc", "parsed"}) compares as stage
    # "full" — but only against a side that HAS a full stage; here: the
    # driver file vs itself
    d = tmp_path / "driver.json"
    d.write_text(json.dumps(
        {"n": 1, "rc": 0, "parsed": {"sec_per_tree": 0.7, "value": 35.0}}))
    proc = run(d, d)
    assert proc.returncode == 0, proc.stdout
    verdict = json.loads(proc.stdout.strip().splitlines()[-1])
    assert verdict["stages_compared"] == 1

    # unreadable input is a distinct exit code, still one JSON line
    proc = run(a, tmp_path / "missing.json")
    assert proc.returncode == 2
    assert json.loads(proc.stdout.strip().splitlines()[-1])["ok"] is False


def test_bench_obs_doctor_stage(tmp_path):
    """The journaled obs_doctor stage (BENCH_SKIP_OBS honored, errors
    never journaled): runs last, emits ranked verdicts next to the
    banked telemetry, and banks under its own key."""
    journal = str(tmp_path / "journal.json")
    stages = _run_worker({"BENCH_JOURNAL": journal,
                          "BENCH_ONLY": "obs_doctor"})
    doc = [s for s in stages
           if s["stage"] == "obs_doctor" and "error" not in s]
    assert doc, stages
    out = doc[0]
    assert "top_verdict" in out and "verdicts" in out
    assert isinstance(out["verdicts"], list) and out["verdicts"]
    for v in out["verdicts"]:
        assert {"name", "score", "summary", "evidence"} <= set(v)
    d = json.load(open(journal))
    assert "obs_doctor" in d["stages"]


def test_bench_journal_fingerprint_invalidation(tmp_path, monkeypatch):
    """A journal written under a different workload shape must not be
    replayed (stale telemetry masquerading as current is worse than a
    rerun)."""
    sys.path.insert(0, REPO)
    journal = str(tmp_path / "j.json")
    monkeypatch.setenv("BENCH_JOURNAL", journal)
    monkeypatch.setenv("BENCH_ROWS", "1000")
    import importlib
    import bench
    importlib.reload(bench)
    bench.journal_put("smoke", {"value": 1.0})
    assert bench.journal_stages() == {"smoke": {"value": 1.0}}
    monkeypatch.setenv("BENCH_ROWS", "2000")
    importlib.reload(bench)
    assert bench.journal_stages() == {}
    importlib.reload(bench)  # leave module state consistent for others
