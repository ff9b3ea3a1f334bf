"""Active observability layer (ISSUE 11, docs/OBSERVABILITY.md):
flight recorder forensic bundles, SLO watchdog, pod-level telemetry
aggregation, bottleneck diagnosis, and metrics-over-HTTP.

Acceptance bars covered here:
- a chaos-injected CollectiveError and a serving quarantine each produce
  a parseable forensic bundle (Chrome-trace ring + metrics snapshot +
  config/env/mesh fingerprint) WITHOUT crashing the host process;
- a simulated stall breaches the watchdog (slo_breach_total) and dumps;
- with the recorder armed, trained model text is byte-identical and the
  recording overhead is way inside the <1% budget;
- obs_doctor names the injected bottleneck for the three canonical
  scenarios (DCN-heavy reduction, cold compile cache, throttled pump).
"""

import glob
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs.flight import FlightRecorder, global_flight
from lightgbm_tpu.obs.metrics import MetricsRegistry, global_registry
from lightgbm_tpu.obs.watchdog import (SLOConfig, Watchdog,
                                       histogram_p99_ms)

pytestmark = pytest.mark.obs


@pytest.fixture
def flight_dir(tmp_path, monkeypatch):
    """Point the PROCESS flight recorder at a scratch dir with a fresh
    dump budget; restore afterwards."""
    monkeypatch.setattr(global_flight, "_out_dir", str(tmp_path))
    monkeypatch.setattr(global_flight, "dumps", 0)
    monkeypatch.setattr(global_flight, "enabled", True)
    return tmp_path


def _bundles(d, pat="flight_*.json"):
    return sorted(glob.glob(os.path.join(str(d), pat)))


def _check_bundle(path):
    """The bundle contract: one JSON file whose ring is a loadable
    Chrome trace and whose metrics section is a registry snapshot."""
    with open(path) as fh:
        b = json.load(fh)
    assert b["flight_bundle"] >= 1
    evs = b["ring"]["traceEvents"]
    assert isinstance(evs, list) and evs
    assert evs[0]["ph"] == "M"                      # process metadata
    body = [e for e in evs[1:]]
    ts = [e["ts"] for e in body]
    assert ts == sorted(ts)                         # timestamp-sorted
    for e in body:
        assert e["ph"] in ("X", "i") and "pid" in e and "tid" in e
    assert "counters" in b["metrics"] and "gauges" in b["metrics"]
    fp = b["fingerprint"]
    assert fp["pid"] == os.getpid()
    assert "env" in fp and "python" in fp
    return b


# ------------------------------------------------------------ ring basics


def test_flight_ring_is_bounded():
    fr = FlightRecorder(max_events=64, enabled=True, max_dumps=0)
    for i in range(1000):
        fr.note("tick", i=i)
    evs = fr.ring_events()
    assert len(evs) == 64                 # O(1) memory: deque maxlen
    assert evs[-1]["args"]["i"] == 999    # newest survive, oldest roll


def test_flight_disabled_records_and_dumps_nothing(tmp_path):
    fr = FlightRecorder(enabled=False, out_dir=str(tmp_path))
    fr.note("x")
    fr.feed({"name": "y", "ph": "i", "ts": 0.0})
    assert fr.ring_events() == []
    assert fr.dump("manual") is None
    assert _bundles(tmp_path) == []


def test_flight_manual_dump_bundle(tmp_path):
    fr = FlightRecorder(max_events=32, enabled=True, out_dir=str(tmp_path))
    fr.set_context(phase="test", rows=123)
    for i in range(5):
        fr.note("step", i=i, dur_us=10.0)
    fr.note_instant("planner.plan", {"variant": "matmul"})
    p = fr.dump("manual", extra={"note": "hello"})
    assert p is not None and os.path.exists(p)
    b = _check_bundle(p)
    assert b["trigger"] == "manual"
    assert b["fingerprint"]["context"]["phase"] == "test"
    assert b["extra"]["note"] == "hello"
    names = [e["name"] for e in b["ring"]["traceEvents"]]
    assert "step" in names and "planner.plan" in names


def test_flight_dump_rate_limit(tmp_path):
    fr = FlightRecorder(enabled=True, out_dir=str(tmp_path), max_dumps=2)
    assert fr.dump("a") and fr.dump("b")
    assert fr.dump("c") is None           # budget spent: no dump storm
    assert len(_bundles(tmp_path)) == 2


def test_flight_metric_deltas():
    fr = FlightRecorder(enabled=True, max_dumps=0)
    reg = MetricsRegistry()
    reg.counter("widgets_total").inc(3)
    fr.sample_metrics(reg, min_interval_s=0.0)
    reg.counter("widgets_total").inc(4)
    fr.sample_metrics(reg, min_interval_s=0.0)
    d = fr._metric_deltas()
    assert d["deltas"]["widgets_total"] == 4


# ------------------------------------------------- failure-trigger dumps


@pytest.mark.chaos
def test_collective_error_dumps_forensic_bundle(flight_dir):
    """The chaos seam (ChaosRegistry) injects a persistent per-rank
    corruption; the rank-consistent abort must leave a parseable bundle
    per rank and the host process keeps running."""
    from lightgbm_tpu.parallel.dist_data import make_fake_allgather
    from lightgbm_tpu.resilience import (ChaosRegistry, ResilienceConfig,
                                         resilient_allgather)
    from lightgbm_tpu.resilience.retry import CollectiveError

    world = 2
    # bit-flip EVERY round rank 1 sends (payload and verdict frames
    # alike) so no attempt can ever commit -> retries exhausted
    chaos = ChaosRegistry(",".join(
        f"allgather.bitflip@{i}:rank=1" for i in range(12)), seed=0)
    fake = make_fake_allgather(world, timeout=2.0)
    cfg = ResilienceConfig(deadline_s=8.0, max_retries=1,
                           base_backoff_s=0.01)
    errs = [None] * world

    def runner(k):
        try:
            resilient_allgather(
                b"payload", chaos.wrap_allgather(fake(k), k),
                world=world, rank=k, config=cfg)
        except Exception as e:  # noqa: BLE001
            errs[k] = e

    threads = [threading.Thread(target=runner, args=(k,))
               for k in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert all(isinstance(e, CollectiveError) for e in errs), errs
    bundles = _bundles(flight_dir, "flight_collective_*.json")
    assert bundles, "no forensic bundle for the collective abort"
    b = _check_bundle(bundles[0])
    assert b["exception"]["type"] == "CollectiveError"
    # the ring shows the retry ladder even with tracing off
    atts = [e for e in b["ring"]["traceEvents"]
            if e["name"] == "allgather.attempt"]
    assert atts and any(not a["args"]["committed"] for a in atts)


def test_serving_quarantine_dumps_forensic_bundle(flight_dir):
    """A low-precision candidate over its accuracy budget is quarantined
    at admission; the quarantine leaves a bundle and the caller gets the
    typed error, not a dead process."""
    from lightgbm_tpu.serving.errors import LowPrecisionQuarantined

    rng = np.random.RandomState(0)
    X = rng.rand(400, 5)
    y = (X[:, 0] > 0.5).astype(np.float32)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1}, lgb.Dataset(X, label=y), 5)
    with pytest.raises(LowPrecisionQuarantined):
        bst.serve(backend="host", precision="int8", accuracy_budget=0.0)
    bundles = _bundles(flight_dir, "flight_serving.swap_*.json")
    assert bundles, "no forensic bundle for the quarantine"
    b = _check_bundle(bundles[0])
    assert b["exception"]["type"] == "LowPrecisionQuarantined"
    assert b["extra"]["precision"] == "int8"


def test_engine_loop_exception_dumps_bundle(flight_dir):
    rng = np.random.RandomState(0)
    X = rng.rand(300, 4)
    y = (X[:, 0] > 0.5).astype(np.float32)

    def exploding_fobj(preds, ds):
        raise RuntimeError("boom at iteration 0")

    with pytest.raises(RuntimeError):
        lgb.train({"objective": "binary", "num_leaves": 7,
                   "verbosity": -1}, lgb.Dataset(X, label=y), 3,
                  fobj=exploding_fobj)
    bundles = _bundles(flight_dir, "flight_engine.train_*.json")
    assert bundles
    b = _check_bundle(bundles[0])
    assert b["exception"]["type"] == "RuntimeError"
    assert b["fingerprint"]["context"]["phase"] == "train"


def test_slice_lost_dumps_bundle(flight_dir):
    """A failed membership probe (dead transport) raises SliceLostError
    AND leaves the elastic bundle."""
    from lightgbm_tpu.resilience import ResilienceConfig
    from lightgbm_tpu.resilience.elastic import (SliceLostError,
                                                 membership_probe)

    def dead_transport(payload):
        raise OSError("host unreachable")

    with pytest.raises(SliceLostError):
        membership_probe(dead_transport, world=2, rank=0,
                         config=ResilienceConfig(deadline_s=0.5,
                                                 max_retries=0,
                                                 base_backoff_s=0.01))
    assert _bundles(flight_dir, "flight_elastic.membership_*.json")


# --------------------------------------------------------------- watchdog


def test_watchdog_stall_breach_and_dump(tmp_path):
    fl = FlightRecorder(enabled=True, out_dir=str(tmp_path))
    reg = MetricsRegistry()
    wd = Watchdog(SLOConfig(heartbeat_stale_s=0.05), registry=reg,
                  flight=fl)
    wd.watch_heartbeat("engine.step")
    time.sleep(0.12)
    breaches = wd.check_once()
    assert [b[0] for b in breaches] == ["stall:engine.step"]
    key = 'slo_breach_total{slo="stall:engine.step"}'
    assert reg.to_dict()["counters"][key] == 1
    assert _bundles(tmp_path, "flight_watchdog_*.json")
    # persistent breach: counter keeps counting, dump only on the edge
    n = len(_bundles(tmp_path))
    wd.check_once()
    assert reg.to_dict()["counters"][key] == 2
    assert len(_bundles(tmp_path)) == n
    # recovery clears the edge so a NEW stall dumps again
    wd.beat("engine.step")
    assert wd.check_once() == []


def test_watchdog_unwatch_stops_stall_checks():
    wd = Watchdog(SLOConfig(heartbeat_stale_s=0.01),
                  registry=MetricsRegistry(),
                  flight=FlightRecorder(enabled=False))
    wd.watch_heartbeat("loop")
    wd.unwatch("loop")
    time.sleep(0.03)
    assert wd.check_once() == []      # a FINISHED loop never breaches


def test_watchdog_rate_floor():
    reg = MetricsRegistry()
    wd = Watchdog(SLOConfig(heartbeat_stale_s=100.0,
                            trees_per_sec_floor=50.0),
                  registry=reg, flight=FlightRecorder(enabled=False))
    wd.watch_heartbeat("engine.step", floor=50.0)
    wd._beats["engine.step"] = (100.0, 0)
    wd._rate_state["engine.step"] = (100.0, 0)
    # 10 trees over 1s = 10/s < floor 50/s -> breach
    wd._beats["engine.step"] = (101.0, 10)
    breaches = wd.check_once(now=101.0)
    assert [b[0] for b in breaches] == ["slo:engine.step"]
    assert breaches[0][1]["rate"] == 10.0
    # 100 trees over the next 1s -> healthy again
    wd._beats["engine.step"] = (102.0, 110)
    assert wd.check_once(now=102.0) == []


def test_watchdog_serving_p99_ceiling():
    reg = MetricsRegistry()
    hist = reg.histogram("request_latency_ms")
    for _ in range(100):
        hist.observe(3.0)
    assert histogram_p99_ms(hist) == 5.0       # bucket upper bound
    wd = Watchdog(SLOConfig(serving_p99_ms=100.0), registry=reg,
                  flight=FlightRecorder(enabled=False))
    wd.watch_histogram_p99("serving", hist)
    assert wd.check_once() == []               # p99 ~5ms under 100ms
    for _ in range(100):
        hist.observe(900.0)
    breaches = wd.check_once()
    assert [b[0] for b in breaches] == ["slo:serving"]
    assert breaches[0][1]["p99_ms"] > 100.0


def test_watchdog_sentry_thread_runs_checks(tmp_path):
    fl = FlightRecorder(enabled=True, out_dir=str(tmp_path))
    wd = Watchdog(SLOConfig(heartbeat_stale_s=0.03,
                            check_interval_s=0.01),
                  registry=MetricsRegistry(), flight=fl)
    wd.watch_heartbeat("x")
    wd.start()
    try:
        deadline = time.time() + 2.0
        while time.time() < deadline and not _bundles(tmp_path):
            time.sleep(0.02)
    finally:
        wd.stop()
    assert not wd.running
    assert _bundles(tmp_path, "flight_watchdog_stall_x*.json")


# --------------------------------------------------- A/B recorder guard


def test_recorder_on_model_byte_identical_and_cheap(tmp_path):
    """The acceptance A/B: arming the recorder must not change a single
    byte of the model.  The <1% overhead budget is asserted where it is
    measurable deterministically: per-event recording cost vs per-
    iteration cost (wall-clock A/B of two short trainings is dominated
    by compile/jitter noise, not by the recorder)."""
    rng = np.random.RandomState(7)
    X = rng.rand(2000, 6)
    y = (X[:, 0] + X[:, 1] > 1.0).astype(np.float32)
    P = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
         "deterministic": True}

    def run(enabled):
        was = global_flight.enabled
        global_flight.enabled = enabled
        try:
            bst = lgb.train(P, lgb.Dataset(X, label=y), 8,
                            verbose_eval=False)
            return bst.model_to_string()
        finally:
            global_flight.enabled = was

    assert run(True) == run(False)      # byte-identical model text
    # recording cost: a note is O(µs); even a 10ms iteration gives the
    # recorder (1 note + 2 gauge sets + 1 beat per step) <1% headroom
    fr = FlightRecorder(max_events=2048, enabled=True, max_dumps=0)
    t0 = time.perf_counter()
    for i in range(10_000):
        fr.note("engine.step", i=i, dur_us=1.0)
    per_note_s = (time.perf_counter() - t0) / 10_000
    assert per_note_s < 50e-6, f"note() costs {per_note_s * 1e6:.1f}us"


# ----------------------------------------------------- pod aggregation


def test_pod_vector_roundtrip():
    from lightgbm_tpu.obs.aggregate import (pack_rank_vector,
                                            unpack_rank_vector)
    rank, vals = unpack_rank_vector(pack_rank_vector(
        {"iter_seconds": 1.5, "dcn_payload_bytes": 4096.0}, rank=3))
    assert rank == 3
    assert vals["iter_seconds"] == 1.5
    assert vals["dcn_payload_bytes"] == 4096.0
    assert vals["mfu"] == 0.0                      # absent slot -> 0
    with pytest.raises(ValueError):
        unpack_rank_vector(b"garbage-frame-bytes")


def test_pod_gather_derives_straggler_and_sums():
    """4 ranks / 2 slices through the resilient fake transport: every
    rank converges on the same pod view; slice 1 (ranks 2,3) is the
    straggler."""
    from lightgbm_tpu.obs.aggregate import gather_pod_metrics
    from lightgbm_tpu.parallel.dist_data import make_fake_allgather
    from lightgbm_tpu.resilience import ResilienceConfig

    world = 4
    fake = make_fake_allgather(world, timeout=5.0)
    regs = [MetricsRegistry() for _ in range(world)]
    views, errs = [None] * world, [None] * world

    def runner(k):
        try:
            views[k] = gather_pod_metrics(
                fake(k), world=world, rank=k, num_slices=2,
                registry=regs[k],
                config=ResilienceConfig(deadline_s=10.0, max_retries=2),
                values={"iter_seconds": 1.0 if k < 2 else 2.0,
                        "ici_payload_bytes": 100.0,
                        "dcn_payload_bytes": 10.0,
                        "mfu": 0.004})
        except Exception as e:  # noqa: BLE001
            errs[k] = e

    threads = [threading.Thread(target=runner, args=(k,))
               for k in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert errs == [None] * world
    for k, v in enumerate(views):
        assert v.world == 4 and v.num_slices == 2
        assert v.straggler_slice == 1
        assert v.straggler_skew == pytest.approx(2.0)
        assert v.pod_ici_payload_bytes == 400.0
        assert v.pod_dcn_payload_bytes == 40.0
        assert v.pod_mfu == pytest.approx(0.004)
        g = regs[k].to_dict()["gauges"]
        assert g["pod_straggler_slice"] == 1
        assert g["pod_straggler_skew"] == 2.0
        assert g["pod_world"] == 4


def test_engine_eval_boundary_gathers_when_transport_registered():
    """The engine's eval-boundary hook runs a real telemetry round when
    a pod transport is registered (world=1 self-gather here), and is a
    no-op otherwise."""
    from lightgbm_tpu.obs import aggregate
    from lightgbm_tpu.parallel.dist_data import make_fake_allgather

    rng = np.random.RandomState(0)
    X = rng.rand(400, 4)
    y = (X[:, 0] > 0.5).astype(np.float32)
    assert aggregate.maybe_gather_at_eval() is None     # no transport
    fake = make_fake_allgather(1, timeout=5.0)
    aggregate.register_pod_transport(fake(0), world=1, rank=0,
                                     num_slices=1)
    try:
        ds = lgb.Dataset(X, label=y)
        lgb.train({"objective": "binary", "num_leaves": 7,
                   "verbosity": -1, "metric": "binary_logloss"},
                  ds, 2, valid_sets=[ds], verbose_eval=False)
        g = global_registry.to_dict()["gauges"]
        assert g.get("pod_world") == 1
    finally:
        aggregate.clear_pod_transport()
    assert aggregate.maybe_gather_at_eval() is None


# ----------------------------------------------------------- diagnosis


def _diag_top(signals):
    from lightgbm_tpu.obs.diagnose import diagnose
    return diagnose(signals)[0]


def test_doctor_names_dcn_bound():
    """Forced-hierarchical DCN-heavy reduction: 2 GB crossing a
    6.25 GB/s DCN each sync vs a 1 s iteration -> DCN-bound."""
    v = _diag_top({"train_dcn_payload_bytes": 2e9,
                   "train_num_slices": 4, "train_hier_reduce": 1,
                   "train_iter_seconds": 1.0, "dcn_gbps": 6.25})
    assert v.name == "dcn-bound"
    assert v.evidence["num_slices"] == 4
    assert v.evidence["fraction"] > 0.25


def test_doctor_names_compile_bound():
    """Cold compile cache: 130 s compiling vs 25 s training (the r5
    figure) -> compile-bound."""
    v = _diag_top({"compile_seconds": 130.0, "train_seconds": 25.0,
                   "compile_cache_warm": 0})
    assert v.name == "compile-bound"
    assert v.evidence["compile_cache_warm"] is False
    assert v.score > 0.8


def test_doctor_names_input_bound():
    """Throttled stream pump: overlap efficiency ~1.0 means device_put
    is never hidden -> input-bound."""
    v = _diag_top({"stream_blocks_total": 64, "overlap_efficiency": 1.0})
    assert v.name == "input-bound"
    assert v.evidence["overlap_efficiency"] == 1.0


def test_doctor_names_straggler_and_kernel():
    v = _diag_top({"pod_straggler_skew": 1.8, "pod_straggler_slice": 2})
    assert v.name == "straggler" and v.evidence["straggler_slice"] == 2
    v = _diag_top({"mfu_measured_best": 0.0005})
    assert v.name == "kernel-underutilized"
    v = _diag_top({})
    assert v.name == "healthy"


def test_doctor_ranks_verdicts():
    from lightgbm_tpu.obs.diagnose import diagnose
    vs = diagnose({"compile_seconds": 130.0, "train_seconds": 25.0,
                   "train_dcn_payload_bytes": 3e8,
                   "train_num_slices": 2, "train_iter_seconds": 0.15,
                   "dcn_gbps": 6.25})
    names = [v.name for v in vs]
    assert set(names) == {"compile-bound", "dcn-bound"}
    assert [v.score for v in vs] == sorted(
        (v.score for v in vs), reverse=True)


def test_obs_doctor_tool(tmp_path):
    """The CLI: a registry snapshot in (``tools/obs_dump.py`` writes one),
    human table + machine-readable last line out."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    snap = MetricsRegistry()
    snap.gauge("train_dcn_payload_bytes").set(2e9)
    snap.gauge("train_num_slices").set(4)
    snap.gauge("train_hier_reduce").set(1)
    snap.gauge("train_iter_seconds").set(1.0)
    metrics = tmp_path / "obs_metrics.json"
    metrics.write_text(json.dumps(snap.to_dict()))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "obs_doctor.py"),
         "--metrics", str(metrics)],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-1000:]
    lines = proc.stdout.strip().splitlines()
    report = json.loads(lines[-1])
    assert report["top_verdict"] == "dcn-bound"
    assert report["signals"]["train_num_slices"] == 4
    assert "dcn-bound" in proc.stdout


def test_bundle_fingerprint_carries_the_epoch(tmp_path):
    """A bundle names the absolute ``perf_counter_ns`` its ring's ``ts``
    count from (in the fingerprint and the ring's own metadata), so a
    bundle and a device trace of the same process make one timeline
    (``obs.trace.ring_offset_ns``)."""
    from lightgbm_tpu.obs.trace import global_tracer
    fr = FlightRecorder(max_events=8, out_dir=str(tmp_path))
    t_ns = time.perf_counter_ns()
    fr.note("probe")
    with open(fr.dump("epoch")) as fh:
        b = json.load(fh)
    epoch = b["fingerprint"]["trace_epoch_ns"]
    assert epoch == global_tracer.epoch_ns
    assert b["ring"]["otherData"]["epoch_perf_counter_ns"] == epoch
    (ev,) = [e for e in b["ring"]["traceEvents"] if e["name"] == "probe"]
    assert 0 <= epoch + ev["ts"] * 1e3 - t_ns < 5e6


# -------------------------------------------------------- HTTP endpoint


def test_metrics_http_endpoint():
    from lightgbm_tpu.obs.http import MetricsHTTPServer

    reg = MetricsRegistry()
    reg.counter("requests_total").inc(7)
    reg.gauge("depth").set(3)
    reg.histogram("lat_ms").observe(2.0)
    srv = MetricsHTTPServer(registry=reg, port=0)
    try:
        port = srv.start()
        base = f"http://127.0.0.1:{port}"
        prom = urllib.request.urlopen(f"{base}/metrics",
                                      timeout=5).read().decode()
        assert "# TYPE lgbt_requests_total counter" in prom
        assert "lgbt_requests_total 7" in prom
        snap = json.loads(urllib.request.urlopen(
            f"{base}/metrics.json", timeout=5).read())
        assert snap["counters"]["requests_total"] == 7
        assert snap["gauges"]["depth"] == 3
        hz = urllib.request.urlopen(f"{base}/healthz", timeout=5).read()
        assert hz == b"ok\n"
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/nope", timeout=5)
    finally:
        srv.stop()


def test_metrics_http_env_gate(monkeypatch):
    from lightgbm_tpu.obs import http as obs_http

    monkeypatch.delenv("LIGHTGBM_TPU_METRICS_PORT", raising=False)
    obs_http.stop_process_server()
    assert obs_http.maybe_start_from_env() is None       # opt-in only
    monkeypatch.setenv("LIGHTGBM_TPU_METRICS_PORT", "0")
    try:
        srv = obs_http.maybe_start_from_env()
        assert srv is not None and srv.port > 0
        assert obs_http.maybe_start_from_env() is srv    # idempotent
        prom = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics",
            timeout=5).read().decode()
        assert "# TYPE" in prom or prom == "\n"
    finally:
        obs_http.stop_process_server()


# ------------------------------------------------------ trace event cap


def test_tracer_caps_events_and_counts_drops():
    from lightgbm_tpu.obs.trace import Tracer

    t = Tracer(enabled=True, max_events=10)
    for i in range(25):
        with t.span("s", i=i):
            pass
    assert len(t.events()) == 10          # bounded in-process list
    assert t.dropped == 15
    doc = t.to_chrome_trace()
    tail = doc["traceEvents"][-1]
    assert tail["name"] == "trace_events_dropped"
    assert tail["args"]["dropped"] == 15
    assert global_registry.to_dict()["gauges"][
        "trace_events_dropped"] >= 15
    t.reset()
    assert t.dropped == 0 and t.events() == []


def test_tracer_cap_env(monkeypatch):
    from lightgbm_tpu.obs.trace import Tracer

    monkeypatch.setenv("LIGHTGBM_TPU_TRACE_MAX_EVENTS", "5")
    t = Tracer(enabled=True)
    assert t.max_events == 5
    monkeypatch.setenv("LIGHTGBM_TPU_TRACE_MAX_EVENTS", "junk")
    assert Tracer(enabled=True).max_events > 5          # fallback


def test_flight_ring_sees_training_without_tracing(flight_dir):
    """The whole point of always-on: with LIGHTGBM_TPU_TRACE unset the
    tracer records nothing, yet the ring still holds the step/planner
    history a bundle needs."""
    from lightgbm_tpu.obs.trace import global_tracer

    assert not global_tracer.enabled
    rng = np.random.RandomState(0)
    X = rng.rand(400, 4)
    y = (X[:, 0] > 0.5).astype(np.float32)
    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1},
              lgb.Dataset(X, label=y), 3)
    assert global_tracer.events() == []
    names = {e["name"] for e in global_flight.ring_events()}
    assert "engine.step" in names
    assert "planner.plan" in names
