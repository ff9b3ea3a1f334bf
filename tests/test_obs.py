"""Observability plane (lightgbm_tpu/obs/, docs/OBSERVABILITY.md):
structured tracing, the unified metrics registry + Prometheus exposition,
measured device profiling, and the timer satellite features.

The tracing layer's acceptance bar (ISSUE 6): spans nest and close
correctly under exceptions, the disabled path records no event and is
bounded in cost (a profiler annotation, ISSUE 25), the Chrome-trace JSON
validates (timestamp-sorted, pid/tid on every event), and
allgather-retry / checkpoint spans appear in a chaos-injected run.
"""

import json
import threading

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs.metrics import MetricsRegistry, global_registry
from lightgbm_tpu.obs.trace import (Tracer, global_tracer, span,
                                    span_coverage)
from lightgbm_tpu.utils.timer import Timer, global_timer

pytestmark = pytest.mark.obs


# -------------------------------------------------------------- trace core


def test_spans_record_and_nest():
    t = Tracer(enabled=True)
    with t.span("outer", kind="test"):
        with t.span("inner"):
            pass
    evs = t.events()
    assert [e["name"] for e in evs] == ["inner", "outer"]
    inner, outer = evs
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    for e in evs:
        assert e["ph"] == "X" and "pid" in e and "tid" in e
    assert outer["args"]["kind"] == "test"


def test_span_closes_under_exception():
    t = Tracer(enabled=True)
    with pytest.raises(ValueError):
        with t.span("outer"):
            with t.span("boom"):
                raise ValueError("x")
    evs = {e["name"]: e for e in t.events()}
    # BOTH spans closed despite the raise, tagged with the error type
    assert set(evs) == {"outer", "boom"}
    assert evs["boom"]["args"]["error"] == "ValueError"
    assert evs["outer"]["args"]["error"] == "ValueError"


def test_disabled_mode_records_nothing():
    t = Tracer(enabled=False)
    with t.span("x", a=1):
        pass
    t.instant("y")
    assert t.events() == []
    # the module-level seam is the same object kind: a profiler
    # annotation whether or not the recorder is on; off, no event
    was = global_tracer.enabled
    global_tracer.disable()
    global_tracer.reset()
    try:
        with span("z", a=1) as s:
            s.set(b=2)
        assert type(s) is type(t.span("x"))
        assert global_tracer.events() == []
    finally:
        global_tracer.enabled = was


def test_span_cost_with_tracing_off_is_bounded():
    """What "tracing off" costs: with no profiler session and the recorder
    off a span is one small object, a thread-local push/pop and a
    ``TraceAnnotation`` that finds no session (~1.5 us here).  The bound
    is loose (shared CI cores) and still two orders under the cheapest
    thing a span wraps (a dispatch, a serving request)."""
    import time
    was = global_tracer.enabled
    global_tracer.disable()
    try:
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for i in range(2000):
                with span("cost.probe", it=i):
                    pass
            best = min(best, (time.perf_counter() - t0) / 2000)
        assert global_tracer.enabled is False
        assert best < 50e-6, f"{best * 1e6:.1f} us a span with tracing off"
    finally:
        global_tracer.enabled = was


@pytest.mark.parametrize("name, ring", [
    ("engine.callbacks", False), ("eval.pull", False),
    ("metric.auc", False), ("engine.step", True), ("engine.eval", True)])
def test_turn_seams_cost_with_tracing_off_is_bounded(name, ring):
    """The seams of a loop turn with tracing off: an annotation each for
    the callbacks, the evaluation's pull and each metric, and one ring
    append a turn for ``engine.step`` and ``engine.eval``; under the same
    loose bound as any span, on rounds of seconds."""
    import time
    from lightgbm_tpu.obs.flight import FlightRecorder
    from lightgbm_tpu.obs import trace
    was, sink = global_tracer.enabled, trace._flight_sink
    global_tracer.disable()
    trace.set_flight_sink(FlightRecorder(max_events=64, enabled=True))
    try:
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for i in range(2000):
                with span(name, ring=ring, it=i):
                    pass
            best = min(best, (time.perf_counter() - t0) / 2000)
        assert best < 50e-6, f"{best * 1e6:.1f} us a {name} span"
        assert len(trace._flight_sink.ring_events()) == (64 if ring else 0)
    finally:
        global_tracer.enabled = was
        trace.set_flight_sink(sink)


def test_chrome_dump_carries_the_epoch(tmp_path):
    """The dump names the absolute ``perf_counter_ns`` its ``ts`` count
    from, so its events land on any clock the process read."""
    import time
    t = Tracer(enabled=True)
    assert abs(t.epoch_ns - time.perf_counter_ns()) < 60e9
    t_ns = time.perf_counter_ns()
    with t.span("probe"):
        pass
    with open(t.dump(str(tmp_path / "t.json"))) as fh:
        doc = json.load(fh)
    epoch = doc["otherData"]["epoch_perf_counter_ns"]
    assert epoch == t.epoch_ns
    (ev,) = [e for e in doc["traceEvents"] if e["name"] == "probe"]
    assert 0 <= epoch + ev["ts"] * 1e3 - t_ns < 5e6


def test_chrome_trace_json_validates():
    t = Tracer(enabled=True)

    def worker():
        with t.span("thread_span"):
            pass

    th = threading.Thread(target=worker)
    with t.span("main_span"):
        th.start()
        th.join()
    t.instant("marker", note=1)
    doc = json.loads(json.dumps(t.to_chrome_trace()))
    evs = doc["traceEvents"]
    assert len(evs) == 4              # metadata + 2 spans + 1 instant
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)           # timestamp-sorted
    for e in evs:
        assert "pid" in e and "tid" in e and "ts" in e
        assert e["ph"] in ("X", "i", "M")
    tids = {e["tid"] for e in evs if e["ph"] == "X"}
    assert len(tids) == 2             # two threads visible


def test_dump_and_coverage(tmp_path):
    t = Tracer(enabled=True)
    import time
    with t.span("root"):
        with t.span("a"):
            time.sleep(0.02)
        with t.span("b"):
            time.sleep(0.02)
    cov = span_coverage(t.events(), "root")
    assert cov is not None and cov > 0.9
    p = t.dump(str(tmp_path / "trace.json"))
    with open(p) as fh:
        assert "traceEvents" in json.load(fh)


def test_training_emits_spans_and_registry_instruments():
    global_tracer.reset()
    global_tracer.enable()
    try:
        rng = np.random.RandomState(0)
        X = rng.rand(500, 4)
        y = (X[:, 0] > 0.5).astype(np.float32)
        lgb.train({"objective": "binary", "num_leaves": 7,
                   "verbosity": -1},
                  lgb.Dataset(X, label=y), num_boost_round=3)
        names = {e["name"] for e in global_tracer.events()}
        assert "engine.train" in names
        assert "engine.step" in names
        assert "planner.plan" in names
        # dispatch happens through the fused chunk program by default
        assert names & {"macro.dispatch", "gbdt.dispatch"}
        assert names & {"macro.host_fetch", "gbdt.finish_iter"}
        cov = span_coverage(global_tracer.events(), "engine.train")
        assert cov is not None and cov > 0.9
    finally:
        global_tracer.disable()
        global_tracer.reset()
    d = global_registry.to_dict()
    assert d["counters"].get("train_iterations_total", 0) >= 3
    assert "train_hist_method" in d["gauges"]
    assert d["gauges"]["train_hist_method"] != "auto"
    assert "train_tile_rows" in d["gauges"]
    assert d["gauges"].get("train_hist_predicted_peak_bytes", 0) > 0


def test_training_disabled_trace_stays_empty():
    global_tracer.reset()
    assert not global_tracer.enabled
    rng = np.random.RandomState(0)
    X = rng.rand(300, 4)
    y = (X[:, 0] > 0.5).astype(np.float32)
    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1},
              lgb.Dataset(X, label=y), num_boost_round=2)
    assert global_tracer.events() == []


# ----------------------------------------------- chaos-injected span tests


@pytest.mark.chaos
def test_allgather_retry_spans_under_chaos():
    """An injected transport fault must surface as retried
    ``allgather.attempt`` spans (attempt 0 not committed, a later attempt
    committed) on top of the existing retry/recover behavior."""
    from lightgbm_tpu.parallel.dist_data import make_fake_allgather
    from lightgbm_tpu.resilience import (ChaosRegistry, ResilienceConfig,
                                         resilient_allgather)

    world = 4
    cfg = ResilienceConfig(deadline_s=20.0, max_retries=5,
                           base_backoff_s=0.01)
    chaos = ChaosRegistry("allgather.bitflip@0:rank=1", seed=0)
    fake = make_fake_allgather(world, timeout=2.0)
    global_tracer.reset()
    global_tracer.enable()
    try:
        out, errs = [None] * world, [None] * world

        def runner(k):
            try:
                ag = chaos.wrap_allgather(fake(k), k)
                out[k] = resilient_allgather(
                    f"rank{k}".encode(), ag, world=world, rank=k,
                    config=cfg)
            except Exception as e:  # noqa: BLE001
                errs[k] = e

        threads = [threading.Thread(target=runner, args=(k,))
                   for k in range(world)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert errs == [None] * world
        atts = [e for e in global_tracer.events()
                if e["name"] == "allgather.attempt"]
        assert atts, "no allgather.attempt spans recorded"
        assert any(not a["args"]["committed"] for a in atts), \
            "the injected fault never produced a failed attempt span"
        assert any(a["args"]["committed"] and a["args"]["attempt"] >= 1
                   for a in atts), "no recovered-retry span"
    finally:
        global_tracer.disable()
        global_tracer.reset()


@pytest.mark.chaos
def test_checkpoint_spans_appear(tmp_path):
    rng = np.random.RandomState(0)
    X = rng.rand(400, 4)
    y = (X[:, 0] > 0.5).astype(np.float32)
    P = {"objective": "binary", "num_leaves": 7, "verbosity": -1}
    global_tracer.reset()
    global_tracer.enable()
    try:
        lgb.train(P, lgb.Dataset(X, label=y), 4, verbose_eval=False,
                  snapshot_freq=2, snapshot_out=str(tmp_path / "m.txt"))
        lgb.train(P, lgb.Dataset(X, label=y), 4, verbose_eval=False,
                  resume_from=str(tmp_path / "m.txt.ckpt"))
        names = [e["name"] for e in global_tracer.events()]
        assert "checkpoint.save" in names
        assert "checkpoint.load" in names
    finally:
        global_tracer.disable()
        global_tracer.reset()
    d = global_registry.to_dict()
    assert d["histograms"]["checkpoint_save_ms"]["count"] >= 2
    assert d["histograms"]["checkpoint_load_ms"]["count"] >= 1


# ------------------------------------------------- unified metrics registry


def test_serving_metrics_shim_is_the_obs_registry():
    """Back-compat satellite: the historical import path and to_dict key
    layout survive the move to obs/ unchanged."""
    from lightgbm_tpu.serving.metrics import (LATENCY_BUCKETS_MS,
                                              MetricsRegistry as ShimReg)
    assert ShimReg is MetricsRegistry
    assert LATENCY_BUCKETS_MS[-1] == float("inf")
    r = ShimReg()
    r.counter("requests_total").inc(2)
    r.gauge("queue_depth_rows").set(5)
    r.histogram("request_latency_ms").observe(3.0)
    d = r.to_dict()
    # EXACT historical layout: three sections, no extras without children
    assert sorted(d.keys()) == ["counters", "gauges", "histograms"]
    assert d["counters"] == {"requests_total": 2}
    assert d["gauges"] == {"queue_depth_rows": 5}
    h = d["histograms"]["request_latency_ms"]
    assert h["count"] == 1 and h["buckets"] == {"5.0": 1}
    json.loads(r.dump_json())


def test_registry_components():
    root = MetricsRegistry()
    child = MetricsRegistry()
    child.counter("x").inc()
    name = root.attach_child("serving", child)
    assert name == "serving"
    name2 = root.attach_child("serving", MetricsRegistry())
    assert name2 == "serving_2"        # unique names, no clobber
    d = root.to_dict()
    assert d["components"]["serving"]["counters"]["x"] == 1
    root.detach_child(name)
    root.detach_child(name2)
    assert "components" not in root.to_dict()


def test_prometheus_exposition():
    r = MetricsRegistry()
    r.counter("requests_total").inc(7)
    r.gauge("queue_depth").set(3)
    r.gauge("active_model_digest").set("abc123")
    h = r.histogram("latency_ms", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    h.observe(100.0)
    child = MetricsRegistry()
    child.counter("hits").inc()
    r.attach_child("serving", child)
    text = r.to_prometheus(prefix="lgbt")
    assert "# TYPE lgbt_requests_total counter\nlgbt_requests_total 7" in text
    assert "lgbt_queue_depth 3" in text
    assert 'lgbt_active_model_digest_info{value="abc123"} 1' in text
    # cumulative buckets + +Inf + sum/count
    assert 'lgbt_latency_ms_bucket{le="1.0"} 1' in text
    assert 'lgbt_latency_ms_bucket{le="10.0"} 2' in text
    assert 'lgbt_latency_ms_bucket{le="+Inf"} 3' in text
    assert "lgbt_latency_ms_count 3" in text
    assert "lgbt_serving_hits 1" in text
    # every sample line ends in a parseable number
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        float(line.rsplit(" ", 1)[1])


def test_server_joins_process_registry_and_prometheus():
    rng = np.random.RandomState(0)
    X = rng.rand(300, 5)
    y = (X[:, 0] > 0.5).astype(np.float32)
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1}, lgb.Dataset(X, label=y), 3)
    srv = bst.serve(max_batch_rows=64, backend="host")
    try:
        srv.predict(X[:16])
        comp = global_registry.to_dict().get("components", {})
        assert any(k.startswith("serving") for k in comp)
        text = srv.prometheus_text()
        assert "lgbt_serving_requests_total 1" in text
    finally:
        srv.close()
    comp = global_registry.to_dict().get("components", {})
    assert not any(v is srv.metrics for v in comp.values())


# ------------------------------------------------------------ timer bridge


def test_timer_json_dump(tmp_path):
    t = Timer(enabled=True)
    with t.section("A::B"):
        pass
    with t.section("A::B"):
        pass
    d = t.to_dict()
    assert d["A::B"]["calls"] == 2 and d["A::B"]["total_s"] >= 0
    p = tmp_path / "timers.json"
    s = t.dump_json(str(p))
    loaded = json.loads(p.read_text())
    assert loaded == json.loads(s)
    assert loaded["timers"]["A::B"]["calls"] == 2


def test_timer_env_json_mode(tmp_path, monkeypatch):
    """LIGHTGBM_TPU_TIMETAG=json:<path> writes machine-readable totals at
    exit (satellite: no stderr scraping)."""
    out = tmp_path / "t.json"
    monkeypatch.setenv("LIGHTGBM_TPU_TIMETAG", f"json:{out}")
    from lightgbm_tpu.utils import timer as timer_mod
    assert Timer().enabled        # "json:..." counts as enabled
    was = global_timer.enabled
    global_timer.enable()
    try:
        with global_timer.section("ExitDump::Test"):
            pass
        timer_mod._print_at_exit()
    finally:
        global_timer.enabled = was
    loaded = json.loads(out.read_text())
    assert "ExitDump::Test" in loaded["timers"]


def test_timer_publish_mirrors_registry():
    t = Timer(enabled=True)
    with t.section("Pub::X"):
        pass
    reg = MetricsRegistry()
    t.publish(reg)
    g = reg.to_dict()["gauges"]
    assert g["timer.Pub::X.calls"] == 1
    assert g["timer.Pub::X.total_s"] >= 0


def test_obs_dump_tool(tmp_path):
    import os
    import sys
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    from obs_dump import run_dump

    r = run_dump(out_dir=str(tmp_path), rows=2000, features=6, trees=3,
                 leaves=7)
    assert r["trace_events"] > 0
    assert r["train_coverage"] > 0.9
    assert "checkpoint.save" in r["span_names"]
    assert "serving.dispatch" in r["span_names"]
    trace = json.loads((tmp_path / "obs_trace.json").read_text())
    assert trace["traceEvents"]
    snap = json.loads((tmp_path / "obs_metrics.json").read_text())
    assert "counters" in snap and "gauges" in snap
    # the serving component must be IN the snapshot (dumped before close
    # detaches it) — the whole point of the unified registry
    assert any(k.startswith("serving") for k in snap.get("components", {}))
    prom = (tmp_path / "obs_metrics.prom").read_text()
    assert "# TYPE" in prom
    # the dump restored the disabled-by-default state
    assert not global_tracer.enabled or os.environ.get(
        "LIGHTGBM_TPU_TRACE")
