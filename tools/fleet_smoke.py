#!/usr/bin/env python
"""Fleet smoke: N-model serve, planner-driven eviction, AOT restart,
and opt-in low-precision — the CLI twin of tests/test_fleet.py, for
eyeballs and CI logs.  The LAST stdout line is a single JSON object.

Phases (each banks its own sub-dict in the summary):

* ``serve``   — train N boosters (one multiclass), register them with
  mixed weights/deadline classes, fire a weighted multi-model traffic
  mix (serving/loadgen.fire_fleet_requests), verify every f32 response
  bit-equal to ``StackedForest.predict_raw``.
* ``evict``   — replan against a faked HBM budget sized to the hottest
  model only: colder models must be EVICTED (device arrays + programs
  released) yet stay fully servable through the host path, still
  bit-equal.  No OOM, no serve failure is the acceptance bar.
* ``aot``     — export every resident bucket program (fleet/aot.py),
  stand up a FRESH fleet against the store, warm it, and serve first
  requests: zero ``compile_events``, only ``aot_program_loads``.
* ``lowprec`` — register bf16 and int8 twins of a model under a
  declared accuracy budget; journal the measured deltas; demonstrate
  the quarantine by offering an int8 model a budget of 0.
* ``failover`` (``--devices N``, N >= 2) — stand up a replicated
  ``PodFleet`` over N simulated devices, fire a threaded traffic storm, KILL one device mid-run
  (chaos ``device`` site), and assert the acceptance bars: ZERO
  non-typed request failures, availability >= 0.999, every response
  bit-equal to ``Booster.predict(raw_score=True)``, and recovery
  (every model regains replica coverage) within ONE replan tick.

Usage:
    JAX_PLATFORMS=cpu python tools/fleet_smoke.py \
        [--models 3] [--requests 240] [--threads 6] [--rows 3000] \
        [--max-batch-rows 256] [--accuracy-budget 0.5] [--devices 2]
"""

import argparse
import json
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _train_models(n_models, rows, trees, features, leaves):
    import lightgbm_tpu as lgb
    boosters = []
    for i in range(n_models):
        rng = np.random.RandomState(100 + i)
        X = rng.randn(rows, features).astype(np.float32).astype(np.float64)
        if i == n_models - 1 and n_models >= 2:
            params = {"objective": "multiclass", "num_class": 3,
                      "verbosity": -1, "num_leaves": leaves}
            y = rng.randint(0, 3, rows).astype(float)
        else:
            params = {"objective": "binary", "verbosity": -1,
                      "num_leaves": leaves}
            y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(float)
        boosters.append(lgb.train(params, lgb.Dataset(X, label=y),
                                  num_boost_round=trees,
                                  verbose_eval=False))
    return boosters


def _verify_forests(boosters):
    out = {}
    for i, b in enumerate(boosters):
        n_iter = len(b.models) // b.num_tree_per_iteration
        out[f"m{i}"] = b._forest(0, n_iter)
    return out


def run_smoke(n_models=3, rows=3000, trees=10, features=10, leaves=15,
              requests=240, threads=6, max_request_rows=200,
              max_batch_rows=256, accuracy_budget=0.5,
              aot_dir=None) -> dict:
    """Run all four phases; returns the JSON-ready summary dict.
    ``failed`` is True when any acceptance bar was missed."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.serving import LowPrecisionQuarantined
    from lightgbm_tpu.serving.loadgen import fire_fleet_requests

    summary = {"n_models": n_models, "phases": {}}
    boosters = _train_models(n_models, rows, trees, features, leaves)
    verify = _verify_forests(boosters)
    names = sorted(verify)

    # ----------------------------------------------------------- serve
    fleet = lgb.Fleet(max_batch_rows=max_batch_rows)
    weights = {}
    classes = sorted(fleet.config.deadline_classes)
    for i, b in enumerate(boosters):
        w = float(n_models - i)
        weights[f"m{i}"] = w
        fleet.add_model(f"m{i}", b, weight=w,
                        deadline_class=classes[i % len(classes)])
    fleet.warm()
    storm = fire_fleet_requests(fleet, weights, requests, threads,
                                max_request_rows, verify=verify,
                                timeout=120)
    summary["phases"]["serve"] = {
        "requests": storm["requests"],
        "requests_planned": storm["requests_planned"],
        "rows": storm["rows"],
        "shed": storm["shed"],
        "expired": storm["expired"],
        "failed": storm["failed"],
        "availability": storm["availability"],
        "mismatches": storm["mismatches"],
        "wall_seconds": round(storm["wall_seconds"], 3),
        "rows_per_second": round(
            storm["rows"] / max(storm["wall_seconds"], 1e-9), 1),
        "errors": storm["errors"],
        "models": storm["models"],
        "plan": fleet.plan.summary() if fleet.plan else None,
    }
    # failed requests are typed OUTCOMES now (loadgen no longer kills
    # the thread), so the bar must assert them zero EXPLICITLY — the
    # planned-request tally alone would also catch them, but a named
    # zero reads honestly in the journal
    serve_ok = (not storm["errors"] and storm["failed"] == 0
                and storm["mismatches"] == 0
                and storm["requests"] + storm["shed"] + storm["expired"]
                + storm["failed"] == storm["requests_planned"])

    # ----------------------------------------------------------- evict
    plan0 = fleet.replan()
    hottest = max(plan0.models, key=lambda m: m.priority)
    hot_cost = hottest.forest_bytes + hottest.program_bytes
    from lightgbm_tpu.ops.planner import HEADROOM
    fleet.config.hbm_budget_bytes = int((hot_cost + 1024) / HEADROOM)
    plan = fleet.replan()
    evict_storm = fire_fleet_requests(fleet, weights, requests // 2,
                                      threads, max_request_rows,
                                      verify=verify, timeout=120)
    md = fleet.metrics_dict()
    evictions = sum(v for k, v in md["counters"].items()
                    if k.startswith("fleet_evictions"))
    summary["phases"]["evict"] = {
        "budget_bytes": plan.budget_bytes,
        "evicted_models": list(plan.evicted),
        "evictions": evictions,
        "requests": evict_storm["requests"],
        "shed": evict_storm["shed"],
        "expired": evict_storm["expired"],
        "failed": evict_storm["failed"],
        "mismatches": evict_storm["mismatches"],
        "errors": evict_storm["errors"],
        "all_models_served": all(
            m["requests"] > 0 or m["shed"] > 0 or weights[n] == 0
            for n, m in evict_storm["models"].items()),
    }
    evict_ok = (len(plan.evicted) >= 1 and not evict_storm["errors"]
                and evict_storm["failed"] == 0
                and evict_storm["mismatches"] == 0
                and summary["phases"]["evict"]["all_models_served"])
    fleet.config.hbm_budget_bytes = None
    fleet.replan()

    # ------------------------------------------------------------- aot
    own_tmp = None
    if aot_dir is None:
        own_tmp = tempfile.TemporaryDirectory(prefix="lgbt_fleet_aot_")
        aot_dir = own_tmp.name
    n_exported = fleet.export_aot(aot_dir)
    fleet.close()
    replica = lgb.Fleet(max_batch_rows=max_batch_rows, aot_dir=aot_dir)
    for i, b in enumerate(boosters):
        replica.add_model(f"m{i}", b, weight=weights[f"m{i}"])
    replica.warm()
    rng = np.random.RandomState(7)
    first_ok = True
    for i, name in enumerate(names):
        X = rng.randn(32, features).astype(np.float32).astype(np.float64)
        out = replica.predict(name, X, timeout=60)
        K = replica.entry(name).model.num_class
        ref = verify[name].predict_raw(X, num_class=K)
        first_ok = first_ok and np.array_equal(out,
                                               ref[0] if K == 1 else ref.T)
    compiles = 0
    aot_loads = 0
    for name in names:
        c = replica.entry(name).server.metrics_dict()["counters"]
        compiles += c.get("compile_events", 0)
        aot_loads += c.get("aot_program_loads", 0)
    replica.close()
    if own_tmp is not None:
        own_tmp.cleanup()
    summary["phases"]["aot"] = {
        "exported_programs": n_exported,
        "replica_compile_events": compiles,
        "replica_aot_loads": aot_loads,
        "first_requests_bit_equal": first_ok,
    }
    aot_ok = compiles == 0 and aot_loads > 0 and first_ok

    # --------------------------------------------------------- lowprec
    lp = lgb.Fleet(max_batch_rows=max_batch_rows)
    lp.add_model("full", boosters[0])
    deltas = {}
    for prec in ("bf16", "int8"):
        e = lp.add_model(f"{prec}", boosters[0], precision=prec,
                         accuracy_budget=accuracy_budget)
        deltas[prec] = e.server.metrics.gauge(
            "lowprec_accuracy_delta").value
    X = np.random.RandomState(11).randn(64, features) \
        .astype(np.float32).astype(np.float64)
    ref = boosters[0].predict(X, raw_score=True)
    default_bit_equal = np.array_equal(lp.predict("full", X, timeout=60),
                                       ref)
    lp_served = {p: float(np.max(np.abs(
        lp.predict(p, X, timeout=60) - ref))) for p in ("bf16", "int8")}
    try:
        lp.add_model("int8_zero_budget", boosters[0], precision="int8",
                     accuracy_budget=0.0)
        quarantined = False
    except LowPrecisionQuarantined:
        quarantined = True
    lp.close()
    summary["phases"]["lowprec"] = {
        "accuracy_budget": accuracy_budget,
        "probe_delta": {k: round(float(v), 6) for k, v in deltas.items()},
        "served_delta_vs_full": {k: round(v, 6)
                                 for k, v in lp_served.items()},
        "default_bit_equal": default_bit_equal,
        "zero_budget_quarantined": quarantined,
    }
    lowprec_ok = (default_bit_equal and quarantined
                  and all(d <= accuracy_budget for d in deltas.values()))

    summary["failed"] = not (serve_ok and evict_ok and aot_ok
                             and lowprec_ok)
    summary["phase_ok"] = {"serve": serve_ok, "evict": evict_ok,
                           "aot": aot_ok, "lowprec": lowprec_ok}
    return summary


def run_failover_smoke(devices=3, n_models=2, rows=3000, trees=10,
                       features=10, leaves=15, requests=600, threads=6,
                       max_request_rows=60, max_batch_rows=128,
                       kill_after_s=0.2, availability_floor=0.999) -> dict:
    """Kill-one-device-under-load drill (module docstring ``failover``
    phase).  Returns the JSON-ready summary; ``failed`` True when any
    acceptance bar was missed."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.fleet.router import RouterConfig
    from lightgbm_tpu.resilience.faults import ChaosRegistry
    from lightgbm_tpu.serving.loadgen import fire_fleet_requests

    if devices < 2:
        raise ValueError("failover drill needs --devices >= 2")
    boosters = _train_models(n_models, rows, trees, features, leaves)
    verify = _verify_forests(boosters)
    weights = {f"m{i}": float(n_models - i) for i in range(n_models)}

    chaos = ChaosRegistry()
    pod = lgb.PodFleet(
        devices=devices, chaos=chaos, max_batch_rows=max_batch_rows,
        router=RouterConfig(stale_beat_s=1.0, dead_strikes=2,
                            health_interval_s=0.2))
    # generous deadlines: the drill measures availability under device
    # loss, not queue aging (deadline classes have their own tests)
    for cls in list(pod.deadline_classes):
        pod.deadline_classes[cls] = 60_000.0
    for i, b in enumerate(boosters):
        pod.add_model(f"m{i}", b, weight=weights[f"m{i}"])
    pod.warm()
    victim = pod.topology.replicas["m0"][0]
    lost_before = pod.metrics.counter("fleet_devices_lost_total").value

    import threading
    import time as _time

    def killer():
        _time.sleep(kill_after_s)
        chaos.down_device(victim, "vanish")

    threading.Thread(target=killer, daemon=True).start()
    storm = fire_fleet_requests(pod, weights, requests, threads,
                                max_request_rows, verify=verify,
                                timeout=120)
    # let the health sweep finish declaring/draining the victim even if
    # the storm outran it
    deadline = _time.monotonic() + 10.0
    while _time.monotonic() < deadline and \
            pod.metrics.counter("fleet_devices_lost_total").value \
            <= lost_before:
        _time.sleep(0.1)
    _time.sleep(0.3)        # drain thread: replan + recovery gauge
    recovered = pod.metrics.gauge("fleet_recovered_one_tick").value
    live = pod.live_devices()
    replicas = ({n: list(ids)
                 for n, ids in pod.topology.replicas.items()}
                if pod.topology else {})
    summary = {
        "devices": devices,
        "victim_device": victim,
        "requests": storm["requests"],
        "requests_planned": storm["requests_planned"],
        "outcomes": storm["outcomes"],
        "availability": storm["availability"],
        "mismatches": storm["mismatches"],
        "failures": storm["failures"][:5],
        "errors": storm["errors"],
        "wall_seconds": round(storm["wall_seconds"], 3),
        "devices_lost": pod.metrics.counter(
            "fleet_devices_lost_total").value - lost_before,
        "recovered_within_one_tick": bool(recovered),
        "live_devices": live,
        "replicas_after": replicas,
        "hedges": sum(
            pod.metrics.counter("fleet_hedges_total",
                                labels={"model": n}).value
            for n in weights),
    }
    pod.close(drain=False, timeout=2.0)
    summary["failed"] = not (
        storm["failed"] == 0 and not storm["errors"]
        and storm["mismatches"] == 0
        and (storm["availability"] or 0.0) >= availability_floor
        and summary["devices_lost"] == 1
        and summary["recovered_within_one_tick"]
        and victim not in live
        and all(len(ids) >= 1 for ids in replicas.values()))
    return summary


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", type=int, default=3)
    ap.add_argument("--requests", type=int, default=240)
    ap.add_argument("--threads", type=int, default=6)
    ap.add_argument("--rows", type=int, default=3000,
                    help="training rows per synthetic booster")
    ap.add_argument("--trees", type=int, default=10)
    ap.add_argument("--features", type=int, default=10)
    ap.add_argument("--max-request-rows", type=int, default=200)
    ap.add_argument("--max-batch-rows", type=int, default=256)
    ap.add_argument("--accuracy-budget", type=float, default=0.5)
    ap.add_argument("--aot-dir", default=None,
                    help="AOT store dir (default: a temp dir)")
    ap.add_argument("--devices", type=int, default=1,
                    help=">= 2 adds the kill-one-device failover phase "
                         "(a replicated PodFleet under chaos)")
    args = ap.parse_args()

    print(f"[fleet_smoke] {args.models} models, {args.requests} requests "
          f"from {args.threads} threads", flush=True)
    summary = run_smoke(
        n_models=args.models, rows=args.rows, trees=args.trees,
        features=args.features, requests=args.requests,
        threads=args.threads, max_request_rows=args.max_request_rows,
        max_batch_rows=args.max_batch_rows,
        accuracy_budget=args.accuracy_budget, aot_dir=args.aot_dir)
    if args.devices >= 2:
        print(f"[fleet_smoke] failover drill over {args.devices} "
              "simulated devices", flush=True)
        fo = run_failover_smoke(
            devices=args.devices, n_models=min(args.models, 2),
            rows=args.rows, trees=args.trees, features=args.features,
            requests=args.requests, threads=args.threads,
            max_batch_rows=args.max_batch_rows)
        summary["phases"]["failover"] = fo
        summary["phase_ok"]["failover"] = not fo["failed"]
        summary["failed"] = summary["failed"] or fo["failed"]
    print(json.dumps(summary, indent=1, sort_keys=True))
    return 1 if summary["failed"] else 0


if __name__ == "__main__":
    sys.exit(main())
