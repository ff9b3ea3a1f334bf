#!/usr/bin/env python
"""Co-residency smoke: loadgen traffic AND continual refresh on the SAME
device set, behind the shared residency ledger — the CLI twin of
tests/test_coresident.py.  Stdout ends with one JSON summary object.

Phases (each banks its own sub-dict in the summary):

* ``train``       — train the deployed model, stand up a chaos-armed
  ``PodFleet`` (a scheduled ``device.delay`` window inflates batch
  latency mid-run — the contention shape brownout must catch), lease the
  serving residency out of the ledger.
* ``coresidency`` — drive threaded loadgen traffic through the fleet
  while the ``coresident.Scheduler`` runs refresh rounds on the same
  devices: brownout guards watch every replica's windowed p99 at a
  ceiling well BELOW the serving SLO, the chaos delay window forces at
  least one throttle, and the refreshed model hot-swaps in.

Acceptance bars (``failed`` true when any is missed):
zero non-typed traffic failures; overall request p99 within the serving
SLO; ``model_age_seconds`` drops across the refresh; the brownout
throttle counter moved (training yielded to serving at least once).

Usage:
    JAX_PLATFORMS=cpu python tools/coresident_smoke.py \
        [--rows 4000] [--trees 8] [--refresh-trees 6] [--requests 120]
"""

import argparse
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _make_data(rng, rows, features):
    X = rng.randn(rows, features).astype(np.float32).astype(np.float64)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(float)
    return X, y


def run_smoke(rows=4000, trees=8, refresh_trees=6, features=10,
              leaves=15, requests=120, threads=4, max_request_rows=64,
              slo_ms=2000.0, brownout_ms=30.0, delay_s=0.12,
              directory=None) -> dict:
    """Run both phases; returns the JSON-ready summary dict."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.coresident import CoresidentConfig, Scheduler
    from lightgbm_tpu.fleet import PodFleet
    from lightgbm_tpu.obs.flight import global_flight
    from lightgbm_tpu.obs.watchdog import global_watchdog
    from lightgbm_tpu.ops.planner import ResidencyLedger
    from lightgbm_tpu.resilience.faults import ChaosRegistry, FaultSpec
    from lightgbm_tpu.serving.errors import DeadlineExceeded, QueueFull

    own_tmp = None
    if directory is None:
        own_tmp = tempfile.TemporaryDirectory(prefix="lgbt_coresident_")
        directory = own_tmp.name
    # the chaos delay window DELIBERATELY breaches a brownout guard, so
    # every run dumps a rising-edge bundle — keep it out of the cwd
    prev_flight_dir = global_flight._out_dir
    global_flight._out_dir = directory

    summary = {"rows": rows, "trees": trees, "phases": {}}
    rng = np.random.RandomState(0)
    params = {"objective": "binary", "verbosity": -1,
              "num_leaves": leaves}

    # ----------------------------------------------------------- train
    X, y = _make_data(rng, rows, features)
    base_ds = lgb.Dataset(X, label=y, free_raw_data=False)
    deployed = lgb.train(params, base_ds, trees, verbose_eval=False)

    # a mid-run latency-inflation window on every device: batches 4..23
    # each stall delay_s before SUCCEEDING — contention, not failure
    chaos = ChaosRegistry([
        FaultSpec(site="device", kind="delay", at=i, arg=delay_s)
        for i in range(4, 24)])
    fleet = PodFleet(devices=2, chaos=chaos, max_batch_rows=256)
    fleet.add_model("live", deployed)
    fleet.warm()
    global_watchdog.watch_freshness("live")
    global_watchdog.mark_fresh("live")

    ledger = ResidencyLedger(limit_bytes=1 << 30)
    cfg = CoresidentConfig(brownout_p99_ms=brownout_ms,
                           throttle_delay_s=0.01, recovery_s=0.3,
                           escalate_s=30.0,   # throttle-only smoke
                           poll_interval_s=0.02)
    sched = Scheduler(fleet=fleet, ledger=ledger, config=cfg,
                      workdir=os.path.join(directory, "work"))
    serving_lease = sched.lease_serving_residency()
    guards = sched.guard_fleet()
    summary["phases"]["train"] = {
        "iterations": deployed.current_iteration(),
        "devices": fleet.live_devices(),
        "guards": guards,
        "serving_lease_bytes": (serving_lease.nbytes
                                if serving_lease else 0),
        "ledger": ledger.summary(),
    }

    # ----------------------------------------------------- coresidency
    lat_ms: list = []
    typed: list = []
    untyped: list = []
    stop = threading.Event()

    def worker(tidx):
        r = np.random.RandomState(1000 + tidx)
        per = requests // threads
        for _ in range(per):
            m = int(r.randint(1, max_request_rows + 1))
            Xr = r.randn(m, features).astype(np.float32).astype(np.float64)
            t0 = time.perf_counter()
            try:
                fleet.predict("live", Xr, timeout=120)
                lat_ms.append((time.perf_counter() - t0) * 1e3)
            except (QueueFull, DeadlineExceeded) as e:
                typed.append(type(e).__name__)
            except Exception as e:  # noqa: BLE001 — the bar counts these
                untyped.append(repr(e)[:200])
            if stop.is_set():
                break
            time.sleep(0.002)

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(threads)]
    for t in ts:
        t.start()
    time.sleep(0.3)       # let traffic (and the delay window) ramp first
    age_before = global_watchdog.model_age_s("live")

    Xf, yf = _make_data(rng, rows // 2, features)
    fresh = lgb.Dataset(Xf, label=yf, free_raw_data=False)
    t0 = time.perf_counter()
    booster, stats = sched.refresh("live", fresh, params, refresh_trees,
                                   init_model=deployed)
    refresh_s = time.perf_counter() - t0
    age_after = global_watchdog.model_age_s("live")
    for t in ts:
        t.join(timeout=120)
    stop.set()

    # served output must be the refreshed booster, bit-identical
    probe = X[:128]
    served = fleet.predict("live", probe, timeout=120)
    ref = booster.predict(probe, raw_score=True)
    p99 = (float(np.percentile(np.array(lat_ms), 99))
           if lat_ms else None)
    sstats = sched.stats()
    summary["phases"]["coresidency"] = {
        "requests_ok": len(lat_ms),
        "typed_failures": len(typed),
        "untyped_failures": untyped,
        "p99_ms": round(p99, 2) if p99 is not None else None,
        "slo_ms": slo_ms,
        "throttles": sstats["throttles"],
        "pauses": sstats["pauses"],
        "scheduler_state": sstats["state"],
        "chunk_cap": stats["chunk_cap"],
        "refresh_seconds": round(refresh_s, 3),
        "refreshed_iterations": booster.current_iteration(),
        "served_bit_equal_refreshed": bool(np.array_equal(served, ref)),
        "model_age_before_s": (round(age_before, 3)
                               if age_before is not None else None),
        "model_age_after_s": (round(age_after, 3)
                              if age_after is not None else None),
    }

    sched.close()
    if serving_lease is not None:
        ledger.release(serving_lease)
    fleet.close()
    global_watchdog.unwatch("live")
    summary["phases"]["coresidency"]["flight_dumps"] = sorted(
        d for d in os.listdir(directory) if d.startswith("flight_"))
    global_flight._out_dir = prev_flight_dir
    if own_tmp is not None:
        own_tmp.cleanup()

    phase_ok = {
        "no_untyped_failures": not untyped and len(lat_ms) > 0,
        "p99_within_slo": p99 is not None and p99 <= slo_ms,
        "model_age_dropped": (age_before is not None
                              and age_after is not None
                              and age_after < age_before),
        "throttled": sstats["throttles"] > 0,
        "swap_bit_equal": summary["phases"]["coresidency"]
        ["served_bit_equal_refreshed"],
    }
    summary["phase_ok"] = phase_ok
    summary["failed"] = not all(phase_ok.values())
    return summary


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=4000)
    ap.add_argument("--trees", type=int, default=8)
    ap.add_argument("--refresh-trees", type=int, default=6)
    ap.add_argument("--features", type=int, default=10)
    ap.add_argument("--requests", type=int, default=120)
    ap.add_argument("--threads", type=int, default=4)
    ap.add_argument("--max-request-rows", type=int, default=64)
    ap.add_argument("--slo-ms", type=float, default=2000.0)
    ap.add_argument("--brownout-ms", type=float, default=30.0)
    ap.add_argument("--dir", default=None,
                    help="work dir (default: a temp dir)")
    args = ap.parse_args()

    print(f"[coresident_smoke] {args.rows} rows, {args.trees}+"
          f"{args.refresh_trees} trees, {args.requests} requests on a "
          "shared device set", flush=True)
    summary = run_smoke(
        rows=args.rows, trees=args.trees,
        refresh_trees=args.refresh_trees, features=args.features,
        requests=args.requests, threads=args.threads,
        max_request_rows=args.max_request_rows, slo_ms=args.slo_ms,
        brownout_ms=args.brownout_ms, directory=args.dir)
    print(json.dumps(summary, indent=1, sort_keys=True))
    return 1 if summary["failed"] else 0


if __name__ == "__main__":
    sys.exit(main())
