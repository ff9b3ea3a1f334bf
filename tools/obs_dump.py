#!/usr/bin/env python
"""Observability dump: run one small fully-instrumented train + serve
cycle and emit every obs artifact — the smoke test for the whole
observability plane (docs/OBSERVABILITY.md).

Enables tracing + timers, trains a small booster (with a checkpoint
snapshot so ``checkpoint.save`` spans appear), serves a few requests
through the in-process server (so the serving component joins the
process registry), then writes:

- ``obs_trace.json``      — Chrome trace-event / Perfetto-loadable spans
- ``obs_metrics.json``    — unified registry snapshot (training gauges,
  timer mirrors, serving component)
- ``obs_metrics.prom``    — the same registry in Prometheus text format

The LAST stdout line is one JSON summary (span names, coverage, artifact
paths).

Usage:
    JAX_PLATFORMS=cpu python tools/obs_dump.py \
        [--out-dir .] [--rows 20000] [--trees 8]
"""

import argparse
import json
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_dump(out_dir=".", rows=20_000, features=10, trees=8, leaves=15,
             requests=4):
    """One instrumented train+serve cycle; returns the JSON summary."""
    from lightgbm_tpu.obs.metrics import global_registry
    from lightgbm_tpu.obs.trace import global_tracer, span_coverage
    from lightgbm_tpu.utils.timer import global_timer

    trace_was_on = global_tracer.enabled
    timer_was_on = global_timer.enabled
    global_tracer.enable()
    global_timer.enable()
    try:
        import lightgbm_tpu as lgb

        rng = np.random.RandomState(0)
        X = rng.rand(rows, features)
        y = (X[:, 0] + X[:, 1] * X[:, 2] > 0.8).astype(np.float32)
        with tempfile.TemporaryDirectory() as td:
            booster = lgb.train(
                {"objective": "binary", "verbosity": -1,
                 "num_leaves": leaves},
                lgb.Dataset(X, label=y), num_boost_round=trees,
                verbose_eval=False,
                snapshot_freq=max(trees // 2, 1),
                snapshot_out=os.path.join(td, "ck.txt"))
        # snapshot INSIDE the serve block: close() detaches the serving
        # component from the process registry, and the artifacts exist to
        # show training + serving in ONE snapshot
        with booster.serve(max_batch_rows=256) as server:
            for _ in range(requests):
                server.predict(X[:32])
            global_timer.publish(global_registry)
            os.makedirs(out_dir, exist_ok=True)
            trace_file = os.path.join(out_dir, "obs_trace.json")
            metrics_file = os.path.join(out_dir, "obs_metrics.json")
            prom_file = os.path.join(out_dir, "obs_metrics.prom")
            global_registry.dump_json(metrics_file)
            from lightgbm_tpu.utils.file_io import write_atomic
            write_atomic(prom_file, global_registry.to_prometheus())
            snap = global_registry.to_dict()
        global_tracer.dump(trace_file)   # after close: drain spans included

        events = global_tracer.events()
        return {
            "trace_file": trace_file,
            "metrics_file": metrics_file,
            "prometheus_file": prom_file,
            "trace_events": len(events),
            "span_names": sorted({e["name"] for e in events})[:40],
            "train_coverage": span_coverage(events, "engine.train"),
            "gauges": {k: v for k, v in snap["gauges"].items()
                       if not k.startswith("timer.")},
            "counters": snap["counters"],
            "components": sorted(snap.get("components", {})),
            "timer_sections": sum(1 for k in snap["gauges"]
                                  if k.startswith("timer.")),
        }
    finally:
        if not trace_was_on:
            global_tracer.disable()
            global_tracer.reset()
        if not timer_was_on:
            global_timer.disable()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out-dir", default=".")
    ap.add_argument("--rows", type=int, default=20_000)
    ap.add_argument("--features", type=int, default=10)
    ap.add_argument("--trees", type=int, default=8)
    ap.add_argument("--leaves", type=int, default=15)
    args = ap.parse_args()
    result = run_dump(out_dir=args.out_dir, rows=args.rows,
                      features=args.features, trees=args.trees,
                      leaves=args.leaves)
    print(json.dumps(result, indent=1, sort_keys=True))
    ok = result["trace_events"] > 0 and result["train_coverage"]
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
