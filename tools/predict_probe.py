#!/usr/bin/env python
"""Inference-kernel micro-bench: while vs fori vs fused traversal.

The training kernel war has hist_probe; this is the predict path's
probe.  It trains a small synthetic booster (categorical feature + NaN
column, so the routing recipe is fully exercised), stands up one
``DeviceForest`` per traversal variant, and reports:

- **structural parity**: fori and fused leaf indices bit-identical to
  the while_loop baseline on a mixed batch (zeros / NaN / +-huge rows
  included) — the invariant every other number rests on;
- **serving parity**: the elected forest's ``predict_raw`` bit-equal to
  ``Booster.predict(raw_score=True)`` (the serving acceptance bar);
- **measured utilization** per variant via
  ``obs/devprof.predict_utilization_table`` (compiler-counted
  FLOPs/bytes + wall sec/call -> sec/Mrow, MFU, HBM GB/s);
- **election**: what ``ops/planner.plan_predict`` picks analytically,
  what it picks after the measured timings are banked into the
  autotune store's ``"p-..."`` family (cold vs warm, hit/miss/flip
  counters for bench_diff's election-quality gate);
- ``predict_sec_per_mrow`` (the elected variant) and
  ``speedup_vs_while`` — on accelerators at >= 1M rows the probe FAILS
  (raises) below 3x, the ISSUE 19 acceptance bar; off-accelerator the
  numbers are interpret-mode noise, so rows are capped and only parity
  is enforced.

The LAST stdout line is a single JSON object so bench.py's worker can
bank it as a stage (``stage: predict_probe``;
``BENCH_SKIP_PREDICT_PROBE=1`` skips the stage).

Usage:
    JAX_PLATFORMS=cpu python tools/predict_probe.py \
        [--rows 1000000] [--features 12] [--leaves 31] [--rounds 20] \
        [--reps 3]
"""

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# off-accelerator the fused arm runs in Pallas interpret mode — minutes
# per Mrow, and the timings mean nothing; cap the probe shape there
CPU_ROWS_CAP = 50_000


def _train_booster(rows, features, leaves, rounds, seed=0):
    import lightgbm_tpu as lgb

    rng = np.random.RandomState(seed)
    X = rng.randn(rows, features).astype(np.float32).astype(np.float64)
    X[:, 0] = rng.randint(0, 8, size=rows)          # categorical
    X[rng.rand(rows) < 0.1, 2] = np.nan             # missing routing
    y = (X[:, 1] + X[:, 3] * X[:, 4] > 0).astype(float)
    bst = lgb.train(
        {"objective": "binary", "verbosity": -1, "num_leaves": leaves},
        lgb.Dataset(X, label=y, categorical_feature=[0]),
        num_boost_round=rounds, verbose_eval=False)
    n_iter = len(bst.models) // bst.num_tree_per_iteration
    return bst, bst._forest(0, n_iter), X


def parity_check(forest, X, variants=("while", "fori", "fused")) -> dict:
    """Bit-identical leaf indices across traversal variants on a batch
    salted with the routing edge cases (zeros, NaN rows, +-huge)."""
    import jax.numpy as jnp

    from lightgbm_tpu.predict import DeviceForest

    Xs = np.array(X[:512], np.float64)
    Xs[0, :] = 0.0
    Xs[1, :] = np.nan
    Xs[2, :] = -1e30
    Xs[3, :] = 1e30
    ref = None
    out = {}
    for v in variants:
        dev = DeviceForest(forest, variant=v)
        leaves = np.asarray(dev._leaves_jit(
            jnp.asarray(np.asarray(Xs, np.float32))))
        if ref is None:
            ref = leaves
            out[v] = {"baseline": True}
        else:
            out[v] = {"bit_equal_to_while": bool(np.array_equal(ref, leaves))}
    out["ok"] = all(d.get("bit_equal_to_while", True) for d in out.values()
                    if isinstance(d, dict))
    return out


def autotune_probe(table, rows, features, num_trees, num_class,
                   precision="f32") -> dict:
    """Bank the measured per-variant timings into the planner's
    ``"p-..."`` autotune family and run the election cold and warm —
    the predict twin of hist_probe's --autotune column."""
    from lightgbm_tpu.ops import planner as P

    out = {"enabled": P.autotune_enabled(), "store_dir": P.autotune_dir()}
    if not (P.autotune_enabled() and P.autotune_dir()):
        out["skipped"] = ("the autotune store is switched off "
                          "(LGBM_TPU_AUTOTUNE / LGBM_TPU_AUTOTUNE_DIR)")
        return out
    sec = {v: table[v]["seconds_per_call"] for v in ("while", "fori", "fused")
           if isinstance(table.get(v), dict) and "seconds_per_call" in table[v]}
    if len(sec) < 2:
        out["skipped"] = "fewer than two variants produced timings"
        return out
    P.autotune_counters(reset=True)

    def plan():
        return P.plan_predict(
            num_trees=num_trees, nodes_dim=1, leaves_dim=1,
            features=features, rows=rows, num_class=num_class,
            precision=precision)

    cold = plan()
    for v, s in sec.items():
        P.record_predict_timing(rows, features, num_trees, num_class,
                                precision, v, s)
    warm = plan()
    counters = P.autotune_counters()
    out.update({
        "shape_bucket": warm.autotune_key,
        "cold_variant": cold.variant,
        "cold_elected_by": cold.elected_by,
        "warm_variant": warm.variant,
        "warm_elected_by": warm.elected_by,
        "winner": min(sec, key=sec.get),
        "seconds_per_call": sec,
        "autotune_hits": counters["hits"],
        "autotune_misses": counters["misses"],
        "autotune_flips": counters["flips"],
    })
    return out


def run_probe(rows=1_000_000, features=12, leaves=31, rounds=20,
              reps=3, train_rows=4000) -> dict:
    import jax

    from lightgbm_tpu.obs.devprof import predict_utilization_table
    from lightgbm_tpu.ops.histogram import on_accelerator
    from lightgbm_tpu.predict import DeviceForest

    accel = on_accelerator()
    if not accel:
        rows = min(int(rows), CPU_ROWS_CAP)

    bst, forest, X = _train_booster(train_rows, features, leaves, rounds)
    out = {
        "rows": int(rows), "features": int(features),
        "num_trees": int(forest.num_trees),
        "platform": jax.devices()[0].platform,
        "accelerator": accel,
    }

    # ---- parity first: timings of wrong kernels are worthless ---------
    out["parity"] = parity_check(forest, X)
    if not out["parity"]["ok"]:
        raise RuntimeError(
            f"traversal variant parity FAILED: {out['parity']}")

    # ---- serving bit-parity vs the booster's own raw predict ----------
    dev = DeviceForest(forest)            # planner-elected variant
    out["elected_variant"] = dev.variant
    out["tile_rows"] = dev.tile_rows
    out["chunk_rows"] = dev.chunk_rows
    # predict_raw_padded is the serving entry point (registry programs);
    # predict_raw is the f32 device-accumulation fast path and does NOT
    # carry the bit-parity contract
    raw = dev.predict_raw_padded(X)[0]
    ref = bst.predict(X, raw_score=True)
    out["serving_bit_equal"] = bool(np.array_equal(raw, ref))
    if not out["serving_bit_equal"]:
        raise RuntimeError(
            "elected traversal variant changed Booster.predict("
            "raw_score=True) output — serving parity broken")

    # ---- measured utilization per variant -----------------------------
    table = predict_utilization_table(dev, rows=rows, reps=reps)
    out["utilization"] = table
    mrow = max(rows / 1e6, 1e-9)
    sec_per_mrow = {v: table[v]["seconds_per_call"] / mrow
                    for v in ("while", "fori", "fused")
                    if isinstance(table.get(v), dict)
                    and "seconds_per_call" in table[v]}
    out["sec_per_mrow"] = sec_per_mrow
    elected = dev.variant if dev.variant in sec_per_mrow else "fori"
    if elected in sec_per_mrow and "while" in sec_per_mrow:
        out["predict_sec_per_mrow"] = sec_per_mrow[elected]
        out["speedup_vs_while"] = round(
            sec_per_mrow["while"] / max(sec_per_mrow[elected], 1e-12), 3)
        if accel and rows >= 1_000_000 and out["speedup_vs_while"] < 3.0:
            raise RuntimeError(
                f"elected kernel '{elected}' is only "
                f"{out['speedup_vs_while']}x faster than while_loop at "
                f"{rows} rows — below the 3x acceptance bar")

    # ---- autotune family: banked timings steer the next election ------
    out["autotune"] = autotune_probe(
        table, rows, int(np.asarray(forest.split_feature).max(initial=0)) + 1,
        int(forest.num_trees), 1)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--features", type=int, default=12)
    ap.add_argument("--leaves", type=int, default=31)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args()
    out = run_probe(args.rows, args.features, args.leaves, args.rounds,
                    args.reps)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
