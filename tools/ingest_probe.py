#!/usr/bin/env python
"""Device-ingest micro-bench: the bucketize+pack kernel vs the host oracle.

The training and predict paths have hist_probe / predict_probe; this is
the ingest path's probe (ops/ingest.py).  It reports:

- **byte parity** on the full matrix of binning recipes — NaN routing,
  zero-as-bin EFB sparsity, categorical lookup, uint8 AND uint16 group
  dtypes — device bytes vs the host ``BinMapper.value_to_bin`` path on
  a salted block (zeros / all-NaN / +-1e30 / non-integer / negative
  codes).  Any mismatch raises: timings of wrong kernels are worthless;
- **measured utilization** per VMEM tile rung via
  ``obs/devprof.ingest_utilization_table`` (compiler-counted bytes +
  wall sec/call -> bin rows/sec, HBM GB/s) next to the wall-clocked
  host oracle at the same shape — the kernel-vs-host speedup is read
  straight off the table;
- **election**: what ``ops/planner.plan_ingest`` picks analytically,
  what it picks after the measured timings are banked into the
  autotune store's ``"i-..."`` family (cold vs warm, hit/miss/flip
  counters for bench_diff's election-quality gate);
- ``bin_rows_per_sec`` and ``kernel_speedup_vs_host`` — on accelerators
  at >= 1M rows the probe FAILS (raises) below 5x, the ISSUE 20
  acceptance bar; off-accelerator the kernel interprets (minutes per
  Mrow of jnp emulation), so rows are capped and only parity is
  enforced.

The LAST stdout line is a single JSON object so bench.py's worker can
bank it as a stage (``stage: ingest_probe``;
``BENCH_SKIP_INGEST_PROBE=1`` skips the stage).

Usage:
    JAX_PLATFORMS=cpu python tools/ingest_probe.py \
        [--rows 1000000] [--features 28] [--max-bin 63] [--reps 2]
"""

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# off-accelerator the kernel runs in Pallas interpret mode — the
# timings mean nothing; cap the probe shape there
CPU_ROWS_CAP = 50_000


def _make_raw(rows, features, seed=0, categorical=True):
    """Synthetic block exercising every binning recipe at once: a
    categorical column, NaN routing, and two mostly-zero columns so EFB
    actually bundles (zero-as-bin + the fold's conflict semantics)."""
    rng = np.random.RandomState(seed)
    X = (rng.rand(rows, features) * 10.0).astype(np.float64)
    if categorical:
        X[:, 0] = rng.randint(0, 12, size=rows)
    X[rng.rand(rows) < 0.1, 2] = np.nan
    X[rng.rand(rows) < 0.7, 3] = 0.0
    if features > 5:
        X[rng.rand(rows) < 0.8, 5] = 0.0
    y = (rng.rand(rows) > 0.5).astype(np.float64)
    return X, y


def _build_dataset(rows, features, max_bin, categorical=True, seed=0):
    import lightgbm_tpu as lgb

    X, y = _make_raw(rows, features, seed=seed, categorical=categorical)
    params = {"objective": "binary", "verbosity": -1, "max_bin": max_bin}
    ds = lgb.Dataset(X, label=y, params=params,
                     categorical_feature=[0] if categorical else None)
    ds.construct()
    return ds, X


def parity_case(rows, features, max_bin, categorical, seed, label):
    """One cell of the parity matrix: device bytes vs the host oracle
    on a salted block, for one dataset recipe."""
    from lightgbm_tpu.ops import ingest as ING

    ds, X = _build_dataset(rows, features, max_bin, categorical, seed)
    tables = ING.build_ingest_tables(ds)
    binner = ING.DeviceBinner(tables)
    probe = np.concatenate([
        np.asarray(X[:512], np.float32),
        ING.salt_rows(features, np.asarray(X, np.float32))])
    ref = np.zeros((probe.shape[0], ds.num_groups), tables.out_dtype)
    with np.errstate(invalid="ignore"):      # host int64 cast of +-1e30
        ds._bin_block(probe.astype(np.float64), None, ref)
    got = np.asarray(binner(probe))
    return {"case": label, "rows": int(probe.shape[0]),
            "out_dtype": str(tables.out_dtype),
            "num_groups": int(ds.num_groups),
            "bit_equal": bool(np.array_equal(ref, got))}


def parity_matrix(features=12) -> dict:
    """NaN / zero-as-bin / categorical / uint8+uint16: the acceptance
    criterion's full matrix (max_bin=1000 forces a >256-bin group, the
    uint16 arm)."""
    cases = [
        parity_case(2000, features, 63, True, 0, "uint8+cat+nan+zero"),
        parity_case(2000, features, 1000, True, 1, "uint16+cat+nan+zero"),
        parity_case(2000, features, 63, False, 2, "uint8 numerical"),
    ]
    return {"cases": cases, "ok": all(c["bit_equal"] for c in cases)}


def autotune_probe(rows, features, num_groups, item_bytes,
                   kernel_sec, host_sec) -> dict:
    """Bank the measured kernel/host timings into the planner's
    ``"i-..."`` autotune family and run the election cold and warm —
    the ingest twin of predict_probe's autotune column."""
    from lightgbm_tpu.ops import planner as P

    out = {"enabled": P.autotune_enabled(), "store_dir": P.autotune_dir()}
    if not (P.autotune_enabled() and P.autotune_dir()):
        out["skipped"] = ("the autotune store is switched off "
                          "(LGBM_TPU_AUTOTUNE / LGBM_TPU_AUTOTUNE_DIR)")
        return out
    P.autotune_counters(reset=True)

    def plan():
        return P.plan_ingest(rows=rows, features=features,
                             num_groups=num_groups, item_bytes=item_bytes)

    cold = plan()
    P.record_ingest_timing(rows, features, num_groups, item_bytes,
                           "kernel", kernel_sec)
    P.record_ingest_timing(rows, features, num_groups, item_bytes,
                           "host", host_sec)
    warm = plan()
    counters = P.autotune_counters()
    out.update({
        "shape_bucket": warm.autotune_key,
        "cold_variant": cold.variant,
        "cold_elected_by": cold.elected_by,
        "warm_variant": warm.variant,
        "warm_elected_by": warm.elected_by,
        "winner": "kernel" if kernel_sec < host_sec else "host",
        "seconds_per_call": {"kernel": kernel_sec, "host": host_sec},
        "autotune_hits": counters["hits"],
        "autotune_misses": counters["misses"],
        "autotune_flips": counters["flips"],
    })
    return out


def run_probe(rows=1_000_000, features=28, max_bin=63, reps=2) -> dict:
    import jax

    from lightgbm_tpu.obs.devprof import ingest_utilization_table
    from lightgbm_tpu.ops import planner as P
    from lightgbm_tpu.ops.histogram import on_accelerator

    accel = on_accelerator()
    if not accel:
        rows = min(int(rows), CPU_ROWS_CAP)
    out = {"rows": int(rows), "features": int(features),
           "max_bin": int(max_bin),
           "platform": jax.devices()[0].platform, "accelerator": accel}

    # ---- parity first: timings of wrong kernels are worthless ---------
    out["parity"] = parity_matrix(features=min(int(features), 12))
    if not out["parity"]["ok"]:
        raise RuntimeError(f"ingest parity FAILED: {out['parity']}")

    # ---- measured utilization at the bench workload's shape -----------
    # numerical-only data: the synthetic-HIGGS matrix the bin_seconds
    # acceptance bar is stated against
    ds, X = _build_dataset(int(rows), int(features), int(max_bin),
                           categorical=False, seed=3)
    table = ingest_utilization_table(ds, np.asarray(X, np.float32),
                                     reps=reps)
    out["utilization"] = table
    speedup = table.get("kernel_speedup_vs_host")
    if speedup is not None:
        out["kernel_speedup_vs_host"] = speedup
        out["bin_rows_per_sec"] = table.get("bin_rows_per_sec")
        if accel and rows >= 1_000_000 and speedup < 5.0:
            raise RuntimeError(
                f"ingest kernel is only {speedup}x faster than the host "
                f"oracle at {rows} rows — below the 5x acceptance bar")

    # ---- election: the plan this shape would train under --------------
    item = np.dtype(table["out_dtype"]).itemsize
    out["plan"] = P.plan_ingest(
        rows=int(rows), features=int(features),
        num_groups=int(table["num_groups"]), item_bytes=item).summary()

    # ---- autotune family: banked timings steer the next election ------
    kernel_sec = table.get("best_kernel_seconds_per_call")
    host_sec = table.get("host", {}).get("seconds_per_call")
    if kernel_sec and host_sec:
        out["autotune"] = autotune_probe(
            int(rows), int(features), int(table["num_groups"]), item,
            kernel_sec, host_sec)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--features", type=int, default=28)
    ap.add_argument("--max-bin", type=int, default=63)
    ap.add_argument("--reps", type=int, default=2)
    args = ap.parse_args()
    out = run_probe(args.rows, args.features, args.max_bin, args.reps)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
