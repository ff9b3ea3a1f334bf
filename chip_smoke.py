#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process — the only one that touches JAX — drives the public entry
points once, ``lgb.Dataset`` → ``lgb.train`` → ``Booster.predict`` →
``Booster.serve``, on one TPU chip, and checks what comes out by the
repo's own means.  Earlier stdout lines are JSON, one per phase
(information: seconds, elected kernel variants, memory, cache warmth —
none of it a benchmark); the LAST line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and the exit code is 0 only if every phase ran and every check held.
Without a TPU the script says why, prints ``"ok": false`` and exits 1 —
there is no CPU continuation.

Shape: the published HIGGS experiment's width (28 dense f32 features,
binary objective, 255 leaves, max_bin 63, learning rate 0.1 — the
reference's docs/Experiments.rst and docs/GPU-Performance.rst), synthetic
rows of ``benchmark/datagen/higgs_like.py``'s law from ``--seed``.  Rows
are 1M rather than the published 10.5M, and rounds 10 rather than 500, to
bound a cold run; the oracle pair trains on a 50,000-row prefix for 3 rounds
because the serial oracle's program is the slowest thing here to
compile and to run.

``--chips 4`` (the builder runs it, the driver does not) runs ONLY the
four-chip path and what it is compared with: ``tree_learner=data`` over
a 4-device mesh at 1M×28 / 255 leaves / 5 rounds against the same
config serial on one of those chips.
"""
import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

FEATURES = 28
TRAIN_ROWS = 1_000_000
VALID_ROWS = 100_000
ROUNDS = 10
ORACLE_ROWS = 50_000
ORACLE_ROUNDS = 3
MESH_ROUNDS = 5
AUC_BAR = 0.80          # seed 0 on the chip: 0.9149 at these 10 rounds
SERVE_REQUESTS = 32
PARAMS = {
    "objective": "binary", "num_leaves": 255, "max_bin": 63,
    "learning_rate": 0.1, "tpu_hist_method": "auto",
    "metric": "binary_logloss", "verbosity": -1,
}


class CheckFailed(AssertionError):
    pass


def check(cond, what):
    if not cond:
        raise CheckFailed(what)


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def auc(scores, labels):
    order = np.argsort(scores, kind="stable")
    ranks = np.empty(len(scores), np.float64)
    ranks[order] = np.arange(1, len(scores) + 1)
    npos = float(labels.sum())
    return (ranks[labels > 0].sum() - npos * (npos + 1) / 2) / (
        npos * (len(labels) - npos))


def logloss(prob, labels):
    p = np.clip(prob, 1e-15, 1 - 1e-15)
    return float(-np.mean(labels * np.log(p) + (1 - labels) * np.log(1 - p)))


def gauges(*names):
    from lightgbm_tpu.obs.metrics import global_registry
    g = global_registry.to_dict().get("gauges", {})
    return {n: g.get(n) for n in names}


def peak_bytes(device):
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", 0))


def round_timer():
    """A train callback that stamps the host clock after every round's
    evaluation (the metric pull waits for the device)."""
    stamps = []

    def _callback(env):
        stamps.append(time.perf_counter())
    _callback.order = 100
    _callback._chunk_safe = True
    return _callback, stamps


def tree_structures(bst):
    return [(np.asarray(m.split_feature), np.asarray(m.threshold_in_bin))
            for m in bst.models]


def first_difference(a, b):
    """(tree, split) of the first split that differs, or None.  Splits
    are numbered in the order the leaf-wise grower made them."""
    for i, ((fa, ta), (fb, tb)) in enumerate(zip(a, b)):
        if not (np.array_equal(fa, fb) and np.array_equal(ta, tb)):
            n = min(len(fa), len(fb))
            diff = np.nonzero((fa[:n] != fb[:n]) | (ta[:n] != tb[:n]))[0]
            return i, int(diff[0]) if len(diff) else n
    return None if len(a) == len(b) else (min(len(a), len(b)), 0)


def agreement(bst_a, bst_b, X, y, what):
    """Hold two boosters to the repo's exactness claim: identical trees,
    split for split; where float reassociation broke a near-tie, fall
    back to held-out logloss and AUC within 1e-3 — and say which."""
    first = first_difference(tree_structures(bst_a), tree_structures(bst_b))
    pa, pb = bst_a.predict(X), bst_b.predict(X)
    la, lb = logloss(pa, y), logloss(pb, y)
    out = {"agreement": ("identical_trees" if first is None
                         else "metrics_within_1e-3"),
           "first_differing_tree_and_split": first,
           "max_abs_prediction_diff": float(np.abs(pa - pb).max()),
           "logloss": [la, lb], "auc": [auc(pa, y), auc(pb, y)]}
    emit(what, **out)
    if first is not None:
        check(abs(la - lb) <= 1e-3 * max(la, lb),
              f"{what}: trees differ and logloss {la} vs {lb}")
        check(abs(out["auc"][0] - out["auc"][1]) <= 1e-3,
              f"{what}: trees differ and AUC {out['auc']}")
    return out


# ----------------------------------------------------------------------
# phases
# ----------------------------------------------------------------------

def phase_device(jax, need):
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    if d.platform != "tpu":
        emit("device", reason=f"JAX found no TPU: devices are {devs}", **info)
        return None
    check(len(devs) >= need, f"need {need} chips, JAX reports {len(devs)}")
    # does block_until_ready wait for the device?  Time a long program
    # three ways: dispatch returned, block_until_ready returned, and a
    # value of the result pulled to the host (both programs warm).
    import jax.numpy as jnp
    x = jnp.ones((4096, 4096), jnp.bfloat16)
    f = jax.jit(lambda a: jax.lax.fori_loop(
        0, 200, lambda _, b: (b @ a) * jnp.bfloat16(2.0 ** -12), a))
    pull = jax.jit(lambda a: a[0, 0].astype(jnp.float32))
    float(pull(f(x)))
    t0 = time.perf_counter()
    y = f(x)
    t_dispatch = time.perf_counter() - t0
    y.block_until_ready()
    t_block = time.perf_counter() - t0
    float(pull(y))
    t_pull = time.perf_counter() - t0
    emit("device", **info, sync_check={
        "dispatch_s": t_dispatch, "block_until_ready_s": t_block,
        "host_pull_s": t_pull})
    check(t_block >= 0.5 * t_pull and t_block > 2 * t_dispatch,
          "block_until_ready returned before the device finished")
    return info


def phase_train(lgb, make_data, seed, device):
    t0 = time.perf_counter()
    X, y = make_data(seed, TRAIN_ROWS, FEATURES)
    Xv, yv = make_data(seed + 1, VALID_ROWS, FEATURES)
    t_data = time.perf_counter() - t0
    t0 = time.perf_counter()
    train = lgb.Dataset(X, label=y, params=PARAMS, free_raw_data=False)
    valid = lgb.Dataset(Xv, label=yv, reference=train)
    train.construct()
    valid.construct()
    t_bin = time.perf_counter() - t0
    ingest = gauges("ingest_variant", "ingest_elected_by")
    evals = {}
    cb, stamps = round_timer()
    t0 = time.perf_counter()
    bst = lgb.train(PARAMS, train, num_boost_round=ROUNDS,
                    valid_sets=[valid], evals_result=evals,
                    verbose_eval=False, callbacks=[cb])
    t_train = time.perf_counter() - t0
    rounds = np.diff([t0] + stamps)
    traj = evals["valid_0"]["binary_logloss"]
    leaves = [int(m.num_leaves) for m in bst.models]
    emit("train", rows=TRAIN_ROWS, features=FEATURES, rounds=ROUNDS,
         data_seconds=t_data, bin_seconds=t_bin, train_seconds=t_train,
         first_round_seconds_with_compile=float(rounds[0]),
         later_round_seconds=[float(r) for r in rounds[1:]],
         valid_logloss=traj, leaves_per_tree=leaves,
         peak_bytes_in_use=peak_bytes(device), **ingest,
         **gauges("train_hist_method", "train_hist_elected_by",
                  "train_tile_rows", "train_hist_predicted_peak_bytes"))
    check(len(traj) == ROUNDS and all(np.isfinite(traj)), "logloss trajectory")
    check(all(b < a for a, b in zip(traj, traj[1:])),
          f"valid logloss does not decrease every round: {traj}")
    check(all(n > 1 for n in leaves), f"a tree did not split: {leaves}")
    return bst, X, y, Xv, yv


def phase_oracle(lgb, X, y, Xv, yv):
    """The path ``auto`` elects against the serial oracle (serial grower,
    staged scatter histograms), both trained here on a prefix."""
    t0 = time.perf_counter()
    Xo, yo = X[:ORACLE_ROWS], y[:ORACLE_ROWS]

    def run(extra):
        params = dict(PARAMS, **extra)
        return lgb.train(params, lgb.Dataset(Xo, label=yo, params=params),
                         num_boost_round=ORACLE_ROUNDS, verbose_eval=False)

    elected = run({})
    variant = gauges("train_hist_method", "train_hist_elected_by")
    oracle = run({"tpu_tree_growth": "serial", "tpu_hist_method": "scatter"})
    agreement(elected, oracle, Xv, yv, "oracle")
    emit("oracle_timing", rows=ORACLE_ROWS, rounds=ORACLE_ROUNDS,
         seconds=time.perf_counter() - t0, elected=variant)


def phase_predict(bst, Xv, yv):
    t0 = time.perf_counter()
    dev_prob = bst.predict(Xv, device=True)
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    dev_raw = bst.predict(Xv, raw_score=True, device=True)
    t_second = time.perf_counter() - t0
    dev_leaf = bst.predict(Xv, pred_leaf=True, device=True)
    host_raw = bst.predict(Xv, raw_score=True)
    host_leaf = bst.predict(Xv, pred_leaf=True)
    from lightgbm_tpu.native.build import load_native_lib
    a = auc(dev_prob, yv)
    emit("predict", rows=len(Xv), first_call_seconds_with_compile=t_first,
         second_call_seconds=t_second, holdout_auc=a,
         max_abs_raw_diff=float(np.abs(dev_raw - host_raw).max()),
         host_traversal=("native .so" if load_native_lib() is not None
                         else "numpy fallback"),
         **gauges("predict_variant", "predict_elected_by"))
    check(dev_prob.shape == (len(Xv),) and np.isfinite(dev_prob).all(),
          "device predictions not finite")
    check(np.array_equal(dev_leaf, host_leaf),
          "device leaf indices differ from the host traversal")
    # tests/test_predict.py::test_device_forest: f32 accumulation on the
    # device against the host's f64, equal routing
    check(np.allclose(dev_raw, host_raw, rtol=0, atol=1e-5),
          "device raw scores differ from the host traversal")
    check(a >= AUC_BAR, f"holdout AUC {a} under {AUC_BAR}")


def phase_serve(bst, Xv, seed):
    rng = np.random.RandomState(seed)
    sizes = [1, 256] + [int(s) for s in rng.randint(1, 257, SERVE_REQUESTS - 2)]
    want = bst.predict(Xv[:4096], raw_score=True)
    t0 = time.perf_counter()
    latencies = []
    with bst.serve() as srv:
        futures = []
        for n in sizes:
            lo = int(rng.randint(0, 4096 - n + 1))
            futures.append((lo, n, time.perf_counter(),
                            srv.submit(Xv[lo:lo + n])))
        for lo, n, t_sub, fut in futures:
            got = fut.result(timeout=600)
            latencies.append(time.perf_counter() - t_sub)
            check(np.array_equal(got, want[lo:lo + n]),
                  f"served answer differs from Booster.predict at "
                  f"rows {lo}:{lo + n}")
        metrics = srv.metrics_dict()
    counters = metrics.get("counters", {})
    emit("serve", requests=len(sizes), rows=int(sum(sizes)),
         seconds=time.perf_counter() - t0,
         slowest_request_seconds=max(latencies),
         requests_total=counters.get("requests_total"),
         compile_events=counters.get("compile_events"))
    check(counters.get("requests_total") == len(sizes),
          "server did not count every request")


def phase_mesh(jax, lgb, make_data, seed):
    """Four chips: data-parallel training against the same config serial
    on one of them, held to what tests/test_parallel.py asserts."""
    X, y = make_data(seed, TRAIN_ROWS, FEATURES)
    Xv, yv = make_data(seed + 1, VALID_ROWS, FEATURES)

    def run(learner):
        params = dict(PARAMS, tree_learner=learner)
        t0 = time.perf_counter()
        bst = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                        num_boost_round=MESH_ROUNDS, verbose_eval=False)
        return bst, time.perf_counter() - t0

    bst_d, t_d = run("data")
    b = bst_d.boosting
    check(b._mesh is not None, "tree_learner=data did not build a mesh")
    shards = b.binned.addressable_shards
    shard_rows = [int(s.data.shape[1]) for s in shards]
    shard_devices = sorted(s.device.id for s in shards)
    emit("mesh_train", learner="data", seconds_with_compile=t_d,
         mesh=dict(zip(b._mesh.axis_names, b._mesh.devices.shape)),
         binned_shape=list(b.binned.shape), shard_rows=shard_rows,
         shard_devices=shard_devices,
         per_device_memory=[{
             "id": d.id,
             "bytes_in_use": int((d.memory_stats() or {})
                                 .get("bytes_in_use", 0)),
             "peak_bytes_in_use": peak_bytes(d)} for d in jax.devices()],
         **gauges("train_hist_method", "train_hist_elected_by",
                  "train_psum_payload_bytes"))
    check(len(set(shard_devices)) == 4,
          f"binned matrix sits on devices {shard_devices}, not on four")
    check(all(r == b.binned.shape[1] // 4 for r in shard_rows),
          f"binned shards hold {shard_rows} rows of {b.binned.shape[1]}")
    bst_s, t_s = run("serial")
    emit("mesh_train", learner="serial", seconds_with_compile=t_s,
         **gauges("train_hist_method", "train_hist_elected_by"))
    check(bst_s.boosting._mesh is None, "the serial run built a mesh")
    agreement(bst_d, bst_s, Xv, yv, "mesh_agreement")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the data-parallel path and its "
                         "one-chip comparison")
    args = ap.parse_args()

    last = {"ok": False}
    try:
        import jax

        import lightgbm_tpu as lgb
        from benchmark.datagen.higgs_like import generate as make_higgs_like
        from lightgbm_tpu.utils.platform import (compile_cache_entries,
                                                 enable_compile_cache)
        cache_dir = enable_compile_cache()
        emit("cache", dir=cache_dir, env_set=bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()),
            entries_at_start=compile_cache_entries(cache_dir))
        info = phase_device(jax, args.chips)
        if info is not None:
            t0 = time.perf_counter()
            if args.chips == 4:
                phase_mesh(jax, lgb, make_higgs_like, args.seed)
            else:
                device = jax.devices()[0]
                bst, X, y, Xv, yv = phase_train(
                    lgb, make_higgs_like, args.seed, device)
                phase_oracle(lgb, X, y, Xv, yv)
                phase_predict(bst, Xv, yv)
                phase_serve(bst, Xv, args.seed)
            emit("cache", dir=cache_dir, seconds_total=time.perf_counter() - t0,
                 entries_at_end=compile_cache_entries(cache_dir))
            last = {"ok": True, "device": info}
    except Exception:
        # report and FAIL: the run never ends in 0 from here
        traceback.print_exc()
        emit("error", error=traceback.format_exc(limit=3)[-2000:])
    print(json.dumps(last), flush=True)
    return 0 if last["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
