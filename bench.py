"""Driver benchmark: HIGGS-scale GBDT training wall-clock on one TPU chip.

Prints JSON lines; the LAST line is the result the driver records:
{"metric", "value", "unit", "vs_baseline", ...}.

Workload mirrors the reference's headline experiment (docs/Experiments.rst:
500 trees, 255 leaves, lr=0.1; GPU-comparable max_bin=63 per
docs/GPU-Performance.rst guidance) on a synthetic dataset with HIGGS's shape
(11M x 28 dense float features, binary labels).  HIGGS itself cannot be
downloaded in this environment (zero egress), so the data is synthetic with
label structure (linear + pairwise signal, 20% noise) to keep trees growing
to the leaf budget as on real data.

Baseline: 130.094 s — LightGBM CPU on 2x Xeon E5-2690 v4
(docs/Experiments.rst:114).  vs_baseline = baseline_seconds / our_seconds
(>1 means faster than the reference).

Timing excludes binning/dataset construction (as does the reference's
experiment) and the one-time XLA compile: the clock starts after iteration 1
and the total is rescaled by T/(T-1).

One process.  ``python bench.py`` initialises JAX, refuses a platform
that is not ``tpu`` (exit 3; ``BENCH_WORKER_ALLOW_CPU=1`` lets CI walk the
stage pipeline on the CPU, where no line is a device measurement), runs
its stages in order and emits one JSON "stage" line after each, every one
carrying ``platform`` / ``device_kind`` / ``n_devices``.  A chip belongs
to one process at a time, so nothing here starts a child that needs it.
Exit codes: 0 every stage ran, 3 no TPU (or JAX failed to start), 4 a
headline stage (smoke / full) failed.  The persistent XLA compile cache
is on for every stage (utils/platform.enable_compile_cache).  Stage
contents, metrics and cells are the benchmark's next revision
(ROADMAP.md A1); ``chip_smoke.py`` is the quick end-to-end check.

Env overrides: BENCH_ROWS, BENCH_TREES, BENCH_LEAVES, BENCH_BIN,
BENCH_PROFILE=1 (jax.profiler trace to ./bench_trace),
BENCH_TOTAL_BUDGET (s, default 6600),
BENCH_SMOKE_ROWS / BENCH_SMOKE_TREES,
BENCH_SKIP_SMOKE=1, BENCH_SKIP_KERNEL_PROBE=1, BENCH_SKIP_HIST_PROBE=1,
BENCH_SKIP_OBS=1 (skip the obs_dump + obs_doctor stages AND the measured
per-variant MFU table; obs_doctor — tools/obs_doctor.py over
lightgbm_tpu/obs/diagnose.py — runs LAST and journals ranked bottleneck
verdicts ("dcn-bound", "compile-bound", "input-bound", "straggler",
"contention", "kernel-underutilized") derived from the banked stages, so every bench
round self-reports its bottleneck; the measured MFU table is the
lightgbm_tpu/obs/devprof.py cost_analysis numbers that
otherwise ride in the full run_bench results as "mfu_measured",
banked under their own journal key so retries replay them; the table
now includes the */fused rows — the Pallas histogram→split megakernel,
ops/fused.py — whose MFU against the staged rows at the same shape is
the fusion acceptance figure, and the hist_probe stage journals the
fused-vs-staged sec/level + HBM bytes_accessed drop per level).
Observability: LIGHTGBM_TPU_TRACE=1 records structured spans through
every stage (bench phases, engine loop, dispatch/fetch, serving) and
each run_bench stage dumps a Chrome-trace JSON (bench_trace_<stage>.json)
plus a unified metrics-registry snapshot (bench_obs_metrics.json) under
./bench_out/ (gitignored); "obs" in the stage JSON carries the file + a span-tree
wall-clock coverage figure (docs/OBSERVABILITY.md).
Memory/caching: LGBM_TPU_TILE_ROWS / LGBM_TPU_HBM_BYTES steer the HBM
budget planner (ops/planner.py; the >=10M-row stage is gated on its
feasibility verdict and degrades to smaller row tiles instead of
crashing — the decision is journaled as the "hbm_plan" stage);
BENCH_SKIP_COLLECTIVE_PROBE=1 skips the per-tier collective micro-bench
(tools/collective_probe.py: flat vs hierarchical vs voting reduction
latency + the ops/planner.plan_collectives per-tier byte accounting over
a simulated 2-slice hybrid ("dcn","ici") mesh — the journaled acceptance
signal is voting's DCN bytes strictly below data-parallel's at equal
trees; LGBM_TPU_NUM_SLICES / LGBM_TPU_HIER_REDUCE / LGBM_TPU_ICI_GBPS /
LGBM_TPU_DCN_GBPS steer the pod-scale election itself);
out-of-core streaming (lightgbm_tpu/data/): BENCH_SKIP_STREAM_PROBE=1
skips the block-pump micro-bench (tools/stream_probe.py),
BENCH_SKIP_STREAM=1 skips the graduated 100M-row streamed stage
(BENCH_STREAM_ROWS / BENCH_STREAM_TREES size it; its two-level
host+HBM verdict banks as the "stream_plan" stage and the run
journals planner-predicted vs measured peaks on BOTH memories;
LGBM_TPU_STREAM / LGBM_TPU_STREAM_BLOCK_ROWS / LGBM_TPU_HOST_BYTES
steer the election);
inference kernels (ops/predict_kernels.py): BENCH_SKIP_PREDICT_PROBE=1
skips the traversal micro-bench (tools/predict_probe.py: while vs fori
vs fused sec/Mrow + measured MFU/BW, the plan_predict election cold and
warm against the autotune store's "p-..." family, serving bit-parity;
accelerators raise below the 3x-vs-while bar at 1M rows),
BENCH_SKIP_BULK_SCORE=1 skips the bulk offline-scoring stage
(tools/bulk_score.py: a BENCH_BULK_ROWS-row — default 10M — synthetic
blockstore streamed through the AOT bulk bucket with per-block score
commits and a resume-after-kill byte-identity drill;
LGBM_TPU_PREDICT_KERNEL / LGBM_TPU_PREDICT_CHUNK /
LGBM_TPU_PREDICT_EPILOGUE steer the predict election itself);
BENCH_SKIP_SWEEP=1 skips the batched model-axis sweep micro-bench
(tools/sweep_probe.py: the SAME macro-chunk body solo vs vmapped at
B in {2,4,8} heterogeneous lanes over one shared binned matrix —
per-dispatch latency, aggregate boosting iters/sec and measured MFU
per batch width, plus ops/planner.plan_model_batch's lane-chunk
verdict; on accelerators the journaled acceptance bar is B=8
aggregate iters/sec >= 4x B=1, and a missed bar raises so failed
sweep runs are never journaled; LGBM_TPU_MODEL_BATCH caps the
production lane chunk itself);
BENCH_SKIP_FLEET=1 skips the serving-fleet stage (lightgbm_tpu/fleet/:
N-model registry under a shared-HBM residency plan — measured eviction
with every model still servable, AOT zero-compile replica restart, and
the opt-in bf16/int8 accuracy deltas via tools/fleet_smoke.py; a missed
acceptance bar raises so failed fleet runs are never journaled) AND the
fleet_failover stage (kill one device of a BENCH_FLEET_DEVICES-wide
replicated PodFleet under load: zero non-typed failures, availability
>= 0.999, recovery within one replan tick);
BENCH_SKIP_LIFECYCLE=1 skips the guarded model-lifecycle stage
(lightgbm_tpu/lifecycle/: continual refresh -> shadow/canary promotion
under loadgen traffic -> forced drift rollback with the fleet's output
byte-identical to the pre-promotion model, via
tools/lifecycle_smoke.py; a missed bar raises so failed lifecycle runs
are never journaled);
BENCH_SKIP_CORESIDENT=1 skips the co-resident train+serve stage
(lightgbm_tpu/coresident/: loadgen traffic AND a residency-ledger-
budgeted refresh on the SAME device set, via tools/coresident_smoke.py;
the bars — zero non-typed failures with p99 within SLO, model age
drops, the brownout throttle counter moved — raise when missed so
failed co-residency runs are never journaled);
LGBM_TPU_VMEM_BYTES steers the fused-megakernel VMEM arena election and
LGBM_TPU_FUSED=0 drops the fused arm entirely (staged family only);
cold-vs-warm compile_seconds are recorded per stage under
"compile_cache".

Stage journal: every completed worker stage persists its result to
BENCH_JOURNAL (default ./bench_journal.json, atomic writes) under a
workload fingerprint; a rerun after a mid-run crash replays the banked
stages and executes only the missing ones.  BENCH_ONLY=<stage[,stage]>
selects exactly those worker stages.  BENCH_JOURNAL=0 disables.
"""
import json
import os
import subprocess
import sys
import time
import traceback

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# all per-run observability artifacts (Chrome traces, metrics snapshots)
# land here, NOT in the repo root — gitignored so bench runs stop
# churning the working tree
BENCH_OUT = os.path.join(REPO, "bench_out")

BASELINE_SECONDS = 130.094

N = int(os.environ.get("BENCH_ROWS", 11_000_000))
F = 28
TREES = int(os.environ.get("BENCH_TREES", 500))
LEAVES = int(os.environ.get("BENCH_LEAVES", 255))
MAX_BIN = int(os.environ.get("BENCH_BIN", 63))

SMOKE_N = int(os.environ.get("BENCH_SMOKE_ROWS", 500_000))
SMOKE_TREES = int(os.environ.get("BENCH_SMOKE_TREES", 3))

# MSLR-shaped ranking stage (BASELINE.md: MS LTR 70.417 s / 500 trees CPU)
RANK_QUERIES = int(os.environ.get("BENCH_RANK_QUERIES", 12_000))
RANK_DOCS = int(os.environ.get("BENCH_RANK_DOCS", 100))
RANK_TREES = int(os.environ.get("BENCH_RANK_TREES", 100))

TOTAL_BUDGET = float(os.environ.get("BENCH_TOTAL_BUDGET", 6600))

# peak dense compute per chip for the MFU estimate — ONE table, shared
# with the measured-MFU path (obs/devprof.py) so the lower bound and the
# cost_analysis numbers use the same denominator
from lightgbm_tpu.obs.devprof import peak_flops_for

START = time.time()

# what JAX reports for the device this process holds; set once at init
# and stamped on every stage line (a number without its device is not a
# measurement)
_DEVICE = {}


def dsync(x):
    """Wait for the device work behind ``x``."""
    x.block_until_ready()



def remaining_budget():
    return TOTAL_BUDGET - (time.time() - START)


def emit(d):
    if "stage" in d:
        d = {**d, **_DEVICE}
    print(json.dumps(d), flush=True)


def log(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def error_line(stage, err, extra=None):
    d = {
        "metric": f"bench-error at {stage}",
        "value": 0.0,
        "unit": "seconds",
        "vs_baseline": 0.0,
        "error": str(err)[-1500:],
    }
    if extra:
        d.update(extra)
    return d


def make_mslr_like(n_queries, docs_per_query, f, seed=0):
    """Synthetic MSLR-WEB30K-shaped ranking data: graded 0-4 relevance from
    a noisy nonlinear score (the real set is not downloadable here; shape
    and metric protocol follow docs/Experiments.rst:55-60 / BASELINE.md)."""
    rng = np.random.RandomState(seed)
    n = n_queries * docs_per_query
    w = np.random.RandomState(777).randn(f).astype(np.float32)
    X = rng.rand(n, f).astype(np.float32)
    s = X @ w + 1.5 * X[:, 0] * X[:, 1] - X[:, 2] * (X[:, 3] > 0.5)
    s += rng.randn(n).astype(np.float32) * 0.3 * s.std()
    # per-query relevance grades: quintile buckets of the score
    s = s.reshape(n_queries, docs_per_query)
    order = np.argsort(np.argsort(s, axis=1), axis=1)
    grade = (order * 5 // docs_per_query).astype(np.float32)
    group = np.full(n_queries, docs_per_query, np.int32)
    return X, grade.reshape(-1), group


def ndcg_at_k(scores, labels, docs_per_query, k=10):
    """NDCG@k averaged over equal-size queries (DCGCalculator semantics:
    gain 2^label-1, log2 position discount)."""
    s = scores.reshape(-1, docs_per_query)
    l = labels.reshape(-1, docs_per_query)
    idx = np.argsort(-s, axis=1)[:, :k]
    top = np.take_along_axis(l, idx, axis=1)
    disc = 1.0 / np.log2(np.arange(2, k + 2))
    dcg = ((2.0 ** top - 1) * disc).sum(axis=1)
    ideal = np.sort(l, axis=1)[:, ::-1][:, :k]
    idcg = ((2.0 ** ideal - 1) * disc).sum(axis=1)
    return float((dcg / np.maximum(idcg, 1e-12)).mean())


def run_ranking_bench(n_queries, docs_per_query, trees, leaves, max_bin):
    """Lambdarank wall-clock + NDCG@10 (the MSLR-side benchmark)."""
    import jax

    import lightgbm_tpu as lgb

    F = 136                           # MSLR feature count
    X, y, group = make_mslr_like(n_queries, docs_per_query, F)
    params = {
        "objective": "lambdarank",
        "num_leaves": leaves,
        "learning_rate": 0.1,
        "max_bin": max_bin,
        "metric": "None",
        "verbosity": -1,
        "tpu_tree_growth": "fast",      # see run_bench
    }
    extra = os.environ.get("BENCH_EXTRA_PARAMS")
    if extra:
        params.update(json.loads(extra))
    # params at creation time: constructing first and handing differing
    # dataset params to the Booster is a LightGBMError (reference
    # DatasetUpdateParamChecking semantics)
    ds = lgb.Dataset(X, label=y, group=group, params=params)
    t0 = time.perf_counter()
    ds.construct()
    bin_seconds = time.perf_counter() - t0
    booster = lgb.Booster(params=params, train_set=ds)
    t0 = time.perf_counter()
    booster.update()
    dsync(booster.boosting.train_score)
    compile_seconds = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(trees - 1):
        booster.update()
    dsync(booster.boosting.train_score)
    elapsed = (time.perf_counter() - t0) * trees / max(trees - 1, 1)
    Xh, yh, _ = make_mslr_like(2000, docs_per_query, F, seed=9)
    pred = booster.predict(Xh, device=True)
    return {
        "rows": n_queries * docs_per_query,
        "queries": n_queries,
        "features": F,
        "trees": trees,
        "train_seconds": round(elapsed, 3),
        "sec_per_tree": round(elapsed / trees, 4),
        "compile_seconds": round(compile_seconds, 2),
        "bin_seconds": round(bin_seconds, 2),
        "holdout_ndcg@10": round(ndcg_at_k(pred, yh, docs_per_query), 5),
    }


def higgs_like_chunks(n, f, chunk_rows, seed0=0):
    """The synthetic-HIGGS source, generated chunk by chunk so the raw
    float matrix need never be resident (the out-of-core stage's data
    source; ``make_higgs_like`` is the single-chunk special case — ONE
    signal formula for train, holdout and streamed stages).

    The label concept (w) is drawn from a FIXED rng so train (seed 0)
    and holdout (seed 1) share one distribution; the label threshold is
    calibrated on the first chunk (~the global median — chunks are
    i.i.d. draws), which IS the global median in the single-chunk case.
    """
    w = np.random.RandomState(12345).randn(f).astype(np.float32)
    thresh = None
    lo = 0
    ci = 0
    while lo < n:
        rows = min(chunk_rows, n - lo)
        rng = np.random.RandomState(seed0 + 7919 * ci)
        X = rng.rand(rows, f).astype(np.float32)
        signal = X @ w
        signal += 2.0 * X[:, 0] * X[:, 1] - 1.5 * (X[:, 2] > 0.5) * X[:, 3]
        signal += rng.randn(rows).astype(np.float32) * 0.2 * signal.std()
        if thresh is None:
            thresh = float(np.median(signal))
        yield lo, X, (signal > thresh).astype(np.float32)
        lo += rows
        ci += 1


def make_higgs_like(n, f, seed=0):
    _lo, X, y = next(higgs_like_chunks(n, f, n, seed0=seed))
    return X, y


def holdout_auc(booster, f, seed=1):
    Xh, yh = make_higgs_like(200_000, f, seed=seed)
    pred = booster.predict(Xh, device=True)   # forest traversal on-device
    order = np.argsort(pred)
    ranks = np.empty_like(order, dtype=np.float64)
    ranks[order] = np.arange(1, len(pred) + 1)
    npos = yh.sum()
    return (ranks[yh > 0].sum() - npos * (npos + 1) / 2) / (
        npos * (len(yh) - npos))


def device_memory_stats():
    """peak/limit HBM from the device allocator.  Where the device
    reports no limit (the CPU allocator), the planner's limit model
    stands in and ``hbm_limit_source`` says so."""
    import jax
    out = {}
    stats = jax.local_devices()[0].memory_stats() or {}
    peak = 0
    for k in ("peak_bytes_in_use", "peak_bytes", "bytes_in_use"):
        if int(stats.get(k, 0)) > 0:
            peak = int(stats[k])
            break
    limit = int(stats.get("bytes_limit", 0) or stats.get("bytes_limit_in_use", 0))
    if peak:
        out["peak_hbm_bytes"] = peak
    if limit:
        out["hbm_limit_bytes"] = limit
        out["hbm_limit_source"] = "memory_stats"
    else:
        from lightgbm_tpu.ops.planner import hbm_limit_bytes
        lim, src = hbm_limit_bytes()
        out["hbm_limit_bytes"] = lim
        out["hbm_limit_source"] = src
    return out


def kernel_probe(n_rows=1_000_000, f=F, max_bin=MAX_BIN, reps=3):
    """Time the histogram kernel variants on the live backend.

    Reference analogue: GetShareStates times col-wise vs row-wise histogram
    construction at startup and picks the winner (src/io/dataset.cpp:589-684).
    """
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops import histogram as H

    rng = np.random.RandomState(0)
    binned = jnp.asarray(rng.randint(0, max_bin, (f, n_rows), dtype=np.int64),
                         jnp.uint8)          # feature-major [F, n]
    grad = jnp.asarray(rng.randn(n_rows), jnp.float32)
    hess = jnp.abs(grad) + 0.1
    mask = jnp.ones((n_rows,), jnp.float32)
    B = max_bin + 1
    out = {}
    for method in ("matmul", "matmul_f32", "scatter", "pallas"):
        fn = jax.jit(lambda b, g, h, m, _m=method: H.build_histogram(
            b, g, h, m, B, method=_m))
        try:
            dsync(fn(binned, grad, hess, mask))  # compile
            t0 = time.perf_counter()
            for _ in range(reps):
                dsync(fn(binned, grad, hess, mask))
            out[method] = round((time.perf_counter() - t0) / reps * 1e3, 2)
        except Exception as e:  # a variant may be unsupported on a backend
            out[method] = f"error: {str(e)[:120]}"
    timed = {k: v for k, v in out.items() if isinstance(v, float)}
    if timed:
        out["winner"] = min(timed, key=timed.get)
    return out


def mfu_estimate(n, f, max_bin, leaves, sec_per_tree, peak):
    """Lower-bound MFU of the histogram matmuls.

    Per histogram pass over R rows: [3, R] @ [R, F*B] = 2*3*R*F*B FLOPs.
    Per tree, the bucketed compaction processes ~n rows per frontier level
    and there are ~log2(leaves) levels, so R_total ~ n * log2(leaves).
    Counts ONLY histogram matmul FLOPs (the MXU work) — a lower bound.
    The MEASURED per-variant numbers (compiler cost_analysis, not this
    formula) ride alongside as ``mfu_measured`` (obs/devprof.py).
    """
    if peak <= 0:          # a device the flops table doesn't know
        return 0.0
    levels = max(1.0, np.log2(leaves))
    flops_per_tree = 2.0 * 3.0 * n * levels * f * (max_bin + 1)
    return flops_per_tree / max(sec_per_tree, 1e-9) / peak


def run_bench(n, trees, leaves, max_bin, tag=""):
    """Train in-process on whatever backend is active; return result dict."""
    import jax

    import lightgbm_tpu as lgb

    device = jax.devices()[0]
    platform = device.platform

    # HBM budget verdict BEFORE any allocation: the >=10M-row stage died
    # in compile in r5 (157.7 GB requested vs 17.2 GB HBM); the planner
    # now degrades to a smaller row tile instead, and the decision is
    # journaled with the stage result.  An infeasible verdict aborts the
    # stage up front (cheap, retriable) rather than wedging the chip.
    from lightgbm_tpu.ops.planner import plan_histograms
    plan = plan_histograms(rows=n, features=F, num_bins=max_bin + 1,
                           num_leaves=leaves)
    if not plan.feasible:
        raise RuntimeError(
            f"HBM planner: {n} rows infeasible on this device even at "
            f"tile_rows={plan.tile_rows} (predicted "
            f"{plan.predicted_peak_bytes / 1e9:.1f} GB vs budget "
            f"{plan.budget_bytes / 1e9:.1f} GB)")
    if plan.degraded:
        log(f"hbm planner degraded to tile_rows={plan.tile_rows} "
            f"(untiled predicted {plan.untiled_peak_bytes / 1e9:.1f} GB "
            f"> budget {plan.budget_bytes / 1e9:.1f} GB)")

    from lightgbm_tpu.utils.platform import (
        compile_cache_entries, compile_cache_entries_by_family,
        enable_compile_cache)
    # family="train" scopes the warm-start verdict to TRAINING programs
    # (JIT blobs only) — serving AOT exports in the same store do not
    # fake a warm training start
    cache_dir = enable_compile_cache(family="train")
    cache_before = compile_cache_entries(cache_dir)
    cache_fam_before = compile_cache_entries_by_family()

    # structured tracing (lightgbm_tpu/obs/): with LIGHTGBM_TPU_TRACE set
    # the whole stage records phase spans (+ the engine/grower/serving
    # spans underneath) and dumps a Chrome-trace JSON next to the journal
    from lightgbm_tpu.obs.trace import global_tracer, instant as obs_instant
    from lightgbm_tpu.obs.trace import span as obs_span, span_coverage
    # stages share one process tracer: mark here so this stage's dump and
    # coverage cover ONLY its own slice of events
    trace_mark = global_tracer.mark()
    root_span = obs_span("bench.run", rows=n, trees=trees, tag=tag)
    root_span.__enter__()
    try:

        with obs_span("bench.make_data", rows=n):
            X, y = make_higgs_like(n, F)
        params = {
            "objective": "binary",
            "num_leaves": leaves,
            "learning_rate": 0.1,
            "max_bin": max_bin,
            "metric": "None",
            "verbosity": -1,
            # relaxed batched-frontier growth: ~8 rounds per 255-leaf tree vs
            # 17 for the exact-prefix mode (measured, docs/PERFORMANCE.md);
            # tree-shape deviation class = the reference's own CPU-vs-GPU
            # difference, and the holdout AUC printed in the metric line is
            # the quality check.  BENCH_EXTRA_PARAMS can override.
            "tpu_tree_growth": "fast",
        }
        # measurement experiments: BENCH_EXTRA_PARAMS='{"tpu_tree_growth":
        # "fast", ...}' merges into the training params
        extra = os.environ.get("BENCH_EXTRA_PARAMS")
        if extra:
            params.update(json.loads(extra))
        train_set = lgb.Dataset(X, label=y, params=params)
        t_bin0 = time.perf_counter()
        with obs_span("bench.construct"):
            train_set.construct()      # binning happens here, outside the clock
        bin_seconds = time.perf_counter() - t_bin0
        del X

        with obs_span("bench.build_booster"):
            booster = lgb.Booster(params=params, train_set=train_set)
        t_c0 = time.perf_counter()
        with obs_span("bench.compile"):
            booster.update()           # iteration 1: triggers XLA compile
            dsync(booster.boosting.train_score)
        compile_seconds = time.perf_counter() - t_c0

        profile = os.environ.get("BENCH_PROFILE") == "1"
        if profile:
            os.makedirs(BENCH_OUT, exist_ok=True)
            jax.profiler.start_trace(os.path.join(BENCH_OUT, "bench_trace"))

        t0 = time.perf_counter()
        with obs_span("bench.train_loop", trees=trees - 1):
            for _ in range(trees - 1):
                booster.update()
            dsync(booster.boosting.train_score)
        elapsed = (time.perf_counter() - t0) * trees / max(trees - 1, 1)

        if profile:
            jax.profiler.stop_trace()

        sec_per_tree = elapsed / trees
        with obs_span("bench.holdout_auc"):
            auc = holdout_auc(booster, F)  # metric BEFORE the chunked segment
        # extends the model, so the reported AUC stays comparable to baselines

        # fused macro-steps (lightgbm_tpu/boosting/macro.py): continue the
        # SAME booster with update_chunk so training compute matches and only
        # the dispatch count changes; LGBM_TPU_CHUNK=0 (the compile-variant
        # ladder's chunk-off rung) skips this segment
        from lightgbm_tpu.boosting.macro import chunk_cap, pow2_chunk
        chunk_result = None
        cap = chunk_cap()
        with obs_span("bench.chunked"):
            if cap > 1 and booster.boosting.chunk_supported():
                # whole chunks only: each distinct chunk size is a separate
                # compiled shape, so a ragged tail step would put an XLA compile
                # inside the clock and corrupt iters_per_sec_chunked
                c = pow2_chunk(trees, cap)
                n_chunks = max(trees // c, 1)
                chunk_iters = n_chunks * c
                booster.update_chunk(c)            # chunk program compile
                dsync(booster.boosting.train_score)
                t0 = time.perf_counter()
                for _ in range(n_chunks):
                    booster.update_chunk(c)
                dsync(booster.boosting.train_score)
                chunk_s = time.perf_counter() - t0
                chunk_result = {
                    "chunk_size": c,
                    "chunk_iters": chunk_iters,
                    "iters_per_sec_chunked": round(chunk_iters / chunk_s, 3),
                    "sec_per_tree_chunked": round(chunk_s / chunk_iters, 4),
                }
    except BaseException as e:
        root_span.set(error=type(e).__name__)
        raise
    finally:
        root_span.__exit__(None, None, None)

    result = {
        "metric": f"synthetic-HIGGS {n}x{F} train wall-clock, "
                  f"{trees} trees x {leaves} leaves, max_bin={max_bin} "
                  f"[{platform}{tag}] (holdout AUC {auc:.4f})",
        "value": round(elapsed, 3),
        "unit": "seconds",
        "vs_baseline": round(BASELINE_SECONDS / elapsed, 3),
        "platform": platform,
        "device_kind": getattr(device, "device_kind", ""),
        # sec_per_tree is TRAIN-ONLY (clock starts after iteration 1);
        # _total folds the one-time compile back in — r5's 7.77 s/tree
        # headline was the total being read as the train rate
        "sec_per_tree": round(sec_per_tree, 4),
        "sec_per_tree_train": round(sec_per_tree, 4),
        "sec_per_tree_total": round((elapsed + compile_seconds) / trees, 4),
        "iters_per_sec": round(1.0 / max(sec_per_tree, 1e-9), 3),
        "compile_seconds": round(compile_seconds, 2),
        "compile_cache": {
            "dir": cache_dir,
            # entries/warm_start are the TRAIN family's (the dir above is
            # the family subdir); by_family breaks the whole store down
            "entries_before": cache_before,
            "entries_after": compile_cache_entries(cache_dir),
            "warm_start": cache_before > 0,
            "entries_by_family_before": cache_fam_before,
            "entries_by_family_after": compile_cache_entries_by_family(),
            "warm_start_by_family": {
                k: v > 0 for k, v in cache_fam_before.items()},
        },
        "bin_seconds": round(bin_seconds, 2),
        "bin_rows_per_sec": round(n / max(bin_seconds, 1e-9), 1),
        "holdout_auc": round(float(auc), 5),
        "rows": n,
        "trees": trees,
        "hbm_plan": plan.summary(),
    }
    train_plan = getattr(booster.boosting, "hist_plan", None)
    if train_plan is not None:
        result["hbm_plan"] = train_plan.summary()
    if chunk_result is not None:
        result.update(chunk_result)
    try:
        from lightgbm_tpu.ops.ingest import ingest_last
        il = ingest_last()
        if il:
            result["ingest"] = il
    except Exception:
        pass
    if platform != "cpu":       # a CPU run has no device utilization
        peak = peak_flops_for(device)
        result["mfu_histogram_lower_bound"] = round(
            mfu_estimate(n, F, max_bin, leaves, sec_per_tree, peak), 4)
        result["peak_flops_assumed"] = peak
    mem = device_memory_stats()
    result.update(mem)

    # planner predicted-vs-measured peak bytes as a first-class event +
    # result field (docs/OBSERVABILITY.md): the number that says whether
    # the HBM model (ops/planner.py) is still honest on this backend
    eff_plan = getattr(booster.boosting, "hist_plan", None) or plan
    measured_peak = int(mem.get("peak_hbm_bytes", 0))
    pvm = {
        "predicted_peak_bytes": int(eff_plan.predicted_peak_bytes),
        "measured_peak_bytes": measured_peak,
        "ratio": (round(measured_peak / eff_plan.predicted_peak_bytes, 3)
                  if measured_peak and eff_plan.predicted_peak_bytes
                  else None),
    }
    result["hbm_predicted_vs_measured"] = pvm
    obs_instant("hbm.peak", **pvm)
    from lightgbm_tpu.obs.metrics import global_registry as obs_registry
    obs_registry.gauge("hbm_measured_peak_bytes").set(measured_peak)

    # MEASURED per-variant MFU / HBM-bandwidth utilization from the
    # compiler's own cost model (obs/devprof.py) — the number the
    # lower-bound estimate above only brackets.  Not in the smoke stage
    # (18 variant compiles would dwarf the canary it rides on) and banked
    # under its own journal key so a full-stage retry replays it instead
    # of paying the compiles again.  BENCH_SKIP_OBS=1 skips.
    if os.environ.get("BENCH_SKIP_OBS") != "1" and tag != "-smoke":
        mfu_rows = min(n, 1_000_000)
        mfu_key = f"mfu_measured@{mfu_rows}"

        def _table_ok(t):
            return any(isinstance(v, dict) and "seconds_per_call" in v
                       for v in t.values())

        banked = journal_stages().get(mfu_key)
        if banked is not None and _table_ok(banked):
            result["mfu_measured"] = banked
        else:
            try:
                from lightgbm_tpu.obs.devprof import \
                    histogram_utilization_table
                with obs_span("bench.mfu_measured"):
                    result["mfu_measured"] = histogram_utilization_table(
                        rows=mfu_rows, features=F,
                        num_bins=max_bin + 1,
                        reps=2)
                # best measured MFU as a gauge: the obs_doctor stage and
                # pod telemetry vectors read it (docs/OBSERVABILITY.md)
                best_mfu = max(
                    (v.get("mfu", 0.0)
                     for v in result["mfu_measured"].values()
                     if isinstance(v, dict)), default=0.0)
                if best_mfu:
                    obs_registry.gauge("mfu_measured_best").set(
                        round(best_mfu, 6))
                # bank only a table with at least one real measurement —
                # an all-error table must retry next run (the journal's
                # errors-never-banked rule)
                if _table_ok(result["mfu_measured"]):
                    journal_put(mfu_key, result["mfu_measured"])
            except Exception as e:  # never fail the stage for telemetry
                result["mfu_measured"] = {"error": str(e)[-200:]}

    # trace file + unified-registry snapshot alongside the journal entry
    from lightgbm_tpu.utils.timer import global_timer
    if global_timer.enabled:
        global_timer.publish(obs_registry)
    if global_tracer.enabled:
        safe_tag = (tag or "-full").strip("-").replace("/", "_") or "full"
        evs = global_tracer.since(trace_mark)   # THIS stage's slice only
        try:
            os.makedirs(BENCH_OUT, exist_ok=True)
            result["obs"] = {
                "trace_file": global_tracer.dump(
                    os.path.join(BENCH_OUT, f"bench_trace_{safe_tag}.json"),
                    events=evs),
                "trace_events": len(evs),
                "trace_coverage": round(
                    span_coverage(evs, "bench.run") or 0.0, 4),
            }
        except OSError as e:
            result["obs"] = {"error": str(e)[-200:]}
    try:
        from lightgbm_tpu.utils.file_io import write_atomic
        os.makedirs(BENCH_OUT, exist_ok=True)
        snap_path = os.path.join(BENCH_OUT, "bench_obs_metrics.json")
        write_atomic(snap_path, obs_registry.dump_json())
        result["obs_metrics_file"] = snap_path
    except OSError:
        pass
    return result


def run_stream_bench(n, trees, leaves, max_bin, features=None):
    """The graduated out-of-core stage (lightgbm_tpu/data/): build a
    spill-store dataset of ``n`` rows CHUNK BY CHUNK (the binned matrix
    is never resident on host or device), train ``trees`` streamed
    trees, and journal the planner's predicted peaks on BOTH memories
    next to the measured ones (host VmHWM delta, device allocator
    peak).  LGBM_TPU_STREAM=1 is pinned for the stage — its claim is
    out-of-core execution, not a residency election."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.data.stream import (host_rss_bytes,
                                          host_rss_peak_bytes)
    from lightgbm_tpu.dataset import Dataset
    from lightgbm_tpu.ops.planner import plan_stream

    f = features or F
    trees = max(int(trees), 2)      # the clock starts after iteration 1;
    #                                 one tree would journal a ~0 s value
    plan = plan_stream(rows=n, features=f, num_bins=max_bin + 1,
                       num_leaves=leaves)
    if plan.stream and not plan.feasible:
        raise RuntimeError(
            f"stream planner: {n} rows infeasible even at block_rows="
            f"{plan.block_rows} (predicted device "
            f"{plan.predicted_device_peak_bytes / 1e9:.1f} GB / host "
            f"{plan.predicted_host_peak_bytes / 1e9:.1f} GB)")
    rss_peak0 = host_rss_peak_bytes()
    from lightgbm_tpu.obs.metrics import global_registry as _reg
    blocks0 = int(_reg.counter("stream_blocks_total").value)
    params = {"objective": "binary", "num_leaves": leaves,
              "learning_rate": 0.1, "max_bin": max_bin,
              "metric": "None", "verbosity": -1}
    prev_stream = os.environ.get("LGBM_TPU_STREAM")
    os.environ["LGBM_TPU_STREAM"] = "1"
    try:
        block_rows = plan.block_rows or min(n, 1 << 20)
        chunk_rows = min(block_rows, 1 << 20)
        t0 = time.perf_counter()
        gen = higgs_like_chunks(n, f, chunk_rows)
        lo0, X0, y0 = next(gen)
        ds = Dataset.from_sample(X0[:200_000], n, params=params,
                                 spill=True, spill_block_rows=block_rows)
        labels = np.empty(n, np.float32)
        ds.push_rows(X0)
        labels[lo0:lo0 + len(y0)] = y0
        del X0
        for lo, X, y in gen:
            ds.push_rows(X)
            labels[lo:lo + len(y)] = y
        ds.set_label(labels)
        spill_seconds = time.perf_counter() - t0
        store = ds._block_store

        t0 = time.perf_counter()
        booster = lgb.Booster(params=params, train_set=ds)
        if booster.boosting._stream is None:
            raise RuntimeError("stream stage trained RESIDENT — the "
                               "out-of-core claim would be false")
        booster.update()                      # compiles the block programs
        dsync(booster.boosting.train_score)
        compile_seconds = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(max(trees - 1, 0)):
            booster.update()
        dsync(booster.boosting.train_score)
        train_seconds = (time.perf_counter() - t0) * trees / max(trees - 1,
                                                                 1)
        auc = holdout_auc(booster, f)
        mem = device_memory_stats()
        measured_host_peak = host_rss_peak_bytes()
        result = {
            "metric": f"out-of-core streamed train {n}x{f}, {trees} trees"
                      f" x {leaves} leaves (holdout AUC {auc:.4f})",
            "value": round(train_seconds, 3),
            "unit": "seconds",
            "rows": n,
            "trees": trees,
            "sec_per_tree": round(train_seconds / max(trees, 1), 4),
            "spill_seconds": round(spill_seconds, 2),
            "compile_seconds": round(compile_seconds, 2),
            "holdout_auc": round(float(auc), 5),
            "store_bytes": store.nbytes(),
            "num_blocks": store.num_blocks,
            "block_rows": store.block_rows,
            # this STAGE's pumped blocks (the counter is process-wide
            # and the stream_probe stage pumps the same instrument)
            "blocks_streamed": int(
                _reg.counter("stream_blocks_total").value) - blocks0,
            "stream_plan": plan.summary(),
            "host_predicted_vs_measured": {
                "predicted_peak_bytes": plan.predicted_host_peak_bytes,
                "measured_rss_bytes": host_rss_bytes(),
                "measured_peak_bytes": measured_host_peak,
                "measured_peak_delta_bytes":
                    measured_host_peak - rss_peak0,
            },
            "hbm_predicted_vs_measured": {
                "predicted_peak_bytes": plan.predicted_device_peak_bytes,
                "measured_peak_bytes": int(mem.get("peak_hbm_bytes", 0)),
            },
        }
        result.update(mem)
        return result
    finally:
        if prev_stream is None:
            os.environ.pop("LGBM_TPU_STREAM", None)
        else:
            os.environ["LGBM_TPU_STREAM"] = prev_stream


def run_ingest_11m_bench(n, features=None, max_bin=None):
    """The resurrected higgs_11m ingest stage (ops/ingest.py): construct
    an ``n``-row Dataset chunk by chunk through the streamed device-ingest
    pump — raw f32 rows reach the device in planner-elected chunks and
    come back as binned bytes, so nothing close to r5's single 157 GB
    ``device_put`` ever exists.  Construction ONLY (the full stage trains
    the same scale): the banked claim is that full-scale ingest completes
    within device HBM, with the measured push rows/sec and the ingest
    story (kernel vs host fallback and why) next to the memory peaks."""
    from lightgbm_tpu.dataset import Dataset
    from lightgbm_tpu.ops.ingest import ingest_last

    f = features or F
    mb = max_bin or MAX_BIN
    params = {"objective": "binary", "num_leaves": LEAVES,
              "learning_rate": 0.1, "max_bin": mb,
              "metric": "None", "verbosity": -1}
    chunk_rows = 1 << 20
    t_all0 = time.perf_counter()
    gen = higgs_like_chunks(n, f, chunk_rows)
    lo0, X0, y0 = next(gen)
    ds = Dataset.from_sample(X0[:200_000], n, params=params)
    labels = np.empty(n, np.float32)
    push_seconds = 0.0
    t0 = time.perf_counter()
    ds.push_rows(X0)                 # chunk generation stays OFF the bin
    push_seconds += time.perf_counter() - t0   # clock: push time only
    labels[lo0:lo0 + len(y0)] = y0
    del X0, y0
    for lo, X, y in gen:
        t0 = time.perf_counter()
        ds.push_rows(X)
        push_seconds += time.perf_counter() - t0
        labels[lo:lo + len(y)] = y
    ds.set_label(labels)
    total_seconds = time.perf_counter() - t_all0
    story = ingest_last()
    mem = device_memory_stats()
    result = {
        "metric": f"streamed device ingest {n}x{f}, max_bin={mb} "
                  "(construction only)",
        "value": round(push_seconds, 3),
        "unit": "seconds",
        "rows": n,
        "features": f,
        "bin_seconds": round(push_seconds, 2),
        "bin_rows_per_sec": round(n / max(push_seconds, 1e-9), 1),
        "construct_total_seconds": round(total_seconds, 2),
        "binned_bytes": int(ds.binned.nbytes),
        "ingest": story or {"path": "host", "reason": "no story recorded"},
    }
    result.update(mem)
    return result


def run_serving_bench(n_train=100_000, trees=50, leaves=63, max_bin=63,
                      n_requests=600, n_threads=8, max_request_rows=700,
                      max_batch_rows=1024):
    """Serving-throughput metric: train a small booster, stand up the
    in-process server (lightgbm_tpu/serving/), fire mixed-shape requests
    from concurrent threads, report rows/s + latency + batching telemetry.

    Emitted alongside the training numbers: the ROADMAP north star is
    "serves heavy traffic", and this is the request-path half of it —
    micro-batched, shape-bucketed DeviceForest inference, so after
    warmup the accelerator sees only pre-compiled bucket shapes.
    """
    import lightgbm_tpu as lgb
    from lightgbm_tpu.serving.loadgen import fire_requests

    rng = np.random.RandomState(0)
    f = F
    X = rng.randn(n_train, f).astype(np.float32).astype(np.float64)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(float)
    booster = lgb.train(
        {"objective": "binary", "verbosity": -1, "num_leaves": leaves,
         "max_bin": max_bin},
        lgb.Dataset(X, label=y), num_boost_round=trees, verbose_eval=False)
    del X

    server = booster.serve(max_batch_rows=max_batch_rows,
                           batch_window_ms=2.0)
    # warmup: compile every bucket before the clock starts — off the
    # request path, so the latency/batch metrics report steady-state
    # serving only (no compile-time traffic)
    server.warm()
    storm = fire_requests(server, n_requests, n_threads,
                          max_request_rows, f)
    m = server.metrics_dict()
    server.close()
    lat = m["histograms"].get("request_latency_ms", {})
    fill = m["histograms"].get("batch_fill_ratio", {})
    c = m["counters"]
    wall = storm["wall_seconds"]
    out = {
        "requests": storm["requests"],
        "rows": storm["rows"],
        "trees": trees,
        "wall_seconds": round(wall, 3),
        "rows_per_second": round(storm["rows"] / wall, 1),
        "request_latency_ms_mean": lat.get("mean"),
        "request_latency_ms_max": lat.get("max"),
        "batch_fill_ratio_mean": fill.get("mean"),
        "batches": c.get("batches_total"),
        "multi_submitter_batches": c.get("multi_submitter_batches"),
        "compile_events": c.get("compile_events"),
        "bucket_hits": c.get("bucket_hits"),
    }
    if storm["errors"]:
        out["worker_errors"] = storm["errors"]
    return out


def run_fleet_bench(n_models=3, rows=20_000, trees=16, requests=300,
                    threads=6):
    """Serving-fleet metric (lightgbm_tpu/fleet/): N models behind one
    weighted front door under a shared-HBM residency plan — measured
    eviction with every model still servable (no OOM, no serve failure),
    an AOT-restored replica whose first request completes with ZERO
    compile events, and the opt-in bf16/int8 accuracy deltas, all via
    tools/fleet_smoke.py's phased run.  Raises on any missed acceptance
    bar so a failed fleet run is never journaled (PR 4 convention)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from fleet_smoke import run_smoke
    summary = run_smoke(n_models=n_models, rows=rows, trees=trees,
                        requests=requests, threads=threads)
    if summary.get("failed"):
        raise RuntimeError(
            f"fleet smoke failed phases: "
            f"{[k for k, ok in summary['phase_ok'].items() if not ok]}")
    return summary


def run_fleet_failover_bench(devices=None, n_models=2, rows=20_000,
                             trees=16, requests=600, threads=6):
    """Pod-scale availability metric (lightgbm_tpu/fleet/router.py): a
    replicated multi-device PodFleet serves a threaded traffic storm
    while chaos VANISHES one device mid-run.  Acceptance bars (raised on
    a miss so a failed drill is never journaled, PR 4 convention): zero
    non-typed request failures, availability >= 0.999, every response
    bit-identical to Booster.predict(raw_score=True), and every model's
    replica coverage restored within ONE replan tick.  Device count:
    BENCH_FLEET_DEVICES (default 3)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from fleet_smoke import run_failover_smoke
    if devices is None:
        devices = int(os.environ.get("BENCH_FLEET_DEVICES", "") or 3)
    summary = run_failover_smoke(devices=devices, n_models=n_models,
                                 rows=rows, trees=trees,
                                 requests=requests, threads=threads)
    if summary.get("failed"):
        raise RuntimeError(
            f"fleet failover drill missed its bars: "
            f"availability={summary.get('availability')} "
            f"outcomes={summary.get('outcomes')} "
            f"recovered={summary.get('recovered_within_one_tick')}")
    return summary


def run_lifecycle_bench(rows=20_000, trees=12, refresh_trees=4,
                        requests=120, threads=4):
    """Guarded model-lifecycle metric (lightgbm_tpu/lifecycle/): a full
    train -> continual refresh -> shadow/canary promotion -> forced
    drift rollback cycle under threaded loadgen traffic, via
    tools/lifecycle_smoke.py's phased run.  The acceptance bars: a
    clean promotion serves the candidate bit-identically with
    ``model_age_seconds`` reset, and the forced rollback leaves the
    fleet byte-identical to the pre-promotion model with a
    flight-recorder bundle naming the breached gate.  Raises on any
    missed bar so a failed lifecycle run is never journaled (PR 4
    convention)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from lifecycle_smoke import run_smoke
    summary = run_smoke(rows=rows, trees=trees,
                        refresh_trees=refresh_trees, requests=requests,
                        threads=threads)
    if summary.get("failed"):
        raise RuntimeError(
            f"lifecycle smoke failed phases: "
            f"{[k for k, ok in summary['phase_ok'].items() if not ok]}")
    return summary


def run_coresident_bench(rows=12_000, trees=10, refresh_trees=6,
                         requests=120, threads=4):
    """Co-residency metric (lightgbm_tpu/coresident/): loadgen traffic
    AND a continual refresh on the SAME device set behind the shared
    residency ledger, via tools/coresident_smoke.py's phased run.  The
    acceptance bars: zero non-typed serving failures with overall p99
    within the serving SLO, ``model_age_seconds`` drops across the
    refresh, and the brownout throttle counter moved (training yielded
    to serving through the pause_control seam at least once during the
    injected device-delay window).  Raises on any missed bar so a
    failed co-residency run is never journaled (PR 4 convention)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from coresident_smoke import run_smoke
    summary = run_smoke(rows=rows, trees=trees,
                        refresh_trees=refresh_trees, requests=requests,
                        threads=threads)
    if summary.get("failed"):
        raise RuntimeError(
            f"coresident smoke failed phases: "
            f"{[k for k, ok in summary['phase_ok'].items() if not ok]}")
    return summary


def run_resilience_bench(n_train=50_000, trees=24, leaves=63, max_bin=63,
                         snapshot_freq=8):
    """Fault-tolerance overhead metric: checkpoint-bundle save/load
    latency and resume bit-parity at bench scale (docs/RESILIENCE.md).

    Reports what periodic checkpointing costs the training loop
    (save_seconds covers state capture incl. the device->host score
    fetch, sha256 manifest, atomic write) and proves the resume path on
    THIS backend: a run killed after a bundle and resumed must produce a
    byte-identical model.
    """
    import tempfile

    import lightgbm_tpu as lgb
    from lightgbm_tpu.resilience import CheckpointManager, load_checkpoint

    rng = np.random.RandomState(0)
    X = rng.rand(n_train, F)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0.8).astype(np.float32)
    P = {"objective": "binary", "verbosity": -1, "num_leaves": leaves,
         "max_bin": max_bin, "bagging_fraction": 0.8, "bagging_freq": 2}

    with tempfile.TemporaryDirectory() as td:
        t0 = time.time()
        full = lgb.train(P, lgb.Dataset(X, label=y), trees,
                         verbose_eval=False)
        plain_s = time.time() - t0
        full.save_model(f"{td}/full.txt")

        t0 = time.time()
        lgb.train(P, lgb.Dataset(X, label=y), trees, verbose_eval=False,
                  snapshot_freq=snapshot_freq,
                  snapshot_out=f"{td}/ck.txt")
        ckpt_s = time.time() - t0
        n_saves = trees // snapshot_freq

        mgr = CheckpointManager(f"{td}/ck.txt.ckpt")
        newest = mgr.bundles()[-1]
        t0 = time.time()
        ck = load_checkpoint(f"{td}/ck.txt.ckpt/{newest}")
        load_s = time.time() - t0

        die_at = max(snapshot_freq, trees // 2)
        lgb.train(P, lgb.Dataset(X, label=y), die_at, verbose_eval=False,
                  snapshot_freq=snapshot_freq,
                  snapshot_out=f"{td}/part.txt")
        t0 = time.time()
        res = lgb.train(P, lgb.Dataset(X, label=y), trees,
                        verbose_eval=False,
                        resume_from=f"{td}/part.txt.ckpt")
        resume_s = time.time() - t0
        res.save_model(f"{td}/res.txt")
        identical = (open(f"{td}/full.txt", "rb").read()
                     == open(f"{td}/res.txt", "rb").read())
        bundle_bytes = os.path.getsize(f"{td}/ck.txt.ckpt/{newest}")

    return {
        "trees": trees,
        "rows": n_train,
        "checkpoint_saves": n_saves,
        "save_seconds_each": round(max(0.0, ckpt_s - plain_s)
                                   / max(n_saves, 1), 4),
        "bundle_load_verify_seconds": round(load_s, 4),
        "bundle_bytes": bundle_bytes,
        "bundle_iteration": ck.iteration,
        "resume_wall_seconds": round(resume_s, 3),
        "resume_bit_identical": bool(identical),
    }


# ------------------------------------------------------------------- worker

# ---- stage journal ------------------------------------------------------
# Every completed worker stage persists its result JSON incrementally
# (atomic via file_io.write_atomic), keyed under a workload fingerprint.
# A rerun after a mid-run crash re-emits the banked results and executes
# ONLY the missing stages.
# Errors are emitted but never journaled, so failed stages retry.
# BENCH_JOURNAL=<path> overrides the location (default
# ./bench_journal.json next to this file); BENCH_JOURNAL=0 disables.
# BENCH_ONLY=<stage[,stage]> runs exactly those worker stages (budget
# gates are bypassed for explicitly selected stages).


def _journal_path():
    p = os.environ.get("BENCH_JOURNAL",
                       os.path.join(REPO, "bench_journal.json"))
    return None if str(p).strip().lower() in ("", "0", "off", "none") else p


_JOURNAL_FP_EXTRA = None


def _journal_fingerprint():
    """Workload shape + BACKEND + code revision: a banked result must
    never replay for a different platform (CPU-allowed CI run masking a
    later TPU bench) or after the kernels changed underneath it."""
    global _JOURNAL_FP_EXTRA
    if _JOURNAL_FP_EXTRA is None:
        plat = "unknown"
        try:
            import jax
            plat = jax.default_backend()   # journal use is post-init only
        except Exception:
            pass
        rev = ""
        try:
            r = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                               cwd=REPO, capture_output=True, text=True,
                               timeout=10)
            rev = r.stdout.strip()
        except Exception:
            pass
        _JOURNAL_FP_EXTRA = {"platform": plat, "code": rev}
    return {"rows": N, "trees": TREES, "leaves": LEAVES, "max_bin": MAX_BIN,
            "extra_params": os.environ.get("BENCH_EXTRA_PARAMS", ""),
            **_JOURNAL_FP_EXTRA}


def journal_stages() -> dict:
    """Banked stage results for THIS workload fingerprint ({} otherwise)."""
    path = _journal_path()
    if not path:
        return {}
    try:
        with open(path) as fh:
            d = json.load(fh)
    except (OSError, ValueError):
        return {}
    if d.get("fingerprint") != _journal_fingerprint():
        return {}
    stages = d.get("stages", {})
    return stages if isinstance(stages, dict) else {}


def journal_put(key, result) -> None:
    path = _journal_path()
    if not path:
        return
    from lightgbm_tpu.utils.file_io import write_atomic
    payload = {"fingerprint": _journal_fingerprint(),
               "stages": dict(journal_stages(), **{key: result})}
    try:
        write_atomic(path, json.dumps(payload, indent=1))
    except OSError as e:
        log(f"journal write failed ({e}); continuing without journal")


def bench_only():
    v = os.environ.get("BENCH_ONLY", "").strip()
    if not v:
        return None
    return {s.strip() for s in v.split(",") if s.strip()} or None


def run_stage(name, fn, key=None, budget_floor=0.0):
    """Run one worker stage through the journal + BENCH_ONLY selector.

    Returns the stage dict (fresh or journal-replayed), ``None`` when the
    stage was skipped (deselected / budget floor / skip env), or a dict
    with ``"error"`` when it raised (emitted, not journaled)."""
    only = bench_only()
    if only is not None and name not in only:
        return None
    key = key or name
    saved = journal_stages().get(key)
    if saved is not None and "error" not in saved:
        emit(dict(saved, stage=name, journal=True))
        return saved
    if only is None and budget_floor and remaining_budget() <= budget_floor:
        return None
    t1 = time.time()
    try:
        r = dict(fn())
    except Exception as e:
        err = {"stage": name, "error": str(e)[-800:],
               "traceback_tail": traceback.format_exc()[-800:]}
        emit(err)
        return err
    r["stage"] = name
    r["elapsed"] = round(time.time() - t1, 1)
    journal_put(key, r)
    emit(r)
    return r


def main():
    """One process: backend init -> probes -> smoke -> full -> telemetry
    stages, each routed through the stage journal above.

    Emits a JSON line per stage, so whatever ran before a crash is on
    stdout and in the journal.  Exit codes: 0 every stage ran, 3 no TPU
    (or JAX failed to start), 4 a headline stage failed.
    """
    from lightgbm_tpu.utils.platform import enable_compile_cache
    enable_compile_cache()
    t0 = time.time()
    try:
        import jax
        devs = jax.devices()
        import jax.numpy as jnp
        jnp.ones((8, 8)).sum().block_until_ready()
    except Exception as e:
        emit({"stage": "init", "ok": False, "elapsed": round(time.time() - t0, 1),
              "error": str(e)[-800:]})
        return 3
    d = devs[0]
    _DEVICE.update(platform=d.platform, device_kind=d.device_kind,
                   n_devices=len(devs))
    on_tpu = d.platform == "tpu"
    emit({"stage": "init", "ok": on_tpu,
          "elapsed": round(time.time() - t0, 1)})
    if not on_tpu and os.environ.get("BENCH_WORKER_ALLOW_CPU") != "1":
        # no TPU, no measurement: there is no CPU continuation
        # (BENCH_WORKER_ALLOW_CPU=1 lets CI walk the stage pipeline on
        # the CPU; every line it prints says platform "cpu")
        log(f"no TPU: JAX reports {devs}; refusing to measure")
        return 3

    if os.environ.get("BENCH_SKIP_KERNEL_PROBE") != "1":
        run_stage("kernel_probe",
                  lambda: kernel_probe(min(N, 1_000_000), F, MAX_BIN))

    if os.environ.get("BENCH_SKIP_DISPATCH_PROBE") != "1":
        sys.path.insert(0, os.path.join(REPO, "tools"))

        def _dispatch():
            from dispatch_probe import run_probe
            return run_probe(rows=min(N, 100_000), iters=12, chunks=(8, 32))
        run_stage("dispatch_probe", _dispatch)

    # f32-vs-quantized histogram throughput + psum payload accounting
    # (tools/hist_probe.py) — cheap, banked before the long stages
    if os.environ.get("BENCH_SKIP_HIST_PROBE") != "1":
        sys.path.insert(0, os.path.join(REPO, "tools"))

        def _hist():
            from hist_probe import run_probe as hist_run
            return hist_run(rows=min(N, 1_000_000), features=F,
                            max_bin=MAX_BIN, leaves=LEAVES)
        run_stage("hist_probe", _hist)

    # inference-kernel micro-bench (tools/predict_probe.py): while vs
    # fori vs fused traversal sec/Mrow + measured MFU/BW, the planner's
    # variant election cold/warm against the "p-..." autotune family,
    # and the serving bit-parity check; on accelerators the probe raises
    # below the 3x-vs-while bar at 1M rows, and errors are never
    # journaled so a failed probe retries
    if os.environ.get("BENCH_SKIP_PREDICT_PROBE") != "1":
        sys.path.insert(0, os.path.join(REPO, "tools"))

        def _predict_probe():
            from predict_probe import run_probe as predict_run
            return predict_run(rows=min(N, 1_000_000), features=F)
        run_stage("predict_probe", _predict_probe)

    # device-ingest binning micro-bench (tools/ingest_probe.py): the
    # full parity matrix (NaN / zero-as-bin / categorical / uint16)
    # device-vs-host byte identity, the "i-..." autotune election
    # cold/warm, and measured bin rows/sec + HBM BW per tile rung next
    # to the host oracle; on accelerators the probe raises below the
    # 5x-vs-host bar at 1M rows, and errors are never journaled so a
    # failed probe retries
    if os.environ.get("BENCH_SKIP_INGEST_PROBE") != "1":
        sys.path.insert(0, os.path.join(REPO, "tools"))

        def _ingest_probe():
            from ingest_probe import run_probe as ingest_run
            return ingest_run(rows=min(N, 1_000_000), features=F,
                              max_bin=MAX_BIN)
        run_stage("ingest_probe", _ingest_probe)

    # out-of-core block-pump micro-bench (tools/stream_probe.py):
    # blocks/sec, device_put overlap efficiency, host-RSS peak vs the
    # two-level planner's prediction — cheap, banked early; errors are
    # never journaled so a failed probe retries
    if os.environ.get("BENCH_SKIP_STREAM_PROBE") != "1":
        sys.path.insert(0, os.path.join(REPO, "tools"))

        def _stream_probe():
            from stream_probe import run_probe as stream_run
            return stream_run(rows=min(N, 2_000_000), features=F)
        run_stage("stream_probe", _stream_probe)

    # per-tier collective micro-bench (tools/collective_probe.py): flat
    # vs hierarchical vs voting reduction latency over a simulated
    # 2-slice hybrid ("dcn","ici") mesh + the planner's per-tier byte
    # accounting (the acceptance signal: voting's DCN bytes strictly
    # below data-parallel's at equal trees) — cheap, banked early;
    # errors are never journaled so a failed probe retries
    if os.environ.get("BENCH_SKIP_COLLECTIVE_PROBE") != "1":
        sys.path.insert(0, os.path.join(REPO, "tools"))

        def _coll_probe():
            from collective_probe import run_probe as coll_run
            return coll_run(rows=min(N, 1_000_000), features=F,
                            max_bin=MAX_BIN, leaves=LEAVES, trees=TREES)
        run_stage("collective_probe", _coll_probe)

    # batched model-axis sweep micro-bench (tools/sweep_probe.py): the
    # same chunk body solo vs vmapped at B in {2,4,8} lanes over one
    # shared binned matrix — aggregate iters/sec + measured MFU per
    # batch width next to plan_model_batch's lane-chunk verdict; on
    # accelerators the probe raises below the 4x-at-B=8 bar, and errors
    # are never journaled so a failed sweep retries
    if os.environ.get("BENCH_SKIP_SWEEP") != "1":
        sys.path.insert(0, os.path.join(REPO, "tools"))

        def _sweep():
            from sweep_probe import run_probe as sweep_run
            return sweep_run(rows=min(N, 200_000), features=F,
                             max_bin=MAX_BIN, leaves=LEAVES)
        run_stage("sweep", _sweep)

    # tpulint (tools/lint.py, docs/LINTING.md): the static-analysis
    # suite runs as a journaled stage so every bench round records that
    # the tree it measured was invariant-clean; violations raise, and
    # errors are never journaled (run_stage), so a dirty tree re-lints
    # on the next round instead of banking a stale verdict
    if os.environ.get("BENCH_SKIP_LINT") != "1":
        def _lint():
            if REPO not in sys.path:
                sys.path.insert(0, REPO)
            from tools.lint import load_project, run_lint
            project = load_project(root=REPO)
            violations = run_lint(project)
            if violations:
                raise RuntimeError(
                    f"tpulint: {len(violations)} violation(s), first: "
                    + violations[0].render())
            return {"ok": True, "files": len(project.files),
                    "violations": 0}
        run_stage("lint", _lint)

    # whole-plane observability smoke (tools/obs_dump.py): a tiny
    # instrumented train+serve cycle dumping trace/metrics/prometheus
    # artifacts — cheap, banked before the long stages; errors are never
    # journaled (run_stage), so a failed dump retries on the next run
    if os.environ.get("BENCH_SKIP_OBS") != "1":
        sys.path.insert(0, os.path.join(REPO, "tools"))

        def _obs():
            from obs_dump import run_dump
            return run_dump(out_dir=REPO, rows=20_000, trees=8)
        run_stage("obs_dump", _obs)

    if os.environ.get("BENCH_SKIP_SMOKE") != "1":
        smoke = run_stage(
            "smoke", lambda: run_bench(min(SMOKE_N, N),
                                       min(SMOKE_TREES, TREES),
                                       LEAVES, MAX_BIN, tag="-smoke"))
        if smoke is not None and "error" in smoke:
            return 4

    n_full = N

    # HBM budget verdict for the >=10M-row stage, banked as its own stage
    # so the planner's tile/feasibility decision is journaled even if the
    # run itself later dies.  The stage is restored (not skipped): an
    # infeasible verdict aborts cheaply; a degraded one RUNS with the
    # smaller tile instead of crashing in compile as in r5.
    def _plan():
        from lightgbm_tpu.ops.planner import plan_histograms
        return plan_histograms(rows=n_full, features=F,
                               num_bins=MAX_BIN + 1,
                               num_leaves=LEAVES).summary()
    run_stage("hbm_plan", _plan, key=f"hbm_plan@{n_full}")

    # journal key carries the row count: a rerun at another BENCH_ROWS
    # must not replay a different scale's banked result
    full = run_stage("full",
                     lambda: run_bench(n_full, TREES, LEAVES, MAX_BIN),
                     key=f"full@{n_full}")
    if full is not None and "error" in full:
        return 4

    # the resurrected higgs_11m ingest stage (ops/ingest.py): full-scale
    # construction through the streamed device-ingest pump, journaled so
    # the "11M rows bin within HBM, no 157 GB device_put" claim is a
    # banked number (rows/sec + ingest story + memory peaks), not a
    # side effect buried inside the full stage
    if os.environ.get("BENCH_SKIP_INGEST_11M") != "1":
        run_stage("ingest_11m",
                  lambda: run_ingest_11m_bench(n_full),
                  key=f"ingest_11m@{n_full}", budget_floor=600)

    # the >=10M stage, GRADUATED (lightgbm_tpu/data/): a journaled
    # 100M-row streamed run whose binned matrix never resides whole on
    # host or HBM, with planner-predicted vs measured peaks on BOTH
    # memories.  The two-level verdict banks as its own stage first so
    # the decision survives even if the run dies.
    stream_n = int(os.environ.get("BENCH_STREAM_ROWS", 100_000_000))

    def _stream_plan():
        from lightgbm_tpu.ops.planner import plan_stream
        return plan_stream(rows=stream_n, features=F,
                           num_bins=MAX_BIN + 1,
                           num_leaves=min(LEAVES, 63)).summary()
    run_stage("stream_plan", _stream_plan, key=f"stream_plan@{stream_n}")
    if os.environ.get("BENCH_SKIP_STREAM") != "1":
        run_stage(
            "stream",
            lambda: run_stream_bench(
                stream_n,
                trees=int(os.environ.get("BENCH_STREAM_TREES", 3)),
                leaves=min(LEAVES, 63), max_bin=MAX_BIN),
            key=f"stream@{stream_n}", budget_floor=1500)

    # bulk offline scoring (data/score.py via tools/bulk_score.py): the
    # blockstore pump pointed at inference — a >=10M-row synthetic set
    # streamed through the one AOT bulk bucket, scores banked with
    # per-block manifest commits, plus the crash drill (partial run,
    # resume, byte-identical blocks).  The drill raises on any miss, so
    # failed runs are never journaled; rows/sec/device and the
    # predicted-vs-measured peaks on both memories are the banked
    # numbers bench_diff gates on.
    if os.environ.get("BENCH_SKIP_BULK_SCORE") != "1":
        sys.path.insert(0, os.path.join(REPO, "tools"))
        bulk_n = int(os.environ.get("BENCH_BULK_ROWS", 10_000_000))

        def _bulk():
            from bulk_score import run_bulk
            return run_bulk(rows=bulk_n, features=F)
        run_stage("bulk_score", _bulk, key=f"bulk_score@{bulk_n}",
                  budget_floor=900)

    # MSLR-side benchmark (lambdarank + NDCG@10, BASELINE.md) with the
    # leftover budget — strictly after the headline number is banked
    if os.environ.get("BENCH_SKIP_RANKING") != "1":
        run_stage("ranking",
                  lambda: run_ranking_bench(RANK_QUERIES, RANK_DOCS,
                                            RANK_TREES, LEAVES, MAX_BIN),
                  budget_floor=900)

    # serving-throughput metric (lightgbm_tpu/serving/): the request-path
    # half of the north star, after every training number is banked
    if os.environ.get("BENCH_SKIP_SERVING") != "1":
        run_stage("serving", run_serving_bench, budget_floor=300)

    # serving-fleet stage (lightgbm_tpu/fleet/): N-model registry under a
    # shared-HBM plan — measured eviction, AOT zero-compile restart,
    # opt-in low-precision deltas
    if os.environ.get("BENCH_SKIP_FLEET") != "1":
        run_stage("fleet", run_fleet_bench, budget_floor=240)

    # pod-scale failover drill (fleet/topology.py + fleet/router.py):
    # kill one replicated device under load — zero non-typed failures,
    # availability >= 0.999, recovery within one replan tick
    if os.environ.get("BENCH_SKIP_FLEET") != "1":
        run_stage("fleet_failover", run_fleet_failover_bench,
                  budget_floor=180)

    # fault-tolerance overhead (lightgbm_tpu/resilience/): checkpoint
    # save/load cost + resume bit-parity on the live backend
    if os.environ.get("BENCH_SKIP_RESILIENCE") != "1":
        run_stage("resilience", run_resilience_bench, budget_floor=240)

    # guarded model lifecycle (lightgbm_tpu/lifecycle/): continual
    # refresh -> shadow/canary promotion -> forced rollback under load;
    # errors raise so a failed cycle is never journaled
    if os.environ.get("BENCH_SKIP_LIFECYCLE") != "1":
        run_stage("lifecycle", run_lifecycle_bench, budget_floor=240)

    # co-resident train+serve (lightgbm_tpu/coresident/): traffic and a
    # ledger-budgeted refresh share one device set; brownout must
    # throttle training while p99 stays within SLO; errors raise so a
    # failed co-residency cycle is never journaled
    if os.environ.get("BENCH_SKIP_CORESIDENT") != "1":
        run_stage("coresident", run_coresident_bench, budget_floor=240)

    # automated bottleneck diagnosis (lightgbm_tpu/obs/diagnose.py):
    # joins THIS run's banked stages (mfu_measured, compile_cache,
    # stream_probe, collective_probe) + live registry gauges into ranked
    # verdicts, journaled LAST so every bench round self-reports its
    # bottleneck next to the numbers; errors are never journaled
    # (run_stage) so a failed diagnosis retries
    if os.environ.get("BENCH_SKIP_OBS") != "1":
        sys.path.insert(0, os.path.join(REPO, "tools"))

        def _doctor():
            from obs_doctor import run_doctor
            return run_doctor(stages=journal_stages())
        run_stage("obs_doctor", _doctor)
    return 0


if __name__ == "__main__":
    sys.exit(main())
