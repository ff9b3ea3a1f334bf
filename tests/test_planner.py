"""HBM budget planner (lightgbm_tpu/ops/planner.py).

Planning runs against a FAKE memory model (``budget_bytes`` /
``LGBM_TPU_HBM_BYTES``) so the verdicts are deterministic off-TPU: the
r5 OOM shape must become a planned, feasible run; small shapes must stay
untiled; the int16 psum narrowing decision must match the kernel-side
static bound; and the predicted peak must track reality on a
scaled-down shape (the off-TPU acceptance path).
"""

import os

import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.ops.planner import (DEFAULT_HBM_BYTES, MIN_TILE_ROWS,
                                      HistPlan, apply_plan,
                                      hbm_limit_bytes, plan_histograms,
                                      predict_peak_bytes)

GB = 1 << 30


def test_small_shape_stays_untiled():
    p = plan_histograms(100_000, 28, 64, num_leaves=63,
                        budget_bytes=16 * GB, accel=True)
    assert p.tile_rows == 0 and p.use_pack
    assert p.feasible and not p.degraded


def test_r5_oom_shape_becomes_planned_run():
    """The exact shape that died in r5 (>=10M x 28, 255 leaves, B=64,
    157.7 GB requested vs ~17 GB HBM): the untiled prediction must land
    in the measured order of magnitude, and the plan must degrade to a
    power-of-two tile whose predicted peak fits a 16 GB budget."""
    p = plan_histograms(11_000_000, 28, 64, num_leaves=255,
                        budget_bytes=16 * GB, accel=True)
    # the unplanned pipeline wildly exceeds HBM (r5 measured 157.7 GB)
    assert p.untiled_peak_bytes > 100 * GB
    assert p.degraded and p.feasible
    assert p.tile_rows >= MIN_TILE_ROWS
    assert p.tile_rows & (p.tile_rows - 1) == 0        # power of two
    assert not p.use_pack     # no whole-dataset record arena when tiled
    assert p.predicted_peak_bytes <= p.budget_bytes
    # 10M flavor of the acceptance shape
    p10 = plan_histograms(10_000_000, 28, 64, num_leaves=255,
                          budget_bytes=16 * GB, accel=True)
    assert p10.feasible


def test_infeasible_verdict():
    p = plan_histograms(11_000_000, 28, 64, num_leaves=255,
                        budget_bytes=256 << 20, accel=True)
    assert not p.feasible
    assert p.tile_rows == MIN_TILE_ROWS    # degraded to the floor


def test_peak_monotone_in_tile():
    for variant in ("scatter", "sorted", "matmul"):
        peaks = [predict_peak_bytes(4_000_000, 28, 64, num_leaves=255,
                                    variant=variant, tile_rows=t,
                                    use_pack=(t == 0), accel=True)[0]
                 for t in (0, 1 << 21, 1 << 18, 1 << 16)]
        assert peaks == sorted(peaks, reverse=True), (variant, peaks)


def test_narrowing_decision_matches_kernel_bound():
    from lightgbm_tpu.ops.histogram import quant_psum_narrow
    for rows, bins in ((1_000, 4), (200_000, 4), (1_000_000, 64)):
        p = plan_histograms(rows, 28, 64, quant=True, quant_bins=bins,
                            budget_bytes=16 * GB)
        assert p.narrow_int16 == quant_psum_narrow(rows, bins)


def test_env_overrides(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_TILE_ROWS", "262144")
    p = plan_histograms(11_000_000, 28, 64, num_leaves=255,
                        budget_bytes=16 * GB, accel=True)
    assert p.tile_rows == 262144 and not p.use_pack and not p.degraded
    monkeypatch.setenv("LGBM_TPU_TILE_ROWS", "off")
    p = plan_histograms(11_000_000, 28, 64, num_leaves=255,
                        budget_bytes=16 * GB, accel=True)
    assert p.tile_rows == 0
    monkeypatch.delenv("LGBM_TPU_TILE_ROWS")
    monkeypatch.setenv("LGBM_TPU_HBM_BYTES", str(8 * GB))
    limit, source = hbm_limit_bytes()
    assert limit == 8 * GB and source == "env"


def test_limit_fallback_has_source():
    limit, source = hbm_limit_bytes()
    assert limit > 0 and source in ("memory_stats", "env", "default")
    if source == "default":
        assert limit == DEFAULT_HBM_BYTES


def test_apply_plan_threads_config(monkeypatch):
    from lightgbm_tpu.grower import GrowerConfig
    monkeypatch.setenv("LGBM_TPU_HBM_BYTES", str(16 * GB))
    cfg, plan = apply_plan(GrowerConfig(num_leaves=63, num_bins=64),
                           100_000, 28)
    assert isinstance(plan, HistPlan)
    assert cfg.tile_rows == plan.tile_rows
    # a tiny fake budget forces tiling + clears the record-arena hoist
    monkeypatch.setenv("LGBM_TPU_HBM_BYTES", str(64 << 20))
    cfg, plan = apply_plan(
        GrowerConfig(num_leaves=255, num_bins=64), 4_000_000, 28,
        accel=True)
    assert plan.degraded and cfg.tile_rows > 0 and not cfg.hist_pack


def test_summary_is_json_ready():
    import json
    p = plan_histograms(1_000_000, 28, 64, budget_bytes=16 * GB)
    d = json.loads(json.dumps(p.summary()))
    assert d["hbm_limit_bytes"] == p.limit_bytes
    assert set(d) >= {"tile_rows", "feasible", "predicted_peak_bytes",
                      "untiled_peak_bytes", "degraded", "variant"}


def test_prediction_tracks_measured_lower_bound():
    """Off-TPU acceptance path: on a scaled-down shape the predicted
    peak must be at least the bytes of the arrays the pipeline REALLY
    allocates (binned matrix + hist cache + update buffer) and within a
    small factor of that floor — i.e. the model is anchored to reality,
    not a fudge constant."""
    rows, F, B, L = 200_000, 28, 64, 255
    floor = (rows * F                  # binned u8
             + L * 3 * F * B * 4       # hist cache f32
             + rows * F * 3 * 4)       # untiled scatter updates
    pred = predict_peak_bytes(rows, F, B, num_leaves=L, variant="scatter",
                              tile_rows=0, accel=False)[0]
    assert floor <= pred <= 12 * floor
    # tiled: the update buffer leaves the model, the residents remain
    pred_t = predict_peak_bytes(rows, F, B, num_leaves=L,
                                variant="scatter", tile_rows=1 << 16,
                                use_pack=False, accel=False)[0]
    floor_t = rows * F + L * 3 * F * B * 4
    assert floor_t <= pred_t < pred


def test_booster_exposes_plan(monkeypatch):
    """The GBDT layer plans at build time and a forced tile flows into
    the grower config (end-to-end threading check)."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    X = rng.randn(600, 6)
    y = (X[:, 0] > 0).astype(float)
    monkeypatch.setenv("LGBM_TPU_TILE_ROWS", "128")
    b = lgb.Booster(params={"objective": "binary", "verbosity": -1,
                            "num_leaves": 7},
                    train_set=lgb.Dataset(X, label=y, free_raw_data=False))
    plan = b.boosting.hist_plan
    assert plan.tile_rows == 128
    assert b.boosting.grower_cfg.tile_rows == 128
    assert not b.boosting.grower_cfg.hist_pack
    b.update()
    assert b.boosting.iter == 1


# ======================================================================
# Shape-bucket ladder (the compile-time war)

def test_bucket_rows_ladder():
    """Rungs are {2^k, 1.5*2^k} with a 4096 floor: pad waste is bounded
    at 50% and every rung maps to itself (idempotent)."""
    from lightgbm_tpu.ops.planner import MIN_BUCKET_ROWS, bucket_rows
    assert bucket_rows(1) == MIN_BUCKET_ROWS
    assert bucket_rows(4096) == 4096
    assert bucket_rows(4097) == 6144
    assert bucket_rows(6145) == 8192
    assert bucket_rows(1_000_001) == 1 << 20
    assert bucket_rows(1_100_000) == (1 << 20) + (1 << 19)
    for n in (4096, 6144, 8192, 12288, 1 << 20):
        assert bucket_rows(n) == n
        assert bucket_rows(bucket_rows(n + 1)) == bucket_rows(n + 1)


def test_shape_bucket_quant_model_parity(monkeypatch):
    """Row-count shape buckets (LGBM_TPU_SHAPE_BUCKETS=1): padded rows
    are masked out of every sum, so the quantized model is BYTE-identical
    to the exact-shape run — the invariant that lets the bucket ladder
    collapse the compile count without touching results.  Deterministic
    rounding: the stochastic-rounding uniforms are drawn per PADDED row,
    so that mode legitimately re-randomizes when the pad changes (same
    class of difference as a bagging reseed, not a correctness gap)."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops.planner import bucket_rows
    rng = np.random.RandomState(17)
    n = 5000                       # pads to 6144: a real 1.5*2^k rung
    assert bucket_rows(n) == 6144
    X = rng.randn(n, 6)
    y = (X[:, 0] + 0.3 * X[:, 1] > 0).astype(float)

    def run():
        ds = lgb.Dataset(X, label=y, free_raw_data=False)
        b = lgb.Booster(params={"objective": "binary", "num_leaves": 15,
                                "verbosity": -1,
                                "use_quantized_grad": True,
                                "stochastic_rounding": False},
                        train_set=ds)
        for _ in range(6):
            b.update()
        return b.model_to_string()

    monkeypatch.setenv("LGBM_TPU_SHAPE_BUCKETS", "0")
    exact = run()
    monkeypatch.setenv("LGBM_TPU_SHAPE_BUCKETS", "1")
    bucketed = run()
    assert bucketed == exact
