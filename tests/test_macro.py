"""Fused multi-iteration macro-steps (lightgbm_tpu/boosting/macro.py).

The hard contract: chunked training composes the SAME iter_body in one
runtime-trip-count loop program, so ``update_chunk(c)`` must produce
models BYTE-IDENTICAL to per-iteration ``update()`` for every supported
mode and every chunk decomposition — serial and sharded, eager and
deferred-host, through checkpoints and early stopping.
"""

import os

import numpy as np
import pytest

import lightgbm_tpu as lgb

RNG = np.random.RandomState(7)
N, F = 1200, 10
X = RNG.randn(N, F)
Y_BIN = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + 0.2 * RNG.randn(N) > 0).astype(float)
Y_REG = (X[:, 0] - X[:, 1] + 0.1 * RNG.randn(N))
Y_MC = np.digitize(X[:, 0] + X[:, 1], [-0.5, 0.5]).astype(float)

XV = RNG.randn(400, F)
YV_BIN = (XV[:, 0] + 0.5 * XV[:, 1] * XV[:, 2] + 0.2 * RNG.randn(400) > 0).astype(float)

PARITY_CASES = {
    "gbdt": ({"objective": "binary", "num_leaves": 15,
              "learning_rate": 0.1}, Y_BIN),
    "bagging": ({"objective": "binary", "num_leaves": 15,
                 "learning_rate": 0.1, "bagging_fraction": 0.7,
                 "bagging_freq": 2, "bagging_seed": 11}, Y_BIN),
    "goss": ({"objective": "binary", "boosting": "goss", "num_leaves": 15,
              "learning_rate": 0.2}, Y_BIN),
    "rf": ({"objective": "binary", "boosting": "rf", "num_leaves": 15,
            "bagging_fraction": 0.6, "bagging_freq": 1}, Y_BIN),
    "monotone": ({"objective": "regression", "num_leaves": 15,
                  "learning_rate": 0.1,
                  "monotone_constraints": [1, -1] + [0] * (F - 2)}, Y_REG),
    "multiclass": ({"objective": "multiclass", "num_class": 3,
                    "num_leaves": 7, "learning_rate": 0.1}, Y_MC),
    # quantized-gradient mode: the in-loop discretization (stochastic
    # rounding keys ride the stacked per-round key stream) must keep
    # chunked == per-iteration byte-identical WITHIN the mode
    "quant": ({"objective": "binary", "num_leaves": 15,
               "learning_rate": 0.1, "use_quantized_grad": True}, Y_BIN),
    "quant_renew": ({"objective": "binary", "num_leaves": 15,
                     "learning_rate": 0.1, "use_quantized_grad": True,
                     "quant_train_renew_leaf": True,
                     "bagging_fraction": 0.7, "bagging_freq": 2,
                     "bagging_seed": 11}, Y_BIN),
    # fused Pallas histogram→split megakernel arm (ops/fused.py, CPU
    # interpret mode): the in-kernel scan + VMEM arena must keep chunked
    # == per-iteration byte-identical, f32 and quantized
    "fused": ({"objective": "binary", "num_leaves": 15,
               "learning_rate": 0.1, "tpu_hist_method": "fused"}, Y_BIN),
    "fused_quant": ({"objective": "binary", "num_leaves": 15,
                     "learning_rate": 0.1, "tpu_hist_method": "fused",
                     "use_quantized_grad": True,
                     "bagging_fraction": 0.7, "bagging_freq": 2,
                     "bagging_seed": 11}, Y_BIN),
}


def _booster(params, y, **ds_kw):
    params = dict(params, verbosity=-1)
    ds = lgb.Dataset(X, label=y, free_raw_data=False, **ds_kw)
    return lgb.Booster(params=params, train_set=ds)


def _train(params, y, chunks):
    b = _booster(params, y)
    for c in chunks:
        if c > 1:
            b.update_chunk(c)
        else:
            b.update()
    return b.model_to_string()


@pytest.mark.parametrize("case", sorted(PARITY_CASES))
def test_chunked_equals_per_iteration(case):
    params, y = PARITY_CASES[case]
    per_iter = _train(params, y, [1] * 12)
    chunked = _train(params, y, [8, 4])
    mixed = _train(params, y, [2, 1, 4, 2, 2, 1])
    assert chunked == per_iter, f"{case}: chunk(8,4) != per-iteration"
    assert mixed == per_iter, f"{case}: mixed chunks != per-iteration"


@pytest.mark.parametrize("case", ["gbdt", "quant", "fused_quant"])
def test_chunked_equals_per_iteration_tiled(case, monkeypatch):
    """Planner row tiling active (LGBM_TPU_TILE_ROWS forces tiles far
    smaller than n): chunked == per-iteration must hold unchanged, and
    the tiled models must equal the untiled ones byte-for-byte (the
    kernels' pinned tile-major accumulation order)."""
    params, y = PARITY_CASES[case]
    untiled = _train(params, y, [1] * 12)
    monkeypatch.setenv("LGBM_TPU_TILE_ROWS", "256")
    per_iter = _train(params, y, [1] * 12)
    chunked = _train(params, y, [8, 4])
    assert chunked == per_iter, f"{case}: tiled chunk(8,4) != per-iter"
    assert per_iter == untiled, f"{case}: tiled != untiled"


@pytest.mark.parametrize("case", ["gbdt", "quant"])
def test_chunked_equals_per_iteration_hierarchical(case, monkeypatch):
    """Hybrid ("dcn","ici") mesh with hierarchical tiered reduction
    (pod-scale plane, parallel/collectives.py): chunked == per-iteration
    must hold unchanged, and the hierarchical models must equal the
    flat-schedule ones byte-for-byte — integer payloads are associative;
    the f32 row rides the pinned tier-ordered reduction."""
    params, y = PARITY_CASES[case]
    params = dict(params, tree_learner="data")
    monkeypatch.setenv("LGBM_TPU_NUM_SLICES", "2")
    if case == "gbdt":
        monkeypatch.setenv("LGBM_TPU_PINNED_REDUCE", "1")
    monkeypatch.setenv("LGBM_TPU_HIER_REDUCE", "0")
    flat = _train(params, y, [1] * 12)
    monkeypatch.setenv("LGBM_TPU_HIER_REDUCE", "1")
    per_iter = _train(params, y, [1] * 12)
    chunked = _train(params, y, [8, 4])
    assert chunked == per_iter, f"{case}: hierarchical chunk != per-iter"
    assert per_iter == flat, f"{case}: hierarchical != flat schedule"


def _strip_hist_method_lines(text):
    return "\n".join(ln for ln in text.splitlines()
                     if not ln.startswith("[tpu_hist_method"))


@pytest.mark.parametrize("mesh", ["flat8", "2x4", "4x2"])
def test_sharded_fused_quant_byte_parity(mesh, monkeypatch):
    """The collective seam (grower_rounds.py sharded fused arm):
    data-parallel quantized fused == staged BYTE-identical model text
    across the flat 8-device mesh and both hybrid ("dcn","ici") tier
    shapes — the seam psums the same integer smaller-child arena through
    the same psum_quant_hist routing and the scan body is shared, so
    equality is exact, not approximate."""
    import jax
    if jax.device_count() < 8:
        pytest.skip("needs the virtual 8-device mesh")
    if mesh != "flat8":
        monkeypatch.setenv("LGBM_TPU_NUM_SLICES", mesh.split("x")[0])
        monkeypatch.setenv("LGBM_TPU_HIER_REDUCE", "1")
    params = dict(PARITY_CASES["quant"][0], tree_learner="data",
                  tpu_tree_growth="rounds")
    staged = _strip_hist_method_lines(_train(params, Y_BIN, [1] * 8))
    fused = _strip_hist_method_lines(
        _train(dict(params, tpu_hist_method="fused"), Y_BIN, [1] * 8))
    assert fused == staged, f"{mesh}: sharded fused != staged"


def test_fused_categorical_tree_parity():
    """The lifted categorical gate: per-category stats are the same
    segment reduction, so the fused arm's cat merge (pick_fused_best)
    must reproduce the staged categorical split search — quantized mode,
    byte-identical model text."""
    rng = np.random.RandomState(21)
    Xc = np.column_stack([rng.randint(0, 8, N).astype(float), X[:, 1:]])
    params = {"objective": "binary", "num_leaves": 15,
              "learning_rate": 0.1, "use_quantized_grad": True,
              "tpu_tree_growth": "rounds", "verbosity": -1}

    def run(method):
        ds = lgb.Dataset(Xc, label=Y_BIN, free_raw_data=False,
                         categorical_feature=[0])
        b = lgb.Booster(params=dict(params, tpu_hist_method=method),
                        train_set=ds)
        if method == "fused":
            assert b.boosting.grower_cfg.hist_method == "fused"
        for _ in range(8):
            b.update()
        return _strip_hist_method_lines(b.model_to_string())

    staged = run("auto")
    fused = run("fused")
    assert fused == staged
    # the categorical feature must actually split somewhere, or the
    # parity above proved nothing about the cat merge
    assert "cat_threshold" in fused or "split_feature=0" in fused


@pytest.mark.parametrize("case", ["gbdt", "quant"])
def test_streamed_equals_resident_chunk_matrix(case, monkeypatch):
    """Out-of-core streamed training (lightgbm_tpu/data/) joins the
    chunked==per-iteration matrix: the streamed executor must reproduce
    the resident models byte-for-byte — quant by integer associativity,
    f32 by the pinned-block-order carry fold — under BOTH chunk-gate
    settings (streamed training is per-iteration by construction, so
    the scheduler's c=1 fallback must change nothing)."""
    params, y = PARITY_CASES[case]
    params = dict(params, tpu_tree_growth="rounds")  # the streamed
    # grower mirrors the rounds grower; pin the resident comparator
    monkeypatch.setenv("LGBM_TPU_STREAM", "0")
    resident = _train(params, y, [1] * 12)
    resident_chunked = _train(params, y, [8, 4])
    monkeypatch.setenv("LGBM_TPU_STREAM", "1")
    monkeypatch.setenv("LGBM_TPU_STREAM_BLOCK_ROWS", "256")
    streamed = _train(params, y, [1] * 12)
    assert resident_chunked == resident
    assert streamed == resident, f"{case}: streamed != resident"

    # engine runs (the chunk SCHEDULER in play): engine-streamed must
    # equal engine-resident under both gate settings
    def run_engine(stream, chunk):
        monkeypatch.setenv("LGBM_TPU_STREAM", "1" if stream else "0")
        monkeypatch.setenv("LGBM_TPU_CHUNK", chunk)
        ds = lgb.Dataset(X, label=y, free_raw_data=False)
        return lgb.train(dict(params, verbosity=-1), ds,
                         num_boost_round=12,
                         verbose_eval=False).model_to_string()

    engine_resident = run_engine(False, "32")
    for env in ("0", "32"):
        assert run_engine(True, env) == engine_resident, \
            f"{case}: streamed engine run (chunk={env}) != resident"


def test_chunked_equals_per_iteration_deferred_host(monkeypatch):
    """The deferred-host banking path (accelerator default) slices the
    chunk bundle into per-iteration pending entries; the drain must see
    exactly what per-iteration training banks."""
    monkeypatch.setenv("LGBT_DEFER_HOST_TREES", "1")
    params, y = PARITY_CASES["gbdt"]
    assert _train(params, y, [8, 4]) == _train(params, y, [1] * 12)


def test_chunked_equals_per_iteration_sharded():
    """Data-parallel over the virtual 8-device CPU mesh: the chunk scan
    wraps the shard_map'd iter_body; stacked row inputs keep the row
    sharding (parallel/learners.py put_stacked_rows)."""
    import jax
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    params = {"objective": "binary", "num_leaves": 15,
              "learning_rate": 0.1, "tree_learner": "data"}
    assert _train(params, Y_BIN, [8, 4]) == _train(params, Y_BIN, [1] * 12)


def test_lr_schedule_parity_via_engine():
    """reset_parameter learning-rate schedules ride into the chunk as a
    [c] array; engine-chunked training must equal per-iteration."""
    sched = [0.1 * (0.97 ** i) for i in range(16)]

    def run(env):
        os.environ["LGBM_TPU_CHUNK"] = env
        try:
            ds = lgb.Dataset(X, label=Y_BIN, free_raw_data=False)
            return lgb.train(
                {"objective": "binary", "num_leaves": 15, "verbosity": -1},
                ds, num_boost_round=16, learning_rates=sched,
                verbose_eval=False).model_to_string()
        finally:
            os.environ.pop("LGBM_TPU_CHUNK", None)

    assert run("32") == run("0")


def test_early_stopping_parity_via_engine():
    def run(env):
        os.environ["LGBM_TPU_CHUNK"] = env
        try:
            ds = lgb.Dataset(X, label=Y_BIN, free_raw_data=False)
            vs = lgb.Dataset(XV, label=YV_BIN, reference=ds,
                             free_raw_data=False)
            evals = {}
            bst = lgb.train(
                {"objective": "binary", "num_leaves": 31, "verbosity": -1,
                 "metric": "binary_logloss", "metric_freq": 2},
                ds, num_boost_round=60, valid_sets=[vs],
                early_stopping_rounds=4, evals_result=evals,
                verbose_eval=False)
            return bst.best_iteration, bst.model_to_string(), evals
        finally:
            os.environ.pop("LGBM_TPU_CHUNK", None)

    it_on, model_on, ev_on = run("32")
    it_off, model_off, ev_off = run("0")
    assert it_on == it_off
    assert model_on == model_off
    assert ev_on == ev_off


def test_rf_valid_scores_parity_via_engine():
    """RF's running-mean valid-score renormalization rides the fused
    valid updater (macro.build_chunk_valid rf mode); eval history and
    model must match per-iteration training."""
    def run(env):
        os.environ["LGBM_TPU_CHUNK"] = env
        try:
            ds = lgb.Dataset(X, label=Y_BIN, free_raw_data=False)
            vs = lgb.Dataset(XV, label=YV_BIN, reference=ds,
                             free_raw_data=False)
            evals = {}
            bst = lgb.train(
                {"objective": "binary", "boosting": "rf", "num_leaves": 15,
                 "bagging_fraction": 0.6, "bagging_freq": 1,
                 "verbosity": -1, "metric": "binary_logloss",
                 "metric_freq": 4},
                ds, num_boost_round=8, valid_sets=[vs],
                evals_result=evals, verbose_eval=False)
            return bst.model_to_string(), evals
        finally:
            os.environ.pop("LGBM_TPU_CHUNK", None)

    m_on, ev_on = run("32")
    m_off, ev_off = run("0")
    assert m_on == m_off
    # metric VALUES may differ from the legacy gate-off path by ~1 ulp of
    # score (docs/PERF.md: RF's running-mean renorm contracts differently
    # in the legacy eager ops); within the macro path they are exact
    np.testing.assert_allclose(
        ev_on["valid_0"]["binary_logloss"],
        ev_off["valid_0"]["binary_logloss"], rtol=1e-7)


def test_resume_from_checkpoint_mid_stream(tmp_path):
    """A checkpoint written mid-stream by a chunked run must resume to the
    byte-identical final model — under chunking AND per-iteration."""
    snap = str(tmp_path / "m.txt")

    def run(env, resume=None):
        os.environ["LGBM_TPU_CHUNK"] = env
        try:
            ds = lgb.Dataset(X, label=Y_BIN, free_raw_data=False)
            return lgb.train(
                {"objective": "binary", "num_leaves": 15, "verbosity": -1,
                 "bagging_fraction": 0.7, "bagging_freq": 1},
                ds, num_boost_round=14, verbose_eval=False,
                snapshot_freq=5, snapshot_out=snap,
                resume_from=resume).model_to_string()
        finally:
            os.environ.pop("LGBM_TPU_CHUNK", None)

    full = run("32")
    resumed_chunked = run("32", resume=snap + ".ckpt")
    resumed_periter = run("0", resume=snap + ".ckpt")
    assert resumed_chunked == full
    assert resumed_periter == full


def test_metric_freq_gates_eval():
    """config.metric_freq (alias output_freq) was parsed but never read;
    the engine now evaluates every metric_freq-th iteration like the
    reference's OutputMetric loop."""
    ds = lgb.Dataset(X, label=Y_BIN, free_raw_data=False)
    vs = lgb.Dataset(XV, label=YV_BIN, reference=ds, free_raw_data=False)
    evals = {}
    lgb.train({"objective": "binary", "num_leaves": 15, "verbosity": -1,
               "metric": "binary_logloss", "output_freq": 3},
              ds, num_boost_round=12, valid_sets=[vs],
              evals_result=evals, verbose_eval=False)
    assert len(evals["valid_0"]["binary_logloss"]) == 4


def test_early_stopping_without_valid_raises():
    """The init-time error moved up front (callbacks now skip no-eval
    iterations); training with early stopping but nothing to evaluate
    must still fail loudly."""
    ds = lgb.Dataset(X, label=Y_BIN, free_raw_data=False)
    with pytest.raises(ValueError, match="at least one dataset"):
        lgb.train({"objective": "binary", "num_leaves": 15,
                   "verbosity": -1, "metric": "None"},
                  ds, num_boost_round=5, early_stopping_rounds=2,
                  verbose_eval=False)


@pytest.mark.parametrize("params", [
    {"objective": "binary", "boosting": "dart", "num_leaves": 15},
    {"objective": "binary", "num_leaves": 15, "cegb_penalty_split": 0.1},
])
def test_c1_fallback_modes(params):
    """DART drop/rollback and CEGB bitmaps need per-iteration host logic:
    chunk_supported is False, update_chunk refuses, and engine training
    with the chunk gate ON still works through the c=1 path."""
    b = _booster(params, Y_BIN)
    assert not b.boosting.chunk_supported()
    with pytest.raises(RuntimeError, match="per-iteration"):
        b.update_chunk(4)
    os.environ["LGBM_TPU_CHUNK"] = "32"
    try:
        ds = lgb.Dataset(X, label=Y_BIN, free_raw_data=False)
        bst = lgb.train(dict(params, verbosity=-1), ds, num_boost_round=4,
                        verbose_eval=False)
        assert bst.current_iteration() == 4
    finally:
        os.environ.pop("LGBM_TPU_CHUNK", None)


def test_custom_fobj_not_chunk_supported():
    ds = lgb.Dataset(X, label=Y_BIN, free_raw_data=False)
    bst = lgb.train({"num_leaves": 15, "verbosity": -1}, ds,
                    num_boost_round=3, verbose_eval=False,
                    fobj=lambda preds, d: (
                        1.0 / (1.0 + np.exp(-preds)) - d.get_label(),
                        np.full(len(preds), 0.25)))
    assert bst.num_trees() == 3
    assert not bst.boosting.chunk_supported()


def test_chunk_stop_on_unsplittable():
    """A chunk whose early iteration produces no splittable leaves must
    truncate exactly like per-iteration training (constant labels stop
    at iteration 0 with the boost-from-average constant tree)."""
    params = {"objective": "regression", "num_leaves": 15, "verbosity": -1}
    y_const = np.full(N, 3.25)
    ds = lgb.Dataset(X, label=y_const, free_raw_data=False)
    b = lgb.Booster(params=params, train_set=ds)
    stopped = b.update_chunk(4)
    assert stopped
    assert b.current_iteration() == 0
    assert b.num_trees() == 1          # the constant AsConstantTree stump
    pred = b.predict(X[:5])
    np.testing.assert_allclose(pred, 3.25, rtol=1e-6)


def test_release_host_binned(monkeypatch):
    """free_raw_data + LGBM_TPU_FREE_BINNED=1 drops the host binned
    matrix after device upload; reuse fails with the informative error
    while prediction and training keep working."""
    monkeypatch.setenv("LGBM_TPU_FREE_BINNED", "1")
    ds = lgb.Dataset(X, label=Y_BIN)          # free_raw_data default True
    b = lgb.Booster(params={"objective": "binary", "num_leaves": 15,
                            "verbosity": -1}, train_set=ds)
    assert ds.binned is None
    for _ in range(3):
        b.update()
    assert b.num_trees() == 3
    assert np.isfinite(b.predict(X[:8])).all()
    with pytest.raises(RuntimeError, match="released"):
        lgb.Booster(params={"objective": "binary", "verbosity": -1},
                    train_set=ds)
    # free_raw_data=False keeps the host copy regardless
    ds2 = lgb.Dataset(X, label=Y_BIN, free_raw_data=False)
    lgb.Booster(params={"objective": "binary", "num_leaves": 15,
                        "verbosity": -1}, train_set=ds2)
    assert ds2.binned is not None
