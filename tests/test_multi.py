"""Batched multi-booster training (lightgbm_tpu/multi/).

The hard contract: ``train_many`` vmaps the EXACT solo macro-chunk body
over a leading booster axis, so every extracted booster must be
BYTE-IDENTICAL in model text to the same config trained alone — across
modes (gbdt / bagging / GOSS / multiclass / quantized / lr schedules),
resident and 8-device data-parallel, through per-lane early stopping and
checkpoint bundles; ``cv(fused=True)`` must return the serial ``cv``'s
results dict bit-for-bit.  (Parity scope: the CPU test backend resolves
``hist_method=auto`` to the scatter family, whose accumulation is
order-invariant under vmap — docs/PERF.md "model axis".)
"""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.multi import expand_param_grid, group_boosters

pytestmark = pytest.mark.multi

RNG = np.random.RandomState(7)
N, F = 700, 10
X = RNG.randn(N, F)
Y_BIN = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + 0.2 * RNG.randn(N) > 0).astype(float)
Y_MC = np.digitize(X[:, 0] + X[:, 1], [-0.5, 0.5]).astype(float)

XV = RNG.randn(300, F)
YV_BIN = (XV[:, 0] + 0.5 * XV[:, 1] * XV[:, 2] + 0.2 * RNG.randn(300) > 0).astype(float)

BASE = {"verbosity": -1, "num_leaves": 7, "learning_rate": 0.1}


def _lr_sched():
    return lgb.reset_parameter(learning_rate=lambda i: 0.2 * 0.95 ** i)


# mode -> (two structurally-identical lane configs varying only runtime
# fields, label, per-lane callback factories)
PARITY_CASES = {
    "gbdt": ([dict(BASE, objective="binary"),
              dict(BASE, objective="binary", learning_rate=0.23)],
             Y_BIN, None),
    "bagging": ([dict(BASE, objective="binary", bagging_fraction=0.7,
                      bagging_freq=2, bagging_seed=11),
                 dict(BASE, objective="binary", bagging_fraction=0.5,
                      bagging_freq=1, bagging_seed=3)],
                Y_BIN, None),
    "goss": ([dict(BASE, objective="binary", boosting="goss"),
              dict(BASE, objective="binary", boosting="goss",
                   learning_rate=0.3)],
             Y_BIN, None),
    "multiclass": ([dict(BASE, objective="multiclass", num_class=3),
                    dict(BASE, objective="multiclass", num_class=3,
                         learning_rate=0.2)],
                   Y_MC, None),
    "quant": ([dict(BASE, objective="binary", use_quantized_grad=True),
               dict(BASE, objective="binary", use_quantized_grad=True,
                    learning_rate=0.17)],
              Y_BIN, None),
    "lr_schedule": ([dict(BASE, objective="binary"),
                     dict(BASE, objective="binary")],
                    Y_BIN, _lr_sched),
}


def _ds(y=Y_BIN, x=X):
    return lgb.Dataset(x, label=y, free_raw_data=False)


def _solo(params, y, rounds=8, cb=None):
    return lgb.train(dict(params), _ds(y), num_boost_round=rounds,
                     verbose_eval=False,
                     callbacks=[cb()] if cb else None).model_to_string()


# the full 6-case resident matrix runs in tier-1; the data-parallel arm
# compiles shard_map x vmap programs per case, so one representative
# (gbdt) stays fast and the rest ride the slow marker (-m multi runs all)
_MATRIX = []
for _c in sorted(PARITY_CASES):
    _MATRIX.append(pytest.param(_c, False, id=f"{_c}-resident"))
    _MATRIX.append(pytest.param(
        _c, True, id=f"{_c}-data_parallel",
        marks=() if _c == "gbdt" else (pytest.mark.slow,)))


@pytest.mark.parametrize("case,sharded", _MATRIX)
def test_train_many_matches_solo(case, sharded):
    params_list, y, cb = PARITY_CASES[case]
    if sharded:
        import jax
        if jax.device_count() < 2:
            pytest.skip("needs >= 2 devices")
        params_list = [dict(p, tree_learner="data") for p in params_list]
    solos = [_solo(p, y, cb=cb) for p in params_list]
    many = lgb.train_many(
        [dict(p) for p in params_list], _ds(y), num_boost_round=8,
        callbacks=[[cb()] for _ in params_list] if cb else None)
    for i, bst in enumerate(many):
        assert bst.model_to_string() == solos[i], \
            f"{case} lane {i}: batched != solo"


def test_heterogeneous_configs_one_call():
    """Structurally-different configs in ONE call cross group boundaries
    (binary vs GOSS vs multiclass-on-other-labels can't share a trace)
    yet each lane still lands byte-identical."""
    p0 = dict(BASE, objective="binary")
    p1 = dict(BASE, objective="binary", boosting="goss", num_leaves=15)
    p2 = dict(BASE, objective="binary", bagging_fraction=0.6,
              bagging_freq=1)
    solos = [_solo(p, Y_BIN) for p in (p0, p1, p2)]
    many = lgb.train_many([dict(p0), dict(p1), dict(p2)], _ds(),
                          num_boost_round=8)
    assert [b.model_to_string() for b in many] == solos


def test_per_lane_round_budgets():
    """A lane whose num_iterations ends mid-batch freezes (inert inputs,
    no retrace) while its neighbours train on."""
    p_short = dict(BASE, objective="binary", num_iterations=5)
    p_long = dict(BASE, objective="binary", learning_rate=0.2)
    solo_short = _solo(p_short, Y_BIN, rounds=11)
    solo_long = _solo(p_long, Y_BIN, rounds=11)
    many = lgb.train_many([dict(p_short), dict(p_long)], _ds(),
                          num_boost_round=11)
    assert many[0].current_iteration() == 5
    assert many[0].model_to_string() == solo_short
    assert many[1].current_iteration() == 11
    assert many[1].model_to_string() == solo_long


def test_early_stopping_mid_batch():
    """One lane early-stops (best_iteration, truncated eval history and
    all) while the other lane's bytes are untouched."""
    vs = lgb.Dataset(XV, label=YV_BIN, free_raw_data=False)
    p_es = dict(BASE, objective="binary", metric="binary_logloss")
    p_go = dict(BASE, objective="binary", metric="binary_logloss",
                learning_rate=0.02)
    er_solo = {}
    solo = lgb.train(dict(p_es), _ds(), num_boost_round=30,
                     valid_sets=[vs], early_stopping_rounds=2,
                     evals_result=er_solo, verbose_eval=False)
    solo_go = lgb.train(dict(p_go), _ds(), num_boost_round=30,
                        valid_sets=[vs], early_stopping_rounds=2,
                        verbose_eval=False)
    er_many = [{}, {}]
    many = lgb.train_many([dict(p_es), dict(p_go)], _ds(),
                          num_boost_round=30, valid_sets=[vs],
                          early_stopping_rounds=2, evals_results=er_many)
    assert many[0].model_to_string() == solo.model_to_string()
    assert many[0].best_iteration == solo.best_iteration
    assert er_many[0] == er_solo
    assert many[1].model_to_string() == solo_go.model_to_string()


def test_cv_fused_matches_serial():
    params = dict(BASE, objective="binary", metric="binary_logloss")
    r_serial = lgb.cv(dict(params), _ds(), num_boost_round=8, nfold=3,
                      stratified=False, shuffle=False, verbose_eval=False)
    r_fused = lgb.cv(dict(params), _ds(), num_boost_round=8, nfold=3,
                     stratified=False, shuffle=False, verbose_eval=False,
                     fused=True)
    assert sorted(r_serial) == sorted(r_fused)
    for k in r_serial:
        assert r_serial[k] == r_fused[k], f"cv key {k} diverged"


def test_cv_fused_custom_fobj_falls_back():
    """A custom fobj is not chunk-supported; fused cv must quietly run
    the serial path and return identical results."""

    def fobj(preds, ds):
        lab = ds.get_label()
        p = 1.0 / (1.0 + np.exp(-preds))
        return p - lab, p * (1.0 - p)

    params = dict(BASE, objective="binary", metric="binary_logloss")
    kw = dict(num_boost_round=6, nfold=3, stratified=False, shuffle=False,
              verbose_eval=False, fobj=fobj)
    r_serial = lgb.cv(dict(params), _ds(), **kw)
    r_fused = lgb.cv(dict(params), _ds(), fused=True, **kw)
    assert {k: r_serial[k] for k in r_serial} == \
        {k: r_fused[k] for k in r_fused}


def test_checkpoint_from_batched_run_resumes(tmp_path):
    """A bundle snapshotted mid-batch carries the full solo training
    state, so solo ``train(resume_from=...)`` finishes the run to the
    byte-identical model."""
    p0 = dict(BASE, objective="binary", bagging_fraction=0.7,
              bagging_freq=1)
    p1 = dict(BASE, objective="binary", learning_rate=0.25)
    full = [_solo(p0, Y_BIN, rounds=14), _solo(p1, Y_BIN, rounds=14)]
    snaps = [str(tmp_path / "lane0.txt"), str(tmp_path / "lane1.txt")]
    many = lgb.train_many([dict(p0), dict(p1)], _ds(), num_boost_round=14,
                          snapshot_freq=5, snapshot_outs=snaps)
    assert [b.model_to_string() for b in many] == full
    for p, snap, want in zip((p0, p1), snaps, full):
        resumed = lgb.train(dict(p), _ds(), num_boost_round=14,
                            verbose_eval=False,
                            resume_from=snap + ".ckpt").model_to_string()
        assert resumed == want


def test_expand_param_grid():
    grid = {"objective": "binary", "learning_rate": [0.1, 0.2],
            "num_leaves": [7, 15], "verbosity": -1}
    cfgs = expand_param_grid(grid)
    assert len(cfgs) == 4
    assert sorted((c["learning_rate"], c["num_leaves"]) for c in cfgs) == \
        [(0.1, 7), (0.1, 15), (0.2, 7), (0.2, 15)]
    assert all(c["objective"] == "binary" for c in cfgs)


def test_train_many_grid_dict_matches_solo():
    grid = {"objective": "binary", "verbosity": -1, "num_leaves": 7,
            "learning_rate": [0.1, 0.3]}
    many = lgb.train_many(grid, _ds(), num_boost_round=6)
    for lr, bst in zip((0.1, 0.3), many):
        assert bst.model_to_string() == _solo(
            {"objective": "binary", "verbosity": -1, "num_leaves": 7,
             "learning_rate": lr}, Y_BIN, rounds=6)


def test_structural_grouping():
    """Runtime-varying fields share a trace; structural fields do not;
    chunk-unsupported modes fall to singleton solo groups."""
    shared = _ds()      # shared mode keys on the Dataset's identity
    mk = lambda p: lgb.Booster(params=dict(p, verbosity=-1),
                               train_set=shared).boosting
    b_lr1 = mk(dict(BASE, objective="binary"))
    b_lr2 = mk(dict(BASE, objective="binary", learning_rate=0.3,
                    bagging_fraction=0.5, bagging_freq=1))
    b_leaves = mk(dict(BASE, objective="binary", num_leaves=15))
    b_dart = mk(dict(BASE, objective="binary", boosting="dart"))
    groups = group_boosters([b_lr1, b_lr2, b_leaves, b_dart],
                            stacked=False)
    sizes = sorted(len(g.boosters) for g in groups)
    assert sizes == [1, 1, 2]
    batched = [g for g in groups if len(g.boosters) == 2][0]
    assert batched.key is not None
    assert {id(b) for b in batched.boosters} == {id(b_lr1), id(b_lr2)}
    dart_group = [g for g in groups if g.boosters[0] is b_dart][0]
    assert dart_group.key is None       # solo path, never vmapped


def test_plan_model_batch_budget_degrades():
    from lightgbm_tpu.ops.planner import plan_model_batch
    roomy = plan_model_batch(b_total=8, rows=200_000, features=28,
                             num_bins=64, num_leaves=31,
                             budget_bytes=1 << 34)
    assert roomy.b_chunk == 8 and roomy.num_dispatch_groups == 1
    assert not roomy.degraded
    tight = plan_model_batch(b_total=8, rows=200_000, features=28,
                             num_bins=64, num_leaves=31,
                             budget_bytes=3 * roomy.per_lane_bytes
                             + roomy.shared_bytes)
    assert 1 <= tight.b_chunk < 8
    assert tight.degraded
    assert tight.num_dispatch_groups == -(-8 // tight.b_chunk)
    assert tight.predicted_peak_bytes <= tight.budget_bytes


def test_plan_model_batch_env_override(monkeypatch):
    from lightgbm_tpu.ops.planner import plan_model_batch
    monkeypatch.setenv("LGBM_TPU_MODEL_BATCH", "2")
    plan = plan_model_batch(b_total=8, rows=10_000, features=10,
                            num_bins=64, budget_bytes=1 << 34)
    assert plan.b_chunk == 2 and plan.forced
    monkeypatch.setenv("LGBM_TPU_MODEL_BATCH", "off")
    plan = plan_model_batch(b_total=8, rows=10_000, features=10,
                            num_bins=64, budget_bytes=1 << 34)
    assert plan.b_chunk == 1    # sequential: solo dispatch per booster


def test_model_batch_env_caps_grouping(monkeypatch):
    """LGBM_TPU_MODEL_BATCH=0 must force the solo path end-to-end and
    still produce identical bytes (the degradation arm is not a second
    implementation)."""
    monkeypatch.setenv("LGBM_TPU_MODEL_BATCH", "0")
    p0 = dict(BASE, objective="binary")
    p1 = dict(BASE, objective="binary", learning_rate=0.3)
    many = lgb.train_many([dict(p0), dict(p1)], _ds(), num_boost_round=6)
    monkeypatch.delenv("LGBM_TPU_MODEL_BATCH")
    assert [b.model_to_string() for b in many] == \
        [_solo(p0, Y_BIN, rounds=6), _solo(p1, Y_BIN, rounds=6)]


def test_refresh_many_matches_serial_candidates(tmp_path):
    """Stacked mode: a per-segment family warm-starts from its deployed
    models in one call, each candidate byte-identical to its solo
    train_candidate run."""
    from lightgbm_tpu.lifecycle.refresh import (fresh_dataset,
                                                refresh_many,
                                                train_candidate)
    params = [dict(BASE, objective="binary"),
              dict(BASE, objective="binary", learning_rate=0.2)]
    seg_x = [RNG.randn(500, F), RNG.randn(640, F)]
    seg_y = [(x[:, 0] + 0.3 * x[:, 1] > 0).astype(float) for x in seg_x]
    fresh_x = [x + 0.01 * np.random.RandomState(9).randn(*x.shape)
               for x in seg_x]
    deployed = []
    for p, x, y in zip(params, seg_x, seg_y):
        deployed.append(lgb.train(
            dict(p), lgb.Dataset(x, label=y, free_raw_data=False),
            num_boost_round=5, verbose_eval=False))

    def _fresh_sets():
        return [fresh_dataset(
            lgb.Dataset(x, label=y, free_raw_data=False), fx, y)
            for x, y, fx in zip(seg_x, seg_y, fresh_x)]

    solos = [train_candidate(d, t, dict(p), 6).model_to_string()
             for d, t, p in zip(deployed, _fresh_sets(), params)]
    cands = refresh_many(deployed, _fresh_sets(), params, 6)
    assert [c.model_to_string() for c in cands] == solos


@pytest.mark.fleet
def test_fleet_swaps_sweep_winner():
    """The sweep winner hot-swaps into a serving Fleet through the
    probe-quarantine path and serves its exact raw scores."""
    from lightgbm_tpu.fleet import Fleet
    vs = lgb.Dataset(XV, label=YV_BIN, free_raw_data=False)
    evals = [{}, {}, {}]
    grid = {"objective": "binary", "verbosity": -1, "num_leaves": 7,
            "metric": "binary_logloss", "learning_rate": [0.05, 0.1, 0.2]}
    many = lgb.train_many(grid, _ds(), num_boost_round=8,
                          valid_sets=[vs], evals_results=evals)
    winner = min(
        range(3), key=lambda i: evals[i]["valid_0"]["binary_logloss"][-1])
    fleet = Fleet(max_batch_rows=128)
    fleet.config.deadline_classes["interactive"] = 10_000.0
    try:
        fleet.add_model("seg", many[(winner + 1) % 3], weight=1.0)
        fleet.swap_model("seg", many[winner])   # probe-quarantine path
        q = np.asarray(XV[:16], np.float32)
        assert np.array_equal(
            fleet.predict("seg", q, timeout=60),
            many[winner].predict(q, raw_score=True))
    finally:
        fleet.close()
