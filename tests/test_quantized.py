"""Quantized-gradient training (use_quantized_grad): integer histogram
pipeline, default-mode byte parity, quality, payload accounting.

The hard contracts:

- DEFAULT MODE IS UNTOUCHED: with use_quantized_grad=false the trained
  model files are byte-identical to the pre-quantization codebase
  (goldens recorded from the commit before this feature merged);
- quantized training reaches f32-comparable quality on the synthetic
  suite (the NeurIPS'22 quantized-GBDT result this reproduces);
- the integer kernels agree with each other exactly (int sums have no
  accumulation-order wobble) and the sibling subtraction is exact;
- the data-parallel psum payload accounting matches the dtypes actually
  psum'd (int16 narrowing engages at the static bound).
"""

import hashlib
import json
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb

HERE = os.path.dirname(os.path.abspath(__file__))

RNG = np.random.RandomState(7)
N, F = 1200, 10
X = RNG.randn(N, F)
Y_BIN = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + 0.2 * RNG.randn(N) > 0).astype(float)
Y_MC = np.digitize(X[:, 0] + X[:, 1], [-0.5, 0.5]).astype(float)

GOLDEN_CASES = {
    "gbdt": ({"objective": "binary", "num_leaves": 15,
              "learning_rate": 0.1}, "bin"),
    "bagging": ({"objective": "binary", "num_leaves": 15,
                 "learning_rate": 0.1, "bagging_fraction": 0.7,
                 "bagging_freq": 2, "bagging_seed": 11}, "bin"),
    "goss": ({"objective": "binary", "boosting": "goss", "num_leaves": 15,
              "learning_rate": 0.2}, "bin"),
    "rf": ({"objective": "binary", "boosting": "rf", "num_leaves": 15,
            "bagging_fraction": 0.6, "bagging_freq": 1}, "bin"),
    "multiclass": ({"objective": "multiclass", "num_class": 3,
                    "num_leaves": 7, "learning_rate": 0.1}, "mc"),
}


def _train(params, y, rounds=10, n_rows=None, extra=None):
    p = dict(params)
    p.setdefault("verbosity", -1)
    p.update(extra or {})
    Xt = X if n_rows is None else X[:n_rows]
    yt = y if n_rows is None else y[:n_rows]
    ds = lgb.Dataset(Xt, label=yt, free_raw_data=False)
    return lgb.train(p, ds, num_boost_round=rounds, verbose_eval=False)


# ---------------------------------------------------------------- parity


@pytest.mark.parametrize("case", sorted(GOLDEN_CASES))
def test_default_mode_byte_identical_to_pre_quant_golden(case):
    """Parity guard: with use_quantized_grad absent, training output is
    byte-identical to the recorded pre-quantization goldens (sha256 of
    model text, generated at the commit before the integer pipeline
    merged) across gbdt/bagging/GOSS/RF/multiclass."""
    golden = json.load(open(os.path.join(HERE, "golden",
                                         "default_mode_sha256.json")))
    params, kind = GOLDEN_CASES[case]
    y = Y_MC if kind == "mc" else Y_BIN
    bst = _train(params, y)
    h = hashlib.sha256(bst.model_to_string().encode()).hexdigest()
    assert h == golden[case], (
        f"{case}: default-mode model drifted from the pre-quantization "
        "golden — the quantized code must be inert when disabled")


def _trees_only(model_text):
    """Model text minus the echoed parameters section (which faithfully
    records whatever keys the caller passed, quantization flags included)."""
    return model_text.split("\nparameters:")[0]


def test_quant_off_flag_matches_absent():
    """use_quantized_grad=false must train the identical model as the key
    being absent (only the echoed parameters section may differ)."""
    a = _train({"objective": "binary", "num_leaves": 15}, Y_BIN)
    b = _train({"objective": "binary", "num_leaves": 15,
                "use_quantized_grad": False}, Y_BIN)
    assert _trees_only(a.model_to_string()) == _trees_only(b.model_to_string())


def test_quant_mode_changes_models():
    a = _train({"objective": "binary", "num_leaves": 15}, Y_BIN)
    b = _train({"objective": "binary", "num_leaves": 15,
                "use_quantized_grad": True}, Y_BIN)
    assert a.model_to_string() != b.model_to_string()


def test_quant_deterministic_rerun():
    """Same config + seeds -> byte-identical quantized models (the
    stochastic rounding draws from the per-round key stream)."""
    p = {"objective": "binary", "num_leaves": 15, "use_quantized_grad": True}
    assert _train(p, Y_BIN, rounds=6).model_to_string() == \
        _train(p, Y_BIN, rounds=6).model_to_string()


# ---------------------------------------------------------------- quality


def _auc(y, p):
    o = np.argsort(p)
    r = np.empty_like(o, dtype=float)
    r[o] = np.arange(1, len(p) + 1)
    npos = y.sum()
    return (r[y > 0].sum() - npos * (npos + 1) / 2) / (npos * (len(y) - npos))


QRNG = np.random.RandomState(3)
NQ = 3000
XQ = QRNG.randn(NQ, F)
YQ = (XQ[:, 0] + 0.6 * XQ[:, 1] * XQ[:, 2]
      + 0.4 * QRNG.randn(NQ) > 0).astype(float)
XH = QRNG.randn(1500, F)
YH = (XH[:, 0] + 0.6 * XH[:, 1] * XH[:, 2]
      + 0.4 * QRNG.randn(1500) > 0).astype(float)


def _quality_pair(base, y, extra, rounds=20):
    f32 = lgb.train(dict(base), lgb.Dataset(XQ, label=y,
                                            free_raw_data=False),
                    rounds, verbose_eval=False)
    qnt = lgb.train(dict(base, use_quantized_grad=True, **extra),
                    lgb.Dataset(XQ, label=y, free_raw_data=False),
                    rounds, verbose_eval=False)
    return f32, qnt


# the two non-default variants ride the slow marker: tier-1 keeps one
# binary + one multiclass quality gate, the full suite sweeps the matrix
@pytest.mark.parametrize("extra", [
    {},                                     # defaults: 4 bins, stochastic
    pytest.param({"quant_train_renew_leaf": True},
                 marks=pytest.mark.slow),   # true-f32 leaf renewal
    pytest.param({"num_grad_quant_bins": 16, "stochastic_rounding": False},
                 marks=pytest.mark.slow),
])
def test_quant_quality_binary(extra):
    """Quantized AUC within tolerance of f32 on synthetic binary."""
    base = {"objective": "binary", "num_leaves": 15, "verbosity": -1}
    f32, qnt = _quality_pair(base, YQ, extra)
    a_f = _auc(YH, f32.predict(XH))
    a_q = _auc(YH, qnt.predict(XH))
    assert a_f > 0.9, a_f                 # the suite is learnable at all
    assert a_q > a_f - 0.015, (a_f, a_q, extra)


def test_quant_quality_multiclass():
    ym = np.digitize(XQ[:, 0] + XQ[:, 1], [-0.6, 0.6]).astype(float)
    ymh = np.digitize(XH[:, 0] + XH[:, 1], [-0.6, 0.6]).astype(float)

    def logloss(y, p):
        p = np.clip(p.reshape(-1, 3), 1e-15, 1.0)
        return -np.mean(np.log(p[np.arange(len(y)), y.astype(int)]))

    base = {"objective": "multiclass", "num_class": 3, "num_leaves": 15,
            "verbosity": -1}
    f32, qnt = _quality_pair(base, ym, {}, rounds=15)
    ll_f = logloss(ymh, f32.predict(XH))
    ll_q = logloss(ymh, qnt.predict(XH))
    assert ll_q < ll_f * 1.10 + 0.01, (ll_f, ll_q)


# ----------------------------------------------------------- fallback


@pytest.mark.parametrize("params,blocker", [
    ({"objective": "regression",
      "monotone_constraints": [1, -1] + [0] * (F - 2)},
     "monotone_constraints"),
    ({"objective": "binary", "extra_trees": True}, "extra_trees"),
    ({"objective": "binary", "cegb_penalty_split": 0.1}, "CEGB"),
    ({"objective": "binary", "boosting": "dart"}, "boosting=dart"),
])
def test_quant_fallback_warns_and_trains_f32(params, blocker, capsys):
    y = Y_BIN if params["objective"] == "binary" else Y_BIN
    p = dict(params, num_leaves=15, use_quantized_grad=True, verbosity=1)
    bst = _train(p, y, rounds=3)
    assert bst.num_trees() >= 3
    assert bst.boosting._quant_on is False
    cap = capsys.readouterr()
    out = cap.out + cap.err
    assert "use_quantized_grad" in out and blocker in out
    # ...and the fallback output equals plain f32 training byte-for-byte
    # (modulo the echoed parameters section, which records the flags)
    p2 = dict(params, num_leaves=15, verbosity=-1)
    assert _trees_only(bst.model_to_string()) == \
        _trees_only(_train(p2, y, rounds=3).model_to_string())


def test_quant_bins_validation():
    with pytest.raises(Exception, match="num_grad_quant_bins"):
        _train({"objective": "binary", "use_quantized_grad": True,
                "num_grad_quant_bins": 256}, Y_BIN, rounds=1)


def test_quant_aliases():
    from lightgbm_tpu.config import Config
    cfg = Config.from_params({"quantized_grad": True, "grad_quant_bins": 8})
    assert cfg.use_quantized_grad is True
    assert cfg.num_grad_quant_bins == 8


# ----------------------------------------------------------- kernels


def _synth_hist_inputs(n=4096, f=6, B=32, bins=8, seed=0):
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import quantize_gradients
    import jax
    rng = np.random.RandomState(seed)
    binned_t = jnp.asarray(rng.randint(0, B - 1, (f, n)), jnp.uint8)
    grad = jnp.asarray(rng.randn(n), jnp.float32)
    hess = jnp.abs(jnp.asarray(rng.randn(n), jnp.float32)) + 0.1
    w = jnp.asarray((rng.rand(n) > 0.2).astype(np.float32))
    gq, hq, gs, hs = quantize_gradients(grad, hess, w, bins,
                                        jax.random.PRNGKey(1))
    return binned_t, gq, hq, w, gs, hs


def test_int_kernels_agree_exactly():
    """matmul_int8 and scatter_int produce IDENTICAL int32 histograms
    (no accumulation-order tolerance needed — that is the point)."""
    from lightgbm_tpu.ops.histogram import build_histogram_int, quant_levels
    binned_t, gq, hq, w, _, _ = _synth_hist_inputs()
    B, bins = 32, 8
    hm = build_histogram_int(binned_t, gq, hq, w > 0, B,
                             method="matmul_int8")
    hs_ = build_histogram_int(binned_t, gq, hq, w > 0, B,
                              method="scatter_int",
                              levels=quant_levels(bins))
    assert hm.dtype == np.int32
    np.testing.assert_array_equal(np.asarray(hm), np.asarray(hs_))


def test_int_histogram_matches_quantized_reference():
    """The integer histogram equals a plain numpy accumulation of the
    quantized values — and rescaling tracks the f32 histogram."""
    from lightgbm_tpu.ops.histogram import build_histogram_int
    binned_t, gq, hq, w, gs, hs = _synth_hist_inputs()
    B = 32
    hist = np.asarray(build_histogram_int(binned_t, gq, hq, w > 0, B,
                                          method="matmul_int8"))
    bt = np.asarray(binned_t)
    gqn = np.asarray(gq, np.int64)
    hqn = np.asarray(hq, np.int64)
    member = np.asarray(w) > 0
    for f_i in range(bt.shape[0]):
        ref_g = np.bincount(bt[f_i][member], weights=gqn[member],
                            minlength=B)
        ref_h = np.bincount(bt[f_i][member], weights=hqn[member],
                            minlength=B)
        np.testing.assert_array_equal(hist[0, f_i], ref_g)
        np.testing.assert_array_equal(hist[1, f_i], ref_h)


def test_int_subtraction_exact():
    """Sibling trick in integer domain: parent - child == independently
    built sibling, EXACTLY (the f32 path can only claim this to rounding)."""
    from lightgbm_tpu.ops.histogram import build_histogram_int
    import jax.numpy as jnp
    binned_t, gq, hq, w, _, _ = _synth_hist_inputs()
    B = 32
    n = binned_t.shape[1]
    left = jnp.asarray(np.random.RandomState(5).rand(n) < 0.37)
    member = w > 0
    parent = build_histogram_int(binned_t, gq, hq, member, B,
                                 method="matmul_int8")
    child = build_histogram_int(binned_t, gq, hq, member & left, B,
                                method="matmul_int8")
    sib = build_histogram_int(binned_t, gq, hq, member & ~left, B,
                              method="matmul_int8")
    np.testing.assert_array_equal(np.asarray(parent - child),
                                  np.asarray(sib))


def test_segment_int_kernels_agree():
    """Scatter, sorted-arena and slot-expanded integer segment kernels
    produce identical [S, 2, F, B] histograms."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import (quant_levels,
                                            segment_histogram_expanded_int,
                                            segment_histogram_int,
                                            segment_histogram_sorted_int)
    binned_t, gq, hq, w, _, _ = _synth_hist_inputs()
    B, S = 32, 5
    n = binned_t.shape[1]
    slot = jnp.asarray(np.random.RandomState(9).randint(0, S + 1, n))
    member = w > 0
    ref = np.asarray(segment_histogram_int(binned_t, gq, hq, member, slot,
                                           S, B, levels=quant_levels(8)))
    slot_w = jnp.where(member, slot, S)
    srt = np.asarray(segment_histogram_sorted_int(binned_t, gq, hq, slot_w,
                                                  S, B))
    np.testing.assert_array_equal(ref, srt)
    exp = np.asarray(segment_histogram_expanded_int(binned_t, gq, hq,
                                                    member, slot, B,
                                                    live_cap=S))
    np.testing.assert_array_equal(ref, exp)


def test_quantize_gradients_properties():
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import quantize_gradients
    rng = np.random.RandomState(0)
    g = jnp.asarray(rng.randn(5000), jnp.float32)
    h = jnp.abs(jnp.asarray(rng.randn(5000), jnp.float32))
    w = jnp.asarray((rng.rand(5000) > 0.3).astype(np.float32)) * 2.0
    gq, hq, gs, hs = quantize_gradients(g, h, w, 8, jax.random.PRNGKey(0))
    gqn, hqn = np.asarray(gq, np.int64), np.asarray(hq, np.int64)
    assert gqn.min() >= -3 and gqn.max() <= 3      # bins//2 - 1 = 3
    assert hqn.min() >= 0 and hqn.max() <= 7       # bins - 1
    wn = np.asarray(w)
    assert (gqn[wn == 0] == 0).all() and (hqn[wn == 0] == 0).all()
    # stochastic rounding is unbiased: the rescaled sums track the
    # weighted f32 sums within a few-sigma CLT band
    gw = np.asarray(g) * wn
    err = abs(float(gqn.sum()) * float(gs) - gw.sum())
    assert err < 6.0 * float(gs) * np.sqrt(5000), err


def test_quant_psum_payload_accounting():
    from lightgbm_tpu.ops.histogram import (hist_payload_bytes,
                                            quant_psum_narrow)
    # f32: 3 channels x 4 bytes
    assert hist_payload_bytes(28, 64) == 3 * 28 * 64 * 4
    # int32 channels at HIGGS scale (bound exceeds int16)
    assert hist_payload_bytes(28, 64, 11_000_000, 4) == 2 * 28 * 64 * 4
    # int16 narrowing at small bound: rows * (bins-1) < 2^15
    assert quant_psum_narrow(1200, 4)
    assert not quant_psum_narrow(11_000_000, 4)
    assert hist_payload_bytes(28, 64, 1200, 4) == 2 * 28 * 64 * 2
    # payload always shrinks vs f32
    assert hist_payload_bytes(28, 64, 11_000_000, 4) < \
        hist_payload_bytes(28, 64)


def test_resolve_hist_method_quant(monkeypatch):
    from lightgbm_tpu.ops import histogram as H
    assert H.resolve_hist_method("auto", quantized=True) == "scatter_int"
    monkeypatch.setattr(H, "on_accelerator", lambda: True)
    # int32-accumulation matmul kernel selected on accelerator
    assert H.resolve_hist_method("auto", quantized=True) == "matmul_int8"
    assert H.resolve_hist_method("matmul", quantized=True) == "matmul_int8"
    assert H.resolve_hist_method("scatter", quantized=True) == "scatter_int"


# ----------------------------------------------------------- state


def test_quant_scales_in_checkpoint_state():
    p = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
         "use_quantized_grad": True}
    ds = lgb.Dataset(X, label=Y_BIN, free_raw_data=False)
    bst = lgb.Booster(params=p, train_set=ds)
    bst.update()
    st = bst.boosting.capture_state()
    qs = st["quant_scales"]
    assert qs is not None and qs.shape == (1, 2) and (qs > 0).all()


def test_quant_checkpoint_resume_bit_parity(tmp_path):
    """Mid-stream checkpoint resume reproduces the byte-identical
    quantized model (the SR key streams replay by absolute iteration)."""
    snap = str(tmp_path / "m.txt")
    P = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
         "use_quantized_grad": True, "bagging_fraction": 0.7,
         "bagging_freq": 1}

    def run(resume=None):
        ds = lgb.Dataset(X, label=Y_BIN, free_raw_data=False)
        return lgb.train(P, ds, 9, verbose_eval=False, snapshot_freq=4,
                         snapshot_out=snap,
                         resume_from=resume).model_to_string()

    full = run()
    assert run(resume=snap + ".ckpt") == full


def test_quant_sharded_data_parallel():
    """Quantized training over the 8-device mesh: integer histogram
    psums (int16-narrowed at this scale), chunked == per-iteration."""
    import jax
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")

    def train(chunks):
        p = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
             "use_quantized_grad": True, "tree_learner": "data"}
        ds = lgb.Dataset(X, label=Y_BIN, free_raw_data=False)
        b = lgb.Booster(params=p, train_set=ds)
        for c in chunks:
            b.update_chunk(c) if c > 1 else b.update()
        return b.model_to_string()

    assert train([4, 2]) == train([1] * 6)


def test_quant_voting_parallel():
    import jax
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    p = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
         "use_quantized_grad": True, "tree_learner": "voting", "top_k": 5}
    ds = lgb.Dataset(X, label=Y_BIN, free_raw_data=False)
    bst = lgb.train(p, ds, 4, verbose_eval=False)
    pred = bst.predict(X)
    assert np.isfinite(pred).all()
    assert _auc(Y_BIN, pred) > 0.75


def test_quant_rounds_grower_sorted_arena(monkeypatch):
    """The accelerator-shaped rounds grower path (sorted int arena +
    expanded int pass + quant packed records) trains on CPU via the
    LGBM_TPU_SEGHIST=sorted override."""
    monkeypatch.setenv("LGBM_TPU_SEGHIST", "sorted")
    p = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
         "use_quantized_grad": True, "tpu_tree_growth": "rounds"}
    ds = lgb.Dataset(X, label=Y_BIN, free_raw_data=False)
    bst = lgb.train(p, ds, 4, verbose_eval=False)
    pred = bst.predict(X)
    assert np.isfinite(pred).all()
    assert _auc(Y_BIN, pred) > 0.8


def test_compacted_int_caps_ladder():
    """The bucketed-capacity integer gather path (lax.switch over the
    static cap ladder) matches the full masked pass for a sparse member
    set — training only reaches the ladder above ~16k rows, so cover the
    switch branches directly."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import (build_histogram_int,
                                            compacted_histogram_int,
                                            quant_levels)
    binned_t, gq, hq, w, _, _ = _synth_hist_inputs(n=6000)
    B = 32
    n = binned_t.shape[1]
    member = jnp.asarray(np.random.RandomState(2).rand(n) < 0.05)
    caps = [8192, 2048, 512]
    got = np.asarray(compacted_histogram_int(
        binned_t, gq, hq, w, member, B, caps, method="scatter_int",
        levels=quant_levels(8)))
    want = np.asarray(build_histogram_int(
        binned_t, gq, hq, member & (w > 0), B, method="scatter_int",
        levels=quant_levels(8)))
    np.testing.assert_array_equal(got, want)
