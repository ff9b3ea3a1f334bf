"""Fused Pallas histogram→split megakernel (ops/fused.py) parity suite.

The contract (docs/PERF.md "fused megakernel"):

- QUANT/INT paths: per-feature-best tuples (gain/bin/direction/left
  sums) BIT-IDENTICAL to the staged ``build_histogram_int`` /
  ``segment_histogram_int`` + ``quant_rescale_hist`` +
  ``feature_best_splits`` pipeline, across tile/block sizes (incl. a
  ragged last tile) and sibling-subtraction children — integer
  accumulation is associative and the scan body is SHARED
  (``ops.split.numeric_feature_scan``), so equality is exact.
- F32 paths: the fused histogram matches the staged one to f32
  accumulation order (allclose), and the in-kernel scan is bit-identical
  to the shared scan applied to the fused kernel's own histograms —
  pinning the kernel's epilogue exactly; end-to-end the grower produces
  structurally identical trees and the quantized engine run is
  model-text-identical.
- ``hist_method=auto`` elects fused only when the planner proves the
  VMEM arena fits; the staged family is the fallback arm.

Everything here runs in ``interpret=True`` on the tier-1 CPU run; the
``pallas``-marked stress test exercises the compiled kernel on
accelerators.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.dataset import FeatureMeta
from lightgbm_tpu.grower import GrowerConfig, grow_tree
from lightgbm_tpu.grower_rounds import grow_tree_rounds
from lightgbm_tpu.ops import fused as FU
from lightgbm_tpu.ops import histogram as H
from lightgbm_tpu.ops.split import (SplitHyperparams, feature_best_splits,
                                    numeric_feature_scan, quant_rescale_hist)

pytestmark = pytest.mark.pallas


def _meta(B, F):
    return FeatureMeta(
        num_bin=np.full(F, B, np.int32),
        missing_type=np.zeros(F, np.int32),
        default_bin=np.zeros(F, np.int32),
        most_freq_bin=np.zeros(F, np.int32),
        is_categorical=np.zeros(F, bool),
        max_num_bin=B,
    )


def _data(seed=0, n=3000, F=7, B=32, K=4):
    rng = np.random.RandomState(seed)
    binned = jnp.asarray(rng.randint(0, B - 1, (F, n)), jnp.uint8)
    g = jnp.asarray(rng.randn(n), jnp.float32)
    h = jnp.abs(g) + 0.1
    w = jnp.asarray((rng.rand(n) > 0.3).astype(np.float32) * 1.5)
    slot = jnp.asarray(
        np.where(rng.rand(n) < 0.8, rng.randint(0, K, n), K), jnp.int32)
    return binned, g, h, w, slot


def _slot_sums(seg_ref):
    """Per-slot totals from the staged reference hist (channel sums of
    any one feature's bins — here summed over all features / F)."""
    return jnp.stack([seg_ref[:, c].sum((-1, -2)) / seg_ref.shape[-2]
                      for c in range(3)])


_HP = SplitHyperparams(min_data_in_leaf=5)
# tile/block sizes: ragged last tile (3000 % 512 != 0), minimum block,
# and a feature tile that does not divide F
_SHAPES = [(None, None), (4, 128), (3, 256), (8, 512), (1, 128)]


@pytest.mark.parametrize("feat_tile,block_rows", _SHAPES)
def test_fused_quant_bit_identical(feat_tile, block_rows):
    """Quant leaf mode: hist AND per-feature-best tuples bit-identical
    to the staged pipeline for every arena/tile decomposition."""
    n, F, B, K = 3000, 7, 32, 4
    binned, g, h, w, slot = _data(n=n, F=F, B=B, K=K)
    member = w > 0
    gq, hq, gs, hs = H.quantize_gradients(g, h, w, 8, jax.random.PRNGKey(0))
    slot_w = jnp.where(member, slot, K)
    seg_i = H.segment_histogram_int(binned, gq, hq, member, slot, K, B,
                                    levels=H.quant_levels(8))
    seg_f = H.segment_histogram(binned, g, h, w, slot, K, B)
    sums = _slot_sums(seg_f)
    nb = jnp.full((F,), B, jnp.int32)
    zz = jnp.zeros((F,), jnp.int32)
    fh, fb = FU.fused_segment_splits(
        binned, H._vals_t_int(gq, hq, member), slot_w, K, B, sums,
        nb, zz, zz, _HP, quant_scales=(gs, hs),
        feat_tile=feat_tile, block_rows=block_rows)
    assert np.array_equal(np.asarray(fh), np.asarray(seg_i))
    for k in range(K):
        h3 = quant_rescale_hist(seg_i[k], gs, hs, sums[2][k])
        ref = numeric_feature_scan(h3, sums[0][k], sums[1][k], sums[2][k],
                                   nb, zz, zz, _HP)
        for name in ref._fields:
            assert np.array_equal(np.asarray(getattr(fb, name))[k],
                                  np.asarray(getattr(ref, name))), \
                (name, k, feat_tile, block_rows)


@pytest.mark.parametrize("feat_tile,block_rows", [(None, None), (3, 128)])
def test_fused_f32_hist_and_scan_parity(feat_tile, block_rows):
    """F32 leaf mode: fused hist tracks the staged scatter hist to f32
    accumulation order; the in-kernel scan is BIT-identical to the
    shared scan run on the fused kernel's own histograms."""
    n, F, B, K = 3000, 7, 32, 4
    binned, g, h, w, slot = _data(n=n, F=F, B=B, K=K)
    seg_ref = H.segment_histogram(binned, g, h, w, slot, K, B)
    sums = _slot_sums(seg_ref)
    nb = jnp.full((F,), B, jnp.int32)
    zz = jnp.zeros((F,), jnp.int32)
    fh, fb = FU.fused_segment_splits(
        binned, H._vals_t(g, h, w), slot, K, B, sums, nb, zz, zz, _HP,
        feat_tile=feat_tile, block_rows=block_rows)
    np.testing.assert_allclose(np.asarray(fh), np.asarray(seg_ref),
                               rtol=1e-5, atol=2e-3)
    ref = numeric_feature_scan(fh, sums[0], sums[1], sums[2],
                               nb, zz, zz, _HP)
    for name in ref._fields:
        assert np.array_equal(np.asarray(getattr(fb, name)),
                              np.asarray(getattr(ref, name))), name
    # and the tuples agree with the STAGED scan to f32 tolerance
    staged = numeric_feature_scan(seg_ref, sums[0], sums[1], sums[2],
                                  nb, zz, zz, _HP)
    sg, fg = np.asarray(staged.gain), np.asarray(fb.gain)
    finite = np.isfinite(sg) & np.isfinite(fg)
    assert (np.isfinite(sg) == np.isfinite(fg)).all()
    np.testing.assert_allclose(fg[finite], sg[finite], rtol=1e-4)


def test_fused_frontier_sibling_derivation():
    """Parent mode: the in-kernel ``sibling = parent − smaller``
    derivation + both-children scan must equal the staged subtraction
    pipeline — bit-identical in quant, scan-exact in f32."""
    n, F, B, K = 2000, 5, 16, 3
    binned, g, h, w, slot = _data(seed=2, n=n, F=F, B=B, K=K)
    member = w > 0
    gq, hq, gs, hs = H.quantize_gradients(g, h, w, 8, jax.random.PRNGKey(1))
    slot_w = jnp.where(member, slot, K)
    small = H.segment_histogram_int(binned, gq, hq, member, slot_w, K, B,
                                    levels=H.quant_levels(8))
    rng = np.random.RandomState(3)
    # REAL parents: the small child's rows plus extra rows drawn from the
    # currently-dropped lanes, slotted the same way (a genuine histogram
    # — every feature's bins partition the same parent rows, which is
    # what the kernel's per-block count factor relies on)
    extra_slot = jnp.asarray(
        np.where((np.asarray(slot_w) == K) & (rng.rand(n) < 0.5),
                 rng.randint(0, K, n), K), jnp.int32)
    slot_parent = jnp.where(slot_w < K, slot_w, extra_slot)
    parent = H.segment_histogram_int(binned, gq, hq, member, slot_parent,
                                     K, B, levels=H.quant_levels(8))
    small_left = jnp.asarray([True, False, True])
    h_left = jnp.where(small_left[:, None, None, None], small,
                       parent - small)
    h_right = parent - h_left
    nb = jnp.full((F,), B, jnp.int32)
    zz = jnp.zeros((F,), jnp.int32)
    # per-child totals consistent with the child histograms (sums from
    # the integer hists rescaled; counts = member-row counts)
    children = jnp.concatenate([h_left, h_right])
    csums = jnp.stack([
        children[:, 0].sum((-1, -2)).astype(jnp.float32) / F * gs,
        children[:, 1].sum((-1, -2)).astype(jnp.float32) / F * hs,
        children[:, 1, 0, :].sum(-1).astype(jnp.float32)])
    fh, fb = FU.fused_frontier_splits(
        binned, H._vals_t_int(gq, hq, member), slot_w, K, B, csums,
        small_left, parent, nb, zz, zz, _HP, quant_scales=(gs, hs),
        feat_tile=2, block_rows=128)
    assert np.array_equal(np.asarray(fh), np.asarray(small))
    for c in range(2 * K):
        h3 = quant_rescale_hist(children[c], gs, hs, csums[2][c])
        ref = numeric_feature_scan(h3, csums[0][c], csums[1][c],
                                   csums[2][c], nb, zz, zz, _HP)
        for name in ref._fields:
            assert np.array_equal(np.asarray(getattr(fb, name))[c],
                                  np.asarray(getattr(ref, name))), (name, c)


@pytest.mark.parametrize("grower", ["serial", "rounds"])
def test_fused_grower_quant_bit_identical_trees(grower):
    """Both growers' fused arm must produce BIT-identical TreeArrays to
    the staged arm in quantized mode (integer hists + shared scan)."""
    rng = np.random.RandomState(1)
    n, F, B = 4000, 6, 32
    binned = rng.randint(0, B - 1, (n, F)).astype(np.uint8)
    y = np.sin(binned[:, 0] * 0.3) + 0.2 * binned[:, 1] + rng.randn(n) * 0.1
    grad = (-y).astype(np.float32)
    hess = np.ones(n, np.float32)
    mask = np.ones(n, np.float32)
    meta = _meta(B, F)
    gq, hq, gs, hs = H.quantize_gradients(
        jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(mask), 8,
        jax.random.PRNGKey(7))
    cfg = GrowerConfig(num_leaves=15, hp=SplitHyperparams(min_data_in_leaf=5),
                       num_bins=B, round_width=8, quant=True, quant_bins=8)
    fn = grow_tree if grower == "serial" else grow_tree_rounds
    args = (jnp.asarray(binned.T), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(mask), meta)
    t_st, lid_st = fn(*args, cfg, quant_vals=(gq, hq, gs, hs))
    t_fu, lid_fu = fn(*args, cfg._replace(hist_method="fused",
                                          fused_feat_tile=3,
                                          fused_block_rows=128),
                      quant_vals=(gq, hq, gs, hs))
    assert int(t_fu.num_leaves) == 15
    for name in t_st._fields:
        assert np.array_equal(np.asarray(getattr(t_st, name)),
                              np.asarray(getattr(t_fu, name))), name
    assert np.array_equal(np.asarray(lid_st), np.asarray(lid_fu))


@pytest.mark.parametrize("tile", [0, 256])
@pytest.mark.parametrize("grower", ["serial", "rounds"])
def test_fused_grower_f32_structurally_identical(grower, tile):
    """F32 fused arm, untiled AND under planner row tiling (the tile
    caps the kernel's DMA block, refining the f32 dot partition): same
    splits/structure as staged (floats may differ in the last bits —
    different accumulation order, the CPU-vs-GPU class of difference;
    this is why the f32 fused row is absent from test_macro's
    byte-identical tiled==untiled matrix, where only fused_quant rides)."""
    rng = np.random.RandomState(4)
    n, F, B = 4000, 6, 32
    binned = rng.randint(0, B - 1, (n, F)).astype(np.uint8)
    y = np.sin(binned[:, 0] * 0.3) + 0.2 * binned[:, 1] + rng.randn(n) * 0.1
    grad = (-y).astype(np.float32)
    hess = np.ones(n, np.float32)
    mask = np.ones(n, np.float32)
    meta = _meta(B, F)
    cfg = GrowerConfig(num_leaves=15, hp=SplitHyperparams(min_data_in_leaf=5),
                       num_bins=B, round_width=8)
    fn = grow_tree if grower == "serial" else grow_tree_rounds
    args = (jnp.asarray(binned.T), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(mask), meta)
    t_st, lid_st = fn(*args, cfg._replace(tile_rows=tile))
    t_fu, lid_fu = fn(*args, cfg._replace(hist_method="fused",
                                          fused_feat_tile=3,
                                          fused_block_rows=128,
                                          tile_rows=tile))
    for name in ("split_feature", "threshold_bin", "default_left",
                 "left_child", "right_child", "num_leaves"):
        assert np.array_equal(np.asarray(getattr(t_st, name)),
                              np.asarray(getattr(t_fu, name))), name
    assert np.array_equal(np.asarray(lid_st), np.asarray(lid_fu))
    np.testing.assert_allclose(np.asarray(t_fu.leaf_value),
                               np.asarray(t_st.leaf_value),
                               rtol=3e-5, atol=1e-7)


def _wide_problem(seed=1, n=12000, F=6, B=32):
    """Rows whose 255-leaf tree takes rounds of every size: k climbs from
    1 past 64 and falls back to the last few leaves."""
    rng = np.random.RandomState(seed)
    binned = rng.randint(0, B - 1, (n, F)).astype(np.uint8)
    y = (np.sin(binned[:, 0] * 0.3) + 0.2 * binned[:, 1]
         + 0.1 * binned[:, 2] * np.cos(binned[:, 3] * 0.2)
         + rng.randn(n) * 0.3)
    grad = (-y).astype(np.float32)
    return binned, grad, np.ones(n, np.float32), np.ones(n, np.float32)


@pytest.mark.parametrize("family", ["int8", "f32"])
def test_width_election_matches_the_fixed_width(family, monkeypatch):
    """The rounds grower's accumulate pass at the narrowest compiled slot
    width that holds the round's candidates (root: one) against every
    pass at the round cap: bit-identical trees for the integer family
    (associative sums), the same structure for f32 (the row tiles, hence
    the summation order, differ by width).  The stats say which widths
    ran: with the rungs there, the offer follows the commits
    (``grower_rounds.next_offer``); with the cap alone it is the cap."""
    B, F = 32, 6
    binned, grad, hess, mask = _wide_problem(B=B, F=F)
    quant = family == "int8"
    kw = {}
    if quant:
        kw["quant_vals"] = H.quantize_gradients(
            jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(mask), 8,
            jax.random.PRNGKey(7))
    cfg = GrowerConfig(num_leaves=255, num_bins=B, hist_method="fused",
                       hp=SplitHyperparams(min_data_in_leaf=2),
                       quant=quant, quant_bins=8)
    args = (jnp.asarray(binned.T), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(mask), _meta(B, F), cfg)
    assert FU.NARROW_SLOT_WIDTHS == (16, 64)
    t_el, lid_el, st_el = grow_tree_rounds(*args, with_stats=True, **kw)
    monkeypatch.setattr(FU, "NARROW_SLOT_WIDTHS", ())
    t_fx, lid_fx, st_fx = grow_tree_rounds(*args, with_stats=True, **kw)

    assert int(t_el.num_leaves) == 255
    exact = t_el._fields if quant else (
        "split_feature", "threshold_bin", "default_left", "left_child",
        "right_child", "num_leaves")
    for name in exact:
        assert np.array_equal(np.asarray(getattr(t_el, name)),
                              np.asarray(getattr(t_fx, name))), name
    assert np.array_equal(np.asarray(lid_el), np.asarray(lid_fx))
    np.testing.assert_allclose(np.asarray(t_el.leaf_value),
                               np.asarray(t_fx.leaf_value),
                               rtol=3e-5, atol=1e-7)
    rounds, offered, applied, slots, clipped, lanes = (int(v) for v in st_el)
    rounds_fx, offered_fx, applied_fx, slots_fx, clipped_fx, lanes_fx = (
        int(v) for v in st_fx)
    # the candidate scan (the CPU's form) passes over the live lanes alone
    assert (lanes, lanes_fx) == (offered, offered_fx)
    assert applied == applied_fx == 254
    assert slots_fx == 128 * (rounds_fx + 1)        # the root, then rounds
    assert clipped_fx == 0                          # one rung: no offer binds
    # with the rungs, a round offers what the last one committed, not the
    # frontier: fewer candidates built, a clipped round a trip more at most
    assert offered < offered_fx
    assert rounds_fx <= rounds <= rounds_fx + clipped
    # the root ran at 16, the rounds at their offers' rungs: these rows
    # commit ~4 of the ~60 candidates a round at the cap builds, so the
    # offer stays narrow and a round costs a quarter of the cap or less
    assert 16 * rounds <= slots - 16 < 32 * rounds
    assert offered <= slots - 16


def test_root_through_the_kernel_equals_the_staged_root():
    """The root histogram as one accumulate pass at the narrowest width
    (slot 0 = every member row) is ``build_histogram_int``'s, exactly,
    and the arena it sits in is empty elsewhere."""
    B, F, KCAP = 32, 6, 128
    binned, grad, hess, _ = _wide_problem(seed=3, n=5000, B=B, F=F)
    member = np.random.RandomState(4).rand(len(grad)) > 0.25
    gq, hq, _, _ = H.quantize_gradients(
        jnp.asarray(grad), jnp.asarray(hess),
        jnp.asarray(member.astype(np.float32)), 8, jax.random.PRNGKey(9))
    bt = jnp.asarray(binned.T)
    accumulate = FU.frontier_accumulator(
        bt, H._vals_t_int(gq, hq, jnp.asarray(member)), KCAP, B)
    arena, width, _ = accumulate(
        lambda W: (jnp.where(jnp.asarray(member), 0, KCAP), None), 1)
    assert int(width) == 16 and arena.shape == (KCAP, 2, F, B)
    ref = H.build_histogram_int(bt, gq, hq, jnp.asarray(member), B,
                                levels=H.quant_levels(8))
    assert np.array_equal(np.asarray(arena[0]), np.asarray(ref))
    assert not np.asarray(arena[1:]).any()


def _strip_param_lines(text):
    return "\n".join(ln for ln in text.splitlines()
                     if not ln.startswith("[tpu_hist_method"))


def test_fused_engine_quant_model_text_identical():
    """End-to-end ``lgb.train``: quantized fused == staged model text
    (modulo the echoed tpu_hist_method parameter line)."""
    rng = np.random.RandomState(3)
    X = rng.randn(3000, 8).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] ** 2 + rng.randn(3000) * 0.1 > 0.3
         ).astype(np.float32)
    texts = {}
    for method in ("auto", "fused"):
        ds = lgb.Dataset(X, label=y)
        bst = lgb.train(
            dict(objective="binary", num_leaves=15, min_data_in_leaf=5,
                 verbose=-1, tpu_hist_method=method,
                 use_quantized_grad=True, num_grad_quant_bins=8),
            ds, num_boost_round=5)
        texts[method] = _strip_param_lines(bst.model_to_string())
    assert texts["auto"] == texts["fused"]


def test_fused_engine_f32_predictions_close():
    rng = np.random.RandomState(6)
    X = rng.randn(2500, 8).astype(np.float32)
    y = (X[:, 0] - 0.3 * X[:, 2] + rng.randn(2500) * 0.1).astype(np.float32)
    preds = {}
    for method in ("auto", "fused"):
        ds = lgb.Dataset(X, label=y)
        bst = lgb.train(
            dict(objective="regression", num_leaves=15, verbose=-1,
                 tpu_hist_method=method), ds, num_boost_round=5)
        preds[method] = bst.predict(X[:400])
    np.testing.assert_allclose(preds["fused"], preds["auto"],
                               rtol=1e-4, atol=1e-6)


def test_fused_gate_lifted_monotone_and_categorical():
    """Monotone constraints and categorical features now RIDE the fused
    arm (monotone bounds thread into the in-kernel scan; per-category
    stats are the same segment reduction + pick_fused_best's cat merge)
    — the grower config must KEEP hist_method=fused and still train.
    Contexts genuinely outside the arm (extra_trees' per-node
    randomness) still warn/fall back."""
    rng = np.random.RandomState(8)
    X = rng.randn(800, 5).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    bst = lgb.Booster(
        params=dict(objective="binary", num_leaves=7, verbosity=-1,
                    tpu_hist_method="fused",
                    monotone_constraints=[1, 0, 0, 0, 0]),
        train_set=ds)
    assert bst.boosting.grower_cfg.hist_method == "fused"
    for _ in range(3):
        bst.update()
    assert bst.num_trees() == 3
    # categorical rides the fused arm under the rounds grower
    Xc = np.column_stack([rng.randint(0, 6, 800), X[:, 1:]]).astype(
        np.float32)
    ds2 = lgb.Dataset(Xc, label=y, categorical_feature=[0],
                      free_raw_data=False)
    bst2 = lgb.Booster(
        params=dict(objective="binary", num_leaves=7, verbosity=-1,
                    tpu_hist_method="fused", tpu_tree_growth="rounds"),
        train_set=ds2)
    assert bst2.boosting.grower_cfg.hist_method == "fused"
    for _ in range(3):
        bst2.update()
    assert bst2.num_trees() == 3
    # the SERIAL grower keeps its narrower gate for categorical
    bst3 = lgb.Booster(
        params=dict(objective="binary", num_leaves=7, verbosity=-1,
                    tpu_hist_method="fused", tpu_tree_growth="serial"),
        train_set=lgb.Dataset(Xc, label=y, categorical_feature=[0],
                              free_raw_data=False))
    assert bst3.boosting.grower_cfg.hist_method != "fused"
    # extra_trees stays a genuine fallback (per-node randomized bins)
    bst4 = lgb.Booster(
        params=dict(objective="binary", num_leaves=7, verbosity=-1,
                    tpu_hist_method="fused", extra_trees=True),
        train_set=lgb.Dataset(X, label=y, free_raw_data=False))
    assert bst4.boosting.grower_cfg.hist_method != "fused"
    for _ in range(2):
        bst4.update()
    assert bst4.num_trees() == 2


def test_fused_auto_elects_on_accelerator(monkeypatch):
    """Regression: hist_method=auto must reach the planner's fused
    election AS "auto" on accelerators — the measured-kernel probe
    resolving auto to a concrete name first would make the election
    unreachable (the planner only elects for method in {auto, fused})."""
    import lightgbm_tpu.boosting.gbdt as G
    monkeypatch.setattr(G, "on_accelerator", lambda: True)
    # fused_kernel_verified consults ops.fused.on_accelerator (not
    # patched): CPU -> trivially verified, no accelerator probe runs;
    # measured_best_method likewise short-circuits off-accelerator if
    # the election ever declined to it
    rng = np.random.RandomState(12)
    Xa = rng.randn(1500, 6).astype(np.float32)
    ya = (Xa[:, 0] > 0).astype(np.float32)
    ds = lgb.Dataset(Xa, label=ya, free_raw_data=False)
    bst = lgb.Booster(params=dict(objective="binary", num_leaves=7,
                                  verbosity=-1, tpu_hist_method="auto"),
                      train_set=ds)
    plan = bst.boosting.hist_plan
    assert plan.fused, plan.summary()
    assert bst.boosting.grower_cfg.hist_method == "fused"
    assert bst.boosting.grower_cfg.fused_feat_tile == plan.fused_feat_tile


def test_fused_env_gate(monkeypatch):
    """LGBM_TPU_FUSED=0 drops the fused arm: the planner must never
    elect it and explicit hist_method=fused degrades to staged."""
    from lightgbm_tpu.ops.planner import plan_histograms
    monkeypatch.setenv("LGBM_TPU_FUSED", "0")
    plan = plan_histograms(10_000, 8, 64, method="fused", round_width=8,
                           fused_ok=True)
    assert not plan.fused and plan.variant != "fused"
    monkeypatch.delenv("LGBM_TPU_FUSED")
    plan = plan_histograms(10_000, 8, 64, method="fused", round_width=8,
                           fused_ok=True)
    assert plan.fused and plan.variant == "fused"
    assert plan.fused_feat_tile > 0 and plan.fused_block_rows >= 128


def test_fused_planner_vmem_election():
    """plan_fused: fits at sane shapes, degrades feat_tile under a tight
    fake VMEM budget, refuses when nothing fits (auto then keeps the
    staged family)."""
    from lightgbm_tpu.ops.planner import (fused_vmem_bytes, plan_fused,
                                          plan_histograms)
    fp = plan_fused(128, 256, quant=True)
    assert fp is not None
    # monotone in feat_tile
    assert fused_vmem_bytes(128, 256, 8, 512, True) > \
        fused_vmem_bytes(128, 256, 1, 128, True)
    # a 256 KiB budget fits nothing at frontier width 128
    assert plan_fused(128, 256, quant=False, vmem_bytes=256 << 10) is None
    plan = plan_histograms(100_000, 28, 256, method="auto", round_width=128,
                           fused_ok=True, vmem_bytes=256 << 10)
    assert not plan.fused
    assert plan.variant != "fused"
    # the same shape with the real default budget elects fused
    plan2 = plan_histograms(100_000, 28, 256, method="auto",
                            round_width=128, fused_ok=True)
    assert plan2.fused and plan2.variant == "fused"
    assert plan2.fused_vmem_bytes <= plan2.vmem_limit_bytes


def test_fused_apply_plan_threading():
    """apply_plan flips hist_method to fused (with kernel shape) when
    elected, and degrades an explicit fused that cannot fit."""
    from lightgbm_tpu.ops.planner import apply_plan
    cfg = GrowerConfig(num_leaves=15, num_bins=64, round_width=8,
                       hist_method="auto")
    cfg2, plan = apply_plan(cfg, 10_000, 8, fused_ok=True)
    assert plan.fused and cfg2.hist_method == "fused"
    assert cfg2.fused_feat_tile == plan.fused_feat_tile > 0
    cfg3, plan3 = apply_plan(cfg._replace(hist_method="fused"), 10_000, 8,
                             fused_ok=False)
    assert not plan3.fused and cfg3.hist_method == "auto"


def test_fused_sharded_grower_data_keeps_feature_downgrades():
    """DATA sharding now KEEPS hist_method=fused (the rounds grower
    splits the kernel at the collective seam, grower_rounds.py); only
    FEATURE sharding resolves fused to the staged family (the winner
    exchange moves SplitResults, not histograms).  The payload
    accounting helpers stay in lockstep with the writeback."""
    from lightgbm_tpu.parallel.learners import fused_best_payload_bytes
    assert fused_best_payload_bytes(28) == 6 * 28 * 4
    assert FU.hist_scan_traffic_bytes(8, 28, 64) == 8 * 3 * 28 * 64 * 4 * 4
    assert FU.hist_scan_traffic_bytes(8, 28, 64, quant=True) == \
        8 * 2 * 28 * 64 * 4 * 4
    if jax.device_count() >= 2:
        from lightgbm_tpu.parallel.learners import (make_mesh,
                                                    make_sharded_grower,
                                                    shard_dataset)
        rng = np.random.RandomState(0)
        n, F, B = 2048, 5, 16
        binned = rng.randint(0, B - 1, (n, F)).astype(np.uint8)
        g = rng.randn(n).astype(np.float32)
        mesh = make_mesh(2)
        cfg = GrowerConfig(num_leaves=7, num_bins=B,
                           hp=SplitHyperparams(min_data_in_leaf=5),
                           hist_method="fused")
        grower = make_sharded_grower(mesh, _meta(B, F), cfg)
        (bt, gg, hh, mm), _ = shard_dataset(
            mesh, binned, g, np.ones(n, np.float32),
            np.ones(n, np.float32))
        tree, leaf_id = grower(bt, gg, hh, mm)
        assert int(tree.num_leaves) >= 2


def test_fused_seam_halves_equal_combined():
    """The collective seam (grower_rounds.py's sharded arm): accumulate
    → identity reduce → standalone sibling-derive+scan must reproduce
    the single-program ``fused_frontier_splits`` exactly — quant
    BIT-identical (hist and every best-tuple field), f32 scan-exact —
    with monotone constraints and child bounds threaded through both."""
    n, F, B, K = 2500, 6, 16, 3
    binned, g, h, w, slot = _data(seed=5, n=n, F=F, B=B, K=K)
    member = w > 0
    nb = jnp.full((F,), B, jnp.int32)
    zz = jnp.zeros((F,), jnp.int32)
    mono = jnp.asarray([1, -1, 0, 0, 1, 0], jnp.int32)
    NC = 2 * K
    bounds = (jnp.full((NC,), -4.0, jnp.float32),
              jnp.full((NC,), 4.0, jnp.float32))
    small_left = jnp.asarray([True, False, True])

    # quant: parent = small-child rows plus extra rows (a real histogram)
    gq, hq, gs, hs = H.quantize_gradients(g, h, w, 8, jax.random.PRNGKey(3))
    slot_w = jnp.where(member, slot, K)
    rng = np.random.RandomState(6)
    extra = jnp.asarray(
        np.where((np.asarray(slot_w) == K) & (rng.rand(n) < 0.5),
                 rng.randint(0, K, n), K), jnp.int32)
    slot_parent = jnp.where(slot_w < K, slot_w, extra)
    parent = H.segment_histogram_int(binned, gq, hq, member, slot_parent,
                                     K, B, levels=H.quant_levels(8))
    small = H.segment_histogram_int(binned, gq, hq, member, slot_w, K, B,
                                    levels=H.quant_levels(8))
    h_left = jnp.where(small_left[:, None, None, None], small,
                       parent - small)
    children = jnp.concatenate([h_left, parent - h_left])
    csums = jnp.stack([
        children[:, 0].sum((-1, -2)).astype(jnp.float32) / F * gs,
        children[:, 1].sum((-1, -2)).astype(jnp.float32) / F * hs,
        children[:, 1, 0, :].sum(-1).astype(jnp.float32)])
    vals = H._vals_t_int(gq, hq, member)
    fh_c, fb_c = FU.fused_frontier_splits(
        binned, vals, slot_w, K, B, csums, small_left, parent,
        nb, zz, zz, _HP, quant_scales=(gs, hs),
        monotone_constraints=mono, child_bounds=bounds)
    # the seam: local accumulate, (identity) collective, epilogue scan
    from lightgbm_tpu.parallel.collectives import psum_int_tiered
    acc = FU.fused_frontier_accumulate(binned, vals, slot_w, K, B)
    acc = psum_int_tiered(acc, None)          # unsharded degenerate tier
    fb_s = FU.fused_sibling_scan(
        acc, csums, nb, zz, zz, _HP, small_left=small_left,
        parent_hist=parent, quant_scales=(gs, hs),
        monotone_constraints=mono, child_bounds=bounds)
    assert np.array_equal(np.asarray(acc), np.asarray(fh_c))
    assert np.array_equal(np.asarray(acc), np.asarray(small))
    for name in fb_c._fields:
        assert np.array_equal(np.asarray(getattr(fb_s, name)),
                              np.asarray(getattr(fb_c, name))), name

    # f32 twin (same seam, float arena): scan parity is exact because
    # both arms scan the SAME reduced histogram with the shared body
    smallf = H.segment_histogram(binned, g, h, w, slot_w, K, B)
    parentf = H.segment_histogram(binned, g, h, w, slot_parent, K, B)
    h_lf = jnp.where(small_left[:, None, None, None], smallf,
                     parentf - smallf)
    chf = jnp.concatenate([h_lf, parentf - h_lf])
    csf = jnp.stack([chf[:, 0].sum((-1, -2)) / F,
                     chf[:, 1].sum((-1, -2)) / F,
                     chf[:, 2].sum((-1, -2)) / F])
    valsf = H._vals_t(g, h, w)
    fh_cf, fb_cf = FU.fused_frontier_splits(
        binned, valsf, slot_w, K, B, csf, small_left, parentf,
        nb, zz, zz, _HP, monotone_constraints=mono, child_bounds=bounds)
    from lightgbm_tpu.parallel.collectives import psum_tiered
    accf = psum_tiered(FU.fused_frontier_accumulate(
        binned, valsf, slot_w, K, B), None)
    fb_sf = FU.fused_sibling_scan(
        accf, csf, nb, zz, zz, _HP, small_left=small_left,
        parent_hist=parentf, monotone_constraints=mono,
        child_bounds=bounds)
    np.testing.assert_allclose(np.asarray(accf), np.asarray(fh_cf),
                               rtol=1e-5, atol=2e-3)
    sg, fg = np.asarray(fb_cf.gain), np.asarray(fb_sf.gain)
    finite = np.isfinite(sg) & np.isfinite(fg)
    assert (np.isfinite(sg) == np.isfinite(fg)).all()
    np.testing.assert_allclose(fg[finite], sg[finite], rtol=1e-4)


def test_fused_monotone_scan_matches_staged():
    """The lifted monotone gate: the in-kernel scan with constraints +
    child bounds must equal the shared ``numeric_feature_scan`` given
    the same arguments — bit-identical on the kernel's own hists."""
    n, F, B, K = 2000, 5, 16, 3
    binned, g, h, w, slot = _data(seed=7, n=n, F=F, B=B, K=K)
    seg_ref = H.segment_histogram(binned, g, h, w, slot, K, B)
    sums = _slot_sums(seg_ref)
    nb = jnp.full((F,), B, jnp.int32)
    zz = jnp.zeros((F,), jnp.int32)
    mono = jnp.asarray([1, -1, 0, 1, -1], jnp.int32)
    bounds = (jnp.full((K,), -2.0, jnp.float32),
              jnp.full((K,), 2.0, jnp.float32))
    fh, fb = FU.fused_segment_splits(
        binned, H._vals_t(g, h, w), slot, K, B, sums, nb, zz, zz, _HP,
        monotone_constraints=mono, child_bounds=bounds)
    ref = numeric_feature_scan(fh, sums[0], sums[1], sums[2], nb, zz, zz,
                               _HP, monotone_constraints=mono,
                               leaf_output_bounds=bounds)
    for name in ref._fields:
        assert np.array_equal(np.asarray(getattr(fb, name)),
                              np.asarray(getattr(ref, name))), name
    # constraints actually bit: the constrained election must differ
    # from the unconstrained scan somewhere (gain or threshold)
    fb_un = FU.fused_segment_splits(
        binned, H._vals_t(g, h, w), slot, K, B, sums, nb, zz, zz, _HP)[1]
    assert (not np.array_equal(np.asarray(fb_un.gain), np.asarray(fb.gain))
            or not np.array_equal(np.asarray(fb_un.threshold),
                                  np.asarray(fb.threshold)))


def test_histogram_pallas_tile_rows_parity():
    """Satellite: the bin-only Pallas kernel under the tile_rows regime —
    capping the block must leave results equal to the scatter reference,
    and the planner now models a "pallas" variant peak."""
    from lightgbm_tpu.ops.planner import predict_peak_bytes
    rng = np.random.RandomState(3)
    n, F, B = 2579, 5, 17
    binned = jnp.asarray(rng.randint(0, B, (F, n)), jnp.uint8)
    g = jnp.asarray(rng.randn(n), jnp.float32)
    h = jnp.asarray(rng.rand(n), jnp.float32)
    m = jnp.asarray((rng.rand(n) < 0.6), jnp.float32)
    ref = np.asarray(H.build_histogram(binned, g, h, m, B, method="scatter"))
    for tile in (192, 7, 4096):
        got = np.asarray(H.build_histogram(binned, g, h, m, B,
                                           method="pallas", tile_rows=tile))
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    # peak model: the pallas variant's transient is O(tile), far below
    # the scatter variant's lane-padded update buffer
    pal = predict_peak_bytes(1_000_000, 28, 64, variant="pallas",
                             accel=True)[0]
    sca = predict_peak_bytes(1_000_000, 28, 64, variant="scatter",
                             accel=True)[0]
    assert pal < sca


def _numpy_arena(binned, vals, slot, K, B):
    """[K, ch, F, B] slot histograms by ``np.add.at``, rows with
    ``slot == K`` dropped; int64 inside, the values' family outside."""
    F = binned.shape[0]
    ch = vals.shape[0]
    out = np.zeros((K + 1, ch, F, B),
                   np.int64 if vals.dtype == np.int8 else np.float64)
    for f in range(F):
        for c in range(ch):
            np.add.at(out[:, c, f, :], (slot, binned[f]), vals[c])
    return out[:K]


def _operand_case(B, F, K, n=700, bin_dtype=np.uint8, family="int8"):
    rng = np.random.RandomState(B * 1000 + F * 10 + K)
    binned = rng.randint(0, B, (F, n)).astype(bin_dtype)
    binned[:, :9] = B - 1                         # the last bin, often
    if family == "int8":
        vals = rng.randint(-128, 128, (2, n)).astype(np.int8)
        vals[:, :8] = np.array([-128, 127, -1, 1, 0, -127, 126, 2], np.int8)
    else:
        vals = rng.randint(-9, 10, (3, n)).astype(np.float32)
    slot = rng.randint(0, K + 1, n).astype(np.int32)
    slot[:4] = [K, 0, K - 1, K]                   # dropped rows and the ends
    return binned, vals, slot


def _count_calls(monkeypatch, name):
    calls = []
    real = getattr(FU, name)

    def spy(*a, **k):
        calls.append(1)
        return real(*a, **k)

    monkeypatch.setattr(FU, name, spy)
    return calls


# (num_bins, feat_tile, padded bins): _arena_dims pads a feature's bins to
# whole lane groups of its tile, so tiles 1 and 3 only ever see 128
_PACKED_SHAPES = [(30, 8, 32), (63, 8, 64), (128, 8, 128), (64, 1, 128),
                  (100, 3, 128)]


@pytest.mark.parametrize("K", [16, 64, 128])
@pytest.mark.parametrize("B,feat_tile,padded", _PACKED_SHAPES)
def test_packed_operands_give_the_numpy_arena(B, feat_tile, padded, K,
                                              monkeypatch):
    """Four bins to a word, four slots to a word: the int32 arena is the
    NumPy reference's bit for bit, gradients at both signs and at the
    int8 extremes, dropped rows, the last bin, a ragged last feature block
    and a ragged last row tile."""
    F = 2 * feat_tile + 1
    assert FU._arena_dims(K, B, feat_tile, True) == (K, padded)
    assert FU.packed_operands(True, np.uint8, padded)
    binned, vals, slot = _operand_case(B, F, K)
    calls = _count_calls(monkeypatch, "_packed_onehot")
    got = FU.fused_frontier_accumulate(
        jnp.asarray(binned), jnp.asarray(vals), jnp.asarray(slot), K, B,
        feat_tile=feat_tile, block_rows=256, interpret=True)
    assert calls, "the packed form did not engage"
    assert got.dtype == jnp.int32
    assert np.array_equal(np.asarray(got),
                          _numpy_arena(binned, vals, slot, K, B))


@pytest.mark.parametrize("case,B,feat_tile,padded,bin_dtype,family", [
    ("f32_values", 63, 8, 64, np.uint8, "f32"),
    ("two_byte_bins", 63, 8, 64, np.uint16, "int8"),
    ("16_padded_bins", 16, 8, 16, np.uint8, "int8"),
    ("256_padded_bins", 255, 2, 256, np.uint8, "int8"),
])
def test_compare_form_stays_off_the_packed_shapes(case, B, feat_tile, padded,
                                                  bin_dtype, family,
                                                  monkeypatch):
    """``packed_operands`` says no for the f32 family, 2-byte bins and 16
    or 256 padded bins; the kernel then never touches the packed builder
    and its arena is what it was."""
    K, F = 16, 2 * feat_tile + 1
    quant = family == "int8"
    assert FU._arena_dims(K, B, feat_tile, quant)[1] == padded
    assert not FU.packed_operands(quant, bin_dtype, padded)

    def refuse(*a, **k):
        raise AssertionError("packed builder on a shape it is not exact for")

    monkeypatch.setattr(FU, "_packed_onehot", refuse)
    binned, vals, slot = _operand_case(B, F, K, bin_dtype=bin_dtype,
                                       family=family)
    got = np.asarray(FU.fused_frontier_accumulate(
        jnp.asarray(binned), jnp.asarray(vals), jnp.asarray(slot), K, B,
        feat_tile=feat_tile, block_rows=256, interpret=True))
    # small integers in f32 sum exactly, so both families compare equal
    assert np.array_equal(got, _numpy_arena(binned, vals, slot, K, B))


@pytest.mark.parametrize("params,counter", [
    (dict(tpu_hist_method="fused", use_quantized_grad=True, max_bin=63),
     "hist_passes_packed_total"),
    (dict(tpu_hist_method="fused", use_quantized_grad=True, max_bin=255),
     "hist_passes_compared_total"),
    (dict(tpu_hist_method="fused", max_bin=63), "hist_passes_compared_total"),
    (dict(tpu_hist_method="scatter", use_quantized_grad=True, max_bin=63),
     None),
])
def test_accumulate_passes_are_counted_by_their_form(params, counter):
    """A tree's rounds + 1 passes count into the one counter the booster's
    shape elects when its programs are built; neither without the kernel."""
    from lightgbm_tpu.obs.flight import global_flight
    from lightgbm_tpu.obs.metrics import global_registry
    names = ("hist_passes_packed_total", "hist_passes_compared_total")

    def counters():
        c = global_registry.to_dict().get("counters", {})
        return {n: c.get(n, 0) for n in names}

    rng = np.random.RandomState(5)
    X = rng.randn(2000, 9).astype(np.float32)
    y = (X[:, 0] * X[:, 1] + X[:, 2] > 0).astype(np.float32)
    before = counters()
    bst = lgb.train(dict(objective="binary", num_leaves=15, verbose=-1,
                         min_data_in_leaf=5, tpu_tree_growth="rounds",
                         **params),
                    lgb.Dataset(X, label=y), num_boost_round=3)
    trees = [e["args"] for e in global_flight.ring_events()
             if e.get("name") == "grower.tree"][-3:]
    assert len(trees) == 3 == len(bst.models)
    assert [t["it"] for t in trees] == [0, 1, 2]
    grew = {n: v - before[n] for n, v in counters().items()}
    want = dict.fromkeys(names, 0)
    if counter is not None:
        want[counter] = sum(t["rounds"] + 1 for t in trees)
    assert grew == want


@pytest.mark.slow
def test_fused_stress_wide_frontier():
    """Accelerator-shaped stress: full round_width=64 frontier, B=64,
    u16-capable shapes — quant bit-parity at scale (interpret mode on
    CPU; the compiled kernel on accelerators via -m 'pallas and slow')."""
    n, F, B, K = 20_000, 12, 64, 64
    binned, g, h, w, slot = _data(seed=9, n=n, F=F, B=B, K=K)
    member = w > 0
    gq, hq, gs, hs = H.quantize_gradients(g, h, w, 16, jax.random.PRNGKey(2))
    slot_w = jnp.where(member, slot, K)
    seg_i = H.segment_histogram_int(binned, gq, hq, member, slot, K, B,
                                    levels=H.quant_levels(16))
    sums = _slot_sums(H.segment_histogram(binned, g, h, w, slot, K, B))
    nb = jnp.full((F,), B, jnp.int32)
    zz = jnp.zeros((F,), jnp.int32)
    fh, fb = FU.fused_segment_splits(
        binned, H._vals_t_int(gq, hq, member), slot_w, K, B, sums,
        nb, zz, zz, _HP, quant_scales=(gs, hs))
    assert np.array_equal(np.asarray(fh), np.asarray(seg_i))
    assert np.isfinite(np.asarray(fb.left_count)).all()
