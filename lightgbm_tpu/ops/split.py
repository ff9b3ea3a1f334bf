"""Vectorized best-split search over histograms.

TPU-native replacement for LightGBM's per-feature threshold scan
(reference: src/treelearner/feature_histogram.hpp:782
FindBestThresholdSequentially and the dispatch at :157-200).  Instead of a
sequential scan with a template zoo, both missing-direction variants are
evaluated for EVERY (feature, threshold) cell at once on the VPU:
prefix-sums along the bin axis + a masked argmax.  Semantics preserved:

- gain  = GetLeafGain(GL,HL) + GetLeafGain(GR,HR) with L1 thresholding
  (feature_histogram.hpp:669-780), compared against
  parent_gain + min_gain_to_split.
- missing direction: the missing mass (NaN bin ``num_bin-1`` for
  MissingType::NaN, the zero/default bin for MissingType::Zero) is excluded
  from the threshold prefix and assigned to the default side; both
  directions are scanned, reverse (missing->left) winning ties — matching
  the reference's scan composition order (reverse runs first, later scans
  must be strictly better).
- epsilons: child hessians get +kEpsilon, parent +2*kEpsilon
  (feature_histogram.hpp:91, :796).

Deliberate deviation: min_data_in_leaf uses EXACT per-bin counts (third
histogram channel) rather than the reference's hessian-estimated counts
(``Common::RoundInt(hess * cnt_factor)``, feature_histogram.hpp:813); exact
counts are free here and strictly more faithful to the parameter's meaning.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..binning import MissingType

K_EPSILON = 1e-15
K_MIN_SCORE = -jnp.inf


class SplitHyperparams(NamedTuple):
    """Static split hyper-parameters (trace-time constants)."""

    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_gain_to_split: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    max_delta_step: float = 0.0
    max_cat_threshold: int = 32
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    max_cat_to_onehot: int = 4
    min_data_per_group: int = 100
    extra_trees: bool = False


class SplitResult(NamedTuple):
    """Per-leaf best split; all fields [*] or scalar, f32/i32/bool."""

    gain: jax.Array          # shifted gain (already minus parent gain & min_gain)
    feature: jax.Array       # i32
    threshold: jax.Array     # i32 bin threshold (numerical) or category set size
    default_left: jax.Array  # bool
    left_sum_grad: jax.Array
    left_sum_hess: jax.Array
    left_count: jax.Array    # f32 (exact count)
    right_sum_grad: jax.Array
    right_sum_hess: jax.Array
    right_count: jax.Array
    is_categorical: jax.Array  # bool
    cat_bitset: jax.Array    # [MAX_CAT_WORDS] u32: categories (bins) going LEFT


MAX_CAT_WORDS = 8  # supports bitsets over up to 256 bins


def threshold_l1(s: jax.Array, l1: float) -> jax.Array:
    """reference: ThresholdL1 (feature_histogram.hpp:661)."""
    if l1 <= 0.0:
        return s
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def leaf_gain(g: jax.Array, h: jax.Array, l1: float, l2: float) -> jax.Array:
    """reference: GetLeafGain (feature_histogram.hpp:712)."""
    sg = threshold_l1(g, l1)
    return (sg * sg) / (h + l2)


def leaf_output(g: jax.Array, h: jax.Array, l1: float, l2: float,
                max_delta_step: float = 0.0) -> jax.Array:
    """reference: CalculateSplittedLeafOutput (feature_histogram.hpp:669)."""
    out = -threshold_l1(g, l1) / (h + l2)
    if max_delta_step > 0.0:
        out = jnp.clip(out, -max_delta_step, max_delta_step)
    return out


def leaf_gain_given_output(g: jax.Array, h: jax.Array, l1: float, l2: float,
                           out: jax.Array) -> jax.Array:
    """Gain of a leaf forced to emit ``out`` (e.g. clamped by monotone
    bounds).  reference: GetLeafGainGivenOutput (feature_histogram.hpp:760)."""
    sg = threshold_l1(g, l1)
    return -(2.0 * sg * out + (h + l2) * out * out)


class PerFeatureBest(NamedTuple):
    """Per-feature best split candidates (all arrays [F])."""

    gain: jax.Array
    threshold: jax.Array
    default_left: jax.Array
    left_sum_grad: jax.Array
    left_sum_hess: jax.Array
    left_count: jax.Array
    is_categorical: jax.Array
    cat_bitset: jax.Array     # [F, MAX_CAT_WORDS]


class NumericFeatureBest(NamedTuple):
    """Per-feature best NUMERIC split candidates ([..., F] arrays).

    ``gain`` is already shifted by the leaf's ``parent_gain +
    min_gain_to_split`` (same convention as ``PerFeatureBest.gain``)."""

    gain: jax.Array
    threshold: jax.Array     # i32 bin threshold
    default_left: jax.Array  # bool
    left_sum_grad: jax.Array
    left_sum_hess: jax.Array
    left_count: jax.Array


def numeric_feature_scan(
    hist: jax.Array,            # [..., 3, F, B] (grad, hess, count leading)
    sum_grad: jax.Array,        # [...] leaf totals (broadcast against hist)
    sum_hess: jax.Array,
    num_data: jax.Array,
    num_bin: jax.Array,         # [F] i32 static-shaped per-feature bin counts
    missing_type: jax.Array,    # [F] i32
    default_bin: jax.Array,     # [F] i32
    hp: SplitHyperparams,
    monotone_constraints: Optional[jax.Array] = None,  # [F] i32 in {-1,0,1}
    leaf_output_bounds: Optional[tuple] = None,        # (min, max) scalars
    rand_t_u: Optional[jax.Array] = None,  # [F] uniforms: extra-trees random
                                           # thresholds (one per feature)
) -> NumericFeatureBest:
    """The numeric-feature threshold scan of ``feature_best_splits``,
    extracted as ONE shared body: prefix-sums along the bin axis, both
    missing-direction sweeps, L1/L2-thresholded gains, masked argmax.

    Shared verbatim by the staged pipeline (``feature_best_splits`` below)
    and by the fused Pallas megakernel's in-kernel epilogue
    (``ops/fused.py``), so the two pipelines' per-feature-best tuples are
    bit-identical BY CONSTRUCTION given bit-identical histograms — the
    seam the fused == staged parity suite pins.  Supports arbitrary
    leading batch axes on ``hist`` / the scalar totals (the fused kernel
    scans a whole frontier of children at once); every op is written
    batch-agnostic (negative axes, ``broadcasted_iota``) and produces
    values bit-identical to the historical unbatched code.
    """
    F, B = hist.shape[-2], hist.shape[-1]
    bins = lax.broadcasted_iota(jnp.int32, (F, B), 1)           # [F, B]

    num_data = jnp.asarray(num_data).astype(jnp.float32)
    sum_grad = jnp.asarray(sum_grad)
    sum_hess = jnp.asarray(sum_hess)
    parent_gain = leaf_gain(sum_grad, sum_hess + 2 * K_EPSILON,
                            hp.lambda_l1, hp.lambda_l2)         # [...]
    min_gain_shift = parent_gain + hp.min_gain_to_split
    mgs = min_gain_shift[..., None, None]

    # missing bin per feature: NaN bin = num_bin-1, Zero bin = default_bin.
    # Features WITHOUT a dedicated missing direction (missing_type None, or
    # num_bin <= 2 — the reference's dispatch guard) run the plain scan
    # with the missing bin treated as an ordinary bin
    # (feature_histogram.hpp:96-258: the two-direction template is only
    # instantiated for num_bin > 2 with missing handling).
    has_missing_dir = (missing_type != MissingType.NONE) & (num_bin > 2)
    miss_bin = jnp.where(
        missing_type == MissingType.NAN, num_bin - 1,
        jnp.where(missing_type == MissingType.ZERO, default_bin, -1),
    )  # [F]; -1 = no missing handling
    miss_bin = jnp.where(has_missing_dir, miss_bin, -1)
    is_missing_bin = bins == miss_bin[:, None]                  # [F, B]
    valid_bin = bins < num_bin[:, None]                         # [F, B]

    drop = is_missing_bin | ~valid_bin                          # [F, B]
    hist_nm = jnp.where(drop, 0.0, hist)                        # [..., 3, F, B]
    prefix = jnp.cumsum(hist_nm, axis=-1)
    miss = jnp.where(is_missing_bin, hist, 0.0).sum(axis=-1)    # [..., 3, F]

    total_g = sum_grad[..., None, None]
    total_h = (sum_hess + 2 * K_EPSILON)[..., None, None]
    nd = num_data[..., None, None]

    def eval_dir(missing_left: jax.Array):
        # left sums at threshold t (non-missing bins <= t, missing by dir)
        lg = prefix[..., 0, :, :] + jnp.where(missing_left,
                                              miss[..., 0, :, None], 0.0)
        lh = prefix[..., 1, :, :] + jnp.where(missing_left,
                                              miss[..., 1, :, None], 0.0) \
            + K_EPSILON
        lc = prefix[..., 2, :, :] + jnp.where(missing_left,
                                              miss[..., 2, :, None], 0.0)
        rg = total_g - lg
        rh = total_h - lh
        rc = nd - lc
        ok = (
            (lc >= hp.min_data_in_leaf) & (rc >= hp.min_data_in_leaf)
            & (lh >= hp.min_sum_hessian_in_leaf)
            & (rh >= hp.min_sum_hessian_in_leaf)
        )
        if monotone_constraints is None:
            gain = leaf_gain(lg, lh, hp.lambda_l1, hp.lambda_l2) + \
                leaf_gain(rg, rh, hp.lambda_l1, hp.lambda_l2)
        else:
            # monotone mode (reference: GetSplitGains USE_MC,
            # feature_histogram.hpp:714-747): child outputs are clamped
            # to the leaf's propagated bounds, the gain is computed FROM
            # the clamped outputs, and the split is rejected when the
            # clamped outputs violate the feature's constraint direction.
            lo = leaf_output(lg, lh, hp.lambda_l1, hp.lambda_l2,
                             hp.max_delta_step)
            ro = leaf_output(rg, rh, hp.lambda_l1, hp.lambda_l2,
                             hp.max_delta_step)
            if leaf_output_bounds is not None:
                lob = jnp.asarray(leaf_output_bounds[0])[..., None, None]
                upb = jnp.asarray(leaf_output_bounds[1])[..., None, None]
                lo = jnp.clip(lo, lob, upb)
                ro = jnp.clip(ro, lob, upb)
            mc = monotone_constraints[:, None]
            bad = ((mc > 0) & (lo > ro)) | ((mc < 0) & (lo < ro))
            gain = leaf_gain_given_output(lg, lh, hp.lambda_l1,
                                          hp.lambda_l2, lo) + \
                leaf_gain_given_output(rg, rh, hp.lambda_l1, hp.lambda_l2, ro)
            gain = jnp.where(bad, K_MIN_SCORE, gain)
        gain = jnp.where(ok & (gain > mgs), gain, K_MIN_SCORE)
        return gain, (lg, lh - K_EPSILON, lc)

    # valid thresholds: t in [0, num_bin-2], t not the missing bin when Zero
    # thresholds stop one short of the last scannable bin; with a dedicated
    # NaN bin the last REAL bin is num_bin-2, so t <= num_bin-3 (reference
    # scan bound: num_bin - 2 - NA_AS_MISSING, feature_histogram.hpp:782+)
    na_dir = has_missing_dir & (missing_type == MissingType.NAN)
    t_valid = (bins <
               (num_bin - 1 - na_dir.astype(jnp.int32))[:, None]) & valid_bin
    t_valid &= ~((missing_type[:, None] == MissingType.ZERO) & is_missing_bin)
    if rand_t_u is not None:
        rand_t = jnp.floor(
            rand_t_u * jnp.maximum(num_bin - 1, 1).astype(jnp.float32)
        ).astype(jnp.int32)
        t_valid &= bins == rand_t[:, None]

    gain_r, left_r = eval_dir(jnp.zeros((F, 1), dtype=bool))   # missing -> R
    gain_l, left_l = eval_dir(jnp.ones((F, 1), dtype=bool))    # missing -> L
    gain_r = jnp.where(t_valid, gain_r, K_MIN_SCORE)
    gain_l = jnp.where(t_valid, gain_l, K_MIN_SCORE)
    # features without missing handling: reference runs the REVERSE scan only
    # (missing mass is zero so directions agree); default_left = True there.
    gain_r = jnp.where(has_missing_dir[:, None], gain_r, K_MIN_SCORE)

    # reverse (missing->left) wins ties; within a direction larger threshold
    # wins for reverse, smaller for forward (reference iteration order).
    def argmax_last(x):
        rev = x[..., ::-1]
        idx = jnp.argmax(rev, axis=-1)
        t = x.shape[-1] - 1 - idx
        return t, jnp.take_along_axis(x, t[..., None], -1)[..., 0]

    t_l, g_l = argmax_last(gain_l)                 # [..., F]
    t_r_idx = jnp.argmax(gain_r, axis=-1)
    g_r = jnp.take_along_axis(gain_r, t_r_idx[..., None], -1)[..., 0]
    use_left = g_l >= g_r                          # ties -> missing-left
    num_gain = jnp.where(use_left, g_l, g_r)
    num_thr = jnp.where(use_left, t_l, t_r_idx).astype(jnp.int32)

    def pick(a, b):
        return jnp.where(
            use_left,
            jnp.take_along_axis(a, t_l[..., None], -1)[..., 0],
            jnp.take_along_axis(b, t_r_idx[..., None], -1)[..., 0])

    num_lg = pick(left_l[0], left_r[0])
    num_lh = pick(left_l[1], left_r[1])
    num_lc = pick(left_l[2], left_r[2])
    # plain-scan features: the reference emits default_left=false for
    # NaN-type (so NaN-bin rows follow the ordinary bin comparison at the
    # partition) and default_left=true otherwise (feature_histogram.hpp:
    # 89,200)
    num_dl = jnp.where(has_missing_dir, use_left,
                       missing_type != MissingType.NAN)
    num_gain = jnp.where(jnp.isfinite(num_gain),
                         num_gain - min_gain_shift[..., None], K_MIN_SCORE)
    return NumericFeatureBest(
        gain=num_gain, threshold=num_thr, default_left=num_dl,
        left_sum_grad=num_lg, left_sum_hess=num_lh, left_count=num_lc)


def feature_best_splits(
    hist: jax.Array,            # [3, F, B] (grad, hess, count leading)
    sum_grad: jax.Array,        # scalar: leaf totals
    sum_hess: jax.Array,
    num_data: jax.Array,        # scalar f32/i32: leaf row count
    num_bin: jax.Array,         # [F] i32 static-shaped per-feature bin counts
    missing_type: jax.Array,    # [F] i32
    default_bin: jax.Array,     # [F] i32
    is_categorical: jax.Array,  # [F] bool
    hp: SplitHyperparams,
    feature_mask: Optional[jax.Array] = None,  # [F] f32/bool col-sampling mask
    monotone_constraints: Optional[jax.Array] = None,  # [F] i32 in {-1,0,1}
    leaf_output_bounds: Optional[tuple] = None,        # (min, max) scalars
    has_categorical: bool = False,             # static: any categorical feature
    extra_rand_u: Optional[jax.Array] = None,  # [F, 2] uniforms: extra-trees
    gain_penalty: Optional[jax.Array] = None,  # [F] CEGB gain penalty
) -> PerFeatureBest:
    """Best split PER FEATURE of one leaf. Fully vectorized [F, B].

    The split into per-feature candidates + global argmax (see
    ``best_split_for_leaf``) mirrors the reference's two stages and is the
    seam the voting-parallel learner needs: local per-feature gains drive
    the vote (voting_parallel_tree_learner.cpp:264-305) before any
    histogram is exchanged.

    extra_trees (reference: USE_RAND dispatch, feature_histogram.hpp:96-127):
    when ``hp.extra_trees`` and ``extra_rand_u`` is given, each feature
    evaluates exactly ONE random threshold (numerical: a random bin in
    [0, num_bin-2]; categorical: a random one-hot category / sorted-scan
    position) instead of the full scan.
    """
    _, F, B = hist.shape
    bins = jnp.arange(B, dtype=jnp.int32)
    use_rand = hp.extra_trees and extra_rand_u is not None

    num_data = num_data.astype(jnp.float32)
    valid_bin = bins[None, :] < num_bin[:, None]                    # [F, B]

    # ---- numerical features ------------------------------------------------
    # the shared scan body (also the fused Pallas megakernel's in-kernel
    # epilogue, ops/fused.py — ONE implementation so the staged and fused
    # per-feature-best tuples can never drift); returns SHIFTED gains
    nf = numeric_feature_scan(
        hist, sum_grad, sum_hess, num_data, num_bin, missing_type,
        default_bin, hp, monotone_constraints=monotone_constraints,
        leaf_output_bounds=leaf_output_bounds,
        rand_t_u=(extra_rand_u[:, 0] if use_rand else None))
    num_gain, num_thr, num_dl = nf.gain, nf.threshold, nf.default_left
    num_lg, num_lh, num_lc = (nf.left_sum_grad, nf.left_sum_hess,
                              nf.left_count)

    # ---- categorical features ---------------------------------------------
    cat = None
    if has_categorical:
        with jax.named_scope("lgbm.cat_scan"):
            cat = _best_categorical(
                hist, sum_grad, sum_hess, num_data, num_bin, valid_bin, hp,
                rand_u=(extra_rand_u[:, 1] if use_rand else None),
                missing_type=missing_type)

    # each feature's gain is shifted by ITS OWN parent gain (categorical
    # uses l2+cat_l2, reference feature_histogram.hpp:268-276) so the
    # cross-feature argmax compares the same quantity the reference does
    # (the numeric gains come back from the scan already shifted)
    if cat is not None:
        c_gain, c_thr, c_lg, c_lh, c_lc, c_bitset = cat
        feat_gain = jnp.where(is_categorical, c_gain, num_gain)
        feat_thr = jnp.where(is_categorical, c_thr, num_thr)
        feat_lg = jnp.where(is_categorical, c_lg, num_lg)
        feat_lh = jnp.where(is_categorical, c_lh, num_lh)
        feat_lc = jnp.where(is_categorical, c_lc, num_lc)
        feat_dl = jnp.where(is_categorical, False, num_dl)
        bitsets = c_bitset                     # [F, W]
    else:
        feat_gain, feat_thr = num_gain, num_thr
        feat_lg, feat_lh, feat_lc, feat_dl = num_lg, num_lh, num_lc, num_dl
        bitsets = jnp.zeros((F, MAX_CAT_WORDS), dtype=jnp.uint32)

    if gain_penalty is not None:
        # CEGB (reference: CostEfficientGradientBoosting::DetlaGain,
        # cost_effective_gradient_boosting.hpp:50 — subtracted from the
        # shifted split gain before the cross-feature argmax)
        feat_gain = jnp.where(jnp.isfinite(feat_gain),
                              feat_gain - gain_penalty, K_MIN_SCORE)
    if feature_mask is not None:
        feat_gain = jnp.where(feature_mask.astype(bool), feat_gain, K_MIN_SCORE)

    return PerFeatureBest(
        gain=feat_gain,
        threshold=feat_thr,
        default_left=feat_dl,
        left_sum_grad=feat_lg,
        left_sum_hess=feat_lh,
        left_count=feat_lc,
        is_categorical=is_categorical,
        cat_bitset=bitsets,
    )


def best_split_for_leaf(
    hist: jax.Array,
    sum_grad: jax.Array,
    sum_hess: jax.Array,
    num_data: jax.Array,
    num_bin: jax.Array,
    missing_type: jax.Array,
    default_bin: jax.Array,
    is_categorical: jax.Array,
    hp: SplitHyperparams,
    feature_mask: Optional[jax.Array] = None,
    monotone_constraints: Optional[jax.Array] = None,
    leaf_output_bounds: Optional[tuple] = None,
    has_categorical: bool = False,
    extra_rand_u: Optional[jax.Array] = None,
    gain_penalty: Optional[jax.Array] = None,
) -> SplitResult:
    """Best split over all features of one leaf (see feature_best_splits)."""
    pf = feature_best_splits(
        hist, sum_grad, sum_hess, num_data, num_bin, missing_type,
        default_bin, is_categorical, hp, feature_mask=feature_mask,
        monotone_constraints=monotone_constraints,
        leaf_output_bounds=leaf_output_bounds,
        has_categorical=has_categorical, extra_rand_u=extra_rand_u,
        gain_penalty=gain_penalty)
    return pick_best_feature(pf, sum_grad, sum_hess, num_data)


def pick_best_feature(pf: PerFeatureBest, sum_grad, sum_hess,
                      num_data) -> SplitResult:
    """argmax over features; ties -> smaller feature index (reference:
    SplitInfo::operator> tie-break, split_info.hpp:126-155)."""
    best_f = jnp.argmax(pf.gain).astype(jnp.int32)
    bg = pf.gain[best_f]
    blg, blh, blc = (pf.left_sum_grad[best_f], pf.left_sum_hess[best_f],
                     pf.left_count[best_f])
    return SplitResult(
        gain=bg,
        feature=best_f,
        threshold=pf.threshold[best_f],
        default_left=pf.default_left[best_f],
        left_sum_grad=blg,
        left_sum_hess=blh,
        left_count=blc,
        right_sum_grad=sum_grad - blg,
        right_sum_hess=sum_hess - blh,
        right_count=num_data - blc,
        is_categorical=pf.is_categorical[best_f],
        cat_bitset=pf.cat_bitset[best_f],
    )


def _best_categorical(hist, sum_grad, sum_hess, num_data, num_bin, valid_bin,
                      hp, rand_u=None, missing_type=None):
    """Categorical split search, vectorized over features.

    reference: FindBestThresholdCategoricalInner (feature_histogram.hpp:259-460).
    One-hot mode for small cardinality (num_bin <= max_cat_to_onehot): best
    single category vs rest.  Otherwise: sort categories by
    sum_grad/(sum_hess + cat_smooth) and scan prefixes from both ends, at most
    max_cat_threshold categories on the smaller side; lambda_l2 += cat_l2.
    Returns per-feature (gain, n_left_cats, left sums, bitset of bins LEFT).
    """
    _, F, B = hist.shape
    l2 = hp.lambda_l2 + hp.cat_l2
    g, h, c = hist[0], hist[1], hist[2]
    total_g, total_h = sum_grad, sum_hess + 2 * K_EPSILON
    parent_gain = leaf_gain(sum_grad, total_h, hp.lambda_l1, l2)
    min_gain_shift = parent_gain + hp.min_gain_to_split

    # --- one-hot mode: each category k vs rest
    lg, lh, lc = g, h + K_EPSILON, c
    rg, rh, rc = total_g - lg, total_h - lh, num_data - lc
    ok = ((lc >= hp.min_data_in_leaf) & (rc >= hp.min_data_in_leaf)
          & (lh >= hp.min_sum_hessian_in_leaf) & (rh >= hp.min_sum_hessian_in_leaf)
          & valid_bin)
    onehot_gain = leaf_gain(lg, lh, hp.lambda_l1, l2) + leaf_gain(rg, rh, hp.lambda_l1, l2)
    onehot_gain = jnp.where(ok & (onehot_gain > min_gain_shift), onehot_gain, K_MIN_SCORE)
    if rand_u is not None:
        rand_cat = jnp.floor(rand_u * num_bin.astype(jnp.float32)).astype(jnp.int32)
        onehot_gain = jnp.where(
            jnp.arange(B, dtype=jnp.int32)[None, :] == rand_cat[:, None],
            onehot_gain, K_MIN_SCORE)
    oh_k = jnp.argmax(onehot_gain, axis=1)                        # [F]
    oh_gain = jnp.take_along_axis(onehot_gain, oh_k[:, None], 1)[:, 0]

    # --- sorted many-vs-many
    # order by g/(h + cat_smooth); categories with small count excluded
    usable = valid_bin & (c >= max(1, hp.min_data_per_group // 4))
    ratio = jnp.where(usable, g / (h + hp.cat_smooth), jnp.inf)
    order = jnp.argsort(ratio, axis=1)                            # ascending; unusable last
    sg = jnp.take_along_axis(g, order, 1)
    sh = jnp.take_along_axis(h, order, 1)
    sc = jnp.take_along_axis(c, order, 1)
    s_usable = jnp.take_along_axis(usable, order, 1)
    sg = jnp.where(s_usable, sg, 0.0)
    sh = jnp.where(s_usable, sh, 0.0)
    sc = jnp.where(s_usable, sc, 0.0)
    pg, ph, pc = jnp.cumsum(sg, 1), jnp.cumsum(sh, 1), jnp.cumsum(sc, 1)
    k_idx = jnp.arange(B, dtype=jnp.int32)[None, :]
    max_k = jnp.minimum(hp.max_cat_threshold, B)
    n_usable = jnp.sum(s_usable, axis=1).astype(jnp.int32)[:, None]  # [F, 1]

    def scan_dir(from_low: bool):
        if from_low:
            clg, clh, clc = pg, ph + K_EPSILON, pc
            # left set = sorted[0..k]: size k+1 bounded by max_cat_threshold
            size_ok = k_idx < max_k
        else:
            clg = pg[:, -1:] - pg
            clh = ph[:, -1:] - ph + K_EPSILON
            clc = pc[:, -1:] - pc
            # left set = sorted[k+1..]: bound the SUFFIX size, not k itself
            left_size = n_usable - 1 - k_idx
            size_ok = (left_size <= max_k) & (left_size >= 1)
        crg, crh, crc = total_g - clg, total_h - clh, num_data - clc
        okd = ((clc >= hp.min_data_in_leaf) & (crc >= hp.min_data_in_leaf)
               & (clh >= hp.min_sum_hessian_in_leaf) & (crh >= hp.min_sum_hessian_in_leaf)
               & size_ok)
        gn = leaf_gain(clg, clh, hp.lambda_l1, l2) + leaf_gain(crg, crh, hp.lambda_l1, l2)
        gn = jnp.where(okd & (gn > min_gain_shift), gn, K_MIN_SCORE)
        if rand_u is not None:
            rand_pos = jnp.floor(
                rand_u * n_usable[:, 0].astype(jnp.float32)).astype(jnp.int32)
            gn = jnp.where(k_idx == rand_pos[:, None], gn, K_MIN_SCORE)
        kk = jnp.argmax(gn, axis=1)
        return jnp.take_along_axis(gn, kk[:, None], 1)[:, 0], kk, (clg, clh - K_EPSILON, clc)

    lo_gain, lo_k, lo_sums = scan_dir(True)
    hi_gain, hi_k, hi_sums = scan_dir(False)
    use_lo = lo_gain >= hi_gain
    mm_gain = jnp.where(use_lo, lo_gain, hi_gain)
    mm_k = jnp.where(use_lo, lo_k, hi_k)
    mm_lg = jnp.where(use_lo, jnp.take_along_axis(lo_sums[0], lo_k[:, None], 1)[:, 0],
                      jnp.take_along_axis(hi_sums[0], hi_k[:, None], 1)[:, 0])
    mm_lh = jnp.where(use_lo, jnp.take_along_axis(lo_sums[1], lo_k[:, None], 1)[:, 0],
                      jnp.take_along_axis(hi_sums[1], hi_k[:, None], 1)[:, 0])
    mm_lc = jnp.where(use_lo, jnp.take_along_axis(lo_sums[2], lo_k[:, None], 1)[:, 0],
                      jnp.take_along_axis(hi_sums[2], hi_k[:, None], 1)[:, 0])

    is_onehot = num_bin <= hp.max_cat_to_onehot
    cat_gain = jnp.where(is_onehot, oh_gain, mm_gain)
    cat_gain = jnp.where(jnp.isfinite(cat_gain), cat_gain - min_gain_shift,
                         K_MIN_SCORE)
    cat_lg = jnp.where(is_onehot, jnp.take_along_axis(lg, oh_k[:, None], 1)[:, 0], mm_lg)
    cat_lh = jnp.where(is_onehot,
                       jnp.take_along_axis(lh, oh_k[:, None], 1)[:, 0] - K_EPSILON, mm_lh)
    cat_lc = jnp.where(is_onehot, jnp.take_along_axis(lc, oh_k[:, None], 1)[:, 0], mm_lc)

    # bitset of bins going LEFT
    # one-hot: {oh_k}; many-vs-many low side: sorted[0..k]; high: sorted[k+1..]
    in_left_sorted_lo = k_idx <= mm_k[:, None]
    in_left_sorted = jnp.where(use_lo[:, None], in_left_sorted_lo,
                               (k_idx > mm_k[:, None]) & s_usable)
    member = jnp.zeros((F, B), dtype=bool)
    member = member.at[jnp.arange(F)[:, None], order].set(in_left_sorted & s_usable)
    member_oh = k_idx == oh_k[:, None]
    member = jnp.where(is_onehot[:, None], member_oh, member)
    # normalize: the NaN category (bin num_bin-1 when the feature has one,
    # i.e. missing_type NaN) must never sit in the stored goes-LEFT set —
    # prediction routes NaN right when it is not listed (the reference
    # never emits -1 in a categorical threshold).  Swapping sides keeps
    # the identical partition: new left = old right.
    if missing_type is not None:
        is_nan_bin = (k_idx == (num_bin - 1)[:, None]) & \
            (missing_type == MissingType.NAN)[:, None]
        nan_left = jnp.any(member & is_nan_bin, axis=1)
        member = jnp.where(nan_left[:, None],
                           valid_bin & ~member & ~is_nan_bin, member)
        cat_lg = jnp.where(nan_left, sum_grad - cat_lg, cat_lg)
        cat_lh = jnp.where(nan_left, sum_hess - cat_lh, cat_lh)
        cat_lc = jnp.where(nan_left, num_data - cat_lc, cat_lc)
    word = (jnp.arange(B, dtype=jnp.uint32) // 32)
    bitpos = (jnp.arange(B, dtype=jnp.uint32) % 32)
    bit = jnp.where(member, jnp.uint32(1) << bitpos[None, :], jnp.uint32(0))
    bitset = jnp.zeros((F, MAX_CAT_WORDS), dtype=jnp.uint32)
    bitset = bitset.at[:, word].add(bit)  # each word gets OR'd via add (bits disjoint)

    return cat_gain, mm_k.astype(jnp.int32), cat_lg, cat_lh, cat_lc, bitset


# ======================================================================
# Quantized-gradient training (use_quantized_grad) rescaling
# ======================================================================


def quant_rescale_hist(hist_int: jax.Array, g_scale, h_scale, num_data,
                       cnt_factor=None) -> jax.Array:
    """[2, F, B] (or [2, G, Bg]) integer histogram -> the [3, F, B] f32
    histogram every split kernel above consumes.

    reference: the quantized-training split path converts int32/int64
    bin sums to double before the gain math
    (feature_histogram.hpp GET_GRAD/GET_HESS int-hist specializations);
    here the rescale runs in jnp.float64 — true f64 under
    ``jax_enable_x64``, f32 otherwise — then lands in f32 for the
    vectorized scan.  Per-bin COUNTS are estimated from the hessian
    channel with the leaf's count factor
    (``Common::RoundInt(sum_hess * cnt_factor)``,
    feature_histogram.hpp:813): the count channel is deliberately NOT
    accumulated in quantized mode — dropping it is what shrinks the
    integer histogram to 2 channels and the data-parallel psum payload
    with it (ops/histogram.py ``hist_payload_bytes``).

    ``cnt_factor`` defaults to ``num_data / hess_int_total`` with the
    total read from axis-0 feature/group 0, whose bins partition the
    leaf's rows (every row has exactly one bin per feature).  Voting's
    local-candidate pass overrides it with the globally-derived factor
    (grower.py ``leaf_best_voting``).

    Accepts arbitrary leading batch axes on ``hist_int`` (with
    ``num_data``/``cnt_factor`` broadcastable to them) — the fused
    megakernel's epilogue rescales a whole frontier of children through
    THIS body (ops/fused.py), so the staged and fused rescales can never
    drift; the batched ops are elementwise and bit-identical to the
    historical unbatched code.
    """
    # true f64 only when the session enabled x64 (requesting f64 under
    # the default x64-off config would just warn and truncate to f32)
    wide = jnp.float64 if jax.config.x64_enabled else jnp.float32
    hi = hist_int.astype(wide)
    g = hi[..., 0, :, :] * jnp.asarray(g_scale, wide)
    h = hi[..., 1, :, :] * jnp.asarray(h_scale, wide)
    if cnt_factor is None:
        tot = jnp.sum(hist_int[..., 1, 0, :], axis=-1).astype(jnp.float32)
        cnt_factor = num_data / jnp.maximum(tot, 1.0)
    cf = jnp.asarray(cnt_factor, wide)
    c = jnp.round(hi[..., 1, :, :] * cf[..., None, None])
    return jnp.stack([g, h, c], axis=-3).astype(jnp.float32)
