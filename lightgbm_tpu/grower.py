"""Jitted leaf-wise tree growth.

TPU-native redesign of LightGBM's SerialTreeLearner
(reference: src/treelearner/serial_tree_learner.cpp:149 Train loop).  The
re-design for XLA:

- No DataPartition / ordered-gradient gather (data_partition.hpp:101,
  dataset.cpp:1318): a dense per-row ``leaf_id`` vector is carried instead;
  leaf membership enters the histogram kernel as a multiplicative mask.
- All shapes static: tree arrays sized by ``num_leaves``; the grow loop is a
  ``lax.while_loop`` ending early when no split has positive gain — the
  same best-first (leaf-wise) policy as the reference (:175-193).
- The histogram cache is a dense [num_leaves, F, B, 3] HBM array; the
  smaller child is built by a masked pass, the sibling by subtraction
  (reference "subtraction trick", serial_tree_learner.cpp:380-388).
- Distributed: pass ``axis_name`` when called under shard_map with rows
  sharded across the mesh — histograms and scalar sums are psum'd, after
  which EVERY device computes the identical best split, eliminating the
  reference's best-split allreduce (parallel_tree_learner.h:190-213).

Node numbering matches the reference Tree (include/LightGBM/tree.h:60-85):
internal node s = s-th split; child pointers >= 0 are internal nodes,
negative values are leaves encoded as ``~leaf_index``; the left child keeps
the parent's leaf index, the right child gets leaf index ``num_leaves``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from .dataset import FeatureMeta
from .ops.histogram import (build_histogram, build_histogram_int,
                            capacity_schedule, compacted_histogram,
                            compacted_histogram_int, on_accelerator,
                            psum_quant_hist, quant_levels, take_from_table)
from .ops.split import (K_EPSILON, MAX_CAT_WORDS, PerFeatureBest,
                        SplitHyperparams, SplitResult, best_split_for_leaf,
                        feature_best_splits, leaf_gain, leaf_output,
                        quant_rescale_hist)


class TreeArrays(NamedTuple):
    """Flat-array tree, fixed shapes; L leaves, L-1 internal nodes."""

    split_feature: jax.Array    # [L-1] i32 (index into used features)
    threshold_bin: jax.Array    # [L-1] i32
    default_left: jax.Array     # [L-1] bool
    is_categorical: jax.Array   # [L-1] bool
    cat_bitset: jax.Array       # [L-1, MAX_CAT_WORDS] u32 (bins going left)
    left_child: jax.Array       # [L-1] i32 (>=0 node, <0 ~leaf)
    right_child: jax.Array      # [L-1] i32
    split_gain: jax.Array       # [L-1] f32
    internal_value: jax.Array   # [L-1] f32 (output if node were a leaf)
    internal_weight: jax.Array  # [L-1] f32 (sum_hess)
    internal_count: jax.Array   # [L-1] f32
    leaf_value: jax.Array       # [L] f32
    leaf_weight: jax.Array      # [L] f32
    leaf_count: jax.Array       # [L] f32
    leaf_parent: jax.Array      # [L] i32 (internal node whose child is this leaf)
    leaf_depth: jax.Array       # [L] i32
    num_leaves: jax.Array       # scalar i32

    @staticmethod
    def empty(L: int) -> "TreeArrays":
        n = max(L - 1, 1)
        return TreeArrays(
            split_feature=jnp.zeros(n, jnp.int32),
            threshold_bin=jnp.zeros(n, jnp.int32),
            default_left=jnp.zeros(n, bool),
            is_categorical=jnp.zeros(n, bool),
            cat_bitset=jnp.zeros((n, MAX_CAT_WORDS), jnp.uint32),
            left_child=jnp.zeros(n, jnp.int32),
            right_child=jnp.zeros(n, jnp.int32),
            split_gain=jnp.zeros(n, jnp.float32),
            internal_value=jnp.zeros(n, jnp.float32),
            internal_weight=jnp.zeros(n, jnp.float32),
            internal_count=jnp.zeros(n, jnp.float32),
            leaf_value=jnp.zeros(L, jnp.float32),
            leaf_weight=jnp.zeros(L, jnp.float32),
            leaf_count=jnp.zeros(L, jnp.float32),
            leaf_parent=jnp.full(L, -1, jnp.int32),
            leaf_depth=jnp.zeros(L, jnp.int32),
            num_leaves=jnp.array(1, jnp.int32),
        )


class _LeafBest(NamedTuple):
    """Per-leaf cached best split (SoA over leaves)."""

    gain: jax.Array; feature: jax.Array; threshold: jax.Array
    default_left: jax.Array; left_sum_grad: jax.Array; left_sum_hess: jax.Array
    left_count: jax.Array; right_sum_grad: jax.Array; right_sum_hess: jax.Array
    right_count: jax.Array; is_categorical: jax.Array; cat_bitset: jax.Array

    @staticmethod
    def empty(L: int) -> "_LeafBest":
        return _LeafBest(
            gain=jnp.full(L, -jnp.inf, jnp.float32),
            feature=jnp.zeros(L, jnp.int32),
            threshold=jnp.zeros(L, jnp.int32),
            default_left=jnp.zeros(L, bool),
            left_sum_grad=jnp.zeros(L, jnp.float32),
            left_sum_hess=jnp.zeros(L, jnp.float32),
            left_count=jnp.zeros(L, jnp.float32),
            right_sum_grad=jnp.zeros(L, jnp.float32),
            right_sum_hess=jnp.zeros(L, jnp.float32),
            right_count=jnp.zeros(L, jnp.float32),
            is_categorical=jnp.zeros(L, bool),
            cat_bitset=jnp.zeros((L, MAX_CAT_WORDS), jnp.uint32),
        )

    def store(self, leaf: jax.Array, r: SplitResult) -> "_LeafBest":
        return _LeafBest(
            gain=self.gain.at[leaf].set(r.gain),
            feature=self.feature.at[leaf].set(r.feature),
            threshold=self.threshold.at[leaf].set(r.threshold),
            default_left=self.default_left.at[leaf].set(r.default_left),
            left_sum_grad=self.left_sum_grad.at[leaf].set(r.left_sum_grad),
            left_sum_hess=self.left_sum_hess.at[leaf].set(r.left_sum_hess),
            left_count=self.left_count.at[leaf].set(r.left_count),
            right_sum_grad=self.right_sum_grad.at[leaf].set(r.right_sum_grad),
            right_sum_hess=self.right_sum_hess.at[leaf].set(r.right_sum_hess),
            right_count=self.right_count.at[leaf].set(r.right_count),
            is_categorical=self.is_categorical.at[leaf].set(r.is_categorical),
            cat_bitset=self.cat_bitset.at[leaf].set(r.cat_bitset),
        )


class _LeafFeatBest(NamedTuple):
    """Per-(leaf, feature) cached split candidates (CEGB mode, SoA [L, F]).

    Unlike the reference, which bakes the CEGB penalty into cached
    SplitInfos and has to patch them when a feature's coupled penalty is
    first paid (UpdateLeafBestSplits,
    cost_effective_gradient_boosting.hpp:63-88), the gains cached here are
    penalty-FREE; the penalty is applied at selection time from the
    current used-feature state, so every cached candidate always sees the
    up-to-date coupled penalty — the reference's upgrade pass, made exact.
    The lazy (per-row on-demand) penalty IS cached per leaf (``lazy_pen``)
    because it depends on the rows in the leaf when candidates were
    computed — the same staleness the reference has.
    """

    gain: jax.Array          # [L, F] shifted gains WITHOUT cegb penalties
    threshold: jax.Array     # [L, F] i32
    default_left: jax.Array  # [L, F] bool
    left_sum_grad: jax.Array   # [L, F] f32
    left_sum_hess: jax.Array   # [L, F] f32
    left_count: jax.Array      # [L, F] f32
    cat_bitset: jax.Array    # [L, F, MAX_CAT_WORDS] u32
    lazy_pen: jax.Array      # [L, F] f32 cached on-demand penalties

    @staticmethod
    def empty(L: int, F: int) -> "_LeafFeatBest":
        return _LeafFeatBest(
            gain=jnp.full((L, F), -jnp.inf, jnp.float32),
            threshold=jnp.zeros((L, F), jnp.int32),
            default_left=jnp.zeros((L, F), bool),
            left_sum_grad=jnp.zeros((L, F), jnp.float32),
            left_sum_hess=jnp.zeros((L, F), jnp.float32),
            left_count=jnp.zeros((L, F), jnp.float32),
            cat_bitset=jnp.zeros((L, F, MAX_CAT_WORDS), jnp.uint32),
            lazy_pen=jnp.zeros((L, F), jnp.float32),
        )

    def store(self, leaf: jax.Array, pf: PerFeatureBest,
              lazy_row: jax.Array) -> "_LeafFeatBest":
        return _LeafFeatBest(
            gain=self.gain.at[leaf].set(pf.gain),
            threshold=self.threshold.at[leaf].set(pf.threshold),
            default_left=self.default_left.at[leaf].set(pf.default_left),
            left_sum_grad=self.left_sum_grad.at[leaf].set(pf.left_sum_grad),
            left_sum_hess=self.left_sum_hess.at[leaf].set(pf.left_sum_hess),
            left_count=self.left_count.at[leaf].set(pf.left_count),
            cat_bitset=self.cat_bitset.at[leaf].set(pf.cat_bitset),
            lazy_pen=self.lazy_pen.at[leaf].set(lazy_row),
        )


class GrowerConfig(NamedTuple):
    """Static (trace-time) grower configuration."""

    num_leaves: int = 31
    max_depth: int = -1
    hp: SplitHyperparams = SplitHyperparams()
    hist_method: str = "auto"
    num_bins: int = 255            # padded bin axis B
    learning_rate: float = 0.1
    compact: bool = True           # bucketed leaf-row compaction (see
                                   # ops/histogram.py capacity_schedule)
    voting_top_k: int = 0          # >0 under a data axis: voting-parallel
                                   # (PV-Tree) — only the top-k elected
                                   # features' histograms are psum'd
    num_machines: int = 1          # data-axis size (static; scales the
                                   # voting pass's local constraints)
    bynode_feature_cnt: int = 0    # >0: feature_fraction_bynode — sample
                                   # this many features per NODE (reference
                                   # ColSampler::GetByNode, col_sampler.hpp:87)
    num_feature_shards: int = 1    # feature-axis size (static); with EFB the
                                   # caller pre-arranges meta shard-major so
                                   # each shard owns whole bundles
    rounds_relaxed: bool = False   # rounds grower: skip the best-first
                                   # exactness fallback (tpu_tree_growth=
                                   # "fast"; see grower_rounds.py)
    round_width: int = 128         # rounds grower: max splits per round
                                   # (candidate-scan length / segment-slot
                                   # count; tpu_round_width)
    cegb_tradeoff: float = 1.0     # CEGB (reference cost_effective_
    cegb_penalty_split: float = 0.0  # gradient_boosting.hpp:50 DetlaGain)
    cegb_coupled: bool = False     # static: coupled-penalty array passed
    cegb_lazy: bool = False        # static: per-row on-demand penalties
    n_forced: int = 0              # static count of forced splits (reference
                                   # ForceSplits, serial_tree_learner.cpp:411)
    forced_exact_parity: bool = False  # reproduce the reference's
                                   # GatherInfoForThreshold stats convention
                                   # (bin == threshold accumulates RIGHT,
                                   # feature_histogram.hpp:527 — one bin off
                                   # vs its own DataPartition::Split) so
                                   # forced-split trees match bit-for-bit
    quant: bool = False            # quantized-gradient training: integer
                                   # [2, F, B] i32 histograms, int8 MXU
                                   # matmul, gains from rescaled int sums
                                   # (config use_quantized_grad; the GBDT
                                   # layer falls back to f32 for DART/CEGB/
                                   # monotone/extra_trees)
    quant_bins: int = 4            # num_grad_quant_bins (signed levels)
    quant_renew: bool = False      # quant_train_renew_leaf: re-fit leaf
                                   # outputs from TRUE f32 sums via the
                                   # ops/renew.py seam
    tile_rows: int = 0             # >0: stream every histogram pass
                                   # through row tiles of this size —
                                   # peak transient HBM O(tile), not
                                   # O(n*F).  Chosen by the ops/planner
                                   # HBM budget planner (LGBM_TPU_
                                   # TILE_ROWS overrides); 0 = untiled
    hist_pack: bool = True         # hoist the whole-dataset fused u32
                                   # record arena (pack_cols_u32) for
                                   # the sorted-arena gather; the
                                   # planner clears it when tiling is
                                   # active (records are then assembled
                                   # per tile inside the kernel loops)
    fused_feat_tile: int = 0       # hist_method="fused": features per
                                   # VMEM arena block of the Pallas
                                   # histogram→split megakernel
                                   # (ops/fused.py); 0 = let plan_fused
                                   # pick.  Set by ops/planner.apply_plan
    fused_block_rows: int = 0      # hist_method="fused": rows per
                                   # double-buffered tile DMA; 0 = auto
    hier_reduce: bool = False      # hybrid ("dcn","ici") mesh: reduce the
                                   # fast ICI tier before the slow DCN
                                   # tier (parallel/collectives.py); flat
                                   # when off — byte-identical for
                                   # integer payloads either way
    pinned_reduce: bool = False    # deterministic tier-ordered f32 sums
                                   # (all_gather + fixed-order reduce) so
                                   # flat == hierarchical holds for f32
                                   # model text too
    num_slices: int = 1            # dcn-axis size (static): hierarchical
                                   # voting elects top-k per SLICE, and
                                   # per-voter constraints scale by this
                                   # instead of num_machines


def _psum(x, axis_name, hierarchical: bool = False, pinned: bool = False):
    """Data-axis sum under the active reduction policy.  ``axis_name``
    may be one mesh axis or the hybrid outermost-first tuple; the default
    single-axis flat path is exactly ``lax.psum`` (unchanged HLO)."""
    if axis_name is None:
        return x
    from .parallel.collectives import psum_tiered
    return psum_tiered(x, axis_name, hierarchical=hierarchical,
                       pinned=pinned)


def row_goes_left(col: jax.Array, node_thr: jax.Array, node_dl: jax.Array,
                  node_cat, node_bitset, missing_type: jax.Array,
                  default_bin: jax.Array, num_bin: jax.Array) -> jax.Array:
    """Decision rule in bin space for one node over a column of rows.

    reference: DenseBin::SplitInner (src/io/dense_bin.hpp) — missing rows
    follow default_left, others compare bin <= threshold; categorical rows
    test bitset membership.  ``node_bitset=None`` (with ``node_cat=None``)
    is the numeric-only fast path: it skips the per-row bitset-word gather,
    which matters inside the rounds grower's candidate scan.
    """
    from .binning import MissingType
    col = col.astype(jnp.int32)
    is_missing = ((missing_type == MissingType.NAN) & (col == num_bin - 1)) | \
                 ((missing_type == MissingType.ZERO) & (col == default_bin))
    num_left = jnp.where(is_missing, node_dl, col <= node_thr)
    if node_bitset is None:
        return num_left
    word = (col // 32).astype(jnp.int32)
    bit = (col % 32).astype(jnp.uint32)
    if node_bitset.ndim == 2:  # per-row bitsets (traversal path)
        w = jnp.take_along_axis(node_bitset, word[:, None], axis=1)[:, 0]
    else:
        w = node_bitset[word]
    cat_left = ((w >> bit) & jnp.uint32(1)) == 1
    return jnp.where(node_cat, cat_left, num_left)


def grow_tree(binned_t, *args, **kwargs):
    """Grow one tree (full signature/contract: ``_grow_tree_traced``).

    The wrapper records a ``trace.grow_tree`` span around program-trace
    construction: the body runs on the HOST once per XLA compile (cached
    executions never re-enter it), so the span attributes compile-side
    cost to the grower — the seam the timer table cannot see
    (docs/OBSERVABILITY.md)."""
    from .obs.trace import span as _span
    with _span("trace.grow_tree", rows=int(binned_t.shape[1])):
        return _grow_tree_traced(binned_t, *args, **kwargs)


def _grow_tree_traced(
    binned_t: jax.Array,        # [F, n] uint8/16 feature-major (F, n
                                #   possibly per-shard; see ops/histogram.py
                                #   LAYOUT DOCTRINE)
    grad: jax.Array,            # [n] f32
    hess: jax.Array,            # [n] f32
    row_mask: jax.Array,        # [n] f32 bagging/GOSS weights (0 = excluded)
    meta: FeatureMeta,          # host numpy metadata (trace-time constants)
    cfg: GrowerConfig,
    feature_mask: Optional[jax.Array] = None,   # [F] per-tree col sample
    axis_name: Optional[str] = None,            # mesh axis sharding ROWS
    feature_axis_name: Optional[str] = None,    # mesh axis sharding FEATURES
    monotone_constraints: Optional[jax.Array] = None,  # [F] i32 in {-1,0,1}
    rng_key: Optional[jax.Array] = None,        # PRNG for extra_trees /
                                                # by-node column sampling
                                                # (replicated across shards)
    cegb_coupled_penalty: Optional[jax.Array] = None,  # [F] f32 coupled
                                                # penalties (inner feature idx)
    cegb_lazy_penalty: Optional[jax.Array] = None,     # [F] f32 per-row
                                                # on-demand penalties
    cegb_feat_used: Optional[jax.Array] = None,  # [F] bool: feature already
                                                # used in any split (carried
                                                # across trees by the caller)
    cegb_used_rows: Optional[jax.Array] = None,  # [F, n] bool: (feature, row)
                                                # pairs already paid for
                                                # (lazy mode; carried across
                                                # trees by the caller)
    forced_plan: Optional[tuple] = None,        # (leaf, feat, thr) i32 arrays
                                                # [cfg.n_forced]; see
                                                # GBDT._build_forced_plan
    meta_arrays: Optional[tuple] = None,        # (num_bin, missing_type,
                                                # default_bin, is_cat,
                                                # feat_group, feat_start) as
                                                # RUNTIME arrays -> the
                                                # compiled program is shared
                                                # across same-shaped datasets
    quant_vals: Optional[tuple] = None,         # cfg.quant: (gq [n] i8,
                                                # hq [n] i8, g_scale, h_scale)
                                                # from ops.histogram.
                                                # quantize_gradients; grad/
                                                # hess stay the TRUE f32
                                                # values (leaf renewal)
):
    """Grow one tree; returns (TreeArrays, leaf_id [n] i32).

    Distributed modes (call under shard_map over a Mesh):
    - ``axis_name``: rows sharded — histograms and scalar sums are psum'd,
      then every device finds the identical best split (DataParallel
      semantics, reference data_parallel_tree_learner.cpp, with the
      best-split sync eliminated).
    - ``feature_axis_name``: features sharded — each device scans only its
      own features (meta arrays are full-size; the local slice is taken by
      ``axis_index``), the best split is merged by all_gather + argmax
      (reference SyncUpGlobalBestSplit, parallel_tree_learner.h:190-213),
      and the owner broadcasts the partition mask via psum (replaces the
      reference's no-op because there every machine holds all features).
    Both can be combined (2-D mesh).
    """
    meta = meta.resolved()
    G, n = binned_t.shape
    L = cfg.num_leaves
    B = cfg.num_bins
    Bg = meta.max_group_bin if meta.has_bundles else B
    hp = cfg.hp

    # reduction policy over the (possibly tiered) data axis — every
    # scalar/histogram sum below routes through one closure so the
    # flat/hierarchical/pinned decision is made exactly once
    hier_rd = cfg.hier_reduce
    pinned_rd = cfg.pinned_reduce

    def psum_(x):
        return _psum(x, axis_name, hier_rd, pinned_rd)

    # full (unsliced) constraints for split-time bound propagation, which
    # looks up by GLOBAL feature index even when features are sharded
    mc_full = (jnp.asarray(monotone_constraints)
               if monotone_constraints is not None else None)
    if feature_axis_name is not None:
        # features sharded: each device's binned holds G columns of the
        # full group axis.  Without EFB those are identity groups; with EFB
        # the caller pre-arranged groups SHARD-MAJOR so every shard owns
        # whole bundles (reference partitions features after bundling,
        # feature_parallel_tree_learner.cpp:33-52) and meta.feat_group
        # already holds shard-LOCAL group indices.
        if meta.has_bundles:
            if cfg.num_feature_shards <= 1:
                raise NotImplementedError(
                    "feature-axis sharding over EFB bundles needs the "
                    "shard-major layout: set cfg.num_feature_shards to the "
                    "feature-axis size and pre-arrange meta/columns as "
                    "GBDT._build_group_sharding does (or train through the "
                    "engine, which does this automatically)")
            nsh = cfg.num_feature_shards
            F = len(meta.num_bin) // nsh
        else:
            F = G
    else:
        F = len(meta.num_bin)
    # per-feature metadata: taken from ``meta_arrays`` when the caller
    # passes them as RUNTIME values (so one compiled program serves every
    # same-shaped dataset — cv folds, sklearn fits; the bin layout is then
    # data, not an HLO constant), else embedded as trace-time constants
    if meta_arrays is not None:
        (num_bin_g, missing_type_g, default_bin_g, is_cat_g,
         feat_group_g, feat_start_g) = meta_arrays
    else:
        num_bin_g = jnp.asarray(meta.num_bin)
        missing_type_g = jnp.asarray(meta.missing_type)
        default_bin_g = jnp.asarray(meta.default_bin)
        is_cat_g = jnp.asarray(meta.is_categorical)
        feat_group_g = jnp.asarray(meta.feat_group)
        feat_start_g = jnp.asarray(meta.feat_start)
    if feature_axis_name is not None:
        fidx = lax.axis_index(feature_axis_name)
        def shard_slice(arr):
            return lax.dynamic_slice_in_dim(jnp.asarray(arr), fidx * F, F)
        num_bin = shard_slice(num_bin_g)
        missing_type = shard_slice(missing_type_g)
        default_bin = shard_slice(default_bin_g)
        is_cat = shard_slice(is_cat_g)
        if feature_mask is not None:
            feature_mask = lax.dynamic_slice_in_dim(feature_mask, fidx * F, F)
        if monotone_constraints is not None:
            monotone_constraints = lax.dynamic_slice_in_dim(
                jnp.asarray(monotone_constraints), fidx * F, F)
        f_offset = fidx * F
        if meta.has_bundles:
            feat_group = shard_slice(feat_group_g)   # shard-LOCAL groups
            feat_start = shard_slice(feat_start_g)
        else:
            feat_group = jnp.arange(F, dtype=jnp.int32)
            feat_start = jnp.ones(F, jnp.int32)
    else:
        num_bin = num_bin_g
        missing_type = missing_type_g
        default_bin = default_bin_g
        is_cat = is_cat_g
        f_offset = None
        feat_group = feat_group_g
        feat_start = feat_start_g
    has_cat = bool(meta.is_categorical.any())

    # quantized-gradient mode: integer [2, G, Bg] i32 histograms built
    # from pre-discretized int8 grad/hess (weights folded at quantization
    # time, ops/histogram.py quantize_gradients); the int->f32 rescale
    # happens ONCE per leaf search (quant_rescale_hist), everything
    # upstream of the search — cache, psum, sibling subtraction — stays
    # exact integer arithmetic
    quant = cfg.quant
    # planner-selected row tiling (ops/planner.py): every histogram pass
    # below streams tiles of this many rows; 0/None = untiled
    tile = cfg.tile_rows if cfg.tile_rows > 0 else None
    if quant:
        if quant_vals is None:
            raise ValueError("cfg.quant requires quant_vals="
                             "(gq, hq, g_scale, h_scale)")
        q_grad, q_hess, g_scale, h_scale = quant_vals
        q_levels = quant_levels(cfg.quant_bins)

        @jax.named_scope("lgbm.hist")
        def hist_pass(w):
            return build_histogram_int(binned_t, q_grad, q_hess, w > 0, Bg,
                                       method=cfg.hist_method,
                                       levels=q_levels, tile_rows=tile)

        def split_conv(ghist, cnt, cnt_factor=None):
            return quant_rescale_hist(ghist, g_scale, h_scale, cnt,
                                      cnt_factor=cnt_factor)
    else:
        hist_fn = functools.partial(build_histogram, num_bins=Bg,
                                    method=cfg.hist_method,
                                    tile_rows=tile)

        @jax.named_scope("lgbm.hist")
        def hist_pass(w):
            return hist_fn(binned_t, grad, hess, w)

        def split_conv(ghist, cnt, cnt_factor=None):
            return ghist
    # full-n first capacity: the "smaller" child is chosen by WEIGHTED count
    # (GOSS amplifies weights), so its raw row count may exceed n/2
    caps = capacity_schedule(n) if cfg.compact else [n]

    if meta.has_bundles:
        b_idx = jnp.arange(B, dtype=jnp.int32)

        def expand_hist(ghist, sg, sh, cnt):
            """[3, G, Bg] group histogram -> [3, F, B] per-feature histogram.

            Feature bins b>=1 gather from merged bins feat_start+b-1; bin 0
            (the shared default) is reconstructed from the leaf totals
            (reference: Dataset::FixHistogram, dataset.cpp:1410).
            """
            gather_bins = jnp.clip(feat_start[:, None] + b_idx[None, :] - 1,
                                   0, Bg - 1)                       # [F, B]
            taken = ghist[:, feat_group[:, None], gather_bins]      # [3, F, B]
            valid = (b_idx[None, :] >= 1) & (b_idx[None, :] < num_bin[:, None])
            h = jnp.where(valid[None, :, :], taken, 0.0)
            totals = jnp.stack([sg, sh, cnt])                       # [3]
            return h.at[:, :, 0].set(totals[:, None] - h.sum(axis=2))

        def expand_hist_int(ghist_i, tot_i):
            """Integer twin of expand_hist for the quantized voting path:
            same gather, bin 0 reconstructed from the [2] i32 leaf totals
            — linear, so it commutes with the elected-features psum."""
            gather_bins = jnp.clip(feat_start[:, None] + b_idx[None, :] - 1,
                                   0, Bg - 1)
            taken = ghist_i[:, feat_group[:, None], gather_bins]
            valid = (b_idx[None, :] >= 1) & (b_idx[None, :] < num_bin[:, None])
            h = jnp.where(valid[None, :, :], taken, 0)
            return h.at[:, :, 0].set(tot_i[:, None] - h.sum(axis=2))
    else:
        def expand_hist(ghist, sg, sh, cnt):
            return ghist   # identity groups: group hist IS the feature hist

        def expand_hist_int(ghist_i, tot_i):
            return ghist_i

    voting = (cfg.voting_top_k > 0 and axis_name is not None)
    if voting and feature_axis_name is not None:
        # recorded design exclusion (not a gap vs the reference): the
        # reference's tree_learner is a single choice of
        # serial|feature|data|voting — its factory cross product is
        # (learner x device), never (learner x learner)
        # (src/treelearner/tree_learner.cpp:13-36).  Voting elects features
        # to compress the DATA-axis histogram reduction; sharding features
        # at the same time removes the very all-feature local histograms
        # the vote is computed from.  The data x feature 2-D mesh already
        # exceeds the reference's composition surface.
        raise NotImplementedError("voting-parallel is a data-axis mode; "
                                  "combining it with feature sharding is "
                                  "contradictory (the vote needs all-"
                                  "feature local histograms) — use a "
                                  "data x feature mesh without voting")

    # CEGB (reference: cost_effective_gradient_boosting.hpp) — penalties are
    # subtracted from candidate gains; candidates are cached per
    # (leaf, feature) penalty-free and penalized at selection time, so the
    # coupled penalty disappears for EVERY cached candidate the moment a
    # feature is first used (UpdateLeafBestSplits semantics, made exact).
    # CEGB state (used-feature flags, lazy paid-rows bitmap, penalty
    # arrays) is indexed by GLOBAL feature id even under feature sharding;
    # per-shard views are sliced at the use sites below.
    cegb_enabled = (cfg.cegb_penalty_split > 0.0 or cfg.cegb_coupled
                    or cfg.cegb_lazy)
    if quant and cegb_enabled:
        # the GBDT layer falls back to f32 for CEGB (warn-once); reaching
        # here means a caller bypassed it
        raise NotImplementedError(
            "quantized-gradient training does not support CEGB; the "
            "booster falls back to f32 histograms for this combination")
    F_glob = len(meta.num_bin)    # global feature count (== F when unsharded)
    if cegb_enabled and voting:
        # recorded design exclusion: this build's CEGB is EXACT — it keeps
        # a per-(leaf, feature) candidate cache built from global
        # histograms and penalizes at selection time.  Voting exists to
        # avoid materializing global per-feature candidates (only elected
        # features' histograms are ever summed), so exact CEGB under
        # voting would psum every feature's histogram and degenerate
        # voting into data-parallel.  Use tree_learner=data for CEGB at
        # scale (same result, honest cost).
        raise NotImplementedError(
            "CEGB needs global per-feature candidates; voting-parallel "
            "exists to avoid building exactly those — use "
            "tree_learner=data with CEGB instead")
    if cegb_feat_used is None:
        cegb_feat_used = jnp.zeros(F_glob, bool)
    if cegb_used_rows is None:
        cegb_used_rows = jnp.zeros((F_glob, n) if cfg.cegb_lazy else (1, 1),
                                   bool)

    def _shard_view(arr, axis=0):
        """Slice a globally-indexed per-feature array to this shard."""
        if feature_axis_name is None:
            return arr
        return lax.dynamic_slice_in_dim(arr, f_offset, F, axis=axis)

    def cegb_gains(fb: "_LeafFeatBest", leaf_cnt_arr, used):
        """[L, F] penalized gains from the candidate cache (the reference's
        DetlaGain, cost_effective_gradient_boosting.hpp:50, applied
        dynamically from current state)."""
        pen = jnp.zeros((), jnp.float32)
        if cfg.cegb_penalty_split > 0.0:
            pen = pen + (cfg.cegb_tradeoff * cfg.cegb_penalty_split
                         * leaf_cnt_arr[:, None])
        if cfg.cegb_coupled:
            pen = pen + jnp.where(
                _shard_view(used)[None, :], 0.0,
                cfg.cegb_tradeoff
                * _shard_view(cegb_coupled_penalty)[None, :])
        if cfg.cegb_lazy:
            pen = pen + fb.lazy_pen
        return jnp.where(jnp.isfinite(fb.gain), fb.gain - pen, -jnp.inf)

    def cegb_lazy_row(in_leaf, used_rows):
        """[F] on-demand penalty for one leaf's rows (reference:
        CalculateOndemandCosts, cost_effective_gradient_boosting.hpp:93-113
        — the per-feature penalty times the leaf rows that have not yet
        paid for the feature)."""
        if not cfg.cegb_lazy:
            return jnp.zeros((F,), jnp.float32)
        rows_l = _shard_view(used_rows)
        cnt = (~rows_l).astype(jnp.float32) @ in_leaf.astype(jnp.float32)
        return (cfg.cegb_tradeoff * _shard_view(cegb_lazy_penalty)
                * psum_(cnt))

    def cegb_global_best_gain(fb, leaf_cnt_arr, used, num_leaves):
        """Scalar max penalized gain over active leaves, merged across
        feature shards — computed in the loop BODY and carried so the
        while-loop cond stays collective-free and replicated."""
        active = jnp.arange(L) < num_leaves
        g = cegb_gains(fb, leaf_cnt_arr, used)
        m = jnp.max(jnp.where(active[:, None], g, -jnp.inf))
        if feature_axis_name is not None:
            m = lax.pmax(m, feature_axis_name)
        return m

    # per-node randomness: extra_trees thresholds + by-node column sampling.
    # The key is REPLICATED across shards (reference syncs random seeds
    # across machines, application.cpp:169-174); by-node masks are sampled
    # over the GLOBAL feature axis then sliced per shard.
    F_total = F_glob
    use_rng = hp.extra_trees or cfg.bynode_feature_cnt > 0
    if use_rng and rng_key is None:
        rng_key = jax.random.PRNGKey(0)

    # fused Pallas histogram→split megakernel arm (ops/fused.py): per
    # split, ONE kernel streams the binned matrix once, accumulates the
    # smaller child's bins in VMEM, derives the sibling from the parent
    # arena in-kernel and scans both children's gains before writing
    # back only the smaller-child histogram (the subtraction cache's
    # input) + [2, F] per-feature-best tuples.  Monotone constraints
    # ride into the in-kernel scan (the bound propagation is hoisted
    # above the kernel call — it only needs the parent's cached sums);
    # every other special mode keeps the staged family (same trees: the
    # scan is ops.split.numeric_feature_scan either way).  The rounds
    # grower additionally lifts the categorical and data-parallel gates
    # (grower_rounds.py — the seam-split kernel); this serial arm exists
    # for mode completeness and the parity suite.
    use_fused = (cfg.hist_method == "fused" and axis_name is None
                 and feature_axis_name is None and not voting
                 and not cegb_enabled and cfg.n_forced == 0
                 and not meta.has_bundles and not has_cat
                 and not use_rng)
    if use_fused:
        from .ops.fused import fused_frontier_splits, pick_fused_best
        from .ops.histogram import _vals_t, _vals_t_int
        fused_vals = (_vals_t_int(q_grad, q_hess, row_mask > 0) if quant
                      else _vals_t(grad, hess, row_mask))
        fused_scales = (g_scale, h_scale) if quant else None

    def node_rand(key):
        """(by-node feature mask or None, extra-trees uniforms or None)."""
        fm_bn, eru = None, None
        if cfg.bynode_feature_cnt > 0:
            u = jax.random.uniform(jax.random.fold_in(key, 0), (F_total,))
            kth = -lax.top_k(-u, cfg.bynode_feature_cnt)[0][-1]
            bn = u <= kth
            if feature_axis_name is not None:
                bn = lax.dynamic_slice_in_dim(bn, f_offset, F)
            fm_bn = bn.astype(jnp.float32)
        if hp.extra_trees:
            eru = jax.random.uniform(jax.random.fold_in(key, 1), (F_total, 2))
            if feature_axis_name is not None:
                eru = lax.dynamic_slice_in_dim(eru, f_offset, F, axis=0)
        return fm_bn, eru

    def leaf_best_voting(ghist_local, sg, sh, cnt, bounds, fm, eru):
        """Voting-parallel (PV-Tree) best split: local per-feature gains ->
        top-k vote -> psum ONLY the elected features' histograms.

        reference: voting_parallel_tree_learner.cpp — local candidates with
        1/num_machines-scaled constraints (:57-59), GlobalVoting weighted by
        local leaf count (:153-182), CopyLocalHistogram + ReduceScatter of
        elected features only (:186-245).  Here the reduce-scatter+ownership
        dance collapses to one psum of a [top_k, B, 3] gather.

        Hierarchical mode (``cfg.hier_reduce`` on a ("dcn","ici") mesh):
        the FULL per-feature histogram first psums over the fast ICI tier
        only, each SLICE votes from its slice-level gains, and only the
        elected features' histograms cross the slow DCN tier — PV-Tree's
        bandwidth saver applied to exactly the expensive hop (F*B*ch
        bytes over ICI, k*B*ch over DCN; ops/planner.py plan_collectives
        is the accounting twin).
        """
        from .parallel.collectives import all_gather_tiered, axis_names
        names_v = axis_names(axis_name)
        hier_v = hier_rd and len(names_v) > 1
        # the axis the vote gathers over / elected histograms psum over:
        # the slow outermost tier under hierarchy, the whole ladder flat
        vote_axis = names_v[0] if hier_v else axis_name
        inner_axes = names_v[1:]
        # one "voter" = one slice under hierarchy, one device flat; the
        # reference's per-machine constraint scaling follows the voter
        ndev = max(cfg.num_slices, 1) if hier_v else max(cfg.num_machines, 1)
        if hier_v:
            # fast-tier reduction of the FULL histogram: after this the
            # "local" histogram is slice-level and replicated over ici
            ghist_local = (
                psum_quant_hist(ghist_local, inner_axes, rows_global,
                                cfg.quant_bins) if quant
                else _psum(ghist_local, inner_axes, pinned=pinned_rd))
        k = min(cfg.voting_top_k, F)
        hp_local = hp._replace(
            min_data_in_leaf=max(1, hp.min_data_in_leaf // ndev),
            min_sum_hessian_in_leaf=hp.min_sum_hessian_in_leaf / ndev)
        if quant:
            # local INTEGER totals from group 0 (its bins partition the
            # local rows); counts are estimated with the GLOBAL factor —
            # sh was produced as int_total * h_scale, so sh / h_scale
            # round-trips the global hessian-int total
            loc_i = ghist_local[:, 0, :].sum(axis=1)        # [2] i32
            cnt_f = cnt / jnp.maximum(jnp.round(sh / h_scale), 1.0)
            loc = (loc_i[0].astype(jnp.float32) * g_scale,
                   loc_i[1].astype(jnp.float32) * h_scale,
                   loc_i[1].astype(jnp.float32) * cnt_f)
            hist_loc = expand_hist(
                split_conv(ghist_local, cnt, cnt_factor=cnt_f),
                loc[0], loc[1], loc[2])
        else:
            loc = ghist_local[:, 0, :].sum(axis=1)   # local (sg, sh, cnt):
            # every row lands in exactly one bin of group 0, so its totals
            # are the local leaf totals
            hist_loc = expand_hist(ghist_local, loc[0], loc[1], loc[2])
        pf = feature_best_splits(
            hist_loc, loc[0], loc[1], loc[2], num_bin, missing_type,
            default_bin, is_cat, hp_local, feature_mask=fm,
            monotone_constraints=monotone_constraints,
            leaf_output_bounds=bounds, has_categorical=has_cat,
            extra_rand_u=eru)
        # weighted gain (GlobalVoting :166): local gain scaled by the local
        # share of the leaf's rows
        mean_cnt = jnp.maximum(cnt / ndev, 1.0)
        rc_loc = loc[2] - pf.left_count
        wgain = jnp.where(jnp.isfinite(pf.gain),
                          pf.gain * (pf.left_count + rc_loc) / mean_cnt,
                          -jnp.inf)
        top_g, top_i = lax.top_k(wgain, k)
        all_i = all_gather_tiered(top_i, vote_axis).reshape(-1)
        all_g = all_gather_tiered(top_g, vote_axis).reshape(-1)
        votes = jnp.full(F, -jnp.inf, jnp.float32).at[all_i].max(
            jnp.where(jnp.isfinite(all_g), all_g, -jnp.inf))
        _, elected = lax.top_k(votes, k)
        if quant:
            # the elected-features collective moves INTEGER histograms
            # ([2, k, B] i32, int16-narrowed when the static bound
            # allows) — the quantization-width payload shrink applies to
            # voting's only O(bins) collective too
            sub_i = psum_quant_hist(
                expand_hist_int(ghist_local, loc_i)[:, elected],
                vote_axis, rows_global, cfg.quant_bins)
            sub = split_conv(sub_i, cnt)
        else:
            sub = _psum(hist_loc[:, elected], vote_axis,
                        pinned=pinned_rd)             # [3, k, B]: the only
            # O(bins) collective on this tier — k*B*3 words vs
            # data-parallel's F*B*3
        r = best_split_for_leaf(
            sub, sg, sh, cnt, num_bin[elected], missing_type[elected],
            default_bin[elected], is_cat[elected], hp,
            feature_mask=(fm[elected] if fm is not None else None),
            monotone_constraints=(monotone_constraints[elected]
                                  if monotone_constraints is not None else None),
            leaf_output_bounds=bounds, has_categorical=has_cat,
            extra_rand_u=(eru[elected] if eru is not None else None))
        return r._replace(feature=elected[r.feature])

    @jax.named_scope("lgbm.scan")
    def leaf_best(ghist, sg, sh, cnt, depth, bounds=None, key=None):
        fm_bn, eru = node_rand(key) if (use_rng and key is not None) \
            else (None, None)
        fm = feature_mask
        if fm_bn is not None:
            fm = fm_bn if fm is None else fm * fm_bn
        if voting:
            r = leaf_best_voting(ghist, sg, sh, cnt, bounds, fm, eru)
            if cfg.max_depth > 0:
                r = r._replace(gain=jnp.where(depth >= cfg.max_depth,
                                              -jnp.inf, r.gain))
            return r
        hist = expand_hist(split_conv(ghist, cnt), sg, sh, cnt)
        r = best_split_for_leaf(
            hist, sg, sh, cnt, num_bin, missing_type, default_bin, is_cat,
            hp, feature_mask=fm,
            monotone_constraints=monotone_constraints,
            leaf_output_bounds=bounds,
            has_categorical=has_cat,
            extra_rand_u=eru)
        # depth limit (reference: serial_tree_learner.cpp:261-301 pruning)
        if cfg.max_depth > 0:
            r = r._replace(gain=jnp.where(depth >= cfg.max_depth, -jnp.inf, r.gain))
        if feature_axis_name is not None:
            # merge best splits across the feature shards
            r = r._replace(feature=r.feature + f_offset)
            gathered = jax.tree_util.tree_map(
                lambda x: lax.all_gather(x, feature_axis_name), r)
            winner = jnp.argmax(gathered.gain)
            r = jax.tree_util.tree_map(lambda x: x[winner], gathered)
        return r

    @jax.named_scope("lgbm.scan")
    def leaf_feats(ghist, sg, sh, cnt, depth, bounds=None, key=None):
        """Per-feature best candidates for one leaf, penalty-free (fills a
        row of the CEGB _LeafFeatBest cache)."""
        fm_bn, eru = node_rand(key) if (use_rng and key is not None) \
            else (None, None)
        fm = feature_mask
        if fm_bn is not None:
            fm = fm_bn if fm is None else fm * fm_bn
        hist = expand_hist(split_conv(ghist, cnt), sg, sh, cnt)
        pf = feature_best_splits(
            hist, sg, sh, cnt, num_bin, missing_type, default_bin, is_cat,
            hp, feature_mask=fm, monotone_constraints=monotone_constraints,
            leaf_output_bounds=bounds, has_categorical=has_cat,
            extra_rand_u=eru)
        if cfg.max_depth > 0:
            pf = pf._replace(gain=jnp.where(depth >= cfg.max_depth,
                                            -jnp.inf, pf.gain))
        return pf

    # ---- root ----
    # voting mode: the histogram cache holds LOCAL (per-shard) histograms;
    # only elected features are ever psum'd (inside leaf_best_voting).
    # Scalars stay global either way.  Quantized histograms psum as
    # integers with a statically-narrowed payload (psum_quant_hist) —
    # the data-parallel ICI traffic shrinks with the quantization width.
    rows_global = n * max(cfg.num_machines, 1)
    if voting:
        hist_sync = (lambda h: h)
    elif quant:
        hist_sync = (lambda h: psum_quant_hist(h, axis_name, rows_global,
                                               cfg.quant_bins,
                                               hierarchical=hier_rd))
    else:
        hist_sync = psum_
    root_hist = hist_sync(hist_pass(row_mask))
    if quant:
        member = row_mask > 0
        root_sg = psum_(jnp.sum(jnp.where(member, q_grad, 0).astype(
            jnp.int32))).astype(jnp.float32) * g_scale
        root_sh = psum_(jnp.sum(jnp.where(member, q_hess, 0).astype(
            jnp.int32))).astype(jnp.float32) * h_scale
        # counts are plain member-row counts in quantized mode (the
        # reference's bagging semantics; weights live in the int values)
        root_cnt = psum_(jnp.sum(member.astype(jnp.float32)))
    else:
        root_sg = psum_(jnp.sum(grad * row_mask))
        root_sh = psum_(jnp.sum(hess * row_mask))
        root_cnt = psum_(jnp.sum(row_mask))

    tree = TreeArrays.empty(L)
    hist_cache = jnp.zeros((L, 2, G, Bg), jnp.int32).at[0].set(root_hist) \
        if quant else \
        jnp.zeros((L, 3, G, Bg), jnp.float32).at[0].set(root_hist)
    leaf_sg = jnp.zeros(L, jnp.float32).at[0].set(root_sg)
    leaf_sh = jnp.zeros(L, jnp.float32).at[0].set(root_sh)
    leaf_cnt = jnp.zeros(L, jnp.float32).at[0].set(root_cnt)
    # which internal node points at this leaf, and on which side (0=L,1=R)
    leaf_parent_side = jnp.zeros(L, jnp.int32)
    # per-leaf monotone output bounds (reference: LeafConstraints,
    # monotone_constraints.hpp:32; propagated to descendants on each split)
    use_mc = monotone_constraints is not None
    leaf_min = jnp.full(L, -jnp.inf, jnp.float32)
    leaf_max = jnp.full(L, jnp.inf, jnp.float32)
    root_bounds = (leaf_min[0], leaf_max[0]) if use_mc else None
    # node-identity key (parent -1, side 0) — see apply_split's kl/kr
    root_key = (jax.random.fold_in(jax.random.fold_in(rng_key, 0), 0)
                if use_rng else None)
    if cegb_enabled:
        best = _LeafFeatBest.empty(L, F).store(
            jnp.array(0),
            leaf_feats(root_hist, root_sg, root_sh, root_cnt, jnp.array(0),
                       bounds=root_bounds, key=root_key),
            cegb_lazy_row(row_mask > 0, cegb_used_rows))
    else:
        best = _LeafBest.empty(L).store(
            jnp.array(0), leaf_best(root_hist, root_sg, root_sh,
                                    root_cnt, jnp.array(0),
                                    bounds=root_bounds, key=root_key))
    leaf_id = jnp.zeros(n, jnp.int32)
    is_cat_b = is_cat.astype(bool)

    class Carry(NamedTuple):
        tree: TreeArrays
        best: object          # _LeafBest, or _LeafFeatBest in CEGB mode
        hist: jax.Array
        leaf_sg: jax.Array
        leaf_sh: jax.Array
        leaf_cnt: jax.Array
        leaf_parent_side: jax.Array
        leaf_id: jax.Array
        split_idx: jax.Array  # number of splits applied so far
        leaf_min: jax.Array   # [L] monotone lower bounds
        leaf_max: jax.Array   # [L] monotone upper bounds
        cegb_used: jax.Array  # [F_glob] bool: features used in any split
        cegb_rows: jax.Array  # [F_glob, n] bool lazy-paid rows ([1,1] dummy)
        forced_aborted: jax.Array  # scalar bool: forced plan abandoned
        cegb_next_gain: jax.Array  # scalar f32: globally-merged best
        #                            penalized gain (dummy 0 when CEGB off)

    def current_selection(c: Carry):
        """Best-first choice: (leaf, SplitResult) of the max-gain leaf."""
        active = jnp.arange(L) < c.tree.num_leaves
        if cegb_enabled:
            g = cegb_gains(c.best, c.leaf_cnt, c.cegb_used)
            g = jnp.where(active[:, None], g, -jnp.inf)
            leaf = jnp.argmax(jnp.max(g, axis=1)).astype(jnp.int32)
            gl = g[leaf]
            f = jnp.argmax(gl).astype(jnp.int32)   # ties -> smaller feature
            lg = c.best.left_sum_grad[leaf, f]
            lh = c.best.left_sum_hess[leaf, f]
            lc = c.best.left_count[leaf, f]
            r = SplitResult(
                gain=gl[f], feature=f,
                threshold=c.best.threshold[leaf, f],
                default_left=c.best.default_left[leaf, f],
                left_sum_grad=lg, left_sum_hess=lh, left_count=lc,
                right_sum_grad=c.leaf_sg[leaf] - lg,
                right_sum_hess=c.leaf_sh[leaf] - lh,
                right_count=c.leaf_cnt[leaf] - lc,
                is_categorical=is_cat_b[f],
                cat_bitset=c.best.cat_bitset[leaf, f])
            if feature_axis_name is not None:
                # each shard proposes its local (leaf, feature) winner;
                # the global choice is the max gain across shards (gather
                # order = shard order, so exact ties resolve to the
                # smaller global feature id — the reference's SplitInfo
                # tie-break, split_info.hpp:126)
                r = r._replace(feature=r.feature + f_offset)
                gathered = jax.tree_util.tree_map(
                    lambda x: lax.all_gather(x, feature_axis_name),
                    (leaf, r))
                winner = jnp.argmax(gathered[1].gain)
                leaf, r = jax.tree_util.tree_map(
                    lambda x: x[winner], gathered)
        else:
            b = c.best
            gains = jnp.where(active, b.gain, -jnp.inf)
            leaf = jnp.argmax(gains).astype(jnp.int32)
            r = SplitResult(
                gain=b.gain[leaf], feature=b.feature[leaf],
                threshold=b.threshold[leaf],
                default_left=b.default_left[leaf],
                left_sum_grad=b.left_sum_grad[leaf],
                left_sum_hess=b.left_sum_hess[leaf],
                left_count=b.left_count[leaf],
                right_sum_grad=b.right_sum_grad[leaf],
                right_sum_hess=b.right_sum_hess[leaf],
                right_count=b.right_count[leaf],
                is_categorical=b.is_categorical[leaf],
                cat_bitset=b.cat_bitset[leaf])
        return leaf, r

    if cfg.n_forced > 0:
        fp_leaf = jnp.asarray(forced_plan[0], jnp.int32)
        fp_feat = jnp.asarray(forced_plan[1], jnp.int32)
        fp_thr = jnp.asarray(forced_plan[2], jnp.int32)

        def forced_split_result(c: Carry):
            """Stats for the current forced step's planned split.

            reference: GatherInfoForThreshold (feature_histogram.hpp:486).
            Left/right masses follow this grower's partition rule; with
            cfg.forced_exact_parity the reference's own convention
            (bin == threshold goes RIGHT) is reproduced instead — see
            the deviation note in docs/COMPONENTS.md.

            Learner coverage: under feature sharding the planned feature
            lives on one shard — it computes the left-mass and the others
            receive it by a psum-select (the same owner-broadcast pattern
            as apply_split's partition).  Under voting-parallel the
            histogram cache is shard-local, so the leaf's group histogram
            is psum'd over the data axis first (forced steps are few;
            this one collective replaces the reference's reduce-scatter
            on the forced path).
            """
            from .binning import MissingType
            s = c.split_idx
            leaf = fp_leaf[s]
            feat = fp_feat[s]
            thr = fp_thr[s]
            sg, sh, cnt = c.leaf_sg[leaf], c.leaf_sh[leaf], c.leaf_cnt[leaf]
            h_leaf = c.hist[leaf]
            if voting:
                # local -> global hist (integer psum in quantized mode)
                h_leaf = (psum_quant_hist(h_leaf, axis_name, rows_global,
                                          cfg.quant_bins,
                                          hierarchical=hier_rd) if quant
                          else psum_(h_leaf))
            if feature_axis_name is not None:
                lf_raw = feat - f_offset
                owns = (lf_raw >= 0) & (lf_raw < F)
                lf = jnp.clip(lf_raw, 0, F - 1)
            else:
                owns = jnp.bool_(True)
                lf = feat
            hist_f = expand_hist(split_conv(h_leaf, cnt),
                                 sg, sh, cnt)[:, lf]          # [3, B]
            b = jnp.arange(B, dtype=jnp.int32)
            nb = num_bin[lf]
            mt = missing_type[lf]
            db = default_bin[lf]
            cat = is_cat_b[lf]
            valid = b < nb
            miss_bin = jnp.where(mt == MissingType.NAN, nb - 1,
                                 jnp.where(mt == MissingType.ZERO, db, -1))
            if cfg.forced_exact_parity:
                # reference stats convention: bins >= threshold accumulate
                # on the RIGHT (GatherInfoForThresholdNumerical's loop
                # breaks at t + offset < threshold), default/NaN bins are
                # skipped from the right pass — i.e. land LEFT
                sel_num = valid & ((b < thr) | (b == miss_bin))
            else:
                # self-consistent rule: stats follow this grower's own
                # partition (bin <= threshold goes left), avoiding the
                # reference's stats-vs-partition one-bin mismatch
                sel_num = valid & ((b <= thr) | (b == miss_bin))
            sel_cat = valid & (b == thr)   # one-hot categorical forced split
            sel = jnp.where(cat, sel_cat, sel_num)
            lsum = jnp.sum(jnp.where(sel[None, :], hist_f, 0.0), axis=1)
            if feature_axis_name is not None:
                # owner shard broadcasts its numbers (and the categorical
                # flag, which downstream bitset/default_left logic needs)
                lsum = lax.psum(jnp.where(owns, lsum, 0.0),
                                feature_axis_name)
                cat = lax.psum(jnp.where(owns, cat.astype(jnp.float32),
                                         0.0), feature_axis_name) > 0.5
            lg, lh, lc = lsum[0], lsum[1], lsum[2]
            rg, rh, rc = sg - lg, sh - lh, cnt - lc
            parent_gain = leaf_gain(sg, sh + 2 * K_EPSILON,
                                    hp.lambda_l1, hp.lambda_l2)
            gain = (leaf_gain(lg, lh + K_EPSILON, hp.lambda_l1, hp.lambda_l2)
                    + leaf_gain(rg, rh + K_EPSILON, hp.lambda_l1, hp.lambda_l2)
                    - parent_gain - hp.min_gain_to_split)
            gain = jnp.where(jnp.isnan(gain), -jnp.inf, gain)
            word = (thr // 32).astype(jnp.int32)
            bit = (thr % 32).astype(jnp.uint32)
            bitset = jnp.where(
                cat,
                jnp.zeros((MAX_CAT_WORDS,), jnp.uint32).at[word].set(
                    jnp.uint32(1) << bit),
                jnp.zeros((MAX_CAT_WORDS,), jnp.uint32))
            r = SplitResult(
                gain=gain, feature=feat, threshold=thr,
                default_left=~cat, left_sum_grad=lg, left_sum_hess=lh,
                left_count=lc, right_sum_grad=rg, right_sum_hess=rh,
                right_count=rc, is_categorical=cat, cat_bitset=bitset)
            return leaf, r

    def cond(c: Carry):
        active = jnp.arange(L) < c.tree.num_leaves
        if cegb_enabled:
            # carried scalar (computed in the body, pmax-merged across
            # feature shards there) — collectives are not allowed in a
            # while-loop cond, and a per-shard max would diverge
            best_gain = c.cegb_next_gain
        else:
            best_gain = jnp.max(jnp.where(active, c.best.gain, -jnp.inf))
        more = best_gain > 0.0
        if cfg.n_forced > 0:
            more = more | ((c.split_idx < cfg.n_forced) & ~c.forced_aborted)
        return (c.split_idx < L - 1) & more

    @jax.named_scope("lgbm.commit")
    def apply_split(c: Carry, leaf, r: SplitResult) -> Carry:
        tree, best = c.tree, c.best
        s = c.split_idx                               # new internal node index
        new_leaf = tree.num_leaves                    # right child leaf index

        feat = r.feature
        thr = r.threshold
        dl = r.default_left
        ncat = r.is_categorical
        nbits = r.cat_bitset

        # -- record node (fix the parent's dangling child pointer first)
        parent_node = tree.leaf_parent[leaf]
        side = c.leaf_parent_side[leaf]
        has_parent = parent_node >= 0
        pn = jnp.maximum(parent_node, 0)
        left_child = jnp.where(
            has_parent & (side == 0),
            tree.left_child.at[pn].set(s), tree.left_child)
        right_child = jnp.where(
            has_parent & (side == 1),
            tree.right_child.at[pn].set(s), tree.right_child)
        lg, lh, lc = r.left_sum_grad, r.left_sum_hess, r.left_count
        rg, rh, rc = r.right_sum_grad, r.right_sum_hess, r.right_count
        parent_out = leaf_output(c.leaf_sg[leaf], c.leaf_sh[leaf],
                                 hp.lambda_l1, hp.lambda_l2, hp.max_delta_step)
        new_depth = tree.leaf_depth[leaf] + 1
        tree = tree._replace(
            split_feature=tree.split_feature.at[s].set(feat),
            threshold_bin=tree.threshold_bin.at[s].set(thr),
            default_left=tree.default_left.at[s].set(dl),
            is_categorical=tree.is_categorical.at[s].set(ncat),
            cat_bitset=tree.cat_bitset.at[s].set(nbits),
            left_child=left_child.at[s].set(~leaf),
            right_child=right_child.at[s].set(~new_leaf),
            split_gain=tree.split_gain.at[s].set(r.gain),
            internal_value=tree.internal_value.at[s].set(parent_out),
            internal_weight=tree.internal_weight.at[s].set(c.leaf_sh[leaf]),
            internal_count=tree.internal_count.at[s].set(c.leaf_cnt[leaf]),
            leaf_parent=tree.leaf_parent.at[leaf].set(s).at[new_leaf].set(s),
            leaf_depth=tree.leaf_depth.at[leaf].set(new_depth).at[new_leaf].set(new_depth),
            num_leaves=tree.num_leaves + 1,
        )
        leaf_parent_side = c.leaf_parent_side.at[leaf].set(0).at[new_leaf].set(1)

        # -- partition rows of `leaf` (reference: DataPartition::Split)
        if feature_axis_name is not None:
            # split feature is global; only the owning shard has the column
            local_f = feat - f_offset
            owned = (local_f >= 0) & (local_f < F)
            lf = jnp.clip(local_f, 0, F - 1)
            col_l = jnp.take(binned_t, feat_group[lf], axis=0).astype(jnp.int32)
            dec_l = col_l - feat_start[lf] + 1
            binf_l = jnp.where((dec_l >= 1) & (dec_l < num_bin[lf]), dec_l, 0)
            gl_local = row_goes_left(binf_l, thr, dl, ncat, nbits,
                                     missing_type[lf], default_bin[lf],
                                     num_bin[lf])
            goes_left = lax.psum(
                jnp.where(owned, gl_local.astype(jnp.float32), 0.0),
                feature_axis_name) > 0.5
        else:
            # decode the feature's bin from its (possibly bundled) column
            g = feat_group[feat]
            st = feat_start[feat]
            col = jnp.take(binned_t, g, axis=0).astype(jnp.int32)
            dec = col - st + 1
            binf = jnp.where((dec >= 1) & (dec < num_bin[feat]), dec, 0)
            goes_left = row_goes_left(binf, thr, dl, ncat, nbits,
                                      missing_type[feat], default_bin[feat],
                                      num_bin[feat])
        in_leaf = c.leaf_id == leaf
        leaf_id = jnp.where(in_leaf & ~goes_left, new_leaf, c.leaf_id)

        # -- CEGB state (reference: UpdateLeafBestSplits at the top of
        # SplitInner, serial_tree_learner.cpp:529-532 — the split feature
        # becomes globally used; in lazy mode the PARENT leaf's rows have
        # now paid for it)
        cegb_used, cegb_rows = c.cegb_used, c.cegb_rows
        if cegb_enabled:
            cegb_used = cegb_used.at[feat].set(True)
        if cfg.cegb_lazy:
            in_parent = in_leaf & (row_mask > 0)
            cegb_rows = cegb_rows.at[feat].set(cegb_rows[feat] | in_parent)

        # -- leaf sums
        leaf_sg = c.leaf_sg.at[leaf].set(lg).at[new_leaf].set(rg)
        leaf_sh = c.leaf_sh.at[leaf].set(lh).at[new_leaf].set(rh)
        leaf_cnt = c.leaf_cnt.at[leaf].set(lc).at[new_leaf].set(rc)

        # -- monotone bound propagation (reference: UpdateConstraints,
        # monotone_constraints.hpp:44 — children inherit the parent's
        # bounds, and a numerical split on a constrained feature pins
        # the midpoint of the clamped child outputs between them).
        # Computed BEFORE the histogram section: it needs only the
        # committed split's sums, and the fused megakernel's in-kernel
        # scan consumes the children's bounds.
        leaf_min, leaf_max = c.leaf_min, c.leaf_max
        if use_mc:
            p_min, p_max = leaf_min[leaf], leaf_max[leaf]
            l_out = jnp.clip(leaf_output(lg, lh, hp.lambda_l1, hp.lambda_l2,
                                         hp.max_delta_step), p_min, p_max)
            r_out = jnp.clip(leaf_output(rg, rh, hp.lambda_l1, hp.lambda_l2,
                                         hp.max_delta_step), p_min, p_max)
            mid = (l_out + r_out) * 0.5
            mc_f = mc_full[feat]      # feat is a GLOBAL feature index
            upd = (~ncat) & (mc_f != 0)
            l_min = jnp.where(upd & (mc_f < 0), jnp.maximum(p_min, mid), p_min)
            l_max = jnp.where(upd & (mc_f > 0), jnp.minimum(p_max, mid), p_max)
            r_min = jnp.where(upd & (mc_f > 0), jnp.maximum(p_min, mid), p_min)
            r_max = jnp.where(upd & (mc_f < 0), jnp.minimum(p_max, mid), p_max)
            leaf_min = leaf_min.at[leaf].set(l_min).at[new_leaf].set(r_min)
            leaf_max = leaf_max.at[leaf].set(l_max).at[new_leaf].set(r_max)
            bounds_l = (l_min, l_max)
            bounds_r = (r_min, r_max)
        else:
            bounds_l = bounds_r = None

        # -- histograms: masked pass for smaller child, subtraction for sibling
        left_smaller = lc <= rc
        small_leaf = jnp.where(left_smaller, leaf, new_leaf)
        parent_hist = c.hist[leaf]
        small_member = leaf_id == small_leaf
        fused_best = None
        if use_fused:
            # one streamed pass: smaller-child bins accumulate in VMEM,
            # the sibling derives from the parent arena in-kernel, both
            # children's per-feature-best tuples come back with the
            # smaller-child histogram (ops/fused.py)
            csums = jnp.stack([jnp.stack([lg, rg]), jnp.stack([lh, rh]),
                               jnp.stack([lc, rc])])            # [3, 2]
            f_bounds = ((jnp.stack([bounds_l[0], bounds_r[0]]),
                         jnp.stack([bounds_l[1], bounds_r[1]]))
                        if use_mc else None)
            seg1, fused_best = fused_frontier_splits(
                binned_t, fused_vals, jnp.where(small_member, 0, 1), 1,
                Bg, csums, left_smaller[None], parent_hist[None],
                num_bin, missing_type, default_bin, hp,
                quant_scales=fused_scales,
                monotone_constraints=(mc_full if use_mc else None),
                child_bounds=f_bounds,
                feat_tile=(cfg.fused_feat_tile or None),
                block_rows=(cfg.fused_block_rows or None),
                tile_rows=tile)
            small_hist = seg1[0]
        elif cfg.compact and len(caps) > 1:
            if quant:
                small_hist = hist_sync(compacted_histogram_int(
                    binned_t, q_grad, q_hess, row_mask, small_member, Bg,
                    caps, method=cfg.hist_method, levels=q_levels,
                    tile_rows=tile))
            else:
                small_hist = hist_sync(
                    compacted_histogram(binned_t, grad, hess, row_mask,
                                        small_member, Bg, caps,
                                        method=cfg.hist_method,
                                        tile_rows=tile))
        else:
            small_hist = hist_sync(hist_pass(row_mask * small_member))
        large_hist = parent_hist - small_hist
        hist_l = jnp.where(left_smaller, small_hist, large_hist)
        hist_r = jnp.where(left_smaller, large_hist, small_hist)
        hist = c.hist.at[leaf].set(hist_l).at[new_leaf].set(hist_r)

        # -- best splits for the two children.  Keys derive from NODE
        # IDENTITY (parent node, side) — not application order — so the
        # batched grower (grower_rounds.py) draws identical randomness
        # per node and the two growers stay structurally identical under
        # extra_trees / feature_fraction_bynode.
        kl = jax.random.fold_in(jax.random.fold_in(rng_key, s + 1), 0) \
            if use_rng else None
        kr = jax.random.fold_in(jax.random.fold_in(rng_key, s + 1), 1) \
            if use_rng else None
        if cegb_enabled:
            pfl = leaf_feats(hist_l, lg, lh, lc, new_depth,
                             bounds=bounds_l, key=kl)
            pfr = leaf_feats(hist_r, rg, rh, rc, new_depth,
                             bounds=bounds_r, key=kr)
            in_l = (leaf_id == leaf) & (row_mask > 0)
            in_r = (leaf_id == new_leaf) & (row_mask > 0)
            best = best.store(leaf, pfl, cegb_lazy_row(in_l, cegb_rows)) \
                       .store(new_leaf, pfr, cegb_lazy_row(in_r, cegb_rows))
        elif use_fused:
            # the kernel already scanned both children: pick the best
            # feature (ties -> smaller index, like pick_best_feature),
            # then apply the depth gate exactly where leaf_best does
            res2 = pick_fused_best(fused_best, jnp.stack([lg, rg]),
                                   jnp.stack([lh, rh]),
                                   jnp.stack([lc, rc]),
                                   feature_mask=feature_mask)
            if cfg.max_depth > 0:
                res2 = res2._replace(gain=jnp.where(
                    new_depth >= cfg.max_depth, -jnp.inf, res2.gain))
            rl = jax.tree_util.tree_map(lambda x: x[0], res2)
            rr = jax.tree_util.tree_map(lambda x: x[1], res2)
            best = best.store(leaf, rl).store(new_leaf, rr)
        else:
            rl = leaf_best(hist_l, lg, lh, lc, new_depth,
                           bounds=bounds_l, key=kl)
            rr = leaf_best(hist_r, rg, rh, rc, new_depth,
                           bounds=bounds_r, key=kr)
            best = best.store(leaf, rl).store(new_leaf, rr)

        next_gain = (cegb_global_best_gain(best, leaf_cnt, cegb_used,
                                           tree.num_leaves)
                     if cegb_enabled else jnp.float32(0.0))
        return Carry(tree, best, hist, leaf_sg, leaf_sh, leaf_cnt,
                     leaf_parent_side, leaf_id, s + 1, leaf_min, leaf_max,
                     cegb_used, cegb_rows, c.forced_aborted, next_gain)

    def body(c: Carry) -> Carry:
        leaf, r = current_selection(c)
        if cfg.n_forced == 0:
            return apply_split(c, leaf, r)
        # forced phase (reference: ForceSplits BFS,
        # serial_tree_learner.cpp:411-521): while the plan lasts, the
        # planned split replaces the best-first choice; a failed forced
        # split (non-positive gain) abandons the REST of the plan and
        # training continues best-first (abort_last_forced_split :507-519)
        # forced work (with its voting/feature-shard collectives) runs
        # ONLY while the plan lasts — the predicate is replicated, so
        # every shard takes the same branch and the collectives stay
        # matched; after the forced phase, splits pay nothing extra
        in_forced = (c.split_idx < cfg.n_forced) & ~c.forced_aborted

        def _forced_dummy(cc):
            z = jnp.float32(0.0)
            return jnp.int32(0), SplitResult(
                gain=jnp.float32(-jnp.inf), feature=jnp.int32(0),
                threshold=jnp.int32(0), default_left=jnp.bool_(True),
                left_sum_grad=z, left_sum_hess=z, left_count=z,
                right_sum_grad=z, right_sum_hess=z, right_count=z,
                is_categorical=jnp.bool_(False),
                cat_bitset=jnp.zeros((MAX_CAT_WORDS,), jnp.uint32))

        f_leaf, f_r = lax.cond(in_forced, forced_split_result,
                               _forced_dummy, c)
        ok = f_r.gain > 0.0
        apply_forced = in_forced & ok
        aborted = c.forced_aborted | (in_forced & ~ok)
        leaf = jnp.where(apply_forced, f_leaf, leaf)
        r = jax.tree_util.tree_map(
            lambda a, b_: jnp.where(apply_forced, a, b_), f_r, r)
        do_split = apply_forced | (r.gain > 0.0)
        out = lax.cond(do_split,
                       lambda cc: apply_split(cc, leaf, r),
                       lambda cc: cc, c)
        return out._replace(forced_aborted=aborted)

    init_gain = (cegb_global_best_gain(best, leaf_cnt, cegb_feat_used,
                                       tree.num_leaves)
                 if cegb_enabled else jnp.float32(0.0))
    init = Carry(tree, best, hist_cache, leaf_sg, leaf_sh, leaf_cnt,
                 leaf_parent_side, leaf_id, jnp.array(0, jnp.int32),
                 leaf_min, leaf_max, cegb_feat_used, cegb_used_rows,
                 jnp.array(False), init_gain)
    out = lax.while_loop(cond, body, init)

    # finalize leaf values (clamped to monotone bounds, reference:
    # CalculateSplittedLeafOutput USE_MC, feature_histogram.hpp:697-711).
    # Quantized mode with quant_train_renew_leaf re-fits the outputs from
    # the TRUE f32 gradient sums (ops/renew.py seam), so the committed
    # leaves carry no discretization bias — only the SPLITS came from the
    # integer histograms (reference: RenewIntGradTreeOutput lineage).
    with jax.named_scope("lgbm.leaf_values"):
        tree = out.tree
        leaf_sh_out = out.leaf_sh
        if quant and cfg.quant_renew:
            from .ops.renew import quant_train_renew_leaf
            sg_t, sh_t = quant_train_renew_leaf(out.leaf_id, grad, hess,
                                                row_mask, L)
            sg_t = psum_(sg_t)
            sh_t = psum_(sh_t)
            lv = leaf_output(sg_t, sh_t, hp.lambda_l1, hp.lambda_l2,
                             hp.max_delta_step)
            leaf_sh_out = sh_t
        else:
            lv = leaf_output(out.leaf_sg, out.leaf_sh, hp.lambda_l1,
                             hp.lambda_l2, hp.max_delta_step)
        if use_mc:
            lv = jnp.clip(lv, out.leaf_min, out.leaf_max)
        active = jnp.arange(L) < tree.num_leaves
        tree = tree._replace(
            leaf_value=jnp.where(active, lv, 0.0),
            leaf_weight=jnp.where(active, leaf_sh_out, 0.0),
            leaf_count=jnp.where(active, out.leaf_cnt, 0.0),
        )
    if cegb_enabled:
        # hand the cross-tree CEGB state back to the caller (the reference
        # keeps it in the tree learner across Train calls)
        return tree, out.leaf_id, (out.cegb_used, out.cegb_rows)
    return tree, out.leaf_id


# rows a block of ``route_leaf_index_binned`` resolves at once.  The v5e
# reads the same ms a tree from 32k to 256k rows (PERF.md section 6, PR 33);
# this end of it bounds the [L, block] int32 of leaf against row at 67 MB
# for 255 leaves should a compiler not consume it where the matmul makes it
ROUTE_BLOCK_ROWS = 65536


def leaf_router_engages(meta: FeatureMeta) -> bool:
    """Whether ``predict_leaf_index_binned`` should be traced in its path
    form for a data set with this metadata: known on the host when the
    programs are built, so it is also what the ``valid_update_trees_*``
    counters follow.  The path form compares a node's threshold with the
    row's bin and has no arm for a category set yet (the rounds grower's
    router carries sets as bytes, ``grower_rounds.set_bytes_hold``; doing
    the same here waits for a benchmark cell that would guard it), so categorical
    splits keep the walk; and one-hot matmuls lose off the accelerator."""
    return on_accelerator() and not meta.is_categorical.any()


def _bin_layout(meta: FeatureMeta, meta_arrays: Optional[tuple]):
    """(num_bin, missing_type, default_bin, feat_group, feat_start) from
    the runtime tuple where there is one, else from the static ``meta``."""
    (num_bin, missing_type, default_bin, _is_cat, feat_group,
     feat_start) = (meta_arrays if meta_arrays is not None
                    else meta.as_runtime_arrays())
    return num_bin, missing_type, default_bin, feat_group, feat_start


def predict_leaf_index_binned(tree: TreeArrays, binned_t: jax.Array,
                              meta: FeatureMeta,
                              meta_arrays: Optional[tuple] = None,
                              routed: bool = False) -> jax.Array:
    """Route binned rows ([F, n] feature-major) to leaf indices.

    reference: Tree::Predict inline traversal (include/LightGBM/tree.h:190).
    ``meta_arrays`` (same tuple as grow_tree's) makes the bin layout a
    runtime input so one compiled traversal serves every same-shaped
    dataset.

    Two programs give the same indices.  ``routed=True`` (the caller's
    trace-time ``leaf_router_engages(meta)``: no categorical feature, on
    the accelerator) traces ``route_leaf_index_binned``, which reads whole
    feature rows and resolves every leaf's root path by one matmul.
    Otherwise the walk: all rows advance one level per iteration of a
    ``lax.while_loop``, ten per-row gathers a level (one of them the
    categorical bitset word), done when every row has reached a leaf
    (child pointer < 0).
    """
    if routed:
        return route_leaf_index_binned(tree, binned_t, meta, meta_arrays)
    n = binned_t.shape[1]
    num_bin, missing_type, default_bin, feat_group, feat_start = \
        _bin_layout(meta, meta_arrays)

    # node >= 0: internal; node < 0: leaf ~node
    def cond(state):
        node, _ = state
        return jnp.any(node >= 0)

    def body(state):
        node, it = state
        nd = jnp.maximum(node, 0)
        feat = tree.split_feature[nd]
        col = binned_t[feat_group[feat], jnp.arange(n)].astype(jnp.int32)
        dec = col - feat_start[feat] + 1
        binf = jnp.where((dec >= 1) & (dec < num_bin[feat]), dec, 0)
        gl = row_goes_left(binf, tree.threshold_bin[nd], tree.default_left[nd],
                           tree.is_categorical[nd], tree.cat_bitset[nd],
                           missing_type[feat], default_bin[feat], num_bin[feat])
        nxt = jnp.where(gl, tree.left_child[nd], tree.right_child[nd])
        node = jnp.where(node >= 0, nxt, node)
        return node, it + 1

    has_split = tree.num_leaves > 1
    init_node = jnp.broadcast_to(
        jnp.where(has_split, 0, -1).astype(jnp.int32), (n,))
    node, _ = lax.while_loop(cond, body, (init_node, jnp.array(0)))
    return ~node  # leaf index


def _leaf_paths(tree: TreeArrays):
    """The tree's root paths as a matrix: ``P[l, j]`` int8 is +1 where leaf
    ``l``'s path goes left at internal node ``j``, -1 where right, 0 where
    ``j`` is not on it; ``target[l]`` int32 is the path's length, or a
    value no row can reach for a leaf ``>= num_leaves``.  Nodes
    ``>= num_leaves - 1`` are on no path, and a stump's leaf 0 has the
    empty path every row matches.  Read from ``left_child`` /
    ``right_child`` / ``num_leaves`` alone (what the walk reads), with
    arrays of L elements: every leaf climbs to the root at once, one
    ancestor an iteration."""
    L = tree.leaf_value.shape[0]
    Ln = tree.left_child.shape[0]
    iota_n = jnp.arange(Ln, dtype=jnp.int32)
    iota_l = jnp.arange(L, dtype=jnp.int32)
    live_n = iota_n < tree.num_leaves - 1
    # Ln is no node's index and no leaf's code: a dead node is no parent
    lc = jnp.where(live_n, tree.left_child, Ln)
    rc = jnp.where(live_n, tree.right_child, Ln)

    def parent_of(code):
        """(parent node or -1, +1 / -1 the side) of each child code."""
        is_l = lc[None, :] == code[:, None]
        is_r = rc[None, :] == code[:, None]
        par = jnp.sum(jnp.where(is_l | is_r, iota_n[None, :], 0), axis=1)
        par = jnp.where(jnp.any(is_l | is_r, axis=1), par, -1)
        return par, jnp.where(jnp.any(is_l, axis=1), 1, -1).astype(jnp.int8)

    node_par, node_side = parent_of(iota_n)
    leaf_par, leaf_side = parent_of(~iota_l)
    live_l = iota_l < tree.num_leaves

    def cond(state):
        cur, _, _, it = state
        return jnp.any(cur >= 0) & (it < Ln)

    def body(state):
        cur, side, paths, it = state
        paths = jnp.where(iota_n[None, :] == cur[:, None], side[:, None],
                          paths)
        up = jnp.maximum(cur, 0)
        return (jnp.where(cur >= 0, node_par[up], -1), node_side[up],
                paths, it + 1)

    _, _, paths, _ = lax.while_loop(
        cond, body, (jnp.where(live_l, leaf_par, -1), leaf_side,
                     jnp.zeros((L, Ln), jnp.int8), jnp.array(0)))
    depth = jnp.sum(jnp.abs(paths.astype(jnp.int32)), axis=1)
    return paths, jnp.where(live_l, depth, Ln + 1)


def route_leaf_index_binned(tree: TreeArrays, binned_t: jax.Array,
                            meta: FeatureMeta,
                            meta_arrays: Optional[tuple] = None,
                            block: int = ROUTE_BLOCK_ROWS) -> jax.Array:
    """The leaf index of every binned row ([G, n] feature-major) of a tree
    whose splits are all numeric, with no per-row gather and no loop over
    levels; equal to ``predict_leaf_index_binned``'s walk to the bit, on
    any backend.  For a block of rows:

    1. node columns: row ``feat_group[split_feature[j]]`` of the matrix
       for each of the L-1 internal nodes, whole and contiguous: a
       [L-1, G] x [G, b] one-hot matmul where the bins are uint8 (< 256,
       exact in bf16; 2.7x a leading-axis take of the same rows on the
       v5e), the take for wider bins;
    2. node decisions ``D[j, r]`` int8, +1 left / -1 right: the walk's bin
       decode and ``row_goes_left``'s numeric rule, the node's parameters
       broadcast as [L-1, 1] columns;
    3. ``M = P @ D`` (int8 in, int32 out) against ``_leaf_paths``: row
       ``r`` is in leaf ``l`` iff it agreed with every turn of ``l``'s
       path, ``M[l, r] == depth[l]``; exactly one live leaf matches.

    Blocks of ``block`` rows keep the [L, block] int32 transient small;
    the last block starts at ``n - block`` and overlaps the one before
    (the same rows get the same answer twice) so nothing is padded.
    """
    n = binned_t.shape[1]
    num_bin, missing_type, default_bin, feat_group, feat_start = \
        _bin_layout(meta, meta_arrays)
    feat = tree.split_feature
    grp = feat_group[feat]
    fs, nb = feat_start[feat][:, None], num_bin[feat][:, None]
    mt, db = missing_type[feat][:, None], default_bin[feat][:, None]
    thr, dl = tree.threshold_bin[:, None], tree.default_left[:, None]
    paths, target = _leaf_paths(tree)
    iota_l = jnp.arange(paths.shape[0], dtype=jnp.int32)[:, None]
    pick = None
    if binned_t.dtype == jnp.uint8:
        pick = (grp[:, None] == jnp.arange(binned_t.shape[0])[None, :]
                ).astype(jnp.bfloat16)                       # [L-1, G]

    def leaves_of(rows):
        if pick is not None:
            col = lax.dot(pick, rows.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
        else:
            col = rows[grp]
        col = col.astype(jnp.int32)                          # [L-1, b]
        dec = col - fs + 1
        binf = jnp.where((dec >= 1) & (dec < nb), dec, 0)
        gl = row_goes_left(binf, thr, dl, None, None, mt, db, nb)
        turns = jnp.where(gl, 1, -1).astype(jnp.int8)
        agree = lax.dot(paths, turns, preferred_element_type=jnp.int32)
        return jnp.sum(jnp.where(agree == target[:, None], iota_l, 0),
                       axis=0)

    if n <= block:
        return leaves_of(binned_t)

    def body(i, leaf):
        start = jnp.minimum(i * block, n - block)
        rows = lax.dynamic_slice_in_dim(binned_t, start, block, axis=1)
        return lax.dynamic_update_slice_in_dim(leaf, leaves_of(rows), start,
                                               axis=0)

    return lax.fori_loop(0, -(-n // block), body, jnp.zeros(n, jnp.int32))


def predict_tree_binned(tree: TreeArrays, binned_t: jax.Array,
                        meta: FeatureMeta,
                        meta_arrays: Optional[tuple] = None,
                        routed: bool = False) -> jax.Array:
    leaf = predict_leaf_index_binned(tree, binned_t, meta, meta_arrays,
                                     routed)
    return take_from_table(tree.leaf_value, leaf)
