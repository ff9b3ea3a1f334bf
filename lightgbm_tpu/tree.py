"""Host-side tree: raw-feature prediction, serialization, SHAP.

reference: include/LightGBM/tree.h + src/io/tree.cpp.  Device trees
(grower.TreeArrays, bin-space thresholds over used features) are converted
once per iteration into this host form with REAL feature indices and DOUBLE
thresholds so that models are self-contained (independent of any Dataset)
and text-serializable in the reference's model format.

decision_type bit layout matches the reference exactly (tree.h:19-20,214-233):
bit0 = categorical, bit1 = default_left, bits2-3 = missing type
(0 none, 1 zero, 2 nan).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from .binning import BinType, MissingType
from .obs.metrics import global_registry as _obs_registry

K_CATEGORICAL_MASK = 1
K_DEFAULT_LEFT_MASK = 2
K_ZERO_THRESHOLD = 1e-35


@dataclass
class HostTree:
    """Flat-array tree with real feature indices and double thresholds."""

    num_leaves: int
    # internal nodes [num_leaves-1]
    split_feature: np.ndarray        # real (original) feature index
    split_feature_inner: np.ndarray  # used-feature index (training order)
    threshold: np.ndarray            # double threshold (numerical) / cat idx
    threshold_in_bin: np.ndarray     # bin threshold
    decision_type: np.ndarray        # int8 bitfield
    left_child: np.ndarray
    right_child: np.ndarray
    split_gain: np.ndarray
    internal_value: np.ndarray
    internal_weight: np.ndarray
    internal_count: np.ndarray
    # leaves [num_leaves]
    leaf_value: np.ndarray
    leaf_weight: np.ndarray
    leaf_count: np.ndarray
    # categorical storage (reference: tree.h cat_boundaries_/cat_threshold_)
    num_cat: int = 0
    cat_boundaries: np.ndarray = field(default_factory=lambda: np.zeros(1, np.int32))
    cat_threshold: np.ndarray = field(default_factory=lambda: np.zeros(0, np.uint32))
    shrinkage: float = 1.0
    # convenience copies for importance
    real_feature_index: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int32))

    # ------------------------------------------------------------- transforms

    def add_bias(self, val: float) -> None:
        """reference: Tree::AddBias (tree.h:169)."""
        self.leaf_value = self.leaf_value + val
        self.internal_value = self.internal_value + val

    def scale(self, rate: float) -> None:
        """reference: Tree::Shrinkage (tree.h:158)."""
        self.leaf_value = self.leaf_value * rate
        self.internal_value = self.internal_value * rate
        self.shrinkage *= rate

    @staticmethod
    def constant(value: float) -> "HostTree":
        """reference: Tree::AsConstantTree (tree.h:180)."""
        z = lambda k=0: np.zeros(k)
        return HostTree(
            num_leaves=1,
            split_feature=np.zeros(0, np.int32), split_feature_inner=np.zeros(0, np.int32),
            threshold=z(), threshold_in_bin=np.zeros(0, np.int32),
            decision_type=np.zeros(0, np.int8),
            left_child=np.zeros(0, np.int32), right_child=np.zeros(0, np.int32),
            split_gain=z(), internal_value=z(), internal_weight=z(), internal_count=z(),
            leaf_value=np.array([value]), leaf_weight=z(1), leaf_count=z(1),
            real_feature_index=np.zeros(0, np.int32),
        )

    # ------------------------------------------------------------- prediction

    def _decide(self, fval: np.ndarray, node: int) -> np.ndarray:
        """Vectorized decision; returns bool go-left. reference: tree.h:244-300."""
        dt = int(self.decision_type[node])
        if dt & K_CATEGORICAL_MASK:
            cat_idx = int(self.threshold[node])
            lo, hi = self.cat_boundaries[cat_idx], self.cat_boundaries[cat_idx + 1]
            bitset = self.cat_threshold[lo:hi]
            iv = np.where(np.isnan(fval), -1, fval).astype(np.int64)
            valid = (iv >= 0) & (iv < (hi - lo) * 32)
            ivc = np.clip(iv, 0, max((hi - lo) * 32 - 1, 0))
            inset = (bitset[ivc // 32] >> (ivc % 32).astype(np.uint32)) & 1
            return valid & (inset == 1)
        missing_type = (dt >> 2) & 3
        nan_mask = np.isnan(fval)
        if missing_type != 2:
            fval = np.where(nan_mask, 0.0, fval)
            nan_mask = np.zeros_like(nan_mask)
        is_missing = ((missing_type == 1) & (np.abs(fval) <= K_ZERO_THRESHOLD)) | \
                     ((missing_type == 2) & nan_mask)
        default_left = bool(dt & K_DEFAULT_LEFT_MASK)
        return np.where(is_missing, default_left, fval <= self.threshold[node])

    def predict_np(self, X: np.ndarray) -> np.ndarray:
        """Raw-feature batch prediction (host)."""
        n = X.shape[0]
        if self.num_leaves <= 1:
            return np.full(n, self.leaf_value[0] if len(self.leaf_value) else 0.0)
        node = np.zeros(n, np.int32)
        out = np.empty(n, np.float64)
        active = node >= 0
        # iterative: process node by node (trees are small; vectorize over rows)
        while active.any():
            for nd in np.unique(node[active]):
                rows = active & (node == nd)
                fval = X[rows, self.split_feature[nd]]
                gl = self._decide(fval, nd)
                nxt = np.where(gl, self.left_child[nd], self.right_child[nd])
                node[rows] = nxt
            done = node < 0
            newly = active & done
            out[newly] = self.leaf_value[~node[newly]]
            active = active & ~done
        return out

    def predict_leaf_np(self, X: np.ndarray) -> np.ndarray:
        n = X.shape[0]
        if self.num_leaves <= 1:
            return np.zeros(n, np.int32)
        node = np.zeros(n, np.int32)
        active = node >= 0
        while active.any():
            for nd in np.unique(node[active]):
                rows = active & (node == nd)
                gl = self._decide(X[rows, self.split_feature[nd]], nd)
                node[rows] = np.where(gl, self.left_child[nd], self.right_child[nd])
            active = active & (node >= 0)
        return (~node).astype(np.int32)

    def predict_binned_np(self, binned: np.ndarray,
                          feat_group: Optional[np.ndarray] = None,
                          feat_start: Optional[np.ndarray] = None) -> np.ndarray:
        """Bin-space batch prediction (used for rollback/DART on binned data).

        With EFB, ``binned`` holds merged group columns; pass the dataset's
        feat_group/feat_start to decode each feature's bin (see
        FeatureMeta docstring in dataset.py)."""
        n = binned.shape[0]
        if self.num_leaves <= 1:
            return np.full(n, self.leaf_value[0] if len(self.leaf_value) else 0.0)
        node = np.zeros(n, np.int32)
        out = np.empty(n, np.float64)
        active = node >= 0
        while active.any():
            for nd in np.unique(node[active]):
                rows = active & (node == nd)
                fi = self.split_feature_inner[nd]
                if feat_group is not None:
                    col = binned[rows, feat_group[fi]].astype(np.int64)
                    dec = col - int(feat_start[fi]) + 1
                    nb = int(self._feat_num_bin[nd]) if hasattr(
                        self, "_feat_num_bin") else 1 << 30
                    b = np.where((dec >= 1) & (dec < nb), dec, 0)
                else:
                    b = binned[rows, fi].astype(np.int64)
                dt = int(self.decision_type[nd])
                if dt & K_CATEGORICAL_MASK:
                    gl = self._bin_cat_decide(b, nd)
                else:
                    mt = (dt >> 2) & 3
                    thr = self.threshold_in_bin[nd]
                    mb = self._missing_bin[nd] if hasattr(self, "_missing_bin") else -1
                    is_missing = (mt != 0) & (b == mb)
                    gl = np.where(is_missing, bool(dt & K_DEFAULT_LEFT_MASK), b <= thr)
                node[rows] = np.where(gl, self.left_child[nd], self.right_child[nd])
            done = node < 0
            newly = active & done
            out[newly] = self.leaf_value[~node[newly]]
            active = active & ~done
        return out

    def _bin_cat_decide(self, b: np.ndarray, nd: int) -> np.ndarray:
        bs = self._bin_cat_bitset[nd] if hasattr(self, "_bin_cat_bitset") else None
        if bs is None:
            return np.zeros(len(b), bool)
        return ((bs[b // 32] >> (b % 32).astype(np.uint32)) & 1) == 1

    # ------------------------------------------------------------------- SHAP

    def predict_contrib_np(self, X: np.ndarray, num_features: int) -> np.ndarray:
        """Tree SHAP path attribution (reference: tree.h:137 PredictContrib,
        src/io/tree.cpp TreeSHAP).  Returns [n, num_features+1]."""
        n = X.shape[0]
        out = np.zeros((n, num_features + 1), np.float64)
        if self.num_leaves <= 1:
            out[:, -1] = self.expected_value()
            return out
        from .utils.shap import tree_shap
        for i in range(n):
            tree_shap(self, X[i], out[i])
        return out

    def expected_value(self) -> float:
        """reference: Tree::ExpectedValue — weighted mean of leaf outputs."""
        if self.num_leaves <= 1:
            return float(self.leaf_value[0]) if len(self.leaf_value) else 0.0
        tot = float(self.internal_count[0]) if len(self.internal_count) else 0.0
        if tot <= 0:
            return 0.0
        return float((self.leaf_value * self.leaf_count).sum() / tot)

    def max_depth(self) -> int:
        if self.num_leaves <= 1:
            return 0
        depth = {0: 1}
        md = 1
        for nd in range(self.num_leaves - 1):
            d = depth.get(nd, 1)
            for ch in (self.left_child[nd], self.right_child[nd]):
                if ch >= 0:
                    depth[int(ch)] = d + 1
                    md = max(md, d + 1)
                else:
                    md = max(md, d)
        return md


def tree_to_host(tree_arrays, train_set, shrinkage: float) -> HostTree:
    """Convert device TreeArrays (bin thresholds over used features) into a
    self-contained HostTree (double thresholds, real feature indices)."""
    ta = tree_arrays
    nl = int(ta.num_leaves)
    ns = max(nl - 1, 0)
    used = train_set.used_features
    mappers = train_set.bin_mappers

    split_feature_inner = np.asarray(ta.split_feature[:ns], np.int32)
    real_feat = np.array([used[f] for f in split_feature_inner], np.int32) \
        if ns else np.zeros(0, np.int32)
    thr_bin = np.asarray(ta.threshold_bin[:ns], np.int32)
    is_cat = np.asarray(ta.is_categorical[:ns], bool)
    dl = np.asarray(ta.default_left[:ns], bool)

    threshold = np.zeros(ns, np.float64)
    decision_type = np.zeros(ns, np.int8)
    missing_bin = np.full(ns, -1, np.int32)
    cat_boundaries = [0]
    cat_threshold: List[np.uint32] = []
    bin_cat_bitsets = {}
    num_cat = cat_codes = 0
    for s in range(ns):
        m = mappers[used[split_feature_inner[s]]]
        dt = 0
        if is_cat[s]:
            dt |= K_CATEGORICAL_MASK
            # convert bin bitset -> category-value bitset
            bin_bits = np.asarray(ta.cat_bitset[s], np.uint32)
            bin_cat_bitsets[s] = bin_bits
            cats = []
            for b in range(m.num_bin):
                if (bin_bits[b // 32] >> (b % 32)) & 1:
                    cv = m.bin_2_categorical[b] if b < len(m.bin_2_categorical) else -1
                    if cv >= 0:
                        cats.append(cv)
            max_cat = max(cats) if cats else 0
            nwords = max_cat // 32 + 1
            words = np.zeros(nwords, np.uint32)
            for cv in cats:
                words[cv // 32] |= np.uint32(1) << np.uint32(cv % 32)
            threshold[s] = num_cat
            cat_boundaries.append(cat_boundaries[-1] + nwords)
            cat_threshold.extend(words.tolist())
            num_cat += 1
            cat_codes += len(cats)
            # missing type for categorical is NaN-ish; NaN goes right always
            dt |= (m.missing_type & 3) << 2
        else:
            if dl[s]:
                dt |= K_DEFAULT_LEFT_MASK
            dt |= (m.missing_type & 3) << 2
            r = m.num_bin - 1 - (1 if m.missing_type == MissingType.NAN else 0)
            tb = min(int(thr_bin[s]), max(r - 1, 0))
            threshold[s] = m.bin_upper_bound[tb]
            if m.missing_type == MissingType.NAN:
                missing_bin[s] = m.num_bin - 1
            elif m.missing_type == MissingType.ZERO:
                missing_bin[s] = m.default_bin
        decision_type[s] = dt

    ht = HostTree(
        num_leaves=nl,
        split_feature=real_feat,
        split_feature_inner=split_feature_inner,
        threshold=threshold,
        threshold_in_bin=thr_bin,
        decision_type=decision_type,
        left_child=np.asarray(ta.left_child[:ns], np.int32),
        right_child=np.asarray(ta.right_child[:ns], np.int32),
        split_gain=np.asarray(ta.split_gain[:ns], np.float64),
        internal_value=np.asarray(ta.internal_value[:ns], np.float64),
        internal_weight=np.asarray(ta.internal_weight[:ns], np.float64),
        internal_count=np.asarray(ta.internal_count[:ns], np.float64),
        leaf_value=np.asarray(ta.leaf_value[:nl], np.float64),
        leaf_weight=np.asarray(ta.leaf_weight[:nl], np.float64),
        leaf_count=np.asarray(ta.leaf_count[:nl], np.float64),
        num_cat=num_cat,
        cat_boundaries=np.asarray(cat_boundaries, np.int32),
        cat_threshold=np.asarray(cat_threshold, np.uint32),
        shrinkage=shrinkage,
        real_feature_index=real_feat,
    )
    # what the forest stands on, as the host takes it
    _obs_registry.counter("tree_splits_total").inc(ns)
    if num_cat:
        _obs_registry.counter("tree_splits_categorical_total").inc(num_cat)
        _obs_registry.counter("tree_cat_set_codes_total").inc(cat_codes)
    ht._missing_bin = missing_bin
    ht._feat_num_bin = np.array(
        [mappers[used[f]].num_bin for f in split_feature_inner], np.int32)
    ht._bin_cat_bitset = bin_cat_bitsets
    return ht
