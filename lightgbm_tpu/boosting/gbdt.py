"""GBDT training loop.

reference: src/boosting/gbdt.cpp — GBDT::Init (:42), Train (:246),
TrainOneIter (:338), Boosting (:152), Bagging (:163), BoostFromAverage
(:302), UpdateScore (:459).

TPU re-design:
- the whole per-iteration step (gradients -> bagging mask -> K tree grows ->
  leaf renewal -> shrinkage -> score update) is ONE jitted device program;
  the host only fetches the finished (tiny) tree arrays per iteration.
- bagging and GOSS are weight masks, not index subsets: shapes stay static,
  nothing is compacted (replaces is_use_subset_/bag_data_indices_ machinery,
  gbdt.cpp:163-244); excluded rows keep leaf routing so out-of-bag score
  update (gbdt.cpp:459-478) is free.
- scores live on device [K, n] f32 for train and each valid set.
"""

from __future__ import annotations

import functools
import math
import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..dataset import Dataset, FeatureMeta
from ..obs.flight import note as _flight_note
from ..obs.metrics import global_registry as _obs_registry
from ..obs.trace import span as _span
from ..ops.histogram import (on_accelerator, quantize_gradients,
                             take_from_table)
from ..grower import (GrowerConfig, TreeArrays, grow_tree,
                      leaf_router_engages, predict_tree_binned)
from ..objectives import ObjectiveFunction
from ..ops.renew import leaf_percentile
from ..tree import HostTree, tree_to_host
from ..utils.log import log_info, log_warning

K_EPSILON = 1e-15

# jitted-program cache shared ACROSS boosters: programs whose only
# booster-specific inputs ride as runtime arguments (bin metadata, labels,
# weights, monotone constraints) are keyed by their structural config, so
# cv folds and repeated sklearn fits trace+compile once instead of per
# Booster.  Bounded FIFO — entries hold compiled executables.
_PROGRAM_CACHE: Dict[tuple, object] = {}
_PROGRAM_CACHE_CAP = 64


def _shared_program(key, fn=None):
    """Get (fn is None) or insert a shared jitted program; key=None
    disables sharing (caller keeps a private program)."""
    if key is None:
        return None if fn is None else fn
    if fn is None:
        return _PROGRAM_CACHE.get(key)
    if len(_PROGRAM_CACHE) >= _PROGRAM_CACHE_CAP:
        _PROGRAM_CACHE.pop(next(iter(_PROGRAM_CACHE)))
    _PROGRAM_CACHE[key] = fn
    return fn


class GBDT:
    """reference: class GBDT (src/boosting/gbdt.h)."""

    boosting_type = "gbdt"
    # subclasses with per-iteration host-side model logic (DART's drop &
    # rescale, RF's averaged extension) must keep the eager finish path
    _defer_host_ok = True
    # fused multi-iteration macro-steps (boosting/macro.py): DART's
    # per-iteration host drop & rescale cannot ride inside a lax.scan
    _macro_ok = True
    # quantized-gradient training (use_quantized_grad): DART overrides to
    # False — its host-side drop & rescale re-weights trees whose leaf
    # outputs came from round-local quantization scales, compounding the
    # discretization error in a way the reference never ships
    _quant_ok = True
    # out-of-core streamed execution (lightgbm_tpu/data/): DART needs
    # device re-evaluation of dropped trees over the full matrix and RF
    # renews against running means per iteration — both stay resident
    _stream_ok = True
    # streamed-execution context (data/stream.py StreamContext); None =
    # resident training
    _stream = None

    def __init__(self, config: Config, train_set: Dataset,
                 objective: Optional[ObjectiveFunction]):
        self.config = config
        self.train_set = train_set.construct()
        self.objective = objective
        self.num_class = config.num_class
        self.num_tree_per_iteration = (objective.num_model_per_iteration
                                       if objective is not None else config.num_class)
        self.iter = 0
        self.num_init_iteration = 0        # iterations loaded via init_model
        self._models: List[HostTree] = []  # length = iter * K (drained)
        self.models_version = 0            # bumped on EVERY models mutation
        # (extend/rollback/refit/DART scale) — cache-invalidation token for
        # prediction caches keyed on the model list
        # deferred host materialization: a device->host copy of every
        # tree is a sync per iteration, so on accelerators _finish_iter
        # banks the stacked DEVICE trees here and _drain_pending converts
        # the whole backlog in one bulk transfer when the host list is
        # actually needed (predict/save/eval/len)
        # (abs_iter, shrinkage, stacked device trees, grower counters)
        self._pending: List[tuple] = []
        self._defer_host: Optional[bool] = None   # resolved on first iter
        self.shrinkage_rate = config.learning_rate

        self.meta = self.train_set.feature_meta()
        self.num_data = self.train_set.num_data
        n, F = self.train_set.binned_shape()     # metadata-only accessor:
        # valid for host-resident, released AND block-backed (out-of-core)
        # datasets; captured so _build_jit_fns rebuilds (reset_parameter)
        # never touch the host binned matrix — it may be released below
        self._binned_shape = (n, F)
        # padded bin axis: power-of-two-ish friendly size
        self.num_bins = int(self.meta.max_num_bin)

        # distributed dispatch (reference: GBDT::Init -> CreateTreeLearner,
        # gbdt.cpp:79 + tree_learner.cpp:13-36) — rows (tree_learner=data,
        # voting) or features (tree_learner=feature) are sharded over a
        # device mesh and the WHOLE per-iteration step runs under shard_map
        self._setup_distribution()
        n_pad = self._n_pad
        # out-of-core election (lightgbm_tpu/data/): when the two-level
        # budget planner rules full residency out on either memory (or
        # the Dataset is already block-backed), the matrix stays in the
        # spill store and every histogram pass streams blocks —
        # self.binned stays None and the streamed executor trains
        from ..data.stream import maybe_stream_setup
        # layout (pad, permute, transpose on the host) and the put of the
        # binned matrix: host time until the put is dispatched
        with _span("ingest.to_device", ring=True, rows=n):
            if maybe_stream_setup(self):
                self.binned = None
            elif self._mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P
                if self._data_axis is not None:
                    perm = self._row_perm
                    key = ("data", id(self._mesh), self._data_axis, n_pad,
                           None if perm is None else hash(perm.tobytes()))
                    self.binned = self._cached_device_binned(key)
                    if self.binned is None:
                        src = self.train_set.host_binned()
                        if perm is not None:
                            # query-aligned layout: gather rows (pads -> bin 0)
                            b = np.concatenate(
                                [src, np.zeros((1, src.shape[1]), src.dtype)]
                            )[perm]
                        else:
                            b = np.pad(src, ((0, n_pad - n), (0, 0)))
                        # feature-major device residency (ops/histogram.py
                        # LAYOUT DOCTRINE): minor dim n stays unpadded in the
                        # (8,128)/(32,128) tiles; [n, 28] u8 row-major would
                        # pad 4.6x
                        self.binned = self._cache_device_binned(
                            key, jax.device_put(
                                np.ascontiguousarray(b.T),
                                NamedSharding(self._mesh,
                                              P(None, self._data_axis))))
                else:
                    perm = self._col_perm
                    key = ("feat", id(self._mesh), self._feature_axis,
                           self._f_pad,
                           None if perm is None else hash(perm.tobytes()))
                    self.binned = self._cached_device_binned(key)
                    if self.binned is None:
                        src = self.train_set.host_binned()
                        if perm is not None:
                            # shard-major EFB columns (pads -> all-zero column)
                            b = np.concatenate(
                                [src, np.zeros((src.shape[0], 1), src.dtype)],
                                axis=1)[:, perm]
                        else:
                            b = np.pad(src, ((0, 0), (0, self._f_pad - F)))
                        self.binned = self._cache_device_binned(
                            key, jax.device_put(
                                np.ascontiguousarray(b.T),
                                NamedSharding(self._mesh,
                                              P(self._feature_axis, None))))
            else:
                # n_pad keys the cache: the shape-bucket ladder can pad the
                # serial row axis too (pads -> bin 0, masked everywhere)
                key = ("serial", n_pad)
                self.binned = self._cached_device_binned(key)
                if self.binned is None:
                    src = self.train_set.host_binned()
                    if n_pad > n:
                        src = np.pad(src, ((0, n_pad - n), (0, 0)))
                    self.binned = self._cache_device_binned(
                        key, jnp.asarray(np.ascontiguousarray(src.T)))
        self._row_valid = jnp.asarray(self._pad_rows_np(np.ones(n, np.float32)))
        if objective is not None:
            objective.init(self.train_set.metadata, self.num_data)

        # (self.grower_cfg is derived inside _build_jit_fns, called below)
        K = self.num_tree_per_iteration
        self.train_score = jnp.zeros((K, n_pad), jnp.float32)
        if self._mesh is not None and self._data_axis is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            self.train_score = jax.device_put(
                self.train_score,
                NamedSharding(self._mesh, P(None, self._data_axis)))
        self.init_scores = [0.0] * K
        self._init_score_added = False
        # user-provided init score (reference: score_updater has_init_score)
        if self.train_set.metadata.init_score is not None:
            isc = np.asarray(self.train_set.metadata.init_score, np.float32)
            isc = (isc.reshape(-1, n) if isc.size == K * n else
                   np.broadcast_to(isc.reshape(1, n), (K, n)))
            self.train_score = self.train_score + jnp.asarray(
                np.stack([self._pad_rows_np(row) for row in isc]))
            self._init_score_added = True

        self.valid_sets: List[Dataset] = []
        self.valid_names: List[str] = []
        self.valid_binned: List[jax.Array] = []
        self.valid_scores: List[jax.Array] = []
        self.train_metrics = []
        self.valid_metrics: List[list] = []
        # set i -> (the score upd returned, its metrics' names, their device
        # partials): used only while the set's score is that same array
        self._valid_partials: Dict[int, tuple] = {}

        self._rng = np.random.RandomState(config.bagging_seed)
        self._goss_rng_key = jax.random.PRNGKey(config.bagging_seed)
        # last round's per-class (g_scale, h_scale) quantization factors —
        # device [K, 2] (zeros when quantized training is off); carried
        # alongside the score state, through macro chunk outputs and
        # checkpoint capture/restore (telemetry reads it)
        self._quant_scales = None

        # device-resident history of this run's stacked TreeArrays, so DART
        # drops and rollback re-evaluate trees on device instead of host
        # passes over the full binned matrix ("last": only the most recent
        # iteration, enough for rollback; DART switches to "all")
        self.tree_history: List = []
        self.history_scale: Dict[int, float] = {}
        self._history_mode = "last"

        self._build_jit_fns()

        # device residency established: the host [n, F] binned matrix is a
        # duplicate of self.binned now.  When the user signalled the
        # Dataset is consumed (free_raw_data, the default) drop it —
        # roughly halves peak RSS at HIGGS scale.  Gated to accelerator
        # backends by default (a released Dataset cannot build a second
        # booster / subset / save_binary); LGBM_TPU_FREE_BINNED=1/0
        # overrides either way.
        env_free = os.environ.get("LGBM_TPU_FREE_BINNED", "")
        if self.train_set.free_raw_data and env_free != "0" and (
                env_free == "1" or on_accelerator()):
            self.train_set.release_host_binned()

    # ------------------------------------------------------------------ setup

    def _cached_device_binned(self, key):
        """The Dataset's device-binned cache: a second GBDT on the SAME
        constructed Dataset with the same device layout (mesh, axis,
        padding, permutation) reuses the first upload instead of paying a
        second host->device copy AND a second HBM residency.  This is
        what makes batched multi-booster training (lightgbm_tpu/multi/)
        HBM-cheap in shared-data mode — every lane of a sweep indexes ONE
        matrix (multi/group.py keys shared groups on ``id(binned)``).
        ``release_host_binned`` drops this cache with the host copy — a
        released Dataset keeps its cannot-build-another-booster
        contract."""
        cache = getattr(self.train_set, "_dev_binned_cache", None)
        return cache.get(key) if cache else None

    def _cache_device_binned(self, key, arr):
        cache = getattr(self.train_set, "_dev_binned_cache", None)
        if cache is None:
            cache = self.train_set._dev_binned_cache = {}
        # one entry per layout; two layouts at once (e.g. a serial probe
        # next to a sharded run) is the realistic ceiling — beyond that,
        # evict oldest rather than grow HBM pins unboundedly
        while len(cache) >= 2 and key not in cache:
            cache.pop(next(iter(cache)))
        cache[key] = arr
        return arr

    def _build_forced_plan(self):
        """Parse ``config.forcedsplits_filename`` into plan arrays
        (leaf, inner_feature, threshold_bin), each [n_forced] i32.

        reference: forced_split_json_ loaded at SerialTreeLearner::Init and
        applied by the ForceSplits BFS (serial_tree_learner.cpp:411-521).
        Leaf indices are precomputed here because the grower's split order
        is deterministic: splits apply in BFS order, the left child keeps
        the parent's leaf index, and the right child of the i-th split
        (0-based) gets leaf index i+1.
        """
        fname = self.config.forcedsplits_filename
        if not fname:
            return None
        import json
        from collections import deque

        from ..binning import BinType
        with open(fname) as f:
            root = json.load(f)
        inner = {orig: j for j, orig in
                 enumerate(self.train_set.used_features)}
        mappers = self.train_set.bin_mappers
        leaves: List[int] = []
        feats: List[int] = []
        thrs: List[int] = []
        q = deque()
        if isinstance(root, dict) and "feature" in root and "threshold" in root:
            q.append((root, 0))
        while q and len(leaves) < self.config.num_leaves - 1:
            node, leaf = q.popleft()
            forig = int(node["feature"])
            if forig not in inner:
                log_warning(
                    f"forced split on unused/trivial feature {forig}; "
                    "the rest of the forced-splits plan is dropped")
                break
            m = mappers[forig]
            tb = int(m.value_to_bin(
                np.array([float(node["threshold"])]))[0])
            if m.bin_type == BinType.NUMERICAL:
                tb = min(max(tb, 0), max(m.num_bin - 2, 0))
            leaves.append(leaf)
            feats.append(inner[forig])
            thrs.append(tb)
            right_leaf = len(leaves)      # i+1 for the i-th split
            for side, child_leaf in (("left", leaf), ("right", right_leaf)):
                ch = node.get(side)
                if isinstance(ch, dict) and "feature" in ch \
                        and "threshold" in ch:
                    q.append((ch, child_leaf))
        if not leaves:
            return None
        return (np.asarray(leaves, np.int32), np.asarray(feats, np.int32),
                np.asarray(thrs, np.int32))

    def _setup_distribution(self) -> None:
        """Pick the parallel mode from config.tree_learner and build the
        mesh.  reference: CreateTreeLearner (tree_learner.cpp:13-36); with
        one device every mode degenerates to serial (identical results)."""
        self._mesh = None
        self._data_axis = None
        self._feature_axis = None
        # shape-bucket ladder (ops/planner.py bucket_rows, docs/PERF.md):
        # pad the row count up to the next ladder rung so nearby dataset
        # sizes share ONE compiled training program (the jit caches key on
        # n_pad).  Padded rows ride the existing machinery — row_mask 0,
        # zero gradients, bagging always drops them — so trees are
        # unchanged; integer (quantized) accumulation makes that exact,
        # while f32 reduction trees can shift at ulp level, which is why
        # the default is accelerator-only (LGBM_TPU_SHAPE_BUCKETS
        # overrides either way).
        from ..ops.planner import bucket_rows, shape_buckets_enabled
        self._shape_buckets = shape_buckets_enabled()
        self._n_pad = (bucket_rows(self.num_data) if self._shape_buckets
                       else self.num_data)
        self._f_pad = self.train_set.binned_shape()[1]
        self._meta_dist = None
        self._row_perm = None      # [n_pad] padded-slot -> original row
        self._inv_perm = None      # [n] original row -> padded slot
        self._feat_perm = None     # [F_pad] padded feature slot -> inner
        self._col_perm = None      # [G_pad] padded column slot -> group
        tl = str(self.config.tree_learner).lower()
        aliases = {"data_parallel": "data", "feature_parallel": "feature",
                   "voting_parallel": "voting", "serial_tree_learner": "serial"}
        tl = aliases.get(tl, tl)
        if tl not in ("serial", "data", "feature", "voting"):
            raise ValueError(f"unknown tree_learner {tl!r}")
        self.tree_learner_type = tl
        self._num_slices = 1
        if tl == "serial" or jax.device_count() <= 1:
            return
        from ..parallel.learners import (DATA_AXIS, FEATURE_AXIS, make_mesh,
                                         pad_rows_to)
        ndev = jax.device_count()
        if tl == "feature" and self.config.num_machines > 1:
            # historical num_machines device cap; the data/voting branch
            # gets its shard count from mesh_plan's verdict instead
            ndev = min(ndev, self.config.num_machines)
        need_group = (self.objective is not None and
                      getattr(self.objective, "need_group", False))
        if tl in ("data", "voting"):
            # hybrid ICI x DCN mesh election (pod-scale plane): the
            # reference's num_machines / local_listen_port keys round-trip
            # through parallel/network.mesh_plan — real multi-host
            # topology > simulated slices (LGBM_TPU_NUM_SLICES) >
            # num_machines-as-slice-count > flat.  On a hybrid mesh rows
            # shard over BOTH tiers in the same linear device order as
            # the flat mesh, so electing it never changes shard contents.
            from ..parallel.learners import make_hybrid_mesh
            from ..parallel.network import mesh_plan
            mp = mesh_plan(jax.device_count(),
                           num_machines=self.config.num_machines or None,
                           local_listen_port=self.config.local_listen_port)
            if mp.hybrid:
                self._mesh = make_hybrid_mesh(mp.total_shards,
                                              num_slices=mp.num_slices)
                from ..parallel.learners import HYBRID_AXES
                self._data_axis = HYBRID_AXES
                self._num_slices = mp.num_slices
                ndev = mp.total_shards
            else:
                # the plan's flat verdict also carries the shard COUNT:
                # the historical num_machines device cap, and the
                # shrunk-world device bound of an elastic resume
                ndev = mp.total_shards
                self._mesh = make_mesh(ndev, (DATA_AXIS,))
                self._data_axis = DATA_AXIS
            if need_group:
                # ranking: whole queries per shard (query-aligned layout;
                # shape buckets don't apply — padding is query-driven)
                self._build_query_sharding(ndev)
            else:
                self._n_pad = pad_rows_to(
                    bucket_rows(self.num_data) if self._shape_buckets
                    else self.num_data, ndev)
        else:  # feature
            self._mesh = make_mesh(ndev, (FEATURE_AXIS,))
            self._feature_axis = FEATURE_AXIS
            m = self.meta.resolved()
            if m.has_bundles:
                # shard EFB GROUPS, not raw features (reference partitions
                # features after bundling, feature_parallel_tree_learner.cpp:
                # 33-52): whole bundles per shard, groups/features padded to
                # uniform per-shard counts, meta arranged shard-major
                self._build_group_sharding(ndev, m)
            else:
                F = self.train_set.binned_shape()[1]
                self._f_pad = (F + ndev - 1) // ndev * ndev
                if self._f_pad > F:
                    import dataclasses
                    pad = self._f_pad - F
                    self._meta_dist = dataclasses.replace(
                        m,
                        num_bin=np.concatenate([m.num_bin, np.ones(pad, np.int32)]),
                        missing_type=np.concatenate([m.missing_type, np.zeros(pad, np.int32)]),
                        default_bin=np.concatenate([m.default_bin, np.zeros(pad, np.int32)]),
                        most_freq_bin=np.concatenate([m.most_freq_bin, np.zeros(pad, np.int32)]),
                        is_categorical=np.concatenate([m.is_categorical, np.zeros(pad, bool)]),
                        feat_group=np.arange(self._f_pad, dtype=np.int32),
                        feat_start=np.ones(self._f_pad, np.int32),
                        num_groups=self._f_pad,
                    )
                else:
                    self._meta_dist = m

    def _build_query_sharding(self, ndev: int) -> None:
        """Row layout for distributed ranking: queries are greedily packed
        onto shards (lightest-first) and each shard is padded to the max
        shard size, so no query ever straddles a shard boundary and the
        per-query pairwise lambdas stay shard-local by construction.

        reference analogue: distributed ranking partitions rows at query
        boundaries at load time (Metadata::CheckOrPartition,
        src/io/metadata.cpp:141); the per-query loop is
        rank_objective.hpp:48-65.  Sets ``_n_pad``, ``_row_perm`` (padded
        slot -> original row, ``n`` = padding sentinel), ``_inv_perm``.
        """
        import heapq
        md = self.train_set.metadata
        if md.query_boundaries is None:
            raise RuntimeError("Ranking tasks require query information")
        qb = np.asarray(md.query_boundaries, np.int64)
        sizes = np.diff(qb)
        heap = [(0, d) for d in range(ndev)]
        heapq.heapify(heap)
        shard_queries: List[List[int]] = [[] for _ in range(ndev)]
        for q in range(len(sizes)):
            tot, d = heapq.heappop(heap)
            shard_queries[d].append(q)
            heapq.heappush(heap, (tot + int(sizes[q]), d))
        n_shard = max(1, max((int(sizes[qs].sum()) for qs in shard_queries
                              if qs), default=1))
        self._n_pad = n_shard * ndev
        n = self.num_data
        perm = np.full(self._n_pad, n, np.int64)
        for d, qs in enumerate(shard_queries):
            pos = d * n_shard
            for q in qs:
                lo, hi = int(qb[q]), int(qb[q + 1])
                perm[pos:pos + hi - lo] = np.arange(lo, hi)
                pos += hi - lo
        self._row_perm = perm
        inv = np.empty(n, np.int64)
        inv[perm[perm < n]] = np.nonzero(perm < n)[0]
        self._inv_perm = inv

    def _build_group_sharding(self, ndev: int, m) -> None:
        """Shard-major EFB layout for tree_learner=feature: pack whole
        bundles onto shards (greedy, lightest feature count first), pad
        every shard to G_shard group columns and F_shard features, and
        rewrite the meta arrays in that order with shard-LOCAL group
        indices.  Sets ``_meta_dist``, ``_f_pad``, ``_feat_perm`` (padded
        feature slot -> inner feature, sentinel = F) and ``_col_perm``
        (padded column slot -> group, sentinel = G)."""
        import dataclasses
        import heapq
        F = len(m.num_bin)
        G = m.num_groups
        feats_of: List[List[int]] = [[] for _ in range(G)]
        for f, g in enumerate(np.asarray(m.feat_group)):
            feats_of[int(g)].append(f)
        heap = [(0, d) for d in range(ndev)]
        heapq.heapify(heap)
        shard_groups: List[List[int]] = [[] for _ in range(ndev)]
        for g in sorted(range(G), key=lambda gg: -len(feats_of[gg])):
            cnt, d = heapq.heappop(heap)
            shard_groups[d].append(g)
            heapq.heappush(heap, (cnt + len(feats_of[g]), d))
        G_shard = max(1, max(len(s) for s in shard_groups))
        F_shard = max(1, max(sum(len(feats_of[g]) for g in s)
                             for s in shard_groups))
        if F_shard == G_shard:
            # FeatureMeta.has_bundles tests num_groups != num_features;
            # keep them distinct so the grower stays on the bundle path
            F_shard += 1
        G_pad, F_pad = G_shard * ndev, F_shard * ndev
        col_perm = np.full(G_pad, G, np.int64)
        feat_perm = np.full(F_pad, F, np.int64)
        feat_group_local = np.zeros(F_pad, np.int32)
        for d, gs in enumerate(shard_groups):
            for j, g in enumerate(gs):
                col_perm[d * G_shard + j] = g
            pos = d * F_shard
            for j, g in enumerate(gs):
                for f in feats_of[g]:
                    feat_perm[pos] = f
                    feat_group_local[pos] = j
                    pos += 1

        def takef(arr, fill, dtype):
            ext = np.concatenate(
                [np.asarray(arr, dtype), np.asarray([fill], dtype)])
            return ext[feat_perm]

        self._meta_dist = dataclasses.replace(
            m,
            num_bin=takef(m.num_bin, 1, np.int32),
            missing_type=takef(m.missing_type, 0, np.int32),
            default_bin=takef(m.default_bin, 0, np.int32),
            most_freq_bin=takef(m.most_freq_bin, 0, np.int32),
            is_categorical=takef(m.is_categorical, False, bool),
            feat_group=feat_group_local,
            feat_start=takef(m.feat_start, 1, np.int32),
            num_groups=G_pad,
        )
        self._f_pad = F_pad
        self._feat_perm = feat_perm
        self._col_perm = col_perm

    def _pad_rows_np(self, p: np.ndarray) -> np.ndarray:
        """Pad (and, for query-aligned layouts, permute) a per-row host
        array to the sharded row layout."""
        p = np.asarray(p, np.float32)
        if self._row_perm is not None:
            return np.concatenate([p, np.zeros(1, np.float32)])[self._row_perm]
        pad = self._n_pad - self.num_data
        return np.pad(p, (0, pad)) if pad else p

    def add_valid(self, valid_set: Dataset, name: str) -> None:
        valid_set.construct()
        self.valid_sets.append(valid_set)
        self.valid_names.append(name)
        self.valid_binned.append(jnp.asarray(
            np.ascontiguousarray(valid_set.host_binned().T)))
        K = self.num_tree_per_iteration
        vs = jnp.zeros((K, valid_set.num_data), jnp.float32)
        if valid_set.metadata.init_score is not None:
            isc = np.asarray(valid_set.metadata.init_score, np.float32)
            nv = valid_set.num_data
            vs = vs + jnp.asarray(isc.reshape(-1, nv) if isc.size == K * nv
                                  else np.broadcast_to(isc.reshape(1, nv), (K, nv)))
        self.valid_scores.append(vs)

    def set_metrics(self, train_metrics, valid_metrics_per_set) -> None:
        self.train_metrics = train_metrics
        self.valid_metrics = valid_metrics_per_set

    def _build_jit_fns(self) -> None:
        """Election, grower config and the jitted closures of this
        booster (host work; the programs compile at their first call)."""
        with _span("jit.build", ring=True, what="iter_fns"):
            self._build_jit_fns_inner()

    def _build_jit_fns_inner(self) -> None:
        K = self.num_tree_per_iteration
        nmach = 1
        vote_k = 0
        if self._mesh is not None and self._data_axis is not None:
            from ..parallel.collectives import axis_size
            nmach = axis_size(self._mesh, self._data_axis)
            if self.tree_learner_type == "voting":
                vote_k = self.config.top_k
        # feature_fraction_bynode -> exact per-node sample count
        # (reference: ColSampler::GetCnt, col_sampler.hpp:28-33)
        F_used = len(self.train_set.used_features)
        bynode_cnt = 0
        if self.config.feature_fraction_bynode < 1.0:
            bynode_cnt = max(
                int(round(F_used * self.config.feature_fraction_bynode)),
                min(2, F_used))
        # CEGB wiring (reference: CostEfficientGradientBoosting::IsEnable +
        # Init, cost_effective_gradient_boosting.hpp:25-49): map the
        # per-ORIGINAL-feature penalty lists onto the used (inner) features
        cc = self.config
        coupled = list(cc.cegb_penalty_feature_coupled or [])
        lazy = list(cc.cegb_penalty_feature_lazy or [])
        cegb_enabled = bool(cc.cegb_penalty_split > 0.0 or coupled or lazy)
        ntf = self.train_set.num_total_features
        self._cegb_coupled_pen = None
        self._cegb_lazy_pen = None
        if cegb_enabled:
            if self._mesh is not None and self.tree_learner_type == "voting":
                # recorded design exclusion (see grower.py): exact CEGB
                # needs global per-feature candidates, which voting exists
                # to avoid materializing — data-parallel gives the same
                # result at honest cost
                raise NotImplementedError(
                    "CEGB needs global per-feature candidates; "
                    "voting-parallel exists to avoid building exactly "
                    "those — use tree_learner=data with CEGB instead")
            for name, lst in (("cegb_penalty_feature_coupled", coupled),
                              ("cegb_penalty_feature_lazy", lazy)):
                if lst and len(lst) != ntf:
                    # reference: Log::Fatal at CEGB Init
                    raise ValueError(
                        f"{name} should be the same size as feature number "
                        f"({len(lst)} vs {ntf})")
            uf = np.asarray(self.train_set.used_features, np.int64)

            def _pen_device_layout(vals):
                """Inner-feature penalties -> the grower's global feature
                order (device-slot order under feature sharding; pad slots
                get zero penalty so they can never be selected anyway)."""
                p = np.asarray(vals, np.float32)[uf]
                if self._feat_perm is not None:
                    p = np.concatenate([p, np.zeros(1, np.float32)])[
                        self._feat_perm]
                elif self._feature_axis is not None and self._f_pad > len(p):
                    p = np.concatenate(
                        [p, np.zeros(self._f_pad - len(p), np.float32)])
                return jnp.asarray(p)

            if coupled:
                self._cegb_coupled_pen = _pen_device_layout(coupled)
            if lazy:
                self._cegb_lazy_pen = _pen_device_layout(lazy)
        self._cegb_enabled = cegb_enabled
        # quantized-gradient training (use_quantized_grad): automatic f32
        # fallback with a warn-once for the combos the integer pipeline
        # does not cover (reference: quantized training is likewise gated
        # out of DART-style reweighting and constraint-coupled searches)
        quant_on = bool(cc.use_quantized_grad)
        if quant_on:
            blockers = []
            if not type(self)._quant_ok:
                blockers.append(f"boosting={self.boosting_type}")
            if cegb_enabled:
                blockers.append("CEGB")
            if cc.monotone_constraints:
                blockers.append("monotone_constraints")
            if cc.extra_trees:
                blockers.append("extra_trees (random thresholds)")
            if blockers:
                quant_on = False
                if not getattr(self, "_quant_warned", False):
                    self._quant_warned = True
                    log_warning(
                        "use_quantized_grad=true is not supported with "
                        + ", ".join(blockers)
                        + "; falling back to f32 histograms for this "
                        "booster (training proceeds unquantized)")
        self._quant_on = quant_on
        forced_plan = self._build_forced_plan()
        if forced_plan is not None and self._feat_perm is not None:
            # the grower under sharded-EFB feature layout numbers features
            # by padded DEVICE slot; the plan is built in inner numbering
            Fi = len(self.train_set.used_features)
            inv = np.zeros(Fi, np.int64)
            slot_is_real = self._feat_perm < Fi
            inv[self._feat_perm[slot_is_real]] = \
                np.nonzero(slot_is_real)[0].astype(np.int64)
            forced_plan = (forced_plan[0],
                           inv[np.asarray(forced_plan[1], np.int64)],
                           forced_plan[2])
        # fused Pallas histogram→split megakernel (ops/fused.py) context:
        # the numeric unsharded common case.  hist_method=auto elects it
        # on accelerators when the planner proves the VMEM arena fits
        # (plan_fused, below) AND a one-time numeric probe agreed with
        # the staged pipeline on this backend; an explicit
        # hist_method=fused also runs on CPU (interpret mode — how the
        # tier-1 parity suite executes it).  Computed BEFORE the
        # measured-auto resolution: electing fused must leave the method
        # string "auto" for the planner, and the per-kernel timing probe
        # would be wasted work.
        meta_fused = (self._meta_dist if self._meta_dist is not None
                      else self.meta).resolved()
        fused_ctx = (
            not cegb_enabled and vote_k == 0 and self._stream is None
            and self._feature_axis is None and forced_plan is None
            and not cc.extra_trees and bynode_cnt == 0
            and not meta_fused.has_bundles)
        # categorical features, monotone constraints and data-parallel
        # sharding all ride the fused arm now (the rounds grower's
        # seam-split kernel + pick_fused_best's cat merge) — but the
        # SERIAL grower only lifted monotone, so an explicit serial
        # growth keeps its own narrower gate (grower.py applies it)
        if self.config.tpu_tree_growth == "serial" \
                and (bool(meta_fused.is_categorical.any())
                     or (self._mesh is not None
                         and self._data_axis is not None)):
            fused_ctx = False
        want_fused = fused_ctx and (
            self.config.tpu_hist_method == "fused"
            or (self.config.tpu_hist_method == "auto" and on_accelerator()
                # the serial grower's fused arm streams ALL rows per
                # split (no leaf compaction); auto only elects fused
                # where the per-LEVEL rounds grower can run it
                and self.config.tpu_tree_growth != "serial"))
        fused_demoted = False
        if want_fused and on_accelerator() \
                and self.config.tpu_hist_method != "fused":
            # the one-time numeric parity probe protects the AUTO
            # election only; an EXPLICIT hist_method=fused is honored.
            # Compile errors are nobody's verdict: they propagate from
            # the probe and from the training program alike
            from ..ops.fused import fused_kernel_verified
            want_fused = fused_kernel_verified()
            fused_demoted = not want_fused
        if self.config.tpu_hist_method == "fused" and not fused_ctx \
                and not getattr(self, "_fused_warned", False):
            self._fused_warned = True
            log_warning(
                "tpu_hist_method=fused does not apply to this "
                "configuration (EFB bundles, extra_trees, per-node "
                "column sampling, CEGB, forced splits, streaming, "
                "feature/voting sharding — or categorical/data-parallel "
                "under tpu_tree_growth=serial); falling back to the "
                "staged kernel family")
        # resolve hist_method="auto" by MEASURING the kernel variants on
        # the live accelerator at the training shape (reference: the
        # GetShareStates col-vs-row timed probe, dataset.cpp:589-684);
        # CPU resolves to scatter without probing.  Deferred while a
        # fused election is pending — the planner needs the literal
        # "auto" to elect, and re-resolves below if it declines.
        hist_method = self.config.tpu_hist_method
        if hist_method == "auto" and on_accelerator() and not want_fused \
                and self._stream is None:
            # (streamed boosters skip the probe: it would allocate
            # full-scale synthetic data, and the block fold resolves the
            # kernel family itself — data/stream.py)
            from ..ops.histogram import measured_best_method
            hist_method = measured_best_method(
                self.num_data, self._binned_shape[1], self.num_bins)
        # re-derive the grower config so reset_parameter() of tree
        # hyper-parameters (lambda_l1, min_data_in_leaf, ...) takes effect
        self.grower_cfg = GrowerConfig(
            num_leaves=self.config.num_leaves,
            max_depth=self.config.max_depth,
            hp=self.config.split_hyperparams(),
            hist_method=hist_method,
            num_bins=self.num_bins,
            learning_rate=self.config.learning_rate,
            compact=self.config.tpu_compact_hist,
            round_width=self.config.tpu_round_width,
            voting_top_k=vote_k,
            num_machines=nmach,
            bynode_feature_cnt=bynode_cnt,
            num_feature_shards=(int(self._mesh.shape[self._feature_axis])
                                if self._feature_axis is not None else 1),
            cegb_tradeoff=cc.cegb_tradeoff,
            cegb_penalty_split=cc.cegb_penalty_split,
            cegb_coupled=bool(coupled),
            cegb_lazy=bool(lazy),
            n_forced=0 if forced_plan is None else len(forced_plan[0]),
            forced_exact_parity=self.config.tpu_forced_split_parity,
            quant=quant_on,
            quant_bins=cc.num_grad_quant_bins,
            quant_renew=cc.quant_train_renew_leaf,
        )
        # HBM budget plan (ops/planner.py): model per-variant peak bytes
        # for THIS shape against the device limit and pick {tile_rows,
        # record-arena hoisting, psum narrowing} at trace time.  Planned
        # with PER-SHARD rows so the same verdict governs serial and
        # sharded training (the r5 lesson: an unplanned [n*F, 3] arena
        # requested 157.7 GB against 17.2 GB of HBM).
        from ..ops.planner import apply_plan
        shard_rows = self._n_pad
        if self._mesh is not None and self._data_axis is not None:
            shard_rows = self._n_pad // max(nmach, 1)
        if self._stream is not None:
            # streamed execution: the kernels only ever see one block of
            # rows at a time, so the HBM plan (tile_rows inside a block)
            # is made at block scale
            shard_rows = int(self._stream.store.block_rows)
        # the PADDED device column count, like the device array's leading
        # axis the plan used to read (self.binned may be None when
        # streaming): G_pad under sharded-EFB layout, _f_pad under plain
        # feature sharding, the group count otherwise
        if self._col_perm is not None:
            shard_feats = len(self._col_perm)
        elif self._feature_axis is not None:
            shard_feats = int(self._f_pad)
        else:
            shard_feats = int(self._binned_shape[1])
        if self._feature_axis is not None:
            # the sharded array keeps its GLOBAL shape; each device's
            # kernels see only its feature slice
            shard_feats //= max(int(self._mesh.shape[self._feature_axis]), 1)
        # pod-scale reduction schedule (hybrid ICI x DCN mesh,
        # parallel/collectives.py): the per-tier link model elects flat vs
        # hierarchical — and records voting's DCN payload shrink — at
        # trace time; pinned mode pins one tier-ordered f32 association
        # so flat == hierarchical extends to f32 model text
        self.collective_plan = None
        if nmach > 1 and self._data_axis is not None:
            from ..ops.planner import plan_collectives
            self.collective_plan = plan_collectives(
                features=shard_feats, num_bins=self.num_bins,
                rows_global=self._n_pad, quant=quant_on,
                quant_bins=cc.num_grad_quant_bins,
                num_slices=self._num_slices,
                devices_per_slice=nmach // max(self._num_slices, 1),
                voting_k=vote_k)
            self.grower_cfg = self.grower_cfg._replace(
                num_slices=self._num_slices,
                hier_reduce=self.collective_plan.hierarchical,
                pinned_reduce=self.collective_plan.pinned)
        if want_fused and self.grower_cfg.hist_method == "auto":
            # dry-run the fused VMEM election (plan_histograms emits no
            # trace event and mutates nothing) so a decline can fall
            # back to the measured kernel BEFORE the one real apply_plan
            # — one planner.plan event, modeled on the variant that
            # actually executes, and no hist_pack ratcheting through a
            # provisional plan
            from ..ops.planner import plan_histograms
            probe_plan = plan_histograms(
                rows=shard_rows, features=shard_feats,
                num_bins=self.grower_cfg.num_bins,
                num_leaves=self.grower_cfg.num_leaves,
                quant=self.grower_cfg.quant,
                quant_bins=self.grower_cfg.quant_bins, method="auto",
                round_width=self.grower_cfg.round_width,
                machines=max(nmach, 1), fused_ok=True)
            want_fused = probe_plan.fused
        if not want_fused and self.grower_cfg.hist_method == "auto" \
                and on_accelerator() and self._stream is None:
            # the deferred timed-probe resolution (fused declined or was
            # never in play after all)
            from ..ops.histogram import measured_best_method
            self.grower_cfg = self.grower_cfg._replace(
                hist_method=measured_best_method(
                    self.num_data, self._binned_shape[1], self.num_bins))
        self.grower_cfg, self.hist_plan = apply_plan(
            self.grower_cfg, shard_rows, shard_feats, fused_ok=want_fused)
        # which of the two pass counters this booster's trees feed
        # (_note_trees): fixed here, with the programs, by the predicate
        # the accumulate kernel itself reads; none without the kernel
        self._hist_passes_counter = None
        if self.grower_cfg.hist_method == "fused":
            from ..ops.fused import pass_builds_packed
            cfg = self.grower_cfg
            self._hist_passes_counter = (
                "hist_passes_packed_total" if pass_builds_packed(
                    cfg.num_bins, cfg.fused_feat_tile, shard_feats,
                    cfg.quant, self.train_set.binned_dtype())
                else "hist_passes_compared_total")
        # unified-registry training gauges (the planner.plan trace event
        # itself is emitted inside apply_plan)
        _obs_registry.gauge("train_hist_method").set(
            self.hist_plan.variant)   # resolved variant, never "auto"
        _obs_registry.gauge("train_tile_rows").set(self.hist_plan.tile_rows)
        _obs_registry.gauge("train_hist_predicted_peak_bytes").set(
            int(self.hist_plan.predicted_peak_bytes))
        _obs_registry.gauge("train_hbm_budget_bytes").set(
            int(self.hist_plan.budget_bytes))
        # shape-bucket ladder + election provenance: which rung the row
        # axis landed on and what elected the variant
        _obs_registry.gauge("train_rows_bucketed").set(int(self._n_pad))
        _obs_registry.gauge("train_shape_buckets").set(
            int(getattr(self, "_shape_buckets", False)))
        _obs_registry.gauge("train_hist_elected_by").set(
            "parity_probe" if fused_demoted else self.hist_plan.elected_by)
        if nmach > 1:
            from ..ops.histogram import hist_payload_bytes
            _obs_registry.gauge("train_psum_payload_bytes").set(
                hist_payload_bytes(
                    shard_feats, self.num_bins,
                    rows_global=self._n_pad,
                    quant_bins=(cc.num_grad_quant_bins if quant_on
                                else None)))
        if self.collective_plan is not None:
            # the two-hop ladder's per-tier payloads (docs/OBSERVABILITY
            # .md): what one histogram sync moves over ICI and over DCN
            # under the elected schedule — trace files show the matching
            # per-tier collective.reduce spans
            _obs_registry.gauge("train_ici_payload_bytes").set(
                int(self.collective_plan.ici_bytes))
            _obs_registry.gauge("train_dcn_payload_bytes").set(
                int(self.collective_plan.dcn_bytes))
            _obs_registry.gauge("train_num_slices").set(
                int(self.collective_plan.num_slices))
            _obs_registry.gauge("train_hier_reduce").set(
                int(self.collective_plan.hierarchical))
        # planner plan summaries ride every forensic bundle's fingerprint
        # (obs/flight.py) — the ring may have rolled past the planner
        # instants by the time a long run dies
        from ..obs.flight import global_flight as _flight
        dev0 = jax.devices()[0]
        _flight.set_context(
            device={"platform": dev0.platform, "kind": dev0.device_kind,
                    "count": jax.device_count(),
                    "process_index": jax.process_index(),
                    "process_count": jax.process_count()},
            hist_plan=self.hist_plan.summary(),
            collective_plan=(self.collective_plan.summary()
                             if self.collective_plan is not None else None))
        if not self.hist_plan.feasible:
            log_warning(
                "HBM planner: predicted peak "
                f"{self.hist_plan.predicted_peak_bytes / 1e9:.2f} GB "
                f"exceeds the {self.hist_plan.budget_bytes / 1e9:.2f} GB "
                f"budget even at tile_rows={self.hist_plan.tile_rows}; "
                "training may OOM (set LGBM_TPU_HBM_BYTES / "
                "LGBM_TPU_TILE_ROWS to override)")
        elif self.hist_plan.degraded:
            log_info(
                "HBM planner: untiled peak "
                f"{self.hist_plan.untiled_peak_bytes / 1e9:.2f} GB > "
                f"budget {self.hist_plan.budget_bytes / 1e9:.2f} GB "
                f"({self.hist_plan.limit_source}); streaming row tiles of "
                f"{self.hist_plan.tile_rows} (predicted peak "
                f"{self.hist_plan.predicted_peak_bytes / 1e9:.2f} GB)")
        # cross-tree CEGB device state (reference keeps it in the learner),
        # indexed by the grower's GLOBAL feature id (device slots under
        # feature sharding)
        F_inner = (self._f_pad if self._feature_axis is not None
                   else len(self.train_set.used_features))
        used0 = jnp.zeros((F_inner,), bool)
        rows0 = jnp.zeros((F_inner, self._n_pad) if lazy else (1, 1), bool)
        if lazy and self._mesh is not None and self._data_axis is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            rows0 = jax.device_put(
                rows0, NamedSharding(self._mesh, P(None, self._data_axis)))
        self._cegb_state = (used0, rows0)
        # per-node randomness base key (extra_trees thresholds + by-node
        # column sampling); advanced by iteration in train_one_iter
        self._node_key_base = jax.random.PRNGKey(
            (self.config.extra_trees_seed * 2654435761
             ^ self.config.feature_fraction_seed) % (2 ** 31))
        cfg = self.grower_cfg
        obj = self.objective
        n = self.num_data
        n_pad = self._n_pad
        renew_pct = obj.renew_percentile if obj is not None else None
        weight_np = (np.asarray(self.train_set.metadata.weight, np.float32)
                     if self.train_set.metadata.weight is not None else None)
        label_np = (np.asarray(self.train_set.metadata.label, np.float32)
                    if obj is not None and renew_pct is not None else None)
        # label/weight ride through the (possibly sharded) step as explicit
        # row arrays; dummies when unused (DCE'd by XLA)
        label_a = jnp.asarray(self._pad_rows_np(
            label_np if label_np is not None else np.zeros(n, np.float32)))
        weight_a = jnp.asarray(self._pad_rows_np(
            weight_np if weight_np is not None else np.ones(n, np.float32)))
        use_renew = renew_pct is not None
        mc = self.config.monotone_constraints
        if mc:
            # align per-original-feature constraints with the used (binned)
            # feature columns — trivial features are dropped at binning
            mc_full = np.zeros(self.train_set.num_total_features, np.int32)
            mc_full[:len(mc)] = np.asarray(mc, np.int32)
            mc = mc_full[self.train_set.used_features]
            if self._feat_perm is not None:
                mc = np.concatenate([mc, np.zeros(1, np.int32)])[self._feat_perm]
            elif self._feature_axis is not None and self._f_pad > len(mc):
                mc = np.concatenate(
                    [mc, np.zeros(self._f_pad - len(mc), np.int32)])
            mc = jnp.asarray(mc)
        else:
            mc = None
        meta = self._meta_dist if self._meta_dist is not None else self.meta

        cegb_on = self._cegb_enabled
        coupled_pen = self._cegb_coupled_pen
        lazy_pen = self._cegb_lazy_pen
        # growth strategy: the batched-frontier grower (grower_rounds.py)
        # produces bit-identical trees with ~log2(num_leaves) while_loop
        # steps per tree instead of num_leaves-1; modes it does not cover
        # stay on the serial grower
        growth = self.config.tpu_tree_growth
        rounds_ok = (not cegb_on and cfg.voting_top_k == 0
                     and self._feature_axis is None
                     and forced_plan is None)
        if growth in ("rounds", "fast") and not rounds_ok:
            raise ValueError(
                f"tpu_tree_growth={growth} does not support CEGB, voting, "
                "feature-parallel or forced splits; use serial or auto")
        if growth not in ("auto", "serial", "rounds", "fast"):
            raise ValueError(f"unknown tpu_tree_growth {growth!r}")
        if growth == "fast":
            cfg = self.grower_cfg = cfg._replace(rounds_relaxed=True)
        # auto: rounds only on the accelerator.  Measured (round 4, 200k x
        # 28, 255 leaves): on TPU the serial grower is bound by ~6 ms of
        # per-while-step overhead (2.6 s/tree); on CPU ops are cheap but
        # the rounds body's full-frontier vmapped search is real compute
        # (rounds 19.8 s/tree vs serial 2.4 s/tree there).
        on_accel = on_accelerator()
        use_rounds = growth in ("rounds", "fast") or (
            growth == "auto" and rounds_ok and on_accel)
        # padded-device feature slot -> inner used-feature index (sharded
        # EFB layout); trees must come back in inner feature numbering
        feat_perm_j = (jnp.asarray(self._feat_perm, jnp.int32)
                       if self._feat_perm is not None else None)
        # hoisted to locals so iter_body never closes over `self` (RF sets
        # these BEFORE its second _build_jit_fns call, so build-time
        # capture is current; RF's program is cache-ineligible anyway)
        rf_const_init = getattr(self, "_rf_renew_const_init", False)
        init_scores_c = tuple(float(s) for s in self.init_scores)

        stoch_round = bool(cc.stochastic_rounding)
        quant_bins = int(cc.num_grad_quant_bins)

        def iter_body(binned, score, row_mask, grad, hess, fmask, lr, rng,
                      label_r, weight_r, cegb_used, cegb_rows,
                      axis_name, feature_axis_name,
                      mc_arr=None, meta_args=None):
            """grad/hess: [K, rows]; fmask: [K, F] col-sample masks; lr:
            traced scalar so a learning_rates schedule never recompiles;
            rng: per-iteration PRNG key for node-level randomness;
            cegb_used/cegb_rows: cross-tree CEGB state (pass-through dummies
            when CEGB is off); mc_arr/meta_args: monotone constraints and
            per-feature bin metadata as RUNTIME inputs (shared-program
            mode) — default to the closed-over constants otherwise.
            Returns (new_score, stacked trees, leaf_ids, cegb_used,
            cegb_rows, qscales [K, 2] — per-class quantization scales,
            zeros when quantized training is off — and gstats [K, 8] i32:
            the grower loop's (rounds, candidates offered, splits
            applied, slot widths run, rounds the offer clipped, lanes
            the route compared rows with) per tree, beside the tree and
            not in it, and two zeros where GOSS puts its (kept, top)
            rows of the round: ``boosting/macro.py``)."""
            mc_in = mc if mc_arr is None else mc_arr
            trees = []
            leaf_ids = []
            qscale_rows = []
            gstat_rows = []
            new_score = score
            for k in range(K):
                # quantized-gradient mode: per-round discretization with
                # stochastic rounding seeded from the SAME per-round key
                # stream the node randomness rides (so chunked and
                # per-iteration training replay identical draws); under
                # data sharding each shard folds its axis index in so the
                # rounding noise is i.i.d. across shards while the scales
                # (pmax inside quantize_gradients) stay replicated
                if quant_on:
                    qkey = jax.random.fold_in(
                        jax.random.fold_in(rng, 0x51475442), k)
                    if axis_name is not None:
                        from ..parallel.collectives import axis_index_flat
                        qkey = jax.random.fold_in(
                            qkey, axis_index_flat(axis_name))
                    with jax.named_scope("lgbm.quantize"):
                        quant_vals = quantize_gradients(
                            grad[k], hess[k], row_mask, quant_bins, qkey,
                            stochastic=stoch_round, axis_name=axis_name)
                    qscale_rows.append(jnp.stack([quant_vals[2],
                                                  quant_vals[3]]))
                else:
                    quant_vals = None
                gstat = None
                if cegb_on:
                    tree, leaf_id, (cegb_used, cegb_rows) = grow_tree(
                        binned, grad[k], hess[k], row_mask, meta, cfg,
                        feature_mask=fmask[k], monotone_constraints=mc_in,
                        axis_name=axis_name,
                        feature_axis_name=feature_axis_name,
                        rng_key=jax.random.fold_in(rng, k),
                        cegb_coupled_penalty=coupled_pen,
                        cegb_lazy_penalty=lazy_pen,
                        cegb_feat_used=cegb_used,
                        cegb_used_rows=cegb_rows,
                        forced_plan=forced_plan,
                        meta_arrays=meta_args)
                elif use_rounds:
                    from ..grower_rounds import grow_tree_rounds
                    tree, leaf_id, gstat = grow_tree_rounds(
                        binned, grad[k], hess[k], row_mask, meta, cfg,
                        feature_mask=fmask[k], monotone_constraints=mc_in,
                        axis_name=axis_name,
                        rng_key=jax.random.fold_in(rng, k),
                        meta_arrays=meta_args, quant_vals=quant_vals,
                        with_stats=True)
                else:
                    tree, leaf_id = grow_tree(binned, grad[k], hess[k],
                                              row_mask, meta, cfg,
                                              feature_mask=fmask[k],
                                              monotone_constraints=mc_in,
                                              axis_name=axis_name,
                                              feature_axis_name=feature_axis_name,
                                              rng_key=jax.random.fold_in(rng, k),
                                              forced_plan=forced_plan,
                                              meta_arrays=meta_args,
                                              quant_vals=quant_vals)
                if gstat is None:
                    # the serial grower offers and commits one split a
                    # trip of its loop, in a pass one slot wide, routes its
                    # rows against that one split, and has no offer to clip
                    gstat = jnp.broadcast_to(tree.num_leaves - 1,
                                             (6,)).at[4].set(0)
                gstat_rows.append(jnp.concatenate(
                    [gstat.astype(jnp.int32), jnp.zeros(2, jnp.int32)]))
                if feat_perm_j is not None:
                    tree = tree._replace(
                        split_feature=feat_perm_j[tree.split_feature])
                if use_renew:
                    if rf_const_init:
                        # RF renews leaf outputs against the CONSTANT init
                        # score, not the running average (reference
                        # residual_getter, rf.hpp:130-135); captured as
                        # locals — closing over `self` here would pin the
                        # booster (and its device matrix) inside the
                        # module program cache
                        residual = label_r - jnp.float32(init_scores_c[k])
                    else:
                        residual = label_r - new_score[k]
                    w = row_mask * weight_r
                    pct = leaf_percentile(leaf_id, residual, w,
                                          cfg.num_leaves, float(renew_pct))
                    if axis_name is not None:
                        # reference: distributed RenewTreeOutput averages the
                        # per-machine renewed outputs over machines that have
                        # rows in the leaf (serial_tree_learner.cpp:654-663)
                        has = jax.ops.segment_sum(
                            (w > 0).astype(jnp.float32), leaf_id,
                            num_segments=cfg.num_leaves) > 0
                        cnt = jax.lax.psum(has.astype(jnp.float32), axis_name)
                        pct = jax.lax.psum(jnp.where(has, pct, 0.0), axis_name)
                        pct = pct / jnp.maximum(cnt, 1.0)
                    active = jnp.arange(cfg.num_leaves) < tree.num_leaves
                    tree = tree._replace(
                        leaf_value=jnp.where(active, pct, tree.leaf_value))
                with jax.named_scope("lgbm.leaf_values"):
                    tree = tree._replace(
                        leaf_value=tree.leaf_value * lr,
                        internal_value=tree.internal_value * lr,
                    )
                with jax.named_scope("lgbm.score_update"):
                    new_score = new_score.at[k].add(
                        take_from_table(tree.leaf_value, leaf_id))
                trees.append(tree)
                leaf_ids.append(leaf_id)
            stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)
            qscales = (jnp.stack(qscale_rows) if quant_on
                       else jnp.zeros((K, 2), jnp.float32))
            return (new_score, stacked, jnp.stack(leaf_ids), cegb_used,
                    cegb_rows, qscales, jnp.stack(gstat_rows))

        if self._stream is not None:
            # streamed executor (lightgbm_tpu/data/stream.py): the
            # resident per-iteration/macro programs close over a resident
            # device matrix this mode does not have — never built.  The
            # engine's chunk scheduler sees chunk_supported() False and
            # trains per-iteration; _train_one_iter_inner routes each
            # step through the StreamGrower instead of _iter_fn.
            def one_iter(*_a, **_k):
                raise RuntimeError(
                    "streamed (out-of-core) booster has no resident "
                    "iteration program; training routes through "
                    "data/stream.py")
            self._iter_fn = one_iter
            macro_core = None
        elif self._mesh is None:
            # binned rides as an explicit jit argument: a closed-over
            # device array would be captured as a program CONSTANT, and at
            # HIGGS scale (11M x 28 = 308 MB) constant-embedding bloats
            # lowering/compile.  Per-feature bin metadata, labels/weights
            # and monotone constraints ride as runtime args too, so ONE
            # traced+compiled program serves every structurally-identical
            # booster (cv folds, repeated sklearn fits) via the module
            # program cache below.
            mr = meta.resolved()
            meta_args = meta.as_runtime_arrays()
            mc_j = mc  # device array or None (None -> different pytree)
            cache_key = None
            # RF's const-init renewal reads self.init_scores at TRACE time
            # (set after build) — its program is booster-specific
            if (not cegb_on and forced_plan is None
                    and not (use_renew and rf_const_init)):
                # the trace-time env gates select program VARIANTS (the
                # compile-hang ladders flip them between attempts in one
                # process) — they must key the cache or a variant switch
                # would silently reuse the previous variant's program
                env_gates = tuple(
                    os.environ.get(k, "") for k in
                    ("LGBM_TPU_SEGHIST", "LGBM_TPU_SMALL_ROUNDS",
                     "LGBM_TPU_PACK", "LGBM_TPU_TABLE_MATMUL",
                     "LGBM_TPU_ROUTER", "LGBM_TPU_FUSED"))
                cache_key = (
                    "one_iter", K, n_pad, self.binned.shape,
                    str(self.binned.dtype), cfg, use_rounds, use_renew,
                    renew_pct, obj is None, mc is None,
                    mr.has_bundles, int(mr.max_group_bin),
                    len(mr.num_bin), int(mr.num_groups),
                    bool(mr.is_categorical.any()), env_gates,
                    stoch_round)
            shared = _shared_program(cache_key)
            if shared is None:
                def one_iter_full(binned, score, row_mask, grad, hess,
                                  fmask, lr, rng, cegb_used, cegb_rows,
                                  label_r, weight_r, mc_arr, meta_a):
                    return iter_body(binned, score, row_mask, grad, hess,
                                     fmask, lr, rng, label_r, weight_r,
                                     cegb_used, cegb_rows, None, None,
                                     mc_arr=mc_arr, meta_args=meta_a)
                shared = jax.jit(one_iter_full, donate_argnums=(1,))
                _shared_program(cache_key, shared)

            def one_iter(binned, score, row_mask, grad, hess, fmask, lr,
                         rng, cegb_used, cegb_rows, _fn=shared):
                return _fn(binned, score, row_mask, grad, hess, fmask,
                           lr, rng, cegb_used, cegb_rows,
                           label_a, weight_a, mc_j, meta_args)
            self._iter_fn = one_iter

            def macro_core(binned, score, row_mask, grad, hess, fmask, lr,
                           rng, cu, cr, label_r, weight_r):
                return iter_body(binned, score, row_mask, grad, hess,
                                 fmask, lr, rng, label_r, weight_r, cu, cr,
                                 None, None, mc_arr=mc_j,
                                 meta_args=meta_args)
        else:
            from jax.sharding import PartitionSpec as P
            ax_d, ax_f = self._data_axis, self._feature_axis

            def core(binned, score, row_mask, grad, hess, fmask, lr, rng,
                     label_r, weight_r, cegb_used, cegb_rows):
                return iter_body(binned, score, row_mask, grad, hess, fmask,
                                 lr, rng, label_r, weight_r,
                                 cegb_used, cegb_rows, ax_d, ax_f)

            row = P(ax_d)          # replicated when ax_d is None
            krow = P(None, ax_d)
            # lazy-mode used-rows bitmap is sharded with the rows
            rows_spec = krow if (cegb_on and cfg.cegb_lazy) else P()
            sharded = jax.shard_map(
                core, mesh=self._mesh,
                in_specs=(P(ax_f, ax_d), krow, row, krow, krow, P(), P(),
                          P(), row, row, P(), rows_spec),
                out_specs=(krow, P(), krow, P(), rows_spec, P(), P()),
                check_vma=False)

            def one_iter(binned, score, row_mask, grad, hess, fmask, lr,
                         rng, cegb_used, cegb_rows):
                return sharded(binned, score, row_mask, grad, hess,
                               fmask, lr, rng, label_a, weight_a,
                               cegb_used, cegb_rows)
            self._iter_fn = jax.jit(one_iter, donate_argnums=(1,))

            def macro_core(binned, score, row_mask, grad, hess, fmask, lr,
                           rng, cu, cr, label_r, weight_r):
                return sharded(binned, score, row_mask, grad, hess,
                               fmask, lr, rng, label_r, weight_r, cu, cr)
        if not hasattr(self, "_feature_rng"):  # survive jit-fn rebuilds
            self._feature_rng = np.random.RandomState(
                self.config.feature_fraction_seed)
        self._ones_fmask = None

        perm_j = (jnp.asarray(self._row_perm)
                  if self._row_perm is not None else None)
        inv_perm_j = (jnp.asarray(self._inv_perm)
                      if self._inv_perm is not None else None)

        @jax.named_scope("lgbm.gradients")
        def gradients_fn(score, obj_tables):
            # obj_tables: the objective's ``device_tables``, a runtime
            # argument so that they are no constants of the program
            if obj is None:
                raise RuntimeError("no objective: gradients must be provided")
            if perm_j is not None:
                # query-aligned layout: objective works in ORIGINAL row order
                s = score[:, inv_perm_j]
            else:
                s = score if n_pad == n else score[:, :n]
            s = s if K > 1 else s[0]
            g, h = obj.get_gradients(s, obj_tables)
            g = g.reshape(K, n)
            h = h.reshape(K, n)
            if perm_j is not None:
                zcol = jnp.zeros((K, 1), g.dtype)
                g = jnp.concatenate([g, zcol], axis=1)[:, perm_j]
                h = jnp.concatenate([h, zcol], axis=1)[:, perm_j]
            elif n_pad > n:
                g = jnp.pad(g, ((0, 0), (0, n_pad - n)))
                h = jnp.pad(h, ((0, 0), (0, n_pad - n)))
            return g, h

        obj_tables = getattr(obj, "device_tables", None)
        gradients_jit = jax.jit(gradients_fn)
        self._gradients_fn = lambda score: gradients_jit(score, obj_tables)

        # fused macro-step context (boosting/macro.py): the SAME iter_body
        # (serial or shard_map'd) and the same gradient closure, re-traced
        # inside a lax.scan chunk program; rebuilt alongside the
        # per-iteration programs so reset_parameter invalidates both
        self._macro_core = macro_core
        self._macro_grad = gradients_fn
        self._macro_ctx = {"label": label_a, "weight": weight_a,
                           "obj_tables": obj_tables}
        self._macro_chunk_jit = None
        self._macro_valid_jit = {}
        self._partials_jit = {}
        self._has_forced_plan = forced_plan is not None
        if self._stream is not None:
            # (re)built with the programs so reset_parameter rebuilds
            # refresh the streamed grower's jitted pieces too
            from ..data.stream import StreamGrower
            self._stream.grower = StreamGrower(self)

        # prediction-side programs share across boosters the same way:
        # bin metadata rides as runtime args, keyed on structure only
        mrp = self.meta.resolved()
        pred_meta_args = self.meta.as_runtime_arrays()
        pred_key_tail = (len(mrp.num_bin), int(mrp.num_groups),
                         mrp.has_bundles, int(mrp.max_group_bin))

        # which program finds a row's leaf (grower.predict_leaf_index_binned)
        # is decided here, on the host, from the data set's own metadata;
        # the shared programs are keyed on it
        routed = self._leaf_routed = leaf_router_engages(self.meta)
        pred_key_tail += (routed,)

        vkey = ("valid_update", K) + pred_key_tail
        vfn = _shared_program(vkey)
        if vfn is None:
            def valid_update_full(vscore, stacked_trees, binned, meta_a):
                for k in range(K):
                    tree_k = jax.tree_util.tree_map(lambda x: x[k],
                                                    stacked_trees)
                    vscore = vscore.at[k].add(
                        predict_tree_binned(tree_k, binned, None,
                                            meta_arrays=meta_a,
                                            routed=routed))
                return vscore
            vfn = _shared_program(vkey, jax.jit(valid_update_full,
                                                donate_argnums=(0,)))

        def _valid_update(vscore, trees, binned):
            self._count_valid_update(1)
            return vfn(vscore, trees, binned, pred_meta_args)
        self._valid_update = _valid_update

        # the TRAIN device matrix may have permuted group columns (sharded
        # EFB layout); history-tree traversal over it needs a meta whose
        # feat_group points at the permuted column positions
        meta_train = self.meta
        if self._col_perm is not None:
            import dataclasses
            mr2 = self.meta.resolved()
            inv_col = np.zeros(mr2.num_groups, np.int32)
            valid_cols = self._col_perm < mr2.num_groups
            inv_col[self._col_perm[valid_cols]] = \
                np.nonzero(valid_cols)[0].astype(np.int32)
            meta_train = dataclasses.replace(
                mr2, feat_group=inv_col[np.asarray(mr2.feat_group)],
                num_groups=len(self._col_perm))

        tkey = ("tree_pred",) + pred_key_tail
        tfn = _shared_program(tkey)
        if tfn is None:
            tfn = _shared_program(tkey, jax.jit(
                lambda tree, binned, meta_a:
                predict_tree_binned(tree, binned, None,
                                    meta_arrays=meta_a, routed=routed)))
        self._tree_pred_jit = (lambda tree, binned, _f=tfn:
                               _f(tree, binned, pred_meta_args))
        if self._col_perm is not None or (routed and self._mesh is not None):
            # the walk, whatever the validation sets take: it partitions
            # by rows as it stands, while the path form's row blocks would
            # gather a matrix sharded over the mesh onto every device
            self._tree_pred_train_jit = jax.jit(
                lambda tree, binned: predict_tree_binned(tree, binned,
                                                         meta_train))
        else:
            self._tree_pred_train_jit = self._tree_pred_jit

    # --------------------------------------------------------------- training

    def _bagging_mask(self, it: int) -> jax.Array:
        """reference: GBDT::Bagging (gbdt.cpp:163-244) as a weight mask."""
        c = self.config
        n = self.num_data
        if self.boosting_type == "goss":
            raise RuntimeError("GOSS overrides _bagging_mask")
        need = (c.bagging_freq > 0 and c.bagging_fraction < 1.0)
        need_posneg = (c.pos_bagging_fraction < 1.0 or c.neg_bagging_fraction < 1.0)
        if not (need or need_posneg):
            return self._row_valid
        if it % max(c.bagging_freq, 1) != 0 and self._cur_mask is not None:
            return self._cur_mask
        if need_posneg:
            lbl = np.asarray(self.train_set.metadata.label) > 0
            u = self._rng.rand(n)
            keep = np.where(lbl, u < c.pos_bagging_fraction, u < c.neg_bagging_fraction)
        else:
            # exact count without replacement (matches reference semantics)
            cnt = int(n * c.bagging_fraction)
            idx = self._rng.choice(n, size=cnt, replace=False)
            keep = np.zeros(n, bool)
            keep[idx] = True
        self._cur_mask = jnp.asarray(
            self._pad_rows_np(keep.astype(np.float32)))
        return self._cur_mask

    _cur_mask = None

    def _feature_masks(self) -> jax.Array:
        """Per-tree column sampling (reference: ColSampler by-tree,
        src/treelearner/col_sampler.hpp:19)."""
        K = self.num_tree_per_iteration
        F = len(self.train_set.used_features)   # features, not EFB columns
        Fp = max(self._f_pad, F)                # padded for feature sharding
        frac = self.config.feature_fraction

        def place(inner_masks):   # [K, F] inner order -> [K, Fp] device order
            if self._feat_perm is not None:
                ext = np.concatenate(
                    [inner_masks, np.zeros((K, 1), np.float32)], axis=1)
                return ext[:, self._feat_perm]
            out = np.zeros((K, Fp), np.float32)
            out[:, :F] = inner_masks
            return out

        if frac >= 1.0:
            if self._ones_fmask is None:
                self._ones_fmask = jnp.asarray(
                    place(np.ones((K, F), np.float32)))
            return self._ones_fmask
        cnt = max(1, int(round(F * frac)))
        masks = np.zeros((K, F), np.float32)
        for k in range(K):
            masks[k, self._feature_rng.choice(F, size=cnt, replace=False)] = 1.0
        return jnp.asarray(place(masks))

    def _boost(self, score) -> Tuple[jax.Array, jax.Array]:
        return self._gradients_fn(score)

    def boost_from_average(self) -> None:
        """reference: GBDT::BoostFromAverage (gbdt.cpp:313)."""
        if self.iter > 0 or self.objective is None or self._init_score_added:
            return
        if not self.config.boost_from_average:
            return
        # mark done so a second call in the same iteration (e.g. from a
        # boosting subclass) cannot double-add the init score
        self._init_score_added = True
        K = self.num_tree_per_iteration
        for k in range(K):
            s = self.objective.boost_from_score(k)
            if abs(s) > K_EPSILON:
                self.init_scores[k] = s
                self.train_score = self.train_score.at[k].add(s)
                for i in range(len(self.valid_scores)):
                    self.valid_scores[i] = self.valid_scores[i].at[k].add(s)
                log_info(f"Start training from score {s:.6f}")

    def train_one_iter(self, grad: Optional[np.ndarray] = None,
                       hess: Optional[np.ndarray] = None) -> bool:
        """One boosting iteration; returns True if training should STOP
        (no more splittable leaves).  reference: GBDT::TrainOneIter."""
        from ..utils.timer import global_timer
        with global_timer.section("GBDT::TrainOneIter"):
            return self._train_one_iter_inner(grad, hess)

    def _chunk_single(self) -> Optional[bool]:
        """Run ONE iteration through the fused chunk program (c=1) when
        the macro path is enabled; None = caller takes the legacy path.

        Routing per-iteration training of supported modes through the
        same runtime-trip-count loop body as multi-iteration chunks makes
        training invariant to the chunk decomposition (see
        macro.build_chunk_program) — the invariant behind byte-identical
        chunked vs. per-iteration models and chunk-agnostic
        checkpoint/resume replay.  LGBM_TPU_CHUNK=0 restores the legacy
        per-iteration program for bisection."""
        from .macro import chunk_cap, run_chunk
        if not self.chunk_supported() or chunk_cap() <= 0:
            return None
        return run_chunk(self, 1, None)

    def _train_one_iter_inner(self, grad, hess) -> bool:
        from ..utils.timer import global_timer
        if grad is None:
            single = self._chunk_single()
            if single is not None:
                return single
        K = self.num_tree_per_iteration
        n = self.num_data
        self.boost_from_average()
        if grad is None:
            with global_timer.section("GBDT::Boosting(gradients)"):
                grad, hess = self._boost(self.train_score)
        else:
            grad = np.asarray(grad, np.float32).reshape(K, n)
            hess = np.asarray(hess, np.float32).reshape(K, n)
            if self._n_pad > n:
                grad = np.stack([self._pad_rows_np(r) for r in grad])
                hess = np.stack([self._pad_rows_np(r) for r in hess])
            grad, hess = jnp.asarray(grad), jnp.asarray(hess)
        with global_timer.section("GBDT::Bagging"):
            mask = self._bagging_mask(self.iter)

        if self._stream is not None:
            return self._stream_step(grad, hess, mask)
        with _span("gbdt.dispatch", ring=True, it=self.iter,
                   timer="TreeLearner::Train(dispatch)"):
            (self.train_score, stacked, leaf_ids, cu, cr,
             self._quant_scales, gstats) = self._iter_fn(
                self.binned, self.train_score, mask, grad, hess,
                self._feature_masks(), jnp.float32(self.shrinkage_rate),
                self._node_key(), *self._cegb_state)
            self._cegb_state = (cu, cr)
        return self._finish_iter(stacked, gstats)

    def _node_key(self):
        return jax.random.fold_in(self._node_key_base, self.iter)

    def _stream_step(self, grad, hess, mask) -> bool:
        """One boosting iteration through the out-of-core streamed
        executor (data/stream.py) — the streamed twin of the _iter_fn
        dispatch.  Identical RNG/mask draw order, identical bookkeeping
        via _finish_iter."""
        with _span("stream.iteration", it=self.iter,
                   timer="TreeLearner::Train(dispatch)"):
            (self.train_score, stacked,
             self._quant_scales) = self._stream.grower.run_iteration(
                grad, hess, mask, jnp.float32(self.shrinkage_rate),
                self._node_key(), self._feature_masks())
        return self._finish_iter(stacked)

    # ------------------------------------------------------ fused macro-steps

    def chunk_supported(self) -> bool:
        """True when the fused multi-iteration executor (boosting/macro.py)
        can train this booster.  Paths with per-iteration host logic —
        DART drop/rollback, CEGB penalties, forced splits, custom fobj
        (objective None) — report False and the engine's chunk scheduler
        falls back to c=1 per-iteration training."""
        return (type(self)._macro_ok
                and self._stream is None     # the macro scan cannot
                # device_put host blocks mid-loop; streamed training is
                # per-iteration (and hence trivially chunk-invariant)
                and not self._cegb_enabled
                and not self._has_forced_plan
                and self.objective is not None)

    def train_chunk(self, c: int, lrs=None) -> bool:
        """Train ``c`` boosting iterations in ONE fused, score-donating
        device program (lax.scan over the same iter_body).  Bit-identical
        to ``c`` train_one_iter calls; returns True if training should
        stop (no more splittable leaves)."""
        from ..utils.timer import global_timer
        from .macro import run_chunk
        with global_timer.section("GBDT::TrainChunk"):
            return run_chunk(self, c, lrs)

    def _macro_goss_inputs(self, c: int, it0: int, lrs):
        """Per-iteration GOSS subkeys + sampling flags for a chunk; the
        base class feeds inert dummies (DCE'd by XLA)."""
        key = self._goss_rng_key
        return (jnp.zeros((c,) + key.shape, key.dtype),
                jnp.zeros((c,), bool))

    def _macro_const_grads(self):
        """RF overrides with its constant gradients; dummies otherwise."""
        z = jnp.zeros((1, 1), jnp.float32)
        return z, z

    def _count_valid_update(self, iterations: int) -> None:
        """Trees applied to one validation set, by the program that found
        their rows' leaves (``_leaf_routed``, fixed when the programs were
        built)."""
        _obs_registry.counter(
            "valid_update_trees_routed_total" if self._leaf_routed
            else "valid_update_trees_walked_total").inc(
                iterations * self.num_tree_per_iteration)

    def _chunk_valid_update(self, vscore, stacked_seq, binned, its):
        """The set whose ``binned`` this is, updated by ``upd``; where its
        evaluation takes device forms, ``upd`` also returns their partials
        of the updated score, kept beside it for ``_eval_inner``."""
        self._count_valid_update(its.shape[0])
        i = next((j for j, vb in enumerate(self.valid_binned)
                  if vb is binned), None)
        forms = (self._device_forms(self.valid_metrics[i], self.objective)
                 if i is not None and i < len(self.valid_metrics) else ())
        names = [m.name for m in forms]
        key = tuple(names)
        if key not in self._macro_valid_jit:
            from .macro import build_chunk_valid
            self._macro_valid_jit[key] = build_chunk_valid(self, forms)
        args = (vscore, stacked_seq, binned, its, np.int32(its.shape[0]))
        if not forms:
            return self._macro_valid_jit[key](*args)
        vs, parts = self._macro_valid_jit[key](*args,
                                               forms[0].device_label())
        self._valid_partials[i] = (vs, names, parts)
        return vs

    def _finish_chunk(self, stacked_seq, c: int, shrinks, it0: int,
                      gstats_seq=None) -> bool:
        """Chunk counterpart of _finish_iter: per-iteration bookkeeping
        from ONE stacked ``[c, ...]`` device tree bundle.  Same timer tag
        as _finish_iter — it is the same role, amortized over c.
        ``gstats_seq``: the chunk's ``[c, K, 8]`` grower counters.  The
        seam is host until a block on BOTH paths: the eager path's
        ``device_get`` waits for the chunk, and on the deferred path the
        eager ``x[j]`` slices of ``_chunk_slice`` are dispatches that wait
        for the device once the runtime's queue is full (on the v5e one of
        them held the rest of the round: PERF.md section 5).  Its duration
        is therefore the device's time, not the host's work."""
        with _span("macro.host_fetch", ring=True, it=it0, c=c, it0=it0,
                   timer="GBDT::FinishIter(host trees)"):
            return self._finish_chunk_inner(stacked_seq, c, shrinks, it0,
                                            gstats_seq)

    def _chunk_slice(self, stacked_seq, j: int):
        return jax.tree_util.tree_map(lambda x: x[j], stacked_seq)

    def _chunk_bias_fold(self, st, abs_it: int):
        """Fold the iter-0 init bias into a history slice (mirrors
        _finish_iter's handling of the saved device trees)."""
        if abs_it == 0 and any(abs(s) > K_EPSILON for s in self.init_scores):
            bias = jnp.asarray(self.init_scores, jnp.float32)[:, None]
            st = st._replace(leaf_value=st.leaf_value + bias)
        return st

    def _finish_chunk_inner(self, stacked_seq, c, shrinks, it0,
                            gstats_seq=None) -> bool:
        K = self.num_tree_per_iteration
        if self._defer_enabled():
            # bank per-iteration device slices; host conversion stays one
            # bulk transfer at _drain_pending, stop detection moves there
            # exactly as on the per-iteration deferred path
            for j in range(c):
                self._pending.append(
                    (it0 + j, shrinks[j], self._chunk_slice(stacked_seq, j),
                     None if gstats_seq is None else gstats_seq[j]))
            if self._history_mode == "all":
                for j in range(c):
                    self.tree_history.append(self._chunk_bias_fold(
                        self._chunk_slice(stacked_seq, j), it0 + j))
            else:
                self.tree_history = [self._chunk_bias_fold(
                    self._chunk_slice(stacked_seq, c - 1), it0 + c - 1)]
            self.models_version += 1
            its = jnp.arange(it0, it0 + c, dtype=jnp.int32)
            for i in range(len(self.valid_scores)):
                self.valid_scores[i] = self._chunk_valid_update(
                    self.valid_scores[i], stacked_seq,
                    self.valid_binned[i], its)
            self.iter += c
            return False
        # eager path: ONE bulk device->host transfer for the whole chunk,
        # then the per-iteration host bookkeeping of _finish_iter_inner
        bh, gh = jax.device_get((stacked_seq, gstats_seq))
        stopped = False
        kept = 0
        for j in range(c):
            abs_it = it0 + j
            new_models, any_split = [], False
            for k in range(K):
                tree_k = jax.tree_util.tree_map(
                    lambda x: np.asarray(x[j][k]), bh)
                ht = tree_to_host(tree_k, self.train_set, shrinks[j])
                if ht.num_leaves > 1:
                    any_split = True
                if abs_it == 0 and abs(self.init_scores[k]) > K_EPSILON:
                    ht.add_bias(self.init_scores[k])
                new_models.append(ht)
            if not any_split:
                if abs_it == 0 and not self.models:
                    for k, ht in enumerate(new_models):
                        ht.leaf_value[:1] = self.init_scores[k]
                    self.models.extend(new_models)
                stopped = True
                break
            self.models.extend(new_models)
            self._note_trees(abs_it, None if gh is None else gh[j])
            for k in range(K):
                self.history_scale[len(self.models) - K + k] = 1.0
            kept = j + 1
        self.models_version += 1
        if stopped:
            log_warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
        if kept:
            if self._history_mode == "all":
                for j in range(kept):
                    self.tree_history.append(self._chunk_bias_fold(
                        self._chunk_slice(stacked_seq, j), it0 + j))
            else:
                self.tree_history = [self._chunk_bias_fold(
                    self._chunk_slice(stacked_seq, kept - 1),
                    it0 + kept - 1)]
            seq_kept = (stacked_seq if kept == c else
                        jax.tree_util.tree_map(lambda x: x[:kept],
                                               stacked_seq))
            its = jnp.arange(it0, it0 + kept, dtype=jnp.int32)
            for i in range(len(self.valid_scores)):
                self.valid_scores[i] = self._chunk_valid_update(
                    self.valid_scores[i], seq_kept, self.valid_binned[i],
                    its)
        self.iter = it0 + kept
        return stopped

    @property
    def models(self) -> List[HostTree]:
        """Host trees; drains any deferred device trees first.  Returns the
        live list (callers mutate it in place: rollback, DART rescale)."""
        self._drain_pending()
        return self._models

    @models.setter
    def models(self, value) -> None:
        self._pending = []
        self._models = value

    def _defer_enabled(self) -> bool:
        if self._defer_host is None:
            env = os.environ.get("LGBT_DEFER_HOST_TREES")
            if env is not None:
                self._defer_host = env == "1" and type(self)._defer_host_ok
            else:
                # an accelerator pays a sync per D2H copy; local CPU
                # copies are free and the eager path's per-iteration
                # stop check is reference-exact there
                self._defer_host = (type(self)._defer_host_ok
                                    and on_accelerator())
        return self._defer_host

    def _drain_pending(self) -> None:
        """Materialize deferred device trees as HostTrees in ONE bulk
        device->host transfer (per tree field, not per tree).

        reference semantics preserved at drain time: iteration-0 init-score
        bias (GBDT::Train, gbdt.cpp:387-405 AsConstantTree) and
        stop-on-no-splittable-leaves, which truncates the model at the
        first all-stump iteration.  Deviation (documented): iterations that
        ran AFTER such a stop already added their root-Newton-step outputs
        to train_score/valid_scores before the drain noticed; the eager
        path stops the loop instead.  Only degenerate configs (nothing
        splittable) hit this, and only on the deferred/accelerator path.
        """
        if not self._pending:
            return
        with _span("gbdt.drain_pending", ring=True,
                   it=self._pending[-1][0], pending=len(self._pending)):
            self._drain_pending_inner()

    def _note_trees(self, abs_it: int, gstats) -> None:
        """One ``grower.tree`` flight-ring record a tree, where the host
        takes it: ``gstats`` is the iteration's ``[K, 8]`` (rounds,
        offered, applied, slots, clipped, lanes) of the grower's loop and
        GOSS's (kept, top) rows of the round (0 for a tree grown on no
        sample), pulled beside the trees, or None (streamed executor).
        The rounds also
        count into ``grower_rounds_routed_total`` or ``_scanned_total``, by
        the form their program routes rows in, and the tree's accumulate
        passes (a round each, and the root) into ``hist_passes_packed_total``
        or ``_compared_total``, by the form the kernel builds its one-hot
        operands in (``ops/fused.packed_operands``)."""
        if gstats is None:
            return
        from ..grower_rounds import router_engages
        routed = _obs_registry.counter(
            "grower_rounds_routed_total" if router_engages()
            else "grower_rounds_scanned_total")
        passes = (_obs_registry.counter(self._hist_passes_counter)
                  if self._hist_passes_counter else None)
        for k in range(self.num_tree_per_iteration):
            (rounds, offered, applied, slots, clipped, lanes, goss_kept,
             goss_top) = (int(v) for v in gstats[k])
            routed.inc(rounds)
            if passes is not None:
                passes.inc(rounds + 1)
            _flight_note("grower.tree", it=abs_it, k=k, rounds=rounds,
                         offered=offered, applied=applied, slots=slots,
                         clipped=clipped, lanes=lanes, goss_kept=goss_kept,
                         goss_top=goss_top)

    def _drain_pending_inner(self) -> None:
        K = self.num_tree_per_iteration
        pend = self._pending
        self._pending = []
        # the grower's counters ride the trees' transfer
        stackeds = [(st, gs) for (_it, _sr, st, gs) in pend]
        if len(stackeds) == 1:
            hosts = [jax.device_get(stackeds[0])]
        else:
            bulk = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                          *stackeds)
            bh = jax.device_get(bulk)
            hosts = [jax.tree_util.tree_map(lambda x: x[t], bh)
                     for t in range(len(stackeds))]
        stopped_at = None
        for (abs_it, shrink, _, _), (th, gs) in zip(pend, hosts):
            new_models, any_split = [], False
            for k in range(K):
                tree_k = jax.tree_util.tree_map(lambda x: np.asarray(x[k]),
                                                th)
                ht = tree_to_host(tree_k, self.train_set, shrink)
                if ht.num_leaves > 1:
                    any_split = True
                if abs_it == 0 and abs(self.init_scores[k]) > K_EPSILON:
                    ht.add_bias(self.init_scores[k])
                new_models.append(ht)
            if not any_split:
                if abs_it == 0 and not self._models:
                    for k, ht in enumerate(new_models):
                        ht.leaf_value[:1] = self.init_scores[k]
                    self._models.extend(new_models)
                stopped_at = abs_it
                break
            self._models.extend(new_models)
            self._note_trees(abs_it, gs)
            for k in range(K):
                self.history_scale[len(self._models) - K + k] = 1.0
        self.models_version += 1
        if stopped_at is not None:
            log_warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            # rewind bookkeeping to the stop point; the dropped tail's
            # history entries go with it
            dropped = self.iter - stopped_at
            if self._history_mode == "all" and dropped > 0:
                del self.tree_history[len(self.tree_history) - dropped:]
            self.iter = stopped_at

    def _finish_iter(self, stacked, gstats=None) -> bool:
        """Post-step bookkeeping shared by GBDT/GOSS/DART/RF: host copies of
        the (tiny) tree arrays, first-iteration bias folding, valid-score
        updates.  Returns True when training should stop.  ``gstats``: the
        iteration's ``[K, 8]`` grower counters (device).  Host until a
        block, like ``macro.host_fetch`` (``_finish_chunk``): the eager
        path's host copies wait for the device, and the deferred path's
        eager device ops may."""
        with _span("gbdt.finish_iter", ring=True, it=self.iter,
                   timer="GBDT::FinishIter(host trees)"):
            return self._finish_iter_inner(stacked, gstats)

    def _finish_iter_inner(self, stacked, gstats=None) -> bool:
        K = self.num_tree_per_iteration
        if self._defer_enabled():
            # bank the device trees; host conversion happens in bulk at
            # _drain_pending.  Never stops eagerly — stop detection moves
            # to the drain.
            # shrinkage is recorded NOW: a reset_parameter learning-rate
            # schedule changes self.shrinkage_rate between bank and drain
            self._pending.append((self.iter, self.shrinkage_rate, stacked,
                                  gstats))
            st = stacked
            if self.iter == 0 and any(abs(s) > K_EPSILON
                                      for s in self.init_scores):
                bias = jnp.asarray(self.init_scores, jnp.float32)[:, None]
                st = st._replace(leaf_value=st.leaf_value + bias)
            if self._history_mode == "all":
                self.tree_history.append(st)
            else:
                self.tree_history = [st]
            self.models_version += 1
            for i in range(len(self.valid_scores)):
                self.valid_scores[i] = self._valid_update(
                    self.valid_scores[i], stacked, self.valid_binned[i])
            self.iter += 1
            return False
        new_models = []
        should_continue = False
        for k in range(K):
            tree_k = jax.tree_util.tree_map(lambda x: np.asarray(x[k]), stacked)
            ht = tree_to_host(tree_k, self.train_set, self.shrinkage_rate)
            if ht.num_leaves > 1:
                should_continue = True
            if self.iter == 0 and abs(self.init_scores[k]) > K_EPSILON:
                ht.add_bias(self.init_scores[k])
            new_models.append(ht)
        if not should_continue:
            log_warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            if self.iter == 0 and not self.models:
                # reference: first-iteration stumps are kept as CONSTANT
                # trees carrying the boost-from-average output, so the
                # model predicts the baseline (gbdt.cpp:387-405
                # AsConstantTree); later-iteration stumps are dropped
                for k, ht in enumerate(new_models):
                    ht.leaf_value[:1] = self.init_scores[k]
                self.models.extend(new_models)
                self.models_version += 1
            return True
        self.models.extend(new_models)
        self.models_version += 1
        self._note_trees(self.iter,
                         None if gstats is None else np.asarray(gstats))

        # keep the device trees for drop/rollback re-evaluation; fold the
        # iter-0 init bias into the saved leaf values so a saved tree's
        # device output equals its HostTree counterpart's (add_bias above)
        st = stacked
        if self.iter == 0 and any(abs(s) > K_EPSILON for s in self.init_scores):
            bias = jnp.asarray(self.init_scores, jnp.float32)[:, None]
            st = st._replace(leaf_value=st.leaf_value + bias)
        if self._history_mode == "all":
            self.tree_history.append(st)
        else:
            self.tree_history = [st]
        for k in range(K):
            self.history_scale[len(self.models) - K + k] = 1.0

        for i in range(len(self.valid_scores)):
            self.valid_scores[i] = self._valid_update(
                self.valid_scores[i], stacked, self.valid_binned[i])
        self.iter += 1
        return False

    # ------------------------------------------------------------ checkpoint

    def capture_state(self) -> dict:
        """Pickle-able snapshot of EVERY mutable training-loop state:
        host trees, device score arrays, all RNG streams, bagging mask,
        device tree history.  ``restore_state`` of this dict into a
        structurally-identical booster makes the continued run replay the
        same random decisions and accumulate the same float32 sums — the
        contract behind resilience/checkpoint.py's bit-identical resume.

        Reading ``self.models`` drains any deferred device trees first,
        so the deferred-host accelerator path checkpoints correctly (at
        the cost of one bulk D2H per checkpoint)."""
        import copy as _copy
        models = [_copy.deepcopy(m) for m in self.models]
        return {
            "boosting_type": self.boosting_type,
            "iter": self.iter,
            "num_init_iteration": self.num_init_iteration,
            # the row layout this state was captured under: an ELASTIC
            # resume restores into a DIFFERENT mesh (fewer shards after a
            # slice loss — docs/RESILIENCE.md), and restore_state re-tiles
            # every per-row array through the original layout
            "n_pad": int(self._n_pad),
            "num_data": int(self.num_data),
            "row_perm": (np.asarray(self._row_perm)
                         if self._row_perm is not None else None),
            "models": models,
            "train_score": np.asarray(jax.device_get(self.train_score)),
            "valid_scores": [np.asarray(jax.device_get(v))
                             for v in self.valid_scores],
            "init_scores": list(self.init_scores),
            "init_score_added": self._init_score_added,
            "shrinkage_rate": float(self.shrinkage_rate),
            "bagging_rng": self._rng.get_state(),
            "goss_rng_key": np.asarray(jax.device_get(self._goss_rng_key)),
            "feature_rng": self._feature_rng.get_state(),
            "cur_mask": (np.asarray(jax.device_get(self._cur_mask))
                         if self._cur_mask is not None else None),
            "history_mode": self._history_mode,
            "history_scale": dict(self.history_scale),
            "tree_history": [
                jax.tree_util.tree_map(lambda x: np.asarray(
                    jax.device_get(x)), st) for st in self.tree_history],
            # cross-tree CEGB device state (per-feature used set + lazy
            # row coverage): already-charged penalties must not be charged
            # again after resume
            "cegb_state": tuple(np.asarray(jax.device_get(a))
                                for a in self._cegb_state),
            # last round's gradient-quantization scales (use_quantized_grad
            # telemetry; rides the checkpoint so a resumed run reports the
            # same payload accounting it left off with)
            "quant_scales": (np.asarray(jax.device_get(self._quant_scales))
                             if self._quant_scales is not None else None),
        }

    def restore_state(self, st: dict) -> None:
        """Inverse of ``capture_state`` into a freshly-constructed booster
        of the SAME config/dataset (engine.py builds it before calling)."""
        import copy as _copy
        if st.get("boosting_type") != self.boosting_type:
            raise ValueError(
                f"checkpoint was boosting={st.get('boosting_type')!r}, this "
                f"run is boosting={self.boosting_type!r}")
        if len(st["valid_scores"]) != len(self.valid_scores):
            raise ValueError(
                f"checkpoint has {len(st['valid_scores'])} valid sets, this "
                f"run has {len(self.valid_scores)}")
        self.iter = int(st["iter"])
        self.num_init_iteration = int(st["num_init_iteration"])
        self._pending = []
        self._models = [_copy.deepcopy(m) for m in st["models"]]
        # elastic resume (docs/RESILIENCE.md): the bundle may have been
        # captured under a DIFFERENT row layout (more shards before a
        # slice loss -> larger n_pad / different query permutation).
        # Re-tile every per-row array through the ORIGINAL row order into
        # this booster's layout; padding rows carry zeros either way, so
        # re-tiling is exact — the resumed sums start from the same f32
        # values the old world held
        if "row_perm" not in st:
            # legacy bundle (pre pod-scale): the layout keys were never
            # captured, and the pre-elastic contract was same-world
            # restore — assign directly, NEVER guess a re-tile (treating
            # "absent" as "unpermuted" would scramble a query-sharded
            # ranking resume)
            old_np, old_perm, same_layout = self._n_pad, None, True
        else:
            old_np = st.get("n_pad")
            if old_np is None:
                old_np = int(np.asarray(st["train_score"]).shape[-1])
            old_perm = st.get("row_perm")
            old_perm = np.asarray(old_perm) if old_perm is not None else None
            same_layout = (int(old_np) == self._n_pad
                           and (old_perm is None) == (self._row_perm is None)
                           and (old_perm is None
                                or np.array_equal(old_perm, self._row_perm)))

        def retile(a):
            """Old padded row layout -> this booster's, trailing axis."""
            if a is None or same_layout:
                return a
            a = np.asarray(a)
            n = self.num_data
            if old_perm is not None:
                valid = old_perm < n
                unpad = np.zeros(a.shape[:-1] + (n,), a.dtype)
                unpad[..., old_perm[valid]] = a[..., np.nonzero(valid)[0]]
            else:
                unpad = a[..., :n]
            if self._row_perm is not None:
                ext = np.concatenate(
                    [unpad, np.zeros(a.shape[:-1] + (1,), a.dtype)],
                    axis=-1)
                return ext[..., self._row_perm]
            pad = self._n_pad - n
            if pad:
                return np.concatenate(
                    [unpad, np.zeros(a.shape[:-1] + (pad,), a.dtype)],
                    axis=-1)
            return unpad

        ts = retile(st["train_score"])
        if self._mesh is not None and self._data_axis is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            self.train_score = jax.device_put(
                np.asarray(ts),
                NamedSharding(self._mesh, P(None, self._data_axis)))
        else:
            self.train_score = jnp.asarray(ts)
        self.valid_scores = [jnp.asarray(v) for v in st["valid_scores"]]
        self.init_scores = list(st["init_scores"])
        self._init_score_added = bool(st["init_score_added"])
        self.shrinkage_rate = float(st["shrinkage_rate"])
        self._rng.set_state(st["bagging_rng"])
        self._goss_rng_key = jnp.asarray(st["goss_rng_key"])
        self._feature_rng.set_state(st["feature_rng"])
        self._cur_mask = (jnp.asarray(retile(st["cur_mask"]))
                          if st["cur_mask"] is not None else None)
        self._history_mode = st["history_mode"]
        self.history_scale = dict(st["history_scale"])
        self.tree_history = [jax.tree_util.tree_map(jnp.asarray, t)
                             for t in st["tree_history"]]
        used0, rows0 = st["cegb_state"]
        if np.asarray(rows0).shape != (1, 1):
            rows0 = retile(rows0)
        rows0 = jnp.asarray(rows0)
        if rows0.shape != (1, 1) and self._mesh is not None \
                and self._data_axis is not None:
            # lazy-mode row bitmap is row-sharded (mirrors __init__)
            from jax.sharding import NamedSharding, PartitionSpec as P
            rows0 = jax.device_put(
                np.asarray(rows0),
                NamedSharding(self._mesh, P(None, self._data_axis)))
        self._cegb_state = (jnp.asarray(used0), rows0)
        qs = st.get("quant_scales")
        self._quant_scales = jnp.asarray(qs) if qs is not None else None
        self.models_version += 1

    def refit_leaf_values(self, leaf_preds: np.ndarray,
                          decay_rate: float) -> None:
        """Refit every tree's leaf values against THIS dataset's gradients,
        keeping tree structures fixed.

        reference: GBDT::RefitTree (gbdt.cpp:267-290) routes each row by
        ``leaf_preds`` (pred_leaf output on the new data), recomputes leaf
        sums per tree, and blends
        ``decay * old + (1 - decay) * new_output * shrinkage``
        (SerialTreeLearner::FitByExistingTree, serial_tree_learner.cpp:198-229).
        """
        K = self.num_tree_per_iteration
        n = self.num_data
        leaf_preds = np.asarray(leaf_preds)
        if leaf_preds.ndim == 1:
            leaf_preds = leaf_preds[:, None]
        if leaf_preds.shape != (n, len(self.models)):
            raise ValueError(
                f"leaf_preds shape {leaf_preds.shape} != "
                f"({n}, {len(self.models)})")
        c = self.config
        for it in range(len(self.models) // K):
            grad, hess = self._boost(self.train_score)
            if self._inv_perm is not None:
                g = np.asarray(grad)[:, self._inv_perm]
                h = np.asarray(hess)[:, self._inv_perm]
            else:
                g = np.asarray(grad)[:, :n]
                h = np.asarray(hess)[:, :n]
            for k in range(K):
                mi = it * K + k
                m = self.models[mi]
                lp = leaf_preds[:, mi].astype(np.int64)
                if lp.max(initial=0) >= m.num_leaves:
                    raise ValueError("leaf prediction out of range")
                sg = np.bincount(lp, weights=g[k], minlength=m.num_leaves)
                sh = np.bincount(lp, weights=h[k], minlength=m.num_leaves) \
                    + K_EPSILON
                reg = np.sign(sg) * np.maximum(np.abs(sg) - c.lambda_l1, 0.0)
                out = -reg / (sh + c.lambda_l2)
                if c.max_delta_step > 0:
                    out = np.clip(out, -c.max_delta_step, c.max_delta_step)
                m.leaf_value = (decay_rate * m.leaf_value
                                + (1.0 - decay_rate) * out * m.shrinkage)
                self.models_version += 1
                self.train_score = self.train_score.at[k].add(
                    jnp.asarray(self._pad_rows_np(m.leaf_value[lp])))

    def _tree_pred_device(self, model_idx: int, binned,
                          dataset: Dataset) -> jax.Array:
        """A stored tree's current score contribution over ``binned``
        (device array), via the device history when available; host
        traversal fallback for init-model trees that were never grown in
        this run.  Output rows match ``binned``'s row count."""
        K = self.num_tree_per_iteration
        it, k = divmod(model_idx, K)
        own_it = it - self.num_init_iteration
        own_total = self.iter - self.num_init_iteration
        hist_idx = (own_it if self._history_mode == "all"
                    else own_it - (own_total - len(self.tree_history)))
        if 0 <= hist_idx < len(self.tree_history):
            tree_k = jax.tree_util.tree_map(
                lambda x: x[k], self.tree_history[hist_idx])
            fn = (self._tree_pred_train_jit if binned is self.binned
                  else self._tree_pred_jit)
            out = fn(tree_k, binned)
            scale = self.history_scale.get(model_idx, 1.0)
            return out * jnp.float32(scale) if scale != 1.0 else out
        p = self.models[model_idx].predict_binned_np(
            dataset.host_binned(), dataset.feat_group, dataset.feat_start)
        if binned.shape[1] > len(p):
            p = np.pad(p, (0, binned.shape[1] - len(p)))
        return jnp.asarray(p, jnp.float32)

    def rollback_one_iter(self) -> None:
        """reference: GBDT::RollbackOneIter (gbdt.cpp:422)."""
        if self.iter <= 0:
            return
        if self._stream is not None:
            raise RuntimeError(
                "rollback_one_iter re-evaluates trees over the resident "
                "binned matrix; an out-of-core streamed booster has none "
                "(DART and rollback stay resident — LGBM_TPU_STREAM=0)")
        K = self.num_tree_per_iteration
        first = len(self.models) - K
        for k in range(K):
            self.train_score = self.train_score.at[k].add(
                -self._tree_pred_device(first + k, self.binned,
                                        self.train_set))
            for i in range(len(self.valid_scores)):
                self.valid_scores[i] = self.valid_scores[i].at[k].add(
                    -self._tree_pred_device(first + k, self.valid_binned[i],
                                            self.valid_sets[i]))
            self.history_scale.pop(first + k, None)
        del self.models[-K:]
        self.models_version += 1
        if self.tree_history:
            self.tree_history.pop()
        self.iter -= 1

    # ------------------------------------------------------------------- eval

    def eval_train(self) -> List[Tuple[str, str, float, bool]]:
        return self._eval("training", self.train_score, self.train_metrics,
                          self.objective)

    def eval_valid(self) -> List[Tuple[str, str, float, bool]]:
        out = []
        for i, name in enumerate(self.valid_names):
            out.extend(self._eval(name, self.valid_scores[i],
                                  self.valid_metrics[i], self.objective))
        return out

    def eval_one_valid(self, i: int) -> List[Tuple[str, str, float, bool]]:
        return self._eval(self.valid_names[i], self.valid_scores[i],
                          self.valid_metrics[i], self.objective)

    def _eval(self, dataname, score, metrics, objective):
        with _span("gbdt.eval", dataset=dataname,
                   timer="GBDT::EvalMetrics"):
            return self._eval_inner(dataname, score, metrics, objective)

    def _device_forms(self, metrics, objective) -> tuple:
        """The metrics of one set that its evaluation reduces on the
        device (``metrics.py``): one output a row, an objective, and the
        metric's own ``device_ready`` (no weights, among others)."""
        if self.num_tree_per_iteration != 1 or objective is None:
            return ()
        return tuple(m for m in metrics if m.device_ready())

    def _metric_partials(self, score, forms, rows: int):
        """``forms``' partials over the first ``rows`` rows of ``score``:
        the ones ``upd`` returned with this very array, else the same
        reduction jitted alone (the training score, a score changed after
        ``upd``: rollback, DART, an init score added)."""
        names = [m.name for m in forms]
        for kept, kept_names, parts in self._valid_partials.values():
            if kept is score and kept_names == names:
                return parts
        key = (tuple(names), rows)
        if key not in self._partials_jit:
            from ..metrics import device_partials
            objective = self.objective

            def metric_partials(score, label):
                return device_partials(forms, score[0, :rows], label,
                                       objective)
            self._partials_jit[key] = jax.jit(metric_partials)
        return self._partials_jit[key](score, forms[0].device_label())

    def _eval_inner(self, dataname, score, metrics, objective):
        # annotations only: a device-idle gap inside ``engine.eval`` splits
        # on the trace into the pull (the device forms' partials, the score
        # where a metric has none) and each metric
        training = dataname == "training"
        forms = (() if training and self._inv_perm is not None
                 else self._device_forms(metrics, objective))
        partials, score_np = {}, None
        with _span("eval.pull"):
            if forms:
                rows = self.num_data if training else score.shape[-1]
                partials = dict(zip(forms, jax.device_get(
                    self._metric_partials(score, forms, rows))))
            if len(partials) < len(metrics):
                score_np = np.asarray(score)
        if score_np is not None:
            if training:
                if self._inv_perm is not None:
                    score_np = score_np[:, self._inv_perm]  # query layout
                elif score_np.shape[-1] > self.num_data:
                    score_np = score_np[:, :self.num_data]  # drop pad rows
            s = score_np if self.num_tree_per_iteration > 1 else score_np[0]
        out = []
        for m in metrics:
            on_device = m in partials
            with _span("metric." + getattr(m, "name", type(m).__name__)):
                res = (m.finish(partials[m]) if on_device
                       else m.eval(s, objective))
            _obs_registry.counter("eval_metrics_device_total" if on_device
                                  else "eval_metrics_host_total").inc()
            out.extend((dataname, mname, val, hib)
                       for (mname, val, hib) in res)
        return out

    # -------------------------------------------------------------- inference

    def predict_raw(self, X: np.ndarray, num_iteration: int = -1,
                    start_iteration: int = 0) -> np.ndarray:
        """Raw scores for a raw-feature matrix (host traversal)."""
        K = self.num_tree_per_iteration
        X = np.ascontiguousarray(np.asarray(X, np.float64))
        n = X.shape[0]
        out = np.zeros((K, n), np.float64)
        K_total = len(self.models) // K if K else 0
        stop = K_total if num_iteration < 0 else min(start_iteration + num_iteration, K_total)
        for it in range(start_iteration, stop):
            for k in range(K):
                out[k] += self.models[it * K + k].predict_np(X)
        return out if K > 1 else out[0]

    def num_trees(self) -> int:
        return len(self.models)

    def current_iteration(self) -> int:
        return self.iter

