"""Fast batch prediction over a stacked forest.

reference: src/application/predictor.hpp:29 (OpenMP row-parallel Predictor),
include/LightGBM/tree.h:190 (inline Tree::Predict traversal), and
src/boosting/prediction_early_stop.cpp:13-90 (margin-based early stop).

The reference parallelizes rows across threads, each doing a scalar
root-to-leaf walk per tree.  The vectorized inversion here packs all trees
into padded [T, nodes] arrays and advances EVERY row one level per step
("depth stepping"): a gather of per-row node attributes, one vectorized
decision, one child gather.  Rows that reach a leaf freeze (child pointers
of leaves are < 0).  Work is O(rows * avg_depth) fused vector ops per tree
instead of a Python loop per (tree, node) — the round-2 implementation's
per-node ``np.unique`` passes made 500-tree x 1M-row prediction minutes;
this is seconds.

Prediction early stop (binary/multiclass margins) follows the reference
semantics: every ``freq`` trees, rows whose margin exceeds the threshold are
compacted out of the working set.
"""

from __future__ import annotations

import os
from typing import List, Optional

import numpy as np

K_CATEGORICAL_MASK = 1
K_DEFAULT_LEFT_MASK = 2
K_ZERO_THRESHOLD = 1e-35

_CHUNK_ROWS = 1 << 16


def gather_leaf_sum(forest, leaves: np.ndarray, num_class: int) -> np.ndarray:
    """Host float64 leaf-value gather + iteration-sum epilogue:
    [T, rows] leaf indices -> [K, rows] raw scores.

    Shared by ``DeviceForest.predict_raw_padded`` and the AOT-restored
    serving programs (fleet/aot.py) so the two epilogues cannot drift —
    the serving bit-parity contract hangs on this exact gather +
    ``sum(axis=0)`` reduction order matching ``StackedForest.predict_raw``.
    """
    K = max(num_class, 1)
    iters = forest.num_trees // K
    rows = leaves.shape[1]
    tid = np.arange(forest.num_trees)
    lv = forest.leaf_value[tid[:, None], leaves]             # [T, rows] f64
    return lv.reshape(iters, K, rows).sum(axis=0)            # [K, rows]


class StackedForest:
    """Padded [T, nodes] arrays for a list of HostTrees (raw-feature space)."""

    def __init__(self, trees: List):
        T = len(trees)
        self.num_trees = T
        I = max([max(t.num_leaves - 1, 1) for t in trees], default=1)
        L = max([max(t.num_leaves, 1) for t in trees], default=1)
        self.split_feature = np.zeros((T, I), np.int32)
        self.threshold = np.full((T, I), np.inf, np.float64)
        self.left = np.full((T, I), -1, np.int32)     # ~0 = leaf 0
        self.right = np.full((T, I), -1, np.int32)
        self.is_cat = np.zeros((T, I), bool)
        self.default_left = np.zeros((T, I), bool)
        self.missing_type = np.zeros((T, I), np.int8)
        self.leaf_value = np.zeros((T, L), np.float64)
        self.depth = np.ones(T, np.int32)
        # categorical bitsets: flat word array + per-node offset/word-count
        self.cat_offset = np.zeros((T, I), np.int64)
        self.cat_nwords = np.zeros((T, I), np.int32)
        words: List[np.ndarray] = []
        wpos = 0
        self.has_cat = False
        for t, tr in enumerate(trees):
            ns = tr.num_leaves - 1
            self.leaf_value[t, :tr.num_leaves] = tr.leaf_value[:tr.num_leaves]
            if ns <= 0:
                continue  # single-leaf tree: sentinel node routes to leaf 0
            self.split_feature[t, :ns] = tr.split_feature[:ns]
            self.threshold[t, :ns] = tr.threshold[:ns]
            self.left[t, :ns] = tr.left_child[:ns]
            self.right[t, :ns] = tr.right_child[:ns]
            dt = tr.decision_type[:ns].astype(np.int32)
            self.is_cat[t, :ns] = (dt & K_CATEGORICAL_MASK) != 0
            self.default_left[t, :ns] = (dt & K_DEFAULT_LEFT_MASK) != 0
            self.missing_type[t, :ns] = (dt >> 2) & 3
            self.depth[t] = tr.max_depth()
            for s in np.flatnonzero(self.is_cat[t, :ns]):
                self.has_cat = True
                ci = int(tr.threshold[s])
                lo = int(tr.cat_boundaries[ci])
                hi = int(tr.cat_boundaries[ci + 1])
                w = np.asarray(tr.cat_threshold[lo:hi], np.uint32)
                self.cat_offset[t, s] = wpos
                self.cat_nwords[t, s] = len(w)
                words.append(w)
                wpos += len(w)
        self.cat_words = (np.concatenate(words) if words
                          else np.zeros(1, np.uint32))
        self.max_depth = int(self.depth.max(initial=1))

    # ------------------------------------------------------------- traversal
    #
    # All trees of a block advance one level per step with [T', nc] state
    # arrays — one fused numpy op serves every (tree, row) pair, amortizing
    # interpreter overhead across the block (the reference amortizes its
    # scalar walks across OpenMP threads instead, predictor.hpp:152).

    def _decide_block(self, tid2, nd, fval):
        """Vectorized go-left for a [T', nc] block of (tree, node) states."""
        thr = self.threshold[tid2, nd]
        mt = self.missing_type[tid2, nd]
        nan = np.isnan(fval)
        fz = np.where(nan & (mt != 2), 0.0, fval)
        is_missing = ((mt == 1) & (np.abs(fz) <= K_ZERO_THRESHOLD)) | \
                     ((mt == 2) & nan)
        with np.errstate(invalid="ignore"):
            gl = np.where(is_missing, self.default_left[tid2, nd], fz <= thr)
        if self.has_cat:
            cat = self.is_cat[tid2, nd]
            if cat.any():
                # truncation toward zero matches the reference's
                # static_cast<int> (so -0.5 -> category 0, not "invalid")
                iv = np.where(nan, -1.0, fval).astype(np.int64)
                nw = self.cat_nwords[tid2, nd]
                valid = (iv >= 0) & (iv < nw.astype(np.int64) * 32)
                ivc = np.clip(iv, 0, None)
                widx = self.cat_offset[tid2, nd] + np.minimum(
                    ivc // 32, np.maximum(nw - 1, 0))
                inset = (self.cat_words[widx]
                         >> (ivc % 32).astype(np.uint32)) & 1
                gl = np.where(cat, valid & (inset == 1), gl)
        return gl

    def _leaves_chunk(self, Xc: np.ndarray, tree_ids,
                      block_elems: int = 1 << 23) -> np.ndarray:
        """Leaf index per (tree, row) for one row chunk. Returns [T', nc].

        Trees are processed depth-sorted in blocks so a block's step count
        is its own max depth, not the forest's.
        """
        nc = Xc.shape[0]
        tid = np.asarray(list(tree_ids), np.int32)
        out = np.zeros((len(tid), nc), np.int32)
        rows = np.arange(nc)[None, :]
        order = np.argsort(self.depth[tid], kind="stable")
        t_blk = max(1, block_elems // max(nc, 1))
        for bs in range(0, len(tid), t_blk):
            sel = order[bs:bs + t_blk]
            tb = tid[sel]
            tid2 = tb[:, None]
            node = np.zeros((len(tb), nc), np.int32)
            while True:
                nd = np.maximum(node, 0)
                fval = Xc[rows, self.split_feature[tid2, nd]]
                gl = self._decide_block(tid2, nd, fval)
                nxt = np.where(gl, self.left[tid2, nd], self.right[tid2, nd])
                node = np.where(node < 0, node, nxt)
                if (node < 0).all():
                    break
            out[sel] = ~node
        return out

    # ---------------------------------------------------------- native path

    def _native(self):
        """ctypes handle to the C++ OpenMP predictor, or None."""
        if not hasattr(self, "_native_lib"):
            from .native.build import load_native_lib
            self._native_lib = load_native_lib()
        return self._native_lib

    def _native_predict(self, X: np.ndarray, num_class: int,
                        early_stop=None, want_leaf: bool = False):
        """Run lgbt_predict; returns (raw [K, n] or None, leaf [n, T] or
        None), or None if the native lib is unavailable."""
        lib = self._native()
        if lib is None:
            return None
        import ctypes as ct
        n, _ = X.shape
        K = max(num_class, 1)
        X = np.ascontiguousarray(X, np.float64)
        out = None if want_leaf else np.zeros((K, n), np.float64)
        leaf = np.zeros((n, self.num_trees), np.int32) if want_leaf else None
        kind, freq, margin = 0, 0, 0.0
        if early_stop is not None:
            kind, freq, margin = early_stop
        p = lambda a, t: a.ctypes.data_as(ct.POINTER(t)) if a is not None \
            else None
        lib.lgbt_predict(
            p(X, ct.c_double), ct.c_int64(n), ct.c_int64(X.shape[1]),
            ct.c_int64(self.num_trees), ct.c_int64(self.split_feature.shape[1]),
            ct.c_int64(self.leaf_value.shape[1]),
            p(self.split_feature, ct.c_int32), p(self.threshold, ct.c_double),
            p(self.left, ct.c_int32), p(self.right, ct.c_int32),
            p(self._cat_u8, ct.c_uint8), p(self._dl_u8, ct.c_uint8),
            p(self.missing_type, ct.c_int8), p(self.leaf_value, ct.c_double),
            p(self.cat_offset, ct.c_int64), p(self.cat_nwords, ct.c_int32),
            p(self.cat_words, ct.c_uint32),
            ct.c_int64(K), ct.c_int(kind), ct.c_int(freq), ct.c_double(margin),
            p(out, ct.c_double), p(leaf, ct.c_int32))
        return out, leaf

    @property
    def _cat_u8(self):
        if not hasattr(self, "_cat_u8_arr"):
            self._cat_u8_arr = np.ascontiguousarray(self.is_cat, np.uint8)
        return self._cat_u8_arr

    @property
    def _dl_u8(self):
        if not hasattr(self, "_dl_u8_arr"):
            self._dl_u8_arr = np.ascontiguousarray(self.default_left, np.uint8)
        return self._dl_u8_arr

    def predict_leaf(self, X: np.ndarray,
                     chunk_rows: int = _CHUNK_ROWS) -> np.ndarray:
        """Leaf indices [n, T] (reference pred_leaf output layout)."""
        native = self._native_predict(
            np.asarray(X, np.float64), 1, want_leaf=True)
        if native is not None:
            return native[1]
        n = X.shape[0]
        out = np.zeros((n, self.num_trees), np.int32)
        for s in range(0, n, chunk_rows):
            e = min(s + chunk_rows, n)
            out[s:e] = self._leaves_chunk(X[s:e], range(self.num_trees)).T
        return out

    def predict_raw(
        self,
        X: np.ndarray,
        num_class: int = 1,
        early_stop=None,
        chunk_rows: int = _CHUNK_ROWS,
    ) -> np.ndarray:
        """Summed raw scores [K, n].  Trees are laid out iteration-major
        (iteration i, class k -> tree i*K + k) as in the reference.

        ``early_stop``: optional (freq, margin_fn) pair; every ``freq``
        iterations rows with margin_fn(raw_scores) True are frozen and
        compacted out (reference: prediction_early_stop.cpp:13-60).
        """
        n = X.shape[0]
        K = max(num_class, 1)
        iters = self.num_trees // K
        X = np.ascontiguousarray(X, np.float64)
        es_tuple = (early_stop.kind_code, early_stop.freq,
                    early_stop.margin) if early_stop is not None else None
        native = self._native_predict(X, K, early_stop=es_tuple)
        if native is not None:
            return native[0]
        out = np.zeros((K, n), np.float64)
        for s in range(0, n, chunk_rows):
            e = min(s + chunk_rows, n)
            Xc = X[s:e]
            if early_stop is None:
                leaves = self._leaves_chunk(Xc, range(self.num_trees))
                tid = np.arange(self.num_trees)
                lv = self.leaf_value[tid[:, None], leaves]      # [T, nc]
                out[:, s:e] += lv.reshape(iters, K, e - s).sum(axis=0)
            else:
                freq, margin_fn = early_stop.freq, early_stop.margin_fn
                live = np.arange(e - s)
                acc = np.zeros((K, e - s), np.float64)
                Xl = Xc
                for it in range(iters):
                    ids = range(it * K, (it + 1) * K)
                    leaves = self._leaves_chunk(Xl, ids)
                    for j, t in enumerate(ids):
                        acc[t % K, live] += self.leaf_value[t, leaves[j]]
                    if freq > 0 and (it + 1) % freq == 0 and it + 1 < iters:
                        stop = margin_fn(acc[:, live])
                        if stop.any():
                            live = live[~stop]
                            if live.size == 0:
                                break
                            Xl = Xc[live]
                out[:, s:e] = acc
        return out


class DeviceForest:
    """Jitted stacked-forest traversal (XLA: multithreaded on CPU, fast on
    TPU).  Same depth-stepping algorithm as StackedForest but with [T, nc]
    device state advanced under ``lax.while_loop``.

    Exactness: inputs are compared in float32, with each node threshold
    rounded DOWN to the nearest float32.  For float32 feature values x,
    ``x <= t64``  ⟺  ``x <= round_down_f32(t64)``, so routing matches the
    float64 host path exactly for f32-precision data (float64 inputs with
    sub-f32 precision may route differently at bin boundaries — use the
    host path when that matters).

    ``precision`` controls the device STORAGE of the numeric thresholds
    (the fixed-point serving direction of arXiv 2011.02022): "bf16"
    stores them as bfloat16 and "int8" as int8 codes plus one f32
    dequantization scale per tree — both expect a forest whose host
    thresholds already sit on that grid (fleet/lowprec.quantize_forest),
    so the narrowing is lossless relative to the quantized host forest
    and routing still matches ITS host path exactly.  ``routing_only``
    skips the leaf-value upload entirely (the serving path gathers
    leaves on the host): ``predict_raw`` then refuses; the leaf-index
    paths still work.
    """

    def __init__(self, forest: StackedForest, chunk_rows: Optional[int] = None,
                 precision: str = "f32", routing_only: bool = False,
                 variant: Optional[str] = None,
                 tile_rows: Optional[int] = None):
        import jax
        import jax.numpy as jnp
        if precision not in ("f32", "bf16", "int8"):
            raise ValueError(f"unknown DeviceForest precision {precision!r}")
        self.forest = forest
        self.precision = precision
        self.routing_only = routing_only
        f = forest
        # round thresholds toward -inf in f32 (identity for bf16/int8-grid
        # forests: their values are exactly f32-representable)
        thr32 = f.threshold.astype(np.float32)
        over = thr32.astype(np.float64) > f.threshold
        thr32[over] = np.nextafter(thr32[over], -np.inf, dtype=np.float32)
        self._thr_scale = None
        if precision == "bf16":
            self.threshold = jnp.asarray(thr32, dtype=jnp.bfloat16)
        elif precision == "int8":
            # the quantized forest carries its own int8 artifacts
            # (fleet/lowprec.quantize_forest): code array + per-tree f32
            # scale, so the in-kernel dequantization q * scale reproduces
            # the host threshold grid BIT-exactly instead of re-deriving
            # a scale that could drift an ulp
            q = getattr(f, "threshold_q", None)
            if q is None:
                raise ValueError(
                    "int8 DeviceForest needs a forest quantized by "
                    "fleet/lowprec.quantize_forest (threshold_q missing)")
            self.threshold = jnp.asarray(q)                # int8 codes
            self._thr_scale = jnp.asarray(
                f.threshold_scale.astype(np.float32)[:, None])  # [T, 1]
            # non-quantized nodes (non-finite padding, categorical
            # bitset indices) keep their f32 value through a sparse
            # correction applied at decision time
            self._thr_fix_mask = jnp.asarray(f.threshold_skip)
            self._thr_fix = jnp.asarray(thr32)
        else:
            self.threshold = jnp.asarray(thr32)
        self.split_feature = jnp.asarray(f.split_feature)
        self.left = jnp.asarray(f.left)
        self.right = jnp.asarray(f.right)
        self.is_cat = jnp.asarray(f.is_cat)
        self.default_left = jnp.asarray(f.default_left)
        self.missing_type = jnp.asarray(f.missing_type.astype(np.int32))
        self.leaf_value = (None if routing_only else
                           jnp.asarray(f.leaf_value.astype(np.float32)))
        self.cat_offset = jnp.asarray(f.cat_offset)
        self.cat_nwords = jnp.asarray(f.cat_nwords)
        self.cat_words = jnp.asarray(f.cat_words)
        # kernel + chunk election (ops/planner.plan_predict): HBM-aware
        # chunk, measured-or-analytic variant, fused VMEM row tile.
        # Explicit arguments always win — tests pin shapes, serving pins
        # the bucket ladder.
        from .ops import planner as _planner
        from .ops import predict_kernels as _pk
        elected_by = "caller"
        if chunk_rows is None or variant is None or tile_rows is None:
            plan = _planner.plan_predict(
                num_trees=f.num_trees,
                nodes_dim=f.split_feature.shape[1],
                leaves_dim=f.leaf_value.shape[1],
                features=int(f.split_feature.max(initial=0)) + 1,
                precision=precision, routing_only=routing_only,
                cat_words=int(f.cat_words.size),
                ledger=_planner.active_ledger())
            chunk_rows = plan.chunk_rows if chunk_rows is None else chunk_rows
            if variant is None:
                variant, elected_by = plan.variant, plan.elected_by
            tile_rows = plan.tile_rows if tile_rows is None else tile_rows
        self.chunk_rows = int(chunk_rows)
        self.tile_rows = int(tile_rows) or 512
        if variant not in _pk.PREDICT_VARIANTS:
            raise ValueError(f"unknown predict kernel variant {variant!r}")
        if variant == "fused" and not _pk.fused_predict_verified(self):
            # probe demotion, warned there
            variant, elected_by = "fori", "parity_probe"
        self.variant = variant
        # which traversal the last-built forest runs, and who chose it
        # (the predict twin of train_hist_method / train_hist_elected_by)
        from .obs.metrics import global_registry
        global_registry.gauge("predict_variant").set(variant)
        global_registry.gauge("predict_elected_by").set(elected_by)
        if variant == "while":
            leaves_fn = self._leaves
        elif variant == "fori":
            leaves_fn = lambda X: _pk.leaves_fori(self, X)  # noqa: E731
        else:
            leaves_fn = lambda X: _pk.fused_traverse(  # noqa: E731
                self, X, self.tile_rows)
        self._leaves_jit = jax.jit(leaves_fn)
        # AOT export arm: the fixed-trip fori variant serializes cleanly
        # (static trip count, no convergence sync); a fused election
        # keeps it as the bit-identical export twin (fleet/aot.py)
        self._leaves_export = (jax.jit(lambda X: _pk.leaves_fori(self, X))
                               if variant == "fused" else self._leaves_jit)
        self._epilogue_ok: dict = {}
        self._leaf_sum_jit = jax.jit(self._leaf_sum, static_argnums=1)
        # fused score mode: leaf gather + class accumulation stay
        # in-kernel, only a [K, tile] block ever leaves HBM
        self._scores_jit = (
            jax.jit(lambda X, k: _pk.fused_traverse(
                self, X, self.tile_rows, k, emit_scores=True),
                static_argnums=1)
            if variant == "fused" and self.leaf_value is not None else None)

    def _call_chunk(self, n: int) -> int:
        """Per-call chunk: the elected ``chunk_rows`` ceiling, shrunk to
        the row-count's ladder rung so a small batch is not padded out
        to the full chunk (the compiled-shape set stays ladder-bounded
        either way)."""
        from .ops.planner import bucket_rows
        return max(min(self.chunk_rows, bucket_rows(max(n, 1))), 1)

    def _thr_at(self, tid2, nd):
        """Gather the [T', nc] threshold block in f32 whatever the device
        storage precision is."""
        import jax.numpy as jnp
        if self.precision == "bf16":
            return self.threshold[tid2, nd].astype(jnp.float32)
        if self.precision == "int8":
            thr = (self.threshold[tid2, nd].astype(jnp.float32)
                   * self._thr_scale[tid2, 0])
            return jnp.where(self._thr_fix_mask[tid2, nd],
                             self._thr_fix[tid2, nd], thr)
        return self.threshold[tid2, nd]

    def _leaves(self, Xc):
        """[nc, F] f32 -> leaf index [T, nc] — the legacy while_loop arm
        (ops/predict_kernels shares ONE decision-step expression across
        while/fori/fused, so variant parity is structural)."""
        from .ops import predict_kernels as _pk
        return _pk.leaves_while(self, Xc)

    def _leaf_sum(self, leaves, num_class: int):
        """Device leaf-value epilogue: [T, rows] leaf indices ->
        [K, rows] f32 raw scores, accumulated in pinned iteration-major
        order (bit-stable run to run).  Only promoted into
        ``predict_raw_padded`` after ``_epilogue_verified``."""
        import jax.numpy as jnp
        from jax import lax
        K = max(num_class, 1)
        T = self.forest.num_trees
        tid2 = jnp.arange(T)[:, None]
        lv3 = self.leaf_value[tid2, leaves].reshape(
            T // K, K, leaves.shape[1])
        return lax.fori_loop(
            0, T // K, lambda i, acc: acc + lv3[i],
            jnp.zeros((K, leaves.shape[1]), jnp.float32))

    def _epilogue_verified(self, num_class: int) -> bool:
        """One-time per (forest, K) probe: the float32 device leaf-sum
        epilogue may replace the host float64 ``gather_leaf_sum`` ONLY
        if it reproduces it bit-exactly on a battery of synthetic leaf
        patterns (the ``take_from_table`` demotion precedent) — any
        divergence, now or from a quirky leaf-value distribution, keeps
        the serving bit-parity contract on the host path.
        ``LGBM_TPU_PREDICT_EPILOGUE=0`` pins the host path outright."""
        K = max(num_class, 1)
        if self.leaf_value is None or self.forest.num_trees % K:
            return False
        if os.environ.get("LGBM_TPU_PREDICT_EPILOGUE", "").strip() == "0":
            return False
        ok = self._epilogue_ok.get(K)
        if ok is None:
            import jax.numpy as jnp
            T = self.forest.num_trees
            L = self.forest.leaf_value.shape[1]
            rng = np.random.RandomState(20260807)
            leaves = rng.randint(0, L, size=(T, 128)).astype(np.int32)
            leaves[:, 0] = 0                     # adversarial same-leaf
            leaves[:, 1] = L - 1                 # columns stress carries
            try:
                dev = np.asarray(self._leaf_sum_jit(jnp.asarray(leaves), K),
                                 np.float64)
                ok = bool(np.array_equal(
                    dev, gather_leaf_sum(self.forest, leaves, K)))
            except Exception:
                ok = False
            if not ok:
                # the COMMON case for real-valued forests (f32 sums
                # rarely reproduce f64 bit-for-bit) — a debug note, not
                # a warning; the host path is the contract's default
                from .utils.log import log_debug
                log_debug(
                    "device leaf-sum epilogue demoted: float32 sums not "
                    "bit-identical to the float64 host gather for this "
                    "forest; predict_raw_padded keeps the host path")
            self._epilogue_ok[K] = ok
        return bool(ok)

    def predict_raw_padded(self, Xpad: np.ndarray,
                           num_class: int = 1) -> np.ndarray:
        """Raw scores [K, rows] for ONE already-padded, bucket-shaped
        batch — the serving subsystem's entry point (serving/registry.py).

        Unlike ``predict_raw`` there is no internal chunking or padding:
        the caller owns the shape, so ``jax.jit`` holds exactly one
        executable per distinct (rows, features) it ever passes — the
        shape-bucket ladder guarantees that set stays tiny.

        Routing runs on device; leaf-value accumulation happens on the
        HOST in float64, with the same gather + ``sum(axis=0)`` (a
        sequential reduction over the leading axis in NumPy) that
        ``StackedForest.predict_raw`` uses — so for float32-precision
        feature values the output is bit-identical to the offline host
        path, padding rows included-then-sliced notwithstanding.

        When the one-time ``_epilogue_verified`` probe shows the float32
        device leaf-sum reproduces that host gather BIT-exactly for this
        forest, the epilogue stays on device (only [K, rows] crosses the
        wire); otherwise — and under ``LGBM_TPU_PREDICT_EPILOGUE=0`` —
        the host path runs, so the contract holds either way.
        """
        import jax.numpy as jnp
        leaves = self._leaves_jit(
            jnp.asarray(np.asarray(Xpad, np.float32)))       # [T, rows]
        if self._epilogue_verified(num_class):
            return np.asarray(
                self._leaf_sum_jit(leaves, max(num_class, 1)), np.float64)
        return gather_leaf_sum(self.forest, np.asarray(leaves), num_class)

    def predict_raw(self, X: np.ndarray, num_class: int = 1) -> np.ndarray:
        """Summed raw scores [K, n] (float32 accumulation on device)."""
        import jax.numpy as jnp
        if self.leaf_value is None:
            raise ValueError(
                "routing-only DeviceForest has no device leaf values; use "
                "predict_raw_padded (host leaf gather) instead")
        n = X.shape[0]
        K = max(num_class, 1)
        T = self.forest.num_trees
        iters = T // K
        tid2 = jnp.arange(T)[:, None]
        out = np.zeros((K, n), np.float64)
        cr = self._call_chunk(n)
        for s in range(0, n, cr):
            e = min(s + cr, n)
            Xc = np.asarray(X[s:e], np.float32)
            if e - s < cr:   # pad to the compiled chunk shape
                Xc = np.pad(Xc, ((0, cr - (e - s)), (0, 0)))
            if self._scores_jit is not None:     # fused in-kernel epilogue
                out[:, s:e] = np.asarray(self._scores_jit(
                    jnp.asarray(Xc), K), np.float64)[:, :e - s]
                continue
            leaves = self._leaves_jit(jnp.asarray(Xc))
            lv = self.leaf_value[tid2, leaves].reshape(iters, K, cr)
            out[:, s:e] = np.asarray(jnp.sum(lv, axis=0),
                                     np.float64)[:, :e - s]
        return out

    def predict_leaf(self, X: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp
        n = X.shape[0]
        out = np.zeros((n, self.forest.num_trees), np.int32)
        cr = self._call_chunk(n)
        for s in range(0, n, cr):
            e = min(s + cr, n)
            Xc = np.asarray(X[s:e], np.float32)
            if e - s < cr:
                Xc = np.pad(Xc, ((0, cr - (e - s)), (0, 0)))
            out[s:e] = np.asarray(self._leaves_jit(jnp.asarray(Xc))).T[:e - s]
        return out


class EarlyStop:
    """Prediction early-stop spec (reference:
    CreatePredictionEarlyStopInstance, prediction_early_stop.cpp:62-90):
    'binary' stops when |2*score| > margin, 'multiclass' when the top-2
    score gap > margin, checked every ``freq`` iterations."""

    def __init__(self, kind_code: int, freq: int, margin: float, margin_fn):
        self.kind_code = kind_code
        self.freq = freq
        self.margin = margin
        self.margin_fn = margin_fn


def make_early_stop(kind: str, margin: float, freq: int):
    if freq <= 0 or kind == "none":
        return None
    if kind == "binary":
        def margin_fn(raw):  # [1, rows]
            return np.abs(2.0 * raw[0]) > margin
        return EarlyStop(1, freq, margin, margin_fn)
    if kind == "multiclass":
        def margin_fn(raw):  # [K, rows]
            if raw.shape[0] < 2:
                return np.zeros(raw.shape[1], bool)
            part = np.partition(raw, raw.shape[0] - 2, axis=0)
            return (part[-1] - part[-2]) > margin
        return EarlyStop(2, freq, margin, margin_fn)
    raise ValueError(f"unknown early-stop type {kind!r}")


def predict_csr_chunked(forest_predict, data,
                        chunk_rows: Optional[int] = None):
    """Predict a scipy CSR/CSC matrix without materializing it densely:
    each row chunk is densified on its own (bounded memory), predicted, and
    discarded.  reference predicts CSR natively row-by-row (c_api.h:698);
    bounded chunk densification is the vectorized equivalent.

    ``forest_predict`` maps a dense [nc, F] float64 chunk to its result
    (row-major leading axis); results are concatenated on axis 0.
    ``chunk_rows`` defaults to the planner's host-memory-aware election
    (``LGBM_TPU_PREDICT_CHUNK`` overrides) instead of a hard-coded size.
    """
    if hasattr(data, "tocsr"):
        data = data.tocsr()
    if chunk_rows is None:
        from .ops import planner as _planner
        chunk_rows = _planner.elect_csr_chunk(int(data.shape[1]))
    n = data.shape[0]
    outs = []
    for s in range(0, n, chunk_rows):
        e = min(s + chunk_rows, n)
        chunk = np.asarray(data[s:e].todense(), np.float64)
        outs.append(forest_predict(chunk))
    return np.concatenate(outs, axis=0) if outs else np.zeros((0,))
