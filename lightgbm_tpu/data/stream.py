"""Out-of-core streamed training: block pump + host-driven tree grower.

The resident growers (grower.py / grower_rounds.py) are single jitted
programs over a device-resident ``[G, n]`` binned matrix.  When the
two-level budget planner (ops/planner.py ``plan_stream``) rules full
residency out on EITHER memory, this module trains the same trees with
the matrix living in a checksummed spill store (data/blockstore.py):

- per-row state (scores, gradients, bagging/GOSS weights, leaf routing)
  stays device-resident — it is O(n), not O(n*G);
- every histogram pass re-streams the matrix block by block through a
  double-buffered pump (``BlockPump``: ``jax.device_put`` of block t+1
  overlaps compute on block t), folding per-leaf histograms across
  blocks BEFORE the split scan — the one-pass-per-level access pattern
  of the GPU learners (arXiv 1706.08359, 1806.11248);
- the round/commit logic mirrors the batched-frontier grower
  (grower_rounds.py) op for op, driven from the host between block
  passes instead of inside a ``lax.while_loop``.

Bit-parity contract (tests/test_stream.py): quantized payloads fold in
int32 — associative, so streamed == resident is BYTE-identical model
text.  f32 payloads fold through the carry-in kernels
(ops/histogram.py ``init=``) in PINNED ascending block order, which
continues the exact per-bin add sequence of the resident
scatter-formulation kernels — streamed == resident is bit-identical
when both runs pin the scatter segment path (the CPU default;
``LGBM_TPU_SEGHIST=scatter`` pins it on accelerators, where the
sorted-arena formulation sums in a different order).

Bagging/GOSS masks are evaluated per block (the [n] mask is sliced with
the rows), so sampled workloads stream no extra bytes per excluded row
beyond the binned block itself.
"""

from __future__ import annotations

import functools
import os
import queue
import tempfile
import threading
import weakref
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..grower import GrowerConfig, TreeArrays, _LeafBest, row_goes_left
from ..grower_rounds import _pad_scatter
from ..obs.metrics import global_registry as _obs_registry
from ..obs.trace import instant as _instant, span as _span
from ..obs.watchdog import beat as _beat
from ..ops.histogram import (build_histogram, build_histogram_int,
                             quant_levels, segment_histogram,
                             segment_histogram_int, take_from_table)
from ..ops.split import SplitResult, best_split_for_leaf, leaf_output
from ..utils.log import log_info, log_warning
from .blockstore import BlockStore


def host_rss_bytes() -> int:
    """Current resident-set size of this process (VmRSS), 0 if unknown."""
    return _proc_status_kb("VmRSS:") * 1024


def host_rss_peak_bytes() -> int:
    """Peak resident-set size of this process (VmHWM), falling back to
    the CURRENT RSS on kernels that do not report a high-water mark —
    the measured twin of the planner's predicted host peak."""
    peak = _proc_status_kb("VmHWM:")
    return (peak or _proc_status_kb("VmRSS:")) * 1024


def _proc_status_kb(key: str) -> int:
    try:
        with open("/proc/self/status") as fh:
            for line in fh:
                if line.startswith(key):
                    return int(line.split()[1])
    except (OSError, ValueError, IndexError):
        pass
    return 0


def default_spill_dir() -> str:
    base = os.environ.get("LGBM_TPU_STREAM_DIR")
    if base:
        os.makedirs(base, exist_ok=True)
        return tempfile.mkdtemp(prefix="blocks_", dir=base)
    return tempfile.mkdtemp(prefix="lgbm_tpu_stream_")


class BlockPump:
    """Double-buffered host->device block iterator over a BlockStore.

    A daemon reader thread stays up to ``depth`` blocks ahead: it reads
    block t+1 into a fresh host buffer (``readinto`` — bounded RSS, no
    page-cache mappings inflating VmHWM) and dispatches its
    ``jax.device_put`` while the consumer computes on block t.  Yields
    ``(index, start_row, rows, device_block)`` in the pinned ascending
    block order every parity claim depends on.
    """

    def __init__(self, store: BlockStore, depth: int = 2,
                 prefetch: bool = True):
        self.store = store
        self.depth = max(int(depth), 1)
        self.prefetch = prefetch

    def _load(self, i: int):
        start, rows = self.store.block_bounds(i)
        buf = np.empty((self.store.num_cols, rows), self.store.dtype)
        self.store.read_block(i, out=buf)
        return i, start, rows, jax.device_put(buf)

    def __iter__(self):
        nb = self.store.num_blocks
        _obs_registry.counter("stream_passes_total").inc()
        if not self.prefetch:
            for i in range(nb):
                _obs_registry.counter("stream_blocks_total").inc()
                _beat("stream.pump", count=i + 1)
                yield self._load(i)
            return
        q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        stop = threading.Event()

        def reader():
            try:
                for i in range(nb):
                    if stop.is_set():
                        return
                    with _span("stream.block_put", block=i):
                        item = self._load(i)
                    q.put(item)
                q.put(None)
            except BaseException as e:   # surfaced on the consumer side
                q.put(e)

        t = threading.Thread(target=reader, daemon=True,
                             name="lgbm-stream-pump")
        t.start()
        gauge = _obs_registry.gauge("stream_blocks_inflight")
        try:
            while True:
                item = q.get()
                if item is None:
                    break
                if isinstance(item, BaseException):
                    raise item
                gauge.set(q.qsize() + 1)
                _obs_registry.counter("stream_blocks_total").inc()
                # pump heartbeat: a wedged spill store / reader thread
                # goes stale here and the watchdog names the stall
                _beat("stream.pump", count=item[0] + 1)
                yield item
        finally:
            stop.set()
            gauge.set(0)
            # drain so the reader's blocked put() can observe stop
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass


class IngestPump:
    """Double-buffered host->device iterator over RAW float chunks —
    ``BlockPump``'s ingest twin (ops/ingest.py's device binning path).

    The source is the host [n, F] float32 matrix (or anything row-
    sliceable to one); a daemon reader thread slices chunk t+1 and
    dispatches its ``jax.device_put`` while the consumer's bucketize+
    pack kernel runs on chunk t, so raw floats never materialize whole
    on device and the H2D copy hides under compute.  Yields
    ``(index, start_row, rows, device_chunk)`` in pinned ascending
    order (resume-safe: the binned matrix fills front to back).

    With multiple ``devices``, chunk placement round-robins ICI-before-
    DCN via ``plan_block_shards`` (data/score.py) — each device bins
    only its own row shard of the construction.
    """

    def __init__(self, source, chunk_rows: int, depth: int = 2,
                 devices=None, prefetch: bool = True):
        self.source = source
        self.n = int(source.shape[0])
        self.chunk_rows = max(int(chunk_rows), 1)
        self.num_chunks = max(-(-self.n // self.chunk_rows), 1)
        self.depth = max(int(depth), 1)
        self.prefetch = prefetch
        self.devices = list(devices) if devices else None
        # seconds the consumer waited for its next chunk: the part of the
        # puts that the kernel did not hide
        self.wait_s = 0.0
        if self.devices and len(self.devices) > 1:
            # describe the jax devices through the topology seam (device
            # i = spec i, the row-major mesh order), then round-robin
            # chunks ICI-before-DCN; the returned device_ids index
            # straight back into ``self.devices``
            from ..fleet.topology import plan_devices
            from .score import plan_block_shards
            specs = plan_devices(len(self.devices))
            self._owner = list(plan_block_shards(self.num_chunks, specs))
        else:
            self._owner = [0] * self.num_chunks

    def _load(self, i: int):
        start = i * self.chunk_rows
        rows = min(self.chunk_rows, self.n - start)
        chunk = np.ascontiguousarray(self.source[start:start + rows],
                                     dtype=np.float32)
        dev = self.devices[self._owner[i]] if self.devices else None
        return i, start, rows, jax.device_put(chunk, dev)

    def __iter__(self):
        if not self.prefetch:
            for i in range(self.num_chunks):
                _obs_registry.counter("ingest_blocks_total").inc()
                _beat("ingest.pump", count=i + 1)
                with _span("ingest.wait_put", block=i) as wait:
                    item = self._load(i)
                self.wait_s += wait.seconds
                yield item
            return
        q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        stop = threading.Event()

        def reader():
            try:
                for i in range(self.num_chunks):
                    if stop.is_set():
                        return
                    with _span("ingest.put", block=i):
                        item = self._load(i)
                    q.put(item)
                q.put(None)
            except BaseException as e:   # surfaced on the consumer side
                q.put(e)

        t = threading.Thread(target=reader, daemon=True,
                             name="lgbm-ingest-pump")
        t.start()
        gauge = _obs_registry.gauge("ingest_blocks_inflight")
        try:
            while True:
                with _span("ingest.wait_put") as wait:
                    item = q.get()
                self.wait_s += wait.seconds
                if item is None:
                    break
                if isinstance(item, BaseException):
                    raise item
                gauge.set(q.qsize() + 1)
                _obs_registry.counter("ingest_blocks_total").inc()
                # pump heartbeat: a wedged reader thread goes stale here
                _beat("ingest.pump", count=item[0] + 1)
                yield item
        finally:
            stop.set()
            gauge.set(0)
            # drain so the reader's blocked put() can observe stop
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass


class StreamContext:
    """Everything the streamed executor hangs off a GBDT instance."""

    def __init__(self, store: BlockStore, plan):
        self.store = store
        self.plan = plan
        self.grower: Optional["StreamGrower"] = None


def _config_stream_blockers(b) -> list:
    """Config features the streamed executor does not cover (the resident
    path keeps them); mirrors the fused-kernel context gate's shape."""
    cc = b.config
    meta = b.meta.resolved()
    blockers = []
    if not getattr(type(b), "_stream_ok", True):
        blockers.append(f"boosting={b.boosting_type}")
    if b._mesh is not None:
        blockers.append(f"tree_learner={b.tree_learner_type} sharding")
    if meta.has_bundles:
        blockers.append("EFB bundles")
    if bool(meta.is_categorical.any()):
        blockers.append("categorical features")
    if cc.monotone_constraints:
        blockers.append("monotone_constraints")
    if cc.extra_trees:
        blockers.append("extra_trees")
    if cc.feature_fraction_bynode < 1.0:
        blockers.append("feature_fraction_bynode")
    if (cc.cegb_penalty_split > 0.0 or cc.cegb_penalty_feature_coupled
            or cc.cegb_penalty_feature_lazy):
        blockers.append("CEGB")
    if cc.forcedsplits_filename:
        blockers.append("forced splits")
    return blockers


def maybe_stream_setup(b) -> bool:
    """Decide streamed vs resident execution for booster ``b`` and, when
    streaming, stand up the spill store.  Called by ``GBDT.__init__`` in
    place of the whole-matrix device upload; returns True when the
    booster trains out-of-core (``b.binned`` stays None).
    """
    from ..ops.planner import plan_stream
    ds = b.train_set
    store = getattr(ds, "_block_store", None)
    n, G = b._binned_shape
    plan = plan_stream(
        rows=n, features=G, num_bins=b.num_bins,
        num_leaves=b.config.num_leaves, num_class=b.num_tree_per_iteration,
        quant=bool(b.config.use_quantized_grad),
        method=b.config.tpu_hist_method,
        round_width=b.config.tpu_round_width)
    _instant("planner.plan_stream", rows=n, features=G, **plan.summary())
    if not plan.stream and (store is None or ds.binned is not None):
        # resident fits (or streaming is disabled) and the matrix is
        # available — a leftover spill store from an earlier booster
        # does not force streaming when residency is the better verdict
        return False
    blockers = _config_stream_blockers(b)
    if blockers:
        if store is not None and ds.binned is None:
            from ..config import LightGBMError
            raise LightGBMError(
                "the training Dataset is block-backed (out-of-core spill "
                "store), which requires a streaming-compatible config; "
                "unsupported here: " + ", ".join(blockers))
        log_warning(
            "out-of-core streaming elected by the two-level budget "
            f"planner ({plan.reason}) but not supported with "
            + ", ".join(blockers)
            + "; training resident — expect memory pressure "
            "(LGBM_TPU_STREAM=0 silences this)")
        return False
    if not plan.feasible and store is None:
        log_warning(
            "stream planner: predicted peaks "
            f"(device {plan.predicted_device_peak_bytes / 1e9:.2f} GB, "
            f"host {plan.predicted_host_peak_bytes / 1e9:.2f} GB) exceed "
            "a budget even at block_rows="
            f"{plan.block_rows}; training may OOM")
    if store is None:
        # spill the resident host matrix once; subsequent boosters on the
        # same Dataset (cv folds, resume rebuilds) reuse the store
        path = default_spill_dir()
        with _span("stream.spill", rows=n, block_rows=plan.block_rows):
            store = BlockStore.from_array(path, ds.host_binned(),
                                          plan.block_rows)
        ds._block_store = store
        ds._block_store_owned = True
        weakref.finalize(ds, BlockStore.cleanup, store)
        if ds.free_raw_data:
            ds.release_host_binned()
        log_info(
            f"out-of-core streaming: spilled {n} rows x {G} columns to "
            f"{path} ({store.num_blocks} blocks of {store.block_rows} "
            f"rows, {store.nbytes() / 1e9:.2f} GB; {plan.reason})")
    if not plan.stream:
        # a block-backed Dataset whose host matrix is gone streams even
        # when residency would have fit — re-state the plan in streamed
        # terms (the store's real geometry, streamed-mode predicted
        # peaks) so checkpoint provenance and the trace record what the
        # run actually does, not the election that never applied
        from ..ops.planner import (predict_host_peak_bytes,
                                   predict_stream_device_peak_bytes)
        dp = predict_stream_device_peak_bytes(
            n, G, b.num_bins, store.block_rows, b.config.num_leaves,
            b.num_tree_per_iteration, bool(b.config.use_quantized_grad))
        hp = predict_host_peak_bytes(
            n, G, 1 if b.num_bins <= 256 else 2, store.block_rows)[0]
        plan = plan._replace(
            stream=True, block_rows=int(store.block_rows),
            num_blocks=int(store.num_blocks),
            predicted_device_peak_bytes=dp,
            predicted_host_peak_bytes=hp,
            feasible=(dp <= plan.device_budget_bytes
                      and hp <= plan.host_budget_bytes),
            reason="block-backed dataset (the spill store is the only "
                   "copy of the binned matrix)")
    b._stream = StreamContext(store, plan)
    b.stream_plan = plan
    _obs_registry.gauge("stream_block_rows").set(int(store.block_rows))
    _obs_registry.gauge("stream_num_blocks").set(int(store.num_blocks))
    _obs_registry.gauge("host_rss_peak_bytes").set(host_rss_peak_bytes())
    return True


class StreamCarry(NamedTuple):
    """Between-round device state of one streamed tree (the [L]-sized
    slice of grower_rounds' Carry, plus the [n] leaf routing)."""

    tree: TreeArrays
    best: _LeafBest
    hist: jax.Array            # [L, ch, G, B] hist cache
    leaf_sg: jax.Array
    leaf_sh: jax.Array
    leaf_cnt: jax.Array
    leaf_parent_side: jax.Array
    split_idx: jax.Array
    leaf_id: jax.Array         # [n] i32


class StreamGrower:
    """Host-driven mirror of ``grower_rounds._grow_tree_rounds_traced``
    whose per-row work is folded over spill-store blocks.

    Every [L]/[KCAP]-sized decision (candidate ordering, exact-prefix
    validation, split application, cache refresh) ports the rounds
    grower's expressions verbatim; the per-row passes (histogram fold +
    candidate routing) run per block through the carry-in kernel seam.
    Gated by ``maybe_stream_setup`` to the numeric unsharded case —
    exactly the contexts where the two formulations are bit-equal.
    """

    def __init__(self, b):
        self.b = b
        cfg: GrowerConfig = b.grower_cfg
        self.cfg = cfg
        meta = b.meta.resolved()
        self.L = cfg.num_leaves
        self.B = cfg.num_bins
        self.G = int(b._binned_shape[1])
        self.n = int(b.num_data)
        self.F = len(meta.num_bin)
        self.KCAP = min(max(self.L - 1, 1), max(1, cfg.round_width))
        self.quant = cfg.quant
        self.tile = cfg.tile_rows if cfg.tile_rows > 0 else None
        # pallas/fused point kernels have no carry-in seam; the fold uses
        # the staged scatter/matmul family (auto resolution)
        m = cfg.hist_method
        self.hist_method = "auto" if m in ("pallas", "fused") else m
        (self.num_bin, self.missing_type, self.default_bin, self.is_cat,
         self.feat_group, self.feat_start) = b.meta.as_runtime_arrays()
        self.hp = cfg.hp
        self._q_levels = quant_levels(cfg.quant_bins) if self.quant else None
        self._build_fns()

    def pump(self) -> BlockPump:
        return BlockPump(self.b._stream.store)

    # ------------------------------------------------------------- programs

    def _build_fns(self):
        L, B, G, KCAP = self.L, self.B, self.G, self.KCAP
        F = len(self.b.meta.resolved().num_bin)
        hp = self.hp
        cfg = self.cfg
        quant = self.quant
        tile = self.tile
        num_bin, missing_type = self.num_bin, self.missing_type
        default_bin, is_cat = self.default_bin, self.is_cat
        feat_group, feat_start = self.feat_group, self.feat_start
        iota_L = jnp.arange(L, dtype=jnp.int32)
        iota_K = jnp.arange(KCAP, dtype=jnp.int32)

        def split_conv(ghist, cnt, qscales):
            if not quant:
                return ghist
            from ..ops.split import quant_rescale_hist
            return quant_rescale_hist(ghist, qscales[0], qscales[1], cnt)

        def one_leaf_best(fm, qscales, ghist, sg, sh, cnt, depth):
            hist = split_conv(ghist, cnt, qscales)
            r = best_split_for_leaf(
                hist, sg, sh, cnt, num_bin, missing_type, default_bin,
                is_cat, hp, feature_mask=fm, monotone_constraints=None,
                leaf_output_bounds=None, has_categorical=False,
                extra_rand_u=None)
            if cfg.max_depth > 0:
                r = r._replace(gain=jnp.where(depth >= cfg.max_depth,
                                              -jnp.inf, r.gain))
            return r

        def search_all(fm, qscales, hists, sgs, shs, cnts, depths):
            return jax.vmap(functools.partial(one_leaf_best, fm, qscales))(
                hists, sgs, shs, cnts, depths)

        def cache_from(sr: SplitResult) -> _LeafBest:
            return _LeafBest(
                gain=sr.gain, feature=sr.feature, threshold=sr.threshold,
                default_left=sr.default_left,
                left_sum_grad=sr.left_sum_grad,
                left_sum_hess=sr.left_sum_hess, left_count=sr.left_count,
                right_sum_grad=sr.right_sum_grad,
                right_sum_hess=sr.right_sum_hess,
                right_count=sr.right_count,
                is_categorical=sr.is_categorical, cat_bitset=sr.cat_bitset)

        # ---- root histogram fold + initial carry ------------------------
        def root_block(acc, block, start, grad, hess, mask, gq, hq):
            C = block.shape[1]
            w = jax.lax.dynamic_slice(mask, (start,), (C,))
            if quant:
                g = jax.lax.dynamic_slice(gq, (start,), (C,))
                h = jax.lax.dynamic_slice(hq, (start,), (C,))
                return acc + build_histogram_int(
                    block, g, h, w > 0, B, method=self.hist_method,
                    levels=self._q_levels, tile_rows=tile)
            g = jax.lax.dynamic_slice(grad, (start,), (C,))
            h = jax.lax.dynamic_slice(hess, (start,), (C,))
            return build_histogram(block, g, h, w, B,
                                   method=self.hist_method,
                                   tile_rows=tile, init=acc)

        self._root_block = jax.jit(root_block)

        def root_commit(root_hist, grad, hess, mask, fmask, gq, hq, gs, hs):
            if quant:
                member = mask > 0
                root_sg = jnp.sum(jnp.where(member, gq, 0).astype(
                    jnp.int32)).astype(jnp.float32) * gs
                root_sh = jnp.sum(jnp.where(member, hq, 0).astype(
                    jnp.int32)).astype(jnp.float32) * hs
                root_cnt = jnp.sum(member.astype(jnp.float32))
                qscales = (gs, hs)
                hist_cache = jnp.zeros((L, 2, G, B), jnp.int32) \
                    .at[0].set(root_hist)
            else:
                root_sg = jnp.sum(grad * mask)
                root_sh = jnp.sum(hess * mask)
                root_cnt = jnp.sum(mask)
                qscales = (jnp.float32(1.0), jnp.float32(1.0))
                hist_cache = jnp.zeros((L, 3, G, B), jnp.float32) \
                    .at[0].set(root_hist)
            tree = TreeArrays.empty(L)
            leaf_sg = jnp.zeros(L, jnp.float32).at[0].set(root_sg)
            leaf_sh = jnp.zeros(L, jnp.float32).at[0].set(root_sh)
            leaf_cnt = jnp.zeros(L, jnp.float32).at[0].set(root_cnt)
            best = cache_from(search_all(
                fmask, qscales, hist_cache, leaf_sg, leaf_sh, leaf_cnt,
                tree.leaf_depth))
            return StreamCarry(
                tree=tree, best=best, hist=hist_cache, leaf_sg=leaf_sg,
                leaf_sh=leaf_sh, leaf_cnt=leaf_cnt,
                leaf_parent_side=jnp.zeros(L, jnp.int32),
                split_idx=jnp.array(0, jnp.int32),
                leaf_id=jnp.zeros(self.n, jnp.int32))

        self._root_commit = jax.jit(root_commit)

        def active_gains(c: StreamCarry):
            active = iota_L < c.tree.num_leaves
            return jnp.where(active, c.best.gain, -jnp.inf)

        def cond_state(c: StreamCarry):
            return c.split_idx, jnp.max(active_gains(c))

        self._cond = jax.jit(cond_state)

        # ---- per-round candidate tables (device [L] gathers feed the
        # per-block routing; mirrors the rounds grower's router table) ---
        def round_tables(c: StreamCarry):
            gains = active_gains(c)
            pos = gains > 0.0
            npos = jnp.sum(pos.astype(jnp.int32))
            budget = (L - c.tree.num_leaves).astype(jnp.int32)
            k = jnp.minimum(jnp.minimum(npos, budget), KCAP)
            order = jnp.argsort(-gains, stable=True)
            rank = jnp.zeros(L, jnp.int32).at[order].set(iota_L)
            idl = jnp.clip(order[:KCAP], 0, L - 1)
            b_ = c.best
            feat_l = jnp.clip(b_.feature, 0, F - 1)
            live_l = pos & (rank < k)
            tables = (
                jnp.where(live_l, rank, KCAP),        # crank per leaf
                feat_group[feat_l],                    # group column
                b_.threshold,
                b_.default_left,
                missing_type[feat_l],
                default_bin[feat_l],
                num_bin[feat_l],
                feat_start[feat_l],
                b_.left_count <= b_.right_count,       # smaller-child side
            )
            return tables, gains, rank, k, idl

        self._tables = jax.jit(round_tables)

        # ---- per-block routing + segment-histogram fold -----------------
        def block_step(seg, block, start, grad, hess, mask, leaf_id,
                       tables, gq, hq):
            C = block.shape[1]
            (crank_l, grp_l, thr_l, dl_l, mt_l, db_l, nb_l, fs_l,
             sl_l) = tables
            leaf = jax.lax.dynamic_slice(leaf_id, (start,), (C,))
            w = jax.lax.dynamic_slice(mask, (start,), (C,))
            crank = crank_l[leaf]
            grp = grp_l[leaf]
            nb = nb_l[leaf]
            col = jnp.take_along_axis(block, grp[None, :],
                                      axis=0)[0].astype(jnp.int32)
            dec = col - fs_l[leaf] + 1
            binf = jnp.where((dec >= 1) & (dec < nb), dec, 0)
            gl = row_goes_left(binf, thr_l[leaf], dl_l[leaf], None, None,
                               mt_l[leaf], db_l[leaf], nb)
            row_small = gl == sl_l[leaf]
            slot = jnp.where(row_small, crank, KCAP)
            if quant:
                g = jax.lax.dynamic_slice(gq, (start,), (C,))
                h = jax.lax.dynamic_slice(hq, (start,), (C,))
                seg = seg + segment_histogram_int(
                    block, g, h, w > 0, slot, KCAP, B,
                    levels=self._q_levels, tile_rows=tile)
            else:
                g = jax.lax.dynamic_slice(grad, (start,), (C,))
                h = jax.lax.dynamic_slice(hess, (start,), (C,))
                member = (slot < KCAP) & (w > 0)
                seg = segment_histogram(
                    block, g, h, w, jnp.where(member, slot, KCAP), KCAP,
                    B, tile_rows=tile, init=seg)
            return seg, gl, crank

        self._block_step = jax.jit(block_step)

        def seg_zero():
            ch = 2 if quant else 3
            dt = jnp.int32 if quant else jnp.float32
            return jnp.zeros((KCAP, ch, G, B), dt)

        self._seg_zero = seg_zero

        # ---- children search + exact-prefix validation + commit ---------
        def round_commit(c: StreamCarry, seg, gl_full, crank_full, gains,
                         rank, k, idl, fmask, qscales):
            b_ = c.best
            small_left = b_.left_count <= b_.right_count
            ph = c.hist[idl]
            lg_, lh_, lc_ = (b_.left_sum_grad[idl], b_.left_sum_hess[idl],
                             b_.left_count[idl])
            rg_, rh_, rc_ = (b_.right_sum_grad[idl],
                             b_.right_sum_hess[idl], b_.right_count[idl])
            depth_c = c.tree.leaf_depth[idl] + 1
            sl = small_left[idl][:, None, None, None]
            h_left = jnp.where(sl, seg, ph - seg)
            h_right = ph - h_left
            res = search_all(
                fmask, qscales,
                jnp.concatenate([h_left, h_right]),
                jnp.concatenate([lg_, rg_]), jnp.concatenate([lh_, rh_]),
                jnp.concatenate([lc_, rc_]),
                jnp.concatenate([depth_c, depth_c]))

            cg = jnp.where(jnp.isnan(res.gain), -jnp.inf, res.gain)
            pair_max = jnp.maximum(cg[:KCAP], cg[KCAP:])
            pair_max = jnp.where(iota_K < k, pair_max, -jnp.inf)
            pcm = jax.lax.cummax(pair_max)
            sel_sorted = gains[idl]
            follow = (iota_K == 0) | (sel_sorted >= jnp.concatenate(
                [jnp.full((1,), -jnp.inf), pcm[:-1]]))
            if cfg.rounds_relaxed:
                m = k
            else:
                m = jnp.minimum(k, jnp.cumprod(
                    follow.astype(jnp.int32)).sum().astype(jnp.int32))

            pos = gains > 0.0
            sel = pos & (rank < m)
            node_of = c.split_idx + rank
            newleaf_of = c.tree.num_leaves + rank
            feat = b_.feature
            lg, lh, lc = (b_.left_sum_grad, b_.left_sum_hess, b_.left_count)
            rg, rh, rc = (b_.right_sum_grad, b_.right_sum_hess,
                          b_.right_count)
            tree = c.tree
            pn = jnp.maximum(tree.leaf_parent, 0)
            fixl = sel & (tree.leaf_parent >= 0) & (c.leaf_parent_side == 0)
            fixr = sel & (tree.leaf_parent >= 0) & (c.leaf_parent_side == 1)
            left_child = _pad_scatter(tree.left_child, pn, node_of, fixl)
            right_child = _pad_scatter(tree.right_child, pn, node_of, fixr)
            parent_out = leaf_output(c.leaf_sg, c.leaf_sh, hp.lambda_l1,
                                     hp.lambda_l2, hp.max_delta_step)
            new_depth = tree.leaf_depth + 1
            ps = functools.partial(_pad_scatter, idx=node_of, sel=sel)
            tree = tree._replace(
                split_feature=ps(tree.split_feature, val=feat),
                threshold_bin=ps(tree.threshold_bin, val=b_.threshold),
                default_left=ps(tree.default_left, val=b_.default_left),
                is_categorical=ps(tree.is_categorical,
                                  val=b_.is_categorical),
                cat_bitset=ps(tree.cat_bitset, val=b_.cat_bitset),
                left_child=ps(left_child, val=~iota_L),
                right_child=ps(right_child, val=~newleaf_of),
                split_gain=ps(tree.split_gain, val=b_.gain),
                internal_value=ps(tree.internal_value, val=parent_out),
                internal_weight=ps(tree.internal_weight, val=c.leaf_sh),
                internal_count=ps(tree.internal_count, val=c.leaf_cnt),
                leaf_parent=_pad_scatter(
                    jnp.where(sel, node_of, tree.leaf_parent),
                    newleaf_of, node_of, sel),
                leaf_depth=_pad_scatter(
                    jnp.where(sel, new_depth, tree.leaf_depth),
                    newleaf_of, new_depth, sel),
                num_leaves=tree.num_leaves + m,
            )
            leaf_parent_side = _pad_scatter(
                jnp.where(sel, 0, c.leaf_parent_side),
                newleaf_of, jnp.ones(L, jnp.int32), sel)
            new_leaf_id = jnp.where((crank_full < m) & ~gl_full,
                                    c.tree.num_leaves + crank_full,
                                    c.leaf_id)
            leaf_sg = _pad_scatter(jnp.where(sel, lg, c.leaf_sg),
                                   newleaf_of, rg, sel)
            leaf_sh = _pad_scatter(jnp.where(sel, lh, c.leaf_sh),
                                   newleaf_of, rh, sel)
            leaf_cnt = _pad_scatter(jnp.where(sel, lc, c.leaf_cnt),
                                    newleaf_of, rc, sel)
            small = seg[jnp.clip(rank, 0, KCAP - 1)]
            hist_left = jnp.where(small_left[:, None, None, None],
                                  small, c.hist - small)
            hist_right = c.hist - hist_left
            selb = sel[:, None, None, None]
            hist = _pad_scatter(jnp.where(selb, hist_left, c.hist),
                                newleaf_of, hist_right, sel)
            idc = jnp.concatenate([idl, jnp.clip(c.tree.num_leaves + iota_K,
                                                 0, L - 1)])
            valid_m = jnp.concatenate([iota_K < m, iota_K < m])
            new = cache_from(res)
            best = jax.tree_util.tree_map(
                lambda base, v: _pad_scatter(base, idc, v, valid_m),
                c.best, new)
            return StreamCarry(
                tree=tree, best=best, hist=hist, leaf_sg=leaf_sg,
                leaf_sh=leaf_sh, leaf_cnt=leaf_cnt,
                leaf_parent_side=leaf_parent_side,
                split_idx=c.split_idx + m, leaf_id=new_leaf_id)

        self._round_commit = jax.jit(round_commit)

        # ---- finalize (mirrors grower_rounds' epilogue) ------------------
        def finish(c: StreamCarry, grad, hess, mask):
            tree = c.tree
            leaf_sh_out = c.leaf_sh
            if quant and cfg.quant_renew:
                from ..ops.renew import quant_train_renew_leaf
                sg_t, sh_t = quant_train_renew_leaf(c.leaf_id, grad, hess,
                                                    mask, L)
                lv = leaf_output(sg_t, sh_t, hp.lambda_l1, hp.lambda_l2,
                                 hp.max_delta_step)
                leaf_sh_out = sh_t
            else:
                lv = leaf_output(c.leaf_sg, c.leaf_sh, hp.lambda_l1,
                                 hp.lambda_l2, hp.max_delta_step)
            active = iota_L < tree.num_leaves
            tree = tree._replace(
                leaf_value=jnp.where(active, lv, 0.0),
                leaf_weight=jnp.where(active, leaf_sh_out, 0.0),
                leaf_count=jnp.where(active, c.leaf_cnt, 0.0),
            )
            return tree, c.leaf_id

        self._finish = jax.jit(finish)

        # ---- iteration-level pieces -------------------------------------
        if quant:
            from ..ops.histogram import quantize_gradients
            qb = cfg.quant_bins
            stoch = bool(self.b.config.stochastic_rounding)
            self._quantize = jax.jit(
                lambda g, h, w, key: quantize_gradients(
                    g, h, w, qb, key, stochastic=stoch, axis_name=None))

        # leaf-scale + gather + score-add run in ONE program with the
        # scaled tree as a co-output — the exact dataflow of iter_body's
        # epilogue, so XLA's rounding decisions (the FMA-contraction
        # class boosting/macro.py documents) match the resident programs
        # bit for bit; splitting scale and add across jit boundaries
        # measurably drifts the carried score by 1 ulp per iteration
        def scale_add(score, tree, lid, lr, k):
            tree = tree._replace(
                leaf_value=tree.leaf_value * lr,
                internal_value=tree.internal_value * lr)
            score = score.at[k].add(take_from_table(tree.leaf_value, lid))
            return score, tree

        self._scale_add = jax.jit(scale_add, static_argnums=(4,))

        obj = self.b.objective
        renew_pct = obj.renew_percentile if obj is not None else None
        self._use_renew = renew_pct is not None
        if self._use_renew:
            from ..ops.renew import leaf_percentile
            label_a = self.b._macro_ctx["label"]
            weight_a = self.b._macro_ctx["weight"]
            pctv = float(renew_pct)

            def renew(tree, leaf_id, score_k, mask):
                residual = label_a - score_k
                w = mask * weight_a
                pct = leaf_percentile(leaf_id, residual, w, L, pctv)
                active = iota_L < tree.num_leaves
                return tree._replace(
                    leaf_value=jnp.where(active, pct, tree.leaf_value))

            self._renew = jax.jit(renew)

    # ------------------------------------------------------------ training

    def grow(self, grad_k, hess_k, mask, fmask, qvals):
        """Grow one streamed tree; returns (TreeArrays, leaf_id)."""
        if self.quant:
            gq, hq = qvals[0], qvals[1]
            qscales = (qvals[2], qvals[3])
        else:
            z8 = jnp.zeros((1,), jnp.int8)
            gq = hq = z8
            qscales = (jnp.float32(1.0), jnp.float32(1.0))
        ch = 2 if self.quant else 3
        dt = jnp.int32 if self.quant else jnp.float32
        acc = jnp.zeros((ch, self.G, self.B), dt)
        with _span("stream.root_pass"):
            for (_i, start, _rows, blk) in self.pump():
                acc = self._root_block(acc, blk, start, grad_k, hess_k,
                                       mask, gq, hq)
        c = self._root_commit(acc, grad_k, hess_k, mask, fmask, gq, hq,
                              qscales[0], qscales[1])
        rounds = 0
        while True:
            split_idx, max_gain = jax.device_get(self._cond(c))
            if int(split_idx) >= self.L - 1 or not float(max_gain) > 0.0:
                break
            tables, gains, rank, k, idl = self._tables(c)
            seg = self._seg_zero()
            gl_parts, crank_parts = [], []
            with _span("stream.round_pass", round=rounds):
                for (_i, start, _rows, blk) in self.pump():
                    seg, gl_b, cr_b = self._block_step(
                        seg, blk, start, grad_k, hess_k, mask, c.leaf_id,
                        tables, gq, hq)
                    gl_parts.append(gl_b)
                    crank_parts.append(cr_b)
            gl_full = jnp.concatenate(gl_parts)
            crank_full = jnp.concatenate(crank_parts)
            c = self._round_commit(c, seg, gl_full, crank_full, gains,
                                   rank, k, idl, fmask, qscales)
            rounds += 1
        return self._finish(c, grad_k, hess_k, mask)

    def run_iteration(self, grad, hess, mask, lr, rng, fmasks):
        """One boosting iteration (K trees) — the streamed twin of
        gbdt.py's ``iter_body``; returns (new_score, stacked trees,
        [K, 2] quant scales)."""
        b = self.b
        K = b.num_tree_per_iteration
        score = b.train_score
        trees = []
        qscale_rows = []
        for k in range(K):
            qvals = None
            if self.quant:
                qkey = jax.random.fold_in(
                    jax.random.fold_in(rng, 0x51475442), k)
                qvals = self._quantize(grad[k], hess[k], mask, qkey)
                qscale_rows.append(jnp.stack([qvals[2], qvals[3]]))
            with _span("stream.tree", k=k):
                tree, leaf_id = self.grow(grad[k], hess[k], mask,
                                          fmasks[k], qvals)
            if self._use_renew:
                tree = self._renew(tree, leaf_id, score[k], mask)
            score, tree = self._scale_add(score, tree, leaf_id, lr, k)
            trees.append(tree)
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)
        qscales = (jnp.stack(qscale_rows) if self.quant
                   else jnp.zeros((K, 2), jnp.float32))
        _obs_registry.gauge("host_rss_peak_bytes").set(host_rss_peak_bytes())
        return score, stacked, qscales
