"""Batched-frontier leaf-wise tree growth (the TPU-fast grower).

Same semantics as :mod:`.grower` (LightGBM best-first growth,
reference: src/treelearner/serial_tree_learner.cpp:149-193) but the
``lax.while_loop`` advances a ROUND of splits per iteration instead of one
split, so a 255-leaf tree takes ~log2(255)+eps iterations instead of 254.
Rationale: on TPU the dominant cost of the serial grower is not compute but
the per-iteration execution of a ~1.6k-op loop body (measured: ~6 ms fixed
per split at 100k-500k rows, ~99% of train time); batching the frontier
amortizes that body over up to ``budget`` splits.

Exactness.  Best-first growth applies, at every step, the max-gain leaf
(ties: smallest leaf index — the reference's ArgMax over the leaf array).
A round here applies the top ``k = min(#positive-gain leaves, leaf budget)``
candidates in that same (gain desc, leaf asc) order, which is exactly the
sequence best-first would produce PROVIDED no child created by the round
outranks the round's weakest applied candidate (a child that outranks it
would, under best-first, have been split before the weaker candidate —
potentially consuming budget and changing the applied set).  That proviso
is checked at runtime AFTER the children's best splits are known: if any
new child's gain >= min(applied gains), the round is rolled back to a
single best-first step (the fallback reuses the round's own computation —
the argmax leaf's partition/histogram/search results are slices of the
batched ones, because per-leaf candidates are independent of one another).
Hence trees — including node/leaf numbering — are structurally identical
to the serial grower's for every gain pattern; adversarial
(gain-increasing) patterns only lose the batching speedup, not exactness.
Float fields (histogram sums, gains, leaf values) agree to float32
accumulation order only: the segment scatter sums bins in a different
order than the serial kernels — the same class of difference as the
reference's CPU vs GPU histograms (docs/GPU-Performance.rst accuracy
tables).  Structure can differ only on exact float ties in gains.

Histogram passes.  A round builds its candidates' smaller-child
histograms in one pass over the rows.  Under the fused arm
(``hist_method="fused"``, what ``auto`` elects on an accelerator) the
root and every round share ONE accumulate program family, on one chip
and sharded alike (``ops/fused.frontier_accumulator``): the pass runs at
the narrowest compiled slot width that holds the round's ``k`` live
candidates (the root: one slot, every member row), because the kernel
pays for every slot of its width; everything after it — the collective,
the sibling scan, the pick, the prefix, the commit — stays at the round
cap ``KCAP = tpu_round_width``.  The loop's fourth counter, ``slots``,
sums the widths run.  The staged family keeps ``build_histogram*`` for
the root and runs every segment pass at the cap.

The offer.  How wide the pass has to be is set by what the round offers,
and a round that offers the whole frontier commits a fraction of it (the
prefix ends at the first child that outranks a candidate: 3 of ~57 on
quantized lambdarank gains, ~11 of ~50 on binary log-loss at 25M rows).
So ``k = min(#positive-gain leaves, leaf budget, KCAP, offer)``, where
``offer`` rides the loop's carry: one of the pass's widths, the narrowest
at a tree's first round, afterwards the narrowest that holds what each
of the two rounds before COMMITTED with a quarter to spare, hence a rung
up at least when a whole offer committed (``next_offer``).  It is a
function of the reduced histograms' results alone, so every shard of a
mesh computes the same value with no collective.  A smaller offer only ends a round's
prefix earlier; what it left out is offered again with the same cached
gains and commits in the same order, so the tree does not depend on it.
The fifth counter, ``clipped``, counts the rounds that the offer (not a
child) ended: the passes it may have cost.

Routing.  A round hands every row its candidate's rank (``crank``) and
its goes-left bit.  On the accelerator the router form does it a block of
rows at a time against the round's lanes (``route_lanes``): as many as
the pass is wide on the fused arm, in the same branch of the width switch
as the pass, so a 16-slot round compares a row with 16 lanes and not with
every leaf.  The sixth counter, ``lanes``, sums them.  The candidate scan
(``route_scan``) is the CPU form and the router's oracle.

Support matrix: EFB bundles, bagging/GOSS weights, per-tree and per-node
column sampling, extra_trees, monotone constraints, max_depth, and
data-parallel row sharding (``axis_name`` -> histogram/scalar psums).
Voting-parallel, feature-parallel, CEGB and forced splits stay on the
serial grower (GBDT dispatches automatically; see _build_jit_fns).
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from .dataset import FeatureMeta
from .grower import (GrowerConfig, TreeArrays, _LeafBest, _psum,
                     row_goes_left)
from .ops.histogram import (build_histogram, build_histogram_int,
                            capacity_schedule, compacted_segment_histogram,
                            compacted_segment_histogram_int, pack_cols_u32,
                            pack_cols_u32_quant, psum_quant_hist,
                            quant_levels, resolve_hist_method,
                            use_sorted_seghist)
from .ops.split import (MAX_CAT_WORDS, SplitResult, best_split_for_leaf,
                        leaf_output, quant_rescale_hist)


def _pad_scatter(arr: jax.Array, idx: jax.Array, val: jax.Array,
                 sel: jax.Array) -> jax.Array:
    """``arr[idx] = val`` for lanes where ``sel``; others hit a dummy row."""
    M = arr.shape[0]
    pad = jnp.zeros((1,) + arr.shape[1:], arr.dtype)
    ext = jnp.concatenate([arr, pad], axis=0)
    safe = jnp.where(sel, idx, M)
    return ext.at[safe].set(val.astype(arr.dtype))[:M]


# The offer's rule.  A round may offer no more candidates than ``offer``,
# one of the widths its histogram pass can run at (ops/fused.slot_widths);
# the next round's offer is the narrowest width that holds what the last
# TWO rounds committed, each, with a quarter to spare.  A pass costs its
# width whatever it holds and a clipped round costs a whole pass more, so
# where commits hover at a width's edge the wider one is kept; and two
# rounds, because late in a tree a round that commits one split (a child
# outranked everything) is as a rule followed by one that commits dozens.
# Read on one v5e from the per-round (k, m) of both benchmark
# configurations: root PERF.md section 5.
OFFER_SPARE_NUM, OFFER_SPARE_DEN = 3, 4


def next_offer(rungs: jax.Array, m, m_before) -> jax.Array:
    """The offer that follows rounds which committed ``m_before`` and then
    ``m`` splits: the narrowest of ``rungs`` ([R] i32, ascending, the last
    the round cap) that holds the larger with a quarter to spare.  A round
    whose whole offer committed (the offer, not a child, ended its prefix)
    therefore goes up a rung at least: no rung holds itself with room."""
    need = OFFER_SPARE_DEN * jnp.maximum(m, m_before)
    return rungs[jnp.sum(need > OFFER_SPARE_NUM * rungs[:-1])]


def router_engages() -> bool:
    """Whether a round routes rows by the router form (``route_lanes``:
    one decision a row against the round's lanes) or by the candidate scan
    (one pass over the rows a candidate): fixed by the backend when the
    round program is traced, so it is also what the
    ``grower_rounds_*_total`` counters follow (``GBDT._note_trees``)."""
    return (use_sorted_seghist()
            and os.environ.get("LGBM_TPU_ROUTER") != "0")


# The router decides rows a block at a time: a step of its loop reads a
# block's leaf ids and binned columns and writes only the block's
# (crank, gl, slot); the last block starts at n - block and rewrites rows
# the one before decided the same way, so nothing is padded.
ROUTE_BLOCK = 1 << 17


def _byte_count(bound: int) -> int:
    """Bytes that hold every integer in [0, bound)."""
    return max(1, (max(int(bound) - 1, 1).bit_length() + 7) // 8)


def set_bytes_hold(set_bytes, col):
    """``row_goes_left``'s bitset test on per-row sets that came as their
    little-endian bytes (a sequence of [C] i32 in [0, 256)): whether bit
    ``col`` of each row's set is set.  A select over the bytes and one
    shift, elementwise: no per-row gather.  Bins past the bytes carried are
    in no set."""
    col = col.astype(jnp.int32)
    which = col >> 3
    byte = jnp.zeros(col.shape, jnp.int32)
    for i, b in enumerate(set_bytes):
        byte = jnp.where(which == i, b, byte)
    return ((byte >> (col & 7)) & 1) == 1


def route_lanes(binned_t, leaf_id, idl, k, W: int, best, kcap: int,
                bin_layout, num_bins: int, set_words: int, live_rows=None):
    """The router form of a round's routing against its ``W`` lanes.

    Lane ``r`` is the round's ``r``-th candidate ``idl[r]``, live where
    ``r < k`` (``k <= W``).  Per lane its split's parameters become bytes
    (an integer under 256 each); a block of rows compares its leaf ids with
    the lanes, takes its lane's bytes by one bf16 one-hot matmul (one
    nonzero a column and every value under 256, so exact; on one v5e it
    beat a select over the lanes at 16 lanes too, PERF.md section 5),
    reads the bin of its split's feature by a select-reduce over the
    block's columns, and decides by ``row_goes_left``'s rule.

    Returns ``crank`` ([n] i32: the row's lane, ``kcap`` where none is
    live), ``gl`` ([n] bool, meaningful where ``crank < kcap``) and
    ``slot`` ([n] i32: ``crank`` where the row goes to the smaller child,
    else ``kcap``): what the candidate scan gives, with no per-row
    parameter array outside a block.  ``num_bins`` bounds every bin-valued
    parameter; ``set_words``: how many words of a categorical lane's set
    ride along (0 = no categorical column).

    ``live_rows`` (a traced int32, or None): the rows at or past it are
    dead (a buffer whose live rows were gathered to the front).  The loop
    then stops at the block that holds the last live row, and a dead row
    is in no lane (``crank = slot = kcap``)."""
    num_bin, missing_type, default_bin, feat_group, feat_start = bin_layout
    G, n = binned_t.shape
    F = num_bin.shape[0]
    leaf = idl[:W]
    r = jnp.arange(W, dtype=jnp.int32)
    lane_leaf = jnp.where(r < k, leaf, -1)
    feat = jnp.clip(best.feature[leaf], 0, F - 1)
    flags = (best.default_left[leaf].astype(jnp.int32)
             | ((best.left_count[leaf] <= best.right_count[leaf])
                .astype(jnp.int32) << 1)
             | (missing_type[feat] << 2))
    if set_words:
        flags = flags | (best.is_categorical[leaf].astype(jnp.int32) << 4)
    cols = [(jnp.where(r < k, r + 1, 0), W + 1),    # crank + 1, 0 = none
            (feat_group[feat], G),
            (best.threshold[leaf], num_bins),
            (flags, 32),
            (default_bin[feat], num_bins),
            (num_bin[feat], num_bins + 1),
            (feat_start[feat], num_bins + 1)]
    lane_bytes, spans = [], []
    for v, bound in cols:
        nb_ = _byte_count(bound)
        spans.append((len(lane_bytes), nb_))
        lane_bytes += [(v >> (8 * i)) & 255 for i in range(nb_)]
    set_at = len(lane_bytes)
    if set_words:
        words = best.cat_bitset[leaf][:, :set_words]
        lane_bytes += [((words[:, w] >> jnp.uint32(8 * i)) & jnp.uint32(255))
                       .astype(jnp.int32)
                       for w in range(set_words) for i in range(4)]
    lane_t = jnp.stack(lane_bytes).astype(jnp.bfloat16)    # [P, W]
    iota_G = jnp.arange(G, dtype=jnp.int32)

    def decide(lid, bt):
        eq = (lane_leaf[:, None] == lid[None, :]).astype(jnp.bfloat16)
        rb = lax.dot(lane_t, eq)                          # [P, C], exact
        byte = [rb[p].astype(jnp.int32) for p in range(len(lane_bytes))]

        def col(j):
            at, nb_ = spans[j]
            v = byte[at]
            for i in range(1, nb_):
                v = v | (byte[at + i] << (8 * i))
            return v
        crank1, grp, thr, fl, db, nbr, fs = (col(j) for j in range(7))
        crank = jnp.where(crank1 == 0, kcap, crank1 - 1)
        bin_ = jnp.sum(jnp.where(iota_G[:, None] == grp[None, :],
                                 bt.astype(jnp.int32), 0), axis=0)
        dec = bin_ - fs + 1
        binf = jnp.where((dec >= 1) & (dec < nbr), dec, 0)
        gl = row_goes_left(binf, thr, (fl & 1) == 1, None, None,
                           (fl >> 2) & 3, db, nbr)
        if set_words:
            gl = jnp.where(((fl >> 4) & 1) == 1,
                           set_bytes_hold(byte[set_at:], binf), gl)
        slot = jnp.where(gl == (((fl >> 1) & 1) == 1), crank, kcap)
        return crank, gl, slot

    C = min(ROUTE_BLOCK, n)

    def step(i, outs):
        s = jnp.minimum(i * C, n - C)
        crank, gl, slot = decide(
            lax.dynamic_slice_in_dim(leaf_id, s, C),
            lax.dynamic_slice_in_dim(binned_t, s, C, axis=1))
        if live_rows is not None:
            dead = s + lax.iota(jnp.int32, C) >= live_rows
            crank = jnp.where(dead, kcap, crank)
            slot = jnp.where(dead, kcap, slot)
        return tuple(lax.dynamic_update_slice_in_dim(o, v, s, 0)
                     for o, v in zip(outs, (crank, gl, slot)))

    if live_rows is None:
        return lax.fori_loop(
            0, -(-n // C), step, (jnp.zeros(n, jnp.int32),
                                  jnp.zeros(n, jnp.bool_),
                                  jnp.zeros(n, jnp.int32)))
    none = jnp.full(n, kcap, jnp.int32)
    return lax.fori_loop(0, (live_rows + C - 1) // C, step,
                         (none, jnp.zeros(n, jnp.bool_), none))


def route_scan(binned_t, leaf_id, idl, k, best, kcap: int, bin_layout,
               has_cat: bool, live_rows=None):
    """The candidate scan form of a round's routing: one step per
    candidate reads its split feature as a CONTIGUOUS column of the
    transposed matrix and broadcasts scalar split params (kept for CPU,
    where one-hot matmuls lose, and as the router's oracle: the two forms
    grow the same trees bit for bit, tests/test_cat_router.py).  Returns
    ``route_lanes``'s (crank, gl, slot), ``live_rows`` included: a row at
    or past it is in no candidate."""
    num_bin, missing_type, default_bin, feat_group, feat_start = bin_layout
    n = binned_t.shape[1]
    F = num_bin.shape[0]
    b = best
    if live_rows is not None:
        leaf_id = jnp.where(lax.iota(jnp.int32, n) < live_rows, leaf_id, -1)

    def cstep(carry, kk):
        def live(carry):
            gl_a, crank_a, small_a = carry
            leaf = idl[kk]
            feat = jnp.clip(b.feature[leaf], 0, F - 1)
            col = lax.dynamic_index_in_dim(binned_t, feat_group[feat], 0,
                                           keepdims=False)   # [n]
            nb = num_bin[feat]
            dec = col.astype(jnp.int32) - feat_start[feat] + 1
            binf = jnp.where((dec >= 1) & (dec < nb), dec, 0)
            glk = row_goes_left(
                binf, b.threshold[leaf], b.default_left[leaf],
                b.is_categorical[leaf] if has_cat else None,
                b.cat_bitset[leaf] if has_cat else None,
                missing_type[feat], default_bin[feat], nb)
            mk = leaf_id == leaf
            sl = b.left_count[leaf] <= b.right_count[leaf]
            return (jnp.where(mk, glk, gl_a),
                    jnp.where(mk, kk, crank_a),
                    jnp.where(mk, glk == sl, small_a))
        # skip the O(n) column read + masking for dead candidate
        # lanes (late-tree rounds often have k of 1-2 of KCAP)
        return lax.cond(kk < k, live, lambda c_: c_, carry), None

    (gl, crank, row_small), _ = lax.scan(
        cstep,
        (jnp.zeros(n, jnp.bool_), jnp.full(n, kcap, jnp.int32),
         jnp.zeros(n, jnp.bool_)),
        jnp.arange(kcap, dtype=jnp.int32))
    return crank, gl, jnp.where(row_small, crank, kcap)


def grow_tree_rounds(binned_t, *args, **kwargs):
    """Grow one tree, batched-frontier (full signature:
    ``_grow_tree_rounds_traced``).  Span-wrapped like ``grow_tree``:
    records trace-construction time per compile (docs/OBSERVABILITY.md).
    """
    from .obs.trace import span as _span
    with _span("trace.grow_tree_rounds", rows=int(binned_t.shape[1])):
        return _grow_tree_rounds_traced(binned_t, *args, **kwargs)


def _grow_tree_rounds_traced(
    binned_t: jax.Array,        # [G, n] uint8/16 feature-major (rows
                                #   possibly per-shard)
    grad: jax.Array,            # [n] f32
    hess: jax.Array,            # [n] f32
    row_mask: jax.Array,        # [n] f32 bagging/GOSS weights (0 = excluded)
    meta: FeatureMeta,
    cfg: GrowerConfig,
    feature_mask: Optional[jax.Array] = None,   # [F] per-tree col sample
    axis_name: Optional[str] = None,            # mesh axis sharding ROWS
    monotone_constraints: Optional[jax.Array] = None,  # [F] i32 in {-1,0,1}
    rng_key: Optional[jax.Array] = None,
    meta_arrays: Optional[tuple] = None,    # runtime (num_bin, missing_type,
                                            # default_bin, is_cat, feat_group,
                                            # feat_start) — shares the
                                            # compiled program across
                                            # same-shaped datasets
    quant_vals: Optional[tuple] = None,     # cfg.quant: (gq, hq, g_scale,
                                            # h_scale) — see grower.grow_tree
    with_stats: bool = False,
    live_rows: Optional[jax.Array] = None,
):
    """Grow one tree; returns (TreeArrays, leaf_id [n] i32), and with
    ``with_stats`` a third [7] i32: the loop's trips, the candidates it
    offered (a round builds that many smaller-child histograms), the
    splits it committed, the slot widths its histogram passes ran at,
    summed (the fused arm's root pass included; a staged pass runs at the
    round cap), the trips the offer clipped (it bound ``k`` and all ``k``
    committed: the trips it may have cost a pass), the lanes its
    route compared rows with, summed (the router's ``W`` a round, the
    width of its pass on the fused arm and the cap staged; the scan's
    ``k``), and the rows its histogram passes read, summed over the
    passes and the data axis (exact while that stays under 2**31).

    ``live_rows`` (a traced int32, or None): the rows at or past it weigh
    nothing, as in a buffer whose weighted rows were gathered to the front
    (``boosting/goss.kept_rows_first``).  The fused arm's passes then read
    only the row tiles before it and every route stops at it; the tree is
    the one the same rows would grow in any order, since the integer
    family sums exactly.  ``leaf_id`` is then the buffer's, and past
    ``live_rows`` means nothing."""
    meta = meta.resolved()
    G, n = binned_t.shape
    L = cfg.num_leaves
    Lm1 = max(L - 1, 1)
    B = cfg.num_bins
    Bg = meta.max_group_bin if meta.has_bundles else B
    hp = cfg.hp
    F = len(meta.num_bin)

    if meta_arrays is not None:
        (num_bin, missing_type, default_bin, is_cat,
         feat_group, feat_start) = meta_arrays
    else:
        num_bin = jnp.asarray(meta.num_bin)
        missing_type = jnp.asarray(meta.missing_type)
        default_bin = jnp.asarray(meta.default_bin)
        is_cat = jnp.asarray(meta.is_categorical)
        feat_group = jnp.asarray(meta.feat_group)
        feat_start = jnp.asarray(meta.feat_start)
    has_cat = bool(meta.is_categorical.any())

    # quantized-gradient mode (see grower.grow_tree): integer [2, *, Bg]
    # i32 histogram cache + int8 segment kernels; the int->f32 rescale
    # happens once per leaf search (quant_rescale_hist)
    quant = cfg.quant
    rows_global = n * max(cfg.num_machines, 1)
    # planner-selected row tiling (ops/planner.py): all histogram passes
    # stream tiles of this many rows; 0/None = untiled
    tile = cfg.tile_rows if cfg.tile_rows > 0 else None
    if live_rows is not None and (not quant or cfg.quant_renew):
        raise ValueError("live_rows needs the integer family without "
                         "renewal: the grower then reads no f32 row value")
    if quant:
        if quant_vals is None:
            raise ValueError("cfg.quant requires quant_vals="
                             "(gq, hq, g_scale, h_scale)")
        q_grad, q_hess, g_scale, h_scale = quant_vals
        q_levels = quant_levels(cfg.quant_bins)

        def split_conv(ghist, cnt):
            return quant_rescale_hist(ghist, g_scale, h_scale, cnt)
    else:
        hist_fn = functools.partial(build_histogram, num_bins=Bg,
                                    method=cfg.hist_method,
                                    tile_rows=tile)

        def split_conv(ghist, cnt):
            return ghist
    caps = capacity_schedule(n) if cfg.compact else [n]
    use_mc = monotone_constraints is not None
    use_rng = hp.extra_trees or cfg.bynode_feature_cnt > 0
    # fused Pallas histogram→split megakernel arm (ops/fused.py): per
    # ROUND, one kernel streams every binned row tile HBM→VMEM once,
    # accumulates all K candidates' smaller-child bins in a VMEM arena,
    # derives each sibling from the parent histograms in-kernel and
    # scans both children's per-feature gains before writing back only
    # the smaller-child histograms (the cache's subtraction input) and
    # the [2K, F] best tuples — the staged pipeline's [K,ch,F,B] segment
    # output + [2K,ch,F,B] scan re-read round-trip never touches HBM.
    # Sharded training runs the SEAM-SPLIT form of the same kernel
    # (accumulate → psum of only the smaller-child hists → sibling-derive
    # + scan on the reduced arena); categorical columns accumulate in the
    # same arena (their numeric tuples are overridden by the shared cat
    # scan in pick_fused_best's merge) and monotone constraints/bounds
    # ride into the in-kernel scan.  Only EFB bundles and per-node
    # randomness still fall back to the staged family (same trees: the
    # scan body is shared — ops.split.numeric_feature_scan).
    use_fused = (cfg.hist_method == "fused"
                 and not meta.has_bundles and not use_rng)
    # fused u32 column records for the arena's single gather (sorted-path
    # only: gather cost scales with element count — pack_cols_u32; the
    # quantized record fuses (gq, hq, member) into ONE word, Wb+1 vs
    # Wb+3).  LGBM_TPU_PACK=0 falls back to the separate gathers
    # (compile-cost bisect hook).  Under planner tiling the whole-dataset
    # record arena is NOT hoisted (cfg.hist_pack cleared / tile set):
    # the kernels assemble records per tile inside their loops instead.
    # The fused arm gathers nothing — the record arena would be dead
    # weight.
    use_pack = (use_sorted_seghist() and cfg.hist_pack and tile is None
                and not use_fused
                and os.environ.get("LGBM_TPU_PACK") != "0")
    if not use_pack:
        packed = None
    elif quant:
        packed = pack_cols_u32_quant(binned_t, q_grad, q_hess, row_mask > 0)
    else:
        packed = pack_cols_u32(binned_t, grad, hess, row_mask)
    # router candidate routing (route_lanes): O(n)/round instead of the
    # scan's O(k*n); accelerator-shaped.  A categorical candidate's set
    # rides the lane's bytes, as many words as the widest feature's bins
    # need.  LGBM_TPU_ROUTER=0 forces the scan (bisect/testing hook)
    use_router = router_engages()
    set_words = min(MAX_CAT_WORDS, -(-B // 32)) if has_cat else 0
    bin_layout = tuple(a.astype(jnp.int32) for a in (
        num_bin, missing_type, default_bin, feat_group, feat_start))
    # segment-histogram precision follows the resolved histogram method so
    # parent - smaller-child subtraction stays consistent: only the bf16
    # one-hot matmul is inexact; every other kernel accumulates f32-exact
    seg_f32 = resolve_hist_method(cfg.hist_method) != "matmul"

    if meta.has_bundles:
        b_idx = jnp.arange(B, dtype=jnp.int32)

        def expand_hist(ghist, sg, sh, cnt):
            """[3, G, Bg] group hist -> [3, F, B] (FixHistogram bin-0
            reconstruction; see grower.py)."""
            gather_bins = jnp.clip(feat_start[:, None] + b_idx[None, :] - 1,
                                   0, Bg - 1)
            taken = ghist[:, feat_group[:, None], gather_bins]
            valid = (b_idx[None, :] >= 1) & (b_idx[None, :] < num_bin[:, None])
            h = jnp.where(valid[None, :, :], taken, 0.0)
            totals = jnp.stack([sg, sh, cnt])
            return h.at[:, :, 0].set(totals[:, None] - h.sum(axis=2))
    else:
        def expand_hist(ghist, sg, sh, cnt):
            return ghist

    # max splits committed per round.  Any cap preserves exactness (the
    # round applies a PREFIX of the best-first order and the validation
    # check still guards interleaving); it bounds the changed-slot search
    # width and the segment-histogram slot axis.
    KCAP = min(Lm1, max(1, cfg.round_width))
    # under the cap a round offers no more than the carry's ``offer``: one
    # of the pass's widths, following what the last rounds committed
    # (next_offer), so the pass is as wide as the commits need and not as
    # wide as the frontier.  A smaller offer only ends the prefix earlier;
    # what is left is offered again with the same cached gains.
    from .ops.fused import slot_widths
    rungs = jnp.asarray(slot_widths(KCAP), jnp.int32)

    mc_j = jnp.asarray(monotone_constraints) if use_mc else None
    if use_rng and rng_key is None:
        rng_key = jax.random.PRNGKey(0)
    if use_fused:
        from .ops.fused import (frontier_accumulator, fused_sibling_scan,
                                pick_fused_best)
        from .ops.histogram import _vals_t, _vals_t_int
        from .ops.split import feature_best_splits
        fused_scales = (g_scale, h_scale) if quant else None
        fused_ftile = cfg.fused_feat_tile or None
        # ONE accumulate program family for the root and every round, on
        # one chip and on the sharded seam alike: each pass runs at the
        # narrowest compiled slot width that holds its live candidates
        # (the root: one), over one feature-blocked copy of the binned
        # matrix made here, once a tree (ops/fused.frontier_accumulator)
        with jax.named_scope("lgbm.hist"):
            fused_accumulate = frontier_accumulator(
                binned_t,
                (_vals_t_int(q_grad, q_hess, row_mask > 0) if quant
                 else _vals_t(grad, hess, row_mask)),
                KCAP, Bg, feat_tile=fused_ftile,
                block_rows=cfg.fused_block_rows or None, tile_rows=tile,
                live_rows=live_rows)
        # static categorical column index set for pick_fused_best's merge
        cat_idx = (tuple(int(i) for i, v in
                         enumerate(meta.is_categorical) if v)
                   if has_cat else None)

    # ---- per-leaf best-split search, vmapped over all L slots ----------
    def leaf_key(parent, side):
        # node-identity key: stable across application order, so batched
        # and sequential growth draw the same randomness per node
        return jax.random.fold_in(jax.random.fold_in(rng_key, parent + 1),
                                  side)

    def one_leaf_best(ghist, sg, sh, cnt, depth, bmin, bmax, parent, side):
        fm = feature_mask
        eru = None
        if use_rng:
            key = leaf_key(parent, side)
            if cfg.bynode_feature_cnt > 0:
                u = jax.random.uniform(jax.random.fold_in(key, 0), (F,))
                kth = -lax.top_k(-u, cfg.bynode_feature_cnt)[0][-1]
                bn = (u <= kth).astype(jnp.float32)
                fm = bn if fm is None else fm * bn
            if hp.extra_trees:
                eru = jax.random.uniform(jax.random.fold_in(key, 1), (F, 2))
        bounds = (bmin, bmax) if use_mc else None
        hist = expand_hist(split_conv(ghist, cnt), sg, sh, cnt)
        r = best_split_for_leaf(
            hist, sg, sh, cnt, num_bin, missing_type, default_bin, is_cat,
            hp, feature_mask=fm, monotone_constraints=mc_j,
            leaf_output_bounds=bounds, has_categorical=has_cat,
            extra_rand_u=eru)
        if cfg.max_depth > 0:
            r = r._replace(gain=jnp.where(depth >= cfg.max_depth,
                                          -jnp.inf, r.gain))
        return r

    search_all = jax.vmap(one_leaf_best)

    def cache_from(sr: SplitResult) -> _LeafBest:
        return _LeafBest(
            gain=sr.gain, feature=sr.feature, threshold=sr.threshold,
            default_left=sr.default_left,
            left_sum_grad=sr.left_sum_grad, left_sum_hess=sr.left_sum_hess,
            left_count=sr.left_count,
            right_sum_grad=sr.right_sum_grad,
            right_sum_hess=sr.right_sum_hess, right_count=sr.right_count,
            is_categorical=sr.is_categorical, cat_bitset=sr.cat_bitset)

    # ---- root ----------------------------------------------------------
    # reduction policy over the (possibly tiered, see parallel/
    # collectives.py) data axis — one closure per grower, like grower.py
    hier_rd, pinned_rd = cfg.hier_reduce, cfg.pinned_reduce

    def psum_(x):
        return _psum(x, axis_name, hier_rd, pinned_rd)

    with jax.named_scope("lgbm.hist"):
        if quant:
            member = row_mask > 0
            if use_fused:
                root_arena, root_width, root_rows, _ = fused_accumulate(
                    lambda W: (jnp.where(member, 0, KCAP), None), 1)
                root_local = root_arena[0]
            else:
                root_local = build_histogram_int(
                    binned_t, q_grad, q_hess, member, Bg,
                    method=cfg.hist_method, levels=q_levels, tile_rows=tile)
            root_hist = psum_quant_hist(root_local, axis_name, rows_global,
                                        cfg.quant_bins, hierarchical=hier_rd)
            root_sg = psum_(jnp.sum(jnp.where(member, q_grad, 0).astype(
                jnp.int32))).astype(jnp.float32) * g_scale
            root_sh = psum_(jnp.sum(jnp.where(member, q_hess, 0).astype(
                jnp.int32))).astype(jnp.float32) * h_scale
            root_cnt = psum_(jnp.sum(member.astype(jnp.float32)))
        else:
            if use_fused:
                root_arena, root_width, root_rows, _ = fused_accumulate(
                    lambda W: (jnp.where(row_mask > 0, 0, KCAP), None), 1)
                root_local = root_arena[0]
            else:
                root_local = hist_fn(binned_t, grad, hess, row_mask)
            root_hist = psum_(root_local)
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
    leaf_parent_side = jnp.zeros(L, jnp.int32)
    leaf_min = jnp.full(L, -jnp.inf, jnp.float32)
    leaf_max = jnp.full(L, jnp.inf, jnp.float32)
    leaf_id = jnp.zeros(n, jnp.int32)

    with jax.named_scope("lgbm.scan"):
        best = cache_from(search_all(
            hist_cache, leaf_sg, leaf_sh, leaf_cnt, tree.leaf_depth,
            leaf_min, leaf_max, tree.leaf_parent, leaf_parent_side))

    class Carry(NamedTuple):
        tree: TreeArrays
        best: _LeafBest
        hist: jax.Array
        leaf_sg: jax.Array
        leaf_sh: jax.Array
        leaf_cnt: jax.Array
        leaf_parent_side: jax.Array
        leaf_id: jax.Array
        split_idx: jax.Array
        leaf_min: jax.Array
        leaf_max: jax.Array
        rounds: jax.Array       # trips of the loop so far
        offered: jax.Array      # sum of k: candidates built
        applied: jax.Array      # sum of m: splits committed
        slots: jax.Array        # sum of the slot widths the passes ran at
        offer: jax.Array        # most candidates the next round may offer
        last_m: jax.Array       # splits the last round committed
        clipped: jax.Array      # trips the offer bound and wholly committed
        lanes: jax.Array        # sum of the lanes the route compared rows with
        rows: jax.Array         # sum of the rows the passes read

    iota_L = jnp.arange(L, dtype=jnp.int32)

    def active_gains(c: Carry):
        active = iota_L < c.tree.num_leaves
        return jnp.where(active, c.best.gain, -jnp.inf)

    def cond(c: Carry):
        return (c.split_idx < L - 1) & (jnp.max(active_gains(c)) > 0.0)

    def apply_round(c: Carry, sel, rank, k, gl, seg, crank):
        """Commit the splits of the ``sel`` leaves (rank = application
        order within the round; ``crank`` = per-row candidate rank from the
        candidate scan, KCAP for rows not in a candidate leaf); returns the
        updated carry WITHOUT a refreshed best cache (the caller searches
        afterwards)."""
        b = c.best
        node_of = c.split_idx + rank                  # [L] new node ids
        newleaf_of = c.tree.num_leaves + rank         # [L] right-child leaves

        feat = b.feature
        lg, lh, lc = b.left_sum_grad, b.left_sum_hess, b.left_count
        rg, rh, rc = b.right_sum_grad, b.right_sum_hess, b.right_count

        tree = c.tree
        # fix the parents' dangling child pointers (parents are nodes from
        # earlier rounds; within-round parents don't exist by construction)
        pn = jnp.maximum(tree.leaf_parent, 0)
        fixl = sel & (tree.leaf_parent >= 0) & (c.leaf_parent_side == 0)
        fixr = sel & (tree.leaf_parent >= 0) & (c.leaf_parent_side == 1)
        left_child = _pad_scatter(tree.left_child, pn, node_of, fixl)
        right_child = _pad_scatter(tree.right_child, pn, node_of, fixr)
        # write the new node rows
        parent_out = leaf_output(c.leaf_sg, c.leaf_sh, hp.lambda_l1,
                                 hp.lambda_l2, hp.max_delta_step)
        new_depth = tree.leaf_depth + 1
        ps = functools.partial(_pad_scatter, idx=node_of, sel=sel)
        tree = tree._replace(
            split_feature=ps(tree.split_feature, val=feat),
            threshold_bin=ps(tree.threshold_bin, val=b.threshold),
            default_left=ps(tree.default_left, val=b.default_left),
            is_categorical=ps(tree.is_categorical, val=b.is_categorical),
            cat_bitset=ps(tree.cat_bitset, val=b.cat_bitset),
            left_child=ps(left_child, val=~iota_L),
            right_child=ps(right_child, val=~newleaf_of),
            split_gain=ps(tree.split_gain, val=b.gain),
            internal_value=ps(tree.internal_value, val=parent_out),
            internal_weight=ps(tree.internal_weight, val=c.leaf_sh),
            internal_count=ps(tree.internal_count, val=c.leaf_cnt),
            leaf_parent=_pad_scatter(
                jnp.where(sel, node_of, tree.leaf_parent),
                newleaf_of, node_of, sel),
            leaf_depth=_pad_scatter(
                jnp.where(sel, new_depth, tree.leaf_depth),
                newleaf_of, new_depth, sel),
            num_leaves=tree.num_leaves + k,
        )
        leaf_parent_side = _pad_scatter(
            jnp.where(sel, 0, c.leaf_parent_side),
            newleaf_of, jnp.ones(L, jnp.int32), sel)

        # -- rows: those in a selected leaf that go right get the new leaf.
        # The right-child leaf of the rank-r candidate is num_leaves + r,
        # so the update is pure arithmetic on the per-row candidate rank —
        # no [n]-sized gather from a leaf table (measured ~130 ms per
        # gathered pass at 11M rows on v5e in a builder's r5 probe).
        new_leaf_id = jnp.where((crank < k) & ~gl,
                                c.tree.num_leaves + crank, c.leaf_id)

        # -- leaf stats (left child keeps the leaf index: elementwise)
        leaf_sg = _pad_scatter(jnp.where(sel, lg, c.leaf_sg),
                               newleaf_of, rg, sel)
        leaf_sh = _pad_scatter(jnp.where(sel, lh, c.leaf_sh),
                               newleaf_of, rh, sel)
        leaf_cnt = _pad_scatter(jnp.where(sel, lc, c.leaf_cnt),
                                newleaf_of, rc, sel)

        # -- histograms: seg holds the SMALLER child of each selected leaf
        small_left = lc <= rc
        small = seg[jnp.clip(rank, 0, KCAP - 1)]       # [L, 3, G, Bg]
        hist_left = jnp.where(small_left[:, None, None, None],
                              small, c.hist - small)
        hist_right = c.hist - hist_left
        selb = sel[:, None, None, None]
        hist = _pad_scatter(jnp.where(selb, hist_left, c.hist),
                            newleaf_of, hist_right, sel)

        # -- monotone bound propagation (see grower.py apply_split)
        leaf_min, leaf_max = c.leaf_min, c.leaf_max
        if use_mc:
            l_min, l_max, r_min, r_max = child_bounds(c)
            leaf_min = _pad_scatter(jnp.where(sel, l_min, leaf_min),
                                    newleaf_of, r_min, sel)
            leaf_max = _pad_scatter(jnp.where(sel, l_max, leaf_max),
                                    newleaf_of, r_max, sel)

        return c._replace(
            tree=tree, hist=hist, leaf_sg=leaf_sg, leaf_sh=leaf_sh,
            leaf_cnt=leaf_cnt, leaf_parent_side=leaf_parent_side,
            leaf_id=new_leaf_id, split_idx=c.split_idx + k,
            leaf_min=leaf_min, leaf_max=leaf_max, applied=c.applied + k)

    def child_bounds(c: Carry):
        """Per-leaf monotone bounds the two children of each leaf's cached
        split would inherit ([L] vectors; see grower.py apply_split)."""
        b = c.best
        lg, lh = b.left_sum_grad, b.left_sum_hess
        rg, rh = b.right_sum_grad, b.right_sum_hess
        p_min, p_max = c.leaf_min, c.leaf_max
        l_out = jnp.clip(leaf_output(lg, lh, hp.lambda_l1, hp.lambda_l2,
                                     hp.max_delta_step), p_min, p_max)
        r_out = jnp.clip(leaf_output(rg, rh, hp.lambda_l1, hp.lambda_l2,
                                     hp.max_delta_step), p_min, p_max)
        mid = (l_out + r_out) * 0.5
        mc_f = mc_j[jnp.clip(b.feature, 0, F - 1)]
        upd = (~b.is_categorical) & (mc_f != 0)
        l_min = jnp.where(upd & (mc_f < 0), jnp.maximum(p_min, mid), p_min)
        l_max = jnp.where(upd & (mc_f > 0), jnp.minimum(p_max, mid), p_max)
        r_min = jnp.where(upd & (mc_f > 0), jnp.maximum(p_min, mid), p_min)
        r_max = jnp.where(upd & (mc_f < 0), jnp.minimum(p_max, mid), p_max)
        return l_min, l_max, r_min, r_max

    iota_K = jnp.arange(KCAP, dtype=jnp.int32)

    def cache_scatter(base: _LeafBest, ids, res: SplitResult, valid):
        """Overwrite cache rows ``ids`` (where ``valid``) with ``res``."""
        new = cache_from(res)
        return jax.tree_util.tree_map(
            lambda b_, v: _pad_scatter(b_, ids, v, valid), base, new)

    def body(c: Carry) -> Carry:
        with jax.named_scope("lgbm.route"):
            gains = active_gains(c)
            pos = gains > 0.0
            npos = jnp.sum(pos.astype(jnp.int32))
            budget = (L - c.tree.num_leaves).astype(jnp.int32)
            room = jnp.minimum(jnp.minimum(npos, budget), KCAP)
            k = jnp.minimum(room, c.offer)
            # total order (gain desc, leaf asc) = successive best-first ArgMax
            # picks (reference: SerialTreeLearner::Train loop :175-193)
            order = jnp.argsort(-gains, stable=True)
            rank = jnp.zeros(L, jnp.int32).at[order].set(iota_L)

            # -- candidate routing: per-row goes-left bit, candidate rank, and
            # smaller-child slot for the whole batch.
            b = c.best
            idl = jnp.clip(order[:KCAP], 0, L - 1)          # candidate leaves
            small_left = b.left_count <= b.right_count
            if use_router:
                # the router form (accelerator path): the rows are decided
                # a block at a time against the round's W lanes
                # (route_lanes): O(n) a round at W compares a row instead
                # of the scan's k column passes, and no per-row parameter
                # array.  On the fused arm W is the width of the round's
                # accumulate pass and the route runs in that pass's branch
                # of the width switch; staged, W is the cap
                def route_at(W):
                    with jax.named_scope("lgbm.route"):
                        crank_, gl_, slot_ = route_lanes(
                            binned_t, c.leaf_id, idl, k, W, b, KCAP,
                            bin_layout, max(B, Bg), set_words, live_rows)
                    return slot_, (crank_, gl_)
            else:
                crank_s, gl_s, slot_s = route_scan(
                    binned_t, c.leaf_id, idl, k, b, KCAP, bin_layout,
                    has_cat, live_rows)

                def route_at(W):
                    return slot_s, (crank_s, gl_s)
            if not use_fused:
                slot, (crank, gl) = route_at(KCAP)
        width = jnp.int32(KCAP)
        rows = jnp.int32(n)
        with jax.named_scope("lgbm.hist"):
            if use_fused:
                # the accumulate half of the fused megakernel, at the
                # narrowest compiled width that holds the k candidates, in
                # one switch with the route; sharded, exactly these
                # (padded) hists cross the wire
                seg, width, rows, (crank, gl) = fused_accumulate(route_at,
                                                                 k)
                seg = (psum_quant_hist(seg, axis_name, rows_global,
                                       cfg.quant_bins, hierarchical=hier_rd)
                       if quant else psum_(seg))
            elif quant:
                seg = psum_quant_hist(compacted_segment_histogram_int(
                    binned_t, q_grad, q_hess, row_mask, slot, KCAP, Bg, caps,
                    num_live=k, packed=packed, levels=q_levels,
                    tile_rows=tile),
                    axis_name, rows_global, cfg.quant_bins,
                    hierarchical=hier_rd)
            else:
                seg = _psum(compacted_segment_histogram(
                    binned_t, grad, hess, row_mask, slot, KCAP, Bg, caps,
                    f32_vals=seg_f32, num_live=k, packed=packed,
                    tile_rows=tile), axis_name, hier_rd, pinned_rd)
        # the lanes the route compared rows with: the router's W, the
        # scan's live candidates
        lanes = width if use_router else k

        # -- candidate children's best splits, BEFORE committing anything:
        # per-leaf candidates are independent, so lane i's results are
        # valid under any commit that includes candidate i.  Left children
        # keep the parent's leaf slot; stats come from the cache.
        with jax.named_scope("lgbm.scan"):
            ph = c.hist[idl]                                # [K, 3, G, Bg]
            lg_, lh_, lc_ = (b.left_sum_grad[idl], b.left_sum_hess[idl],
                             b.left_count[idl])
            rg_, rh_, rc_ = (b.right_sum_grad[idl], b.right_sum_hess[idl],
                             b.right_count[idl])
            depth_c = c.tree.leaf_depth[idl] + 1
            if use_fused:
                # fused megakernel (ops/fused.py), split at THE COLLECTIVE
                # SEAM on one chip and sharded alike: gains are not
                # summable across shards but the smaller-child hists are,
                # so `seg` was accumulated in the VMEM arena (and reduced
                # over the data axes) above, and the sibling-derive + scan
                # runs here on the (reduced) arena at the round cap,
                # whatever width the pass ran at.  The reduction routing
                # is byte-identical to the staged arm's (psum_quant_hist /
                # _psum) and integer accumulation is associative, so fused
                # == staged bit-for-bit in quantized mode.  The pick +
                # depth gate mirror search_all's best_split_for_leaf +
                # gain gating exactly.
                csums = jnp.stack([jnp.concatenate([lg_, rg_]),
                                   jnp.concatenate([lh_, rh_]),
                                   jnp.concatenate([lc_, rc_])])   # [3, 2K]
                if use_mc:
                    bl_min, bl_max, br_min, br_max = child_bounds(c)
                    f_bounds = (jnp.concatenate([bl_min[idl], br_min[idl]]),
                                jnp.concatenate([bl_max[idl], br_max[idl]]))
                else:
                    f_bounds = None
                nfb = fused_sibling_scan(
                    seg, csums, num_bin, missing_type, default_bin, hp,
                    small_left=small_left[idl], parent_hist=ph,
                    quant_scales=fused_scales,
                    monotone_constraints=mc_j, child_bounds=f_bounds,
                    feat_tile=fused_ftile)
                if has_cat:
                    # categorical merge: the arena accumulated the cat
                    # columns too (same segment reduction) — derive the
                    # children's cat slices from the cached parents, rescale
                    # (the slice's default count factor is bit-identical to
                    # the full hist's: integer hess totals match across
                    # features), and run the SHARED cat scan; the tuples
                    # override the kernel's numeric ones in the pick below.
                    ci = jnp.asarray(cat_idx, jnp.int32)
                    sm_c = seg[:, :, ci, :]
                    ph_c = ph[:, :, ci, :]
                    slc = small_left[idl][:, None, None, None]
                    hl_c = jnp.where(slc, sm_c, ph_c - sm_c)
                    chc = jnp.concatenate([hl_c, ph_c - hl_c])  # [2K,ch,Fc,B]
                    if quant:
                        chc = quant_rescale_hist(chc, g_scale, h_scale,
                                                 csums[2])
                    nb_c, mt_c, db_c = (num_bin[ci], missing_type[ci],
                                        default_bin[ci])
                    ic_c = is_cat[ci]
                    cat_fb = jax.vmap(
                        lambda hh, sg_, sh_, cn_: feature_best_splits(
                            hh, sg_, sh_, cn_, nb_c, mt_c, db_c, ic_c, hp,
                            has_categorical=True))(
                        chc, csums[0], csums[1], csums[2])
                else:
                    cat_fb = None
                res = pick_fused_best(nfb, csums[0], csums[1], csums[2],
                                      feature_mask=feature_mask,
                                      cat_best=cat_fb, cat_idx=cat_idx)
                if cfg.max_depth > 0:
                    dd = jnp.concatenate([depth_c, depth_c])
                    res = res._replace(gain=jnp.where(
                        dd >= cfg.max_depth, -jnp.inf, res.gain))
            else:
                sl = small_left[idl][:, None, None, None]
                h_left = jnp.where(sl, seg, ph - seg)
                h_right = ph - h_left
                if use_mc:
                    (bl_min, bl_max,
                     br_min, br_max) = child_bounds(c)
                    bmin = jnp.concatenate([bl_min[idl], br_min[idl]])
                    bmax = jnp.concatenate([bl_max[idl], br_max[idl]])
                else:
                    bmin = bmax = jnp.zeros(2 * KCAP, jnp.float32)
                node_of_k = c.split_idx + iota_K            # candidate node ids
                res = search_all(
                    jnp.concatenate([h_left, h_right]),
                    jnp.concatenate([lg_, rg_]), jnp.concatenate([lh_, rh_]),
                    jnp.concatenate([lc_, rc_]),
                    jnp.concatenate([depth_c, depth_c]), bmin, bmax,
                    jnp.concatenate([node_of_k, node_of_k]),
                    jnp.concatenate([jnp.zeros(KCAP, jnp.int32),
                                     jnp.ones(KCAP, jnp.int32)]))

            # -- maximal exact prefix: candidate i (in gain order) is the
            # best-first pop at step i iff its gain >= every child spawned by
            # candidates 0..i-1 (ties go to the existing leaf: children's leaf
            # numbers are always larger, and the reference ArgMax takes the
            # smallest leaf number).
            cg = jnp.where(jnp.isnan(res.gain), -jnp.inf, res.gain)
            pair_max = jnp.maximum(cg[:KCAP], cg[KCAP:])
            pair_max = jnp.where(iota_K < k, pair_max, -jnp.inf)
            pcm = jax.lax.cummax(pair_max)                  # children of 0..i
            sel_sorted = gains[idl]                         # gains by rank
            follow = (iota_K == 0) | (sel_sorted >= jnp.concatenate(
                [jnp.full((1,), -jnp.inf), pcm[:-1]]))
            if cfg.rounds_relaxed:
                # "fast" mode: always commit the whole batch.  Deviates from
                # strict best-first only when a child would have outranked a
                # batched candidate AND the leaf budget later binds — the same
                # class of tree-shape deviation the reference accepts between
                # its CPU and GPU learners.  ~log2(num_leaves) rounds, never a
                # short prefix.
                m = k
            else:
                m = jnp.minimum(k, jnp.cumprod(
                    follow.astype(jnp.int32)).sum().astype(jnp.int32))

        sel_m = pos & (rank < m)
        with jax.named_scope("lgbm.commit"):
            cm = apply_round(c, sel_m, rank, m, gl, seg, crank)
            idc = jnp.concatenate([idl, jnp.clip(
                c.tree.num_leaves + iota_K, 0, L - 1)])
            valid_m = jnp.concatenate([iota_K < m, iota_K < m])
            return cm._replace(
                best=cache_scatter(c.best, idc, res, valid_m),
                rounds=c.rounds + 1, offered=c.offered + k,
                slots=c.slots + width,
                offer=next_offer(rungs, m, c.last_m), last_m=m,
                clipped=c.clipped + ((c.offer < room) & (m == k)),
                lanes=c.lanes + lanes, rows=c.rows + rows)

    zero = jnp.array(0, jnp.int32)
    init = Carry(tree, best, hist_cache, leaf_sg, leaf_sh, leaf_cnt,
                 leaf_parent_side, leaf_id, zero, leaf_min, leaf_max,
                 zero, zero, zero, root_width if use_fused else zero,
                 rungs[0], zero, zero, zero,
                 root_rows if use_fused else jnp.int32(n))
    out = lax.while_loop(cond, body, init)

    # finalize leaf values (reference: CalculateSplittedLeafOutput; clamped
    # to monotone bounds like grower.py; quantized renewal re-fits from
    # TRUE f32 sums — see grower.grow_tree's finalize)
    with jax.named_scope("lgbm.leaf_values"):
        tree = out.tree
        leaf_sh_out = out.leaf_sh
        if quant and cfg.quant_renew:
            from .ops.renew import quant_train_renew_leaf
            sg_t, sh_t = quant_train_renew_leaf(out.leaf_id, grad, hess,
                                                row_mask, L)
            sg_t = _psum(sg_t, axis_name, hier_rd, pinned_rd)
            sh_t = _psum(sh_t, axis_name, hier_rd, pinned_rd)
            lv = leaf_output(sg_t, sh_t, hp.lambda_l1, hp.lambda_l2,
                             hp.max_delta_step)
            leaf_sh_out = sh_t
        else:
            lv = leaf_output(out.leaf_sg, out.leaf_sh, hp.lambda_l1,
                             hp.lambda_l2, hp.max_delta_step)
        if use_mc:
            lv = jnp.clip(lv, out.leaf_min, out.leaf_max)
        active = iota_L < tree.num_leaves
        tree = tree._replace(
            leaf_value=jnp.where(active, lv, 0.0),
            leaf_weight=jnp.where(active, leaf_sh_out, 0.0),
            leaf_count=jnp.where(active, out.leaf_cnt, 0.0),
        )
    if with_stats:
        return tree, out.leaf_id, jnp.stack(
            [out.rounds, out.offered, out.applied, out.slots, out.clipped,
             out.lanes, out.rows * max(cfg.num_machines, 1)])
    return tree, out.leaf_id
