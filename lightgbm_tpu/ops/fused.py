"""Fused Pallas histogram→split megakernel: one HBM pass per level.

Why this module exists (ROADMAP item 2): the staged
pipeline runs histogram build and split-gain scan as SEPARATE device
programs with the materialized ``[L, ch, F, B]`` histogram round-tripping
through HBM between them — ``mfu_histogram_lower_bound`` pinned at
~0.0005 even in the best sorted-arena run (0.852 s/tree at 1M×28).  The
GPU prior art (shared-memory histograms: Wen et al., arXiv 1706.08359;
XGBoost GPU, arXiv 1806.11248) accumulates bins in fast on-chip memory
and scans gains before ever writing back; this is the TPU/Pallas shape
of that move:

- Grid = (feature blocks, row tiles), row axis fastest.  Each binned
  row tile streams HBM→VMEM ONCE per level (Pallas' block pipeline
  double-buffers the tile DMA against compute automatically — the
  planner's ``fused_vmem_bytes`` model charges 2× tile bytes for it).
- Per-leaf grad/hess bins accumulate into a VMEM scratch arena in the
  slot-expanded MXU formulation (``segment_histogram_expanded``'s
  one-hot ⊗ slot-mask matmul — the quantized 2×64-slot layout fills one
  s8 MXU tile exactly), so the arena never leaves the chip between the
  build and the scan.
- After the last tile, STILL IN-KERNEL: sibling-subtraction children
  derive their histograms from the parent arena carried alongside the
  scratch (``sibling = parent − smaller``), the quantized arena is
  rescaled (``quant_rescale_hist``'s formulas, kept in lockstep), and
  the per-feature cumulative-sum gain scan runs — BOTH missing-direction
  sweeps, the L1/L2 thresholds, the monotone clamp when constraints
  ride along — via ``ops.split.numeric_feature_scan``, the SAME function
  the staged pipeline calls, so fused == staged per-feature-best tuples
  are bit-identical by construction given bit-identical histograms
  (exactly the case for the integer family: int32 accumulation is
  associative).
- Writeback per level is the tiny ``[children, F]`` per-feature-best
  tuple set (gain, bin, direction, left sums) plus the one smaller-child
  histogram the growers' subtraction cache needs — the staged pipeline's
  extra hist-cache read for the scan (and the sibling's write+read) never
  happens.  ``hist_scan_traffic_bytes`` is the accounting twin.

**The collective seam** (sharded training): gains are NOT summable
across data shards, but the smaller-child histograms are — so the
megakernel splits into ``fused_frontier_accumulate`` (the accumulate
half, emitting the LOCAL ``[K, ch, F, B]`` arena straight from VMEM)
→ one tiered ``psum``/``psum_int_tiered`` of exactly those hists over
ICI/DCN (``parallel/collectives.py``) → ``fused_sibling_scan`` (the
epilogue half: sibling-derive + rescale + gain scan on the REDUCED
arena).  Both halves run the verbatim code paths of the combined
kernel (``_accumulate_tile`` / ``_derive_and_scan``), so sharded fused
== sharded staged stays bit-identical for the integer family, and the
staged ``[L, ch, F, B]`` HBM scan round-trip disappears from the
data-parallel path too — only hists cross the wire.

**The width the round needs** (the rounds grower, one chip and sharded
alike).  The accumulate half is a one-hot matmul: a pass costs rows x F x
(ch · slots) x B multiply-adds whatever the rows hold, and at 128 int8
slots it runs at ~96% of a v5e's int8 peak (root PERF.md section 5) —
compute-bound, so the slot axis is the lever.  The VPU's part, building
the two one-hot operands, is packed four cells to a 32-bit word where
``packed_operands`` says the shape allows (int8 values, one-byte bins,
32 to 128 padded bins a feature: four int32 ops a word of four cells, no
int32 intermediate); the compare form, one int32 compare a cell, stays
for the f32 family and the other shapes.  ``frontier_accumulator``
compiles the pass at ``NARROW_SLOT_WIDTHS`` and at the round cap, over
ONE feature-blocked copy of the binned matrix built once a tree
(``fused_blocked_bins``), and runs each round — and the root, whose one
slot is every member row — at the narrowest width that holds its live
candidates; the arena is zero-padded to the cap, so the collective and
``fused_sibling_scan`` keep one shape.
How many candidates a round offers, hence the width, is the grower's
(``grower_rounds.next_offer``: what its last rounds committed).

Scope: numeric AND categorical features (per-category stats are the
same segment reduction — the kernel accumulates every column and the
growers override the in-kernel numeric tuples on categorical columns
with the shared ``feature_best_splits`` cat scan via
``pick_fused_best``'s merge), with or without monotone constraints
(the constraint vector rides as a fourth meta row and the per-child
output bounds as a ``[2, NC]`` input into the in-kernel scan).  The
growers still gate the fused arm off for EFB bundles and per-node
randomness (extra_trees / by-node column sampling), falling back to
the staged family; ``hist_method=auto`` elects fused only when
``ops.planner.plan_fused`` proves the VMEM arena fits.  "One HBM pass
per LEVEL" is the rounds grower's contract (one kernel per frontier
round); the serial grower's fused arm streams the full matrix once per
SPLIT with no leaf compaction — it exists for mode completeness and
the parity suite, so ``auto`` only elects fused where the rounds
grower runs (explicit ``hist_method=fused`` still honors a forced
``tpu_tree_growth=serial``).

Off-accelerator the whole family runs under
``pl.pallas_call(..., interpret=True)`` so tier-1's ``JAX_PLATFORMS=cpu``
pytest run executes the kernels instead of skipping them — and tier-1
also COMPILES them for the chip (tests/test_chip_compile.py: a described
v5e, no chip attached), because interpret mode cannot see what the
chip's compiler refuses.

**What runs on the chip.**  The accumulate half compiles and is what an
accelerator runs.  The scan epilogue does not — the Pallas TPU lowering
has no ``cumsum``, and Mosaic has no layout for the combined kernel's
2-D→4-D arena view — so there the in-VMEM scan described above is not
elected: ``interpret=None`` on an accelerator means accumulate kernel,
then ``_derive_and_scan`` as plain XLA over the arena it emits (the same
body, hence the same tuples; the ``[K, ch, F, B]`` arena makes one HBM
round trip the single-kernel form would save).  The combined kernel and
the standalone scan kernel stay for interpret mode and for the rewrite
that can claim that saving; forcing them with ``interpret=False`` raises
the compiler's error (docs/PERF.md "What compiles on the chip").
"""

from __future__ import annotations

import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .histogram import _pad_rows, on_accelerator, resolve_tile_rows
from .split import (K_MIN_SCORE, MAX_CAT_WORDS, NumericFeatureBest,
                    PerFeatureBest, SplitHyperparams, SplitResult,
                    numeric_feature_scan, quant_rescale_hist)

# row-tile (VMEM block) and feature-block defaults; the planner's
# plan_fused() picks per-shape values against the VMEM budget
_DEF_BLOCK_ROWS = 512
_DEF_FEAT_TILE = 8


def _interp(interpret: Optional[bool]) -> bool:
    return (not on_accelerator()) if interpret is None else bool(interpret)


def _scan_in_xla(interpret: Optional[bool]) -> bool:
    """Whether the gain scan runs as plain XLA over the kernel-built
    arena instead of inside a Pallas kernel: what the election
    (``interpret=None``) does on an accelerator, whose compiler has no
    lowering for the scan epilogue (module docstring, "What runs on the
    chip": ``Unimplemented primitive in Pallas TPU lowering ... cumsum``,
    ``infer-vector-layout: unsupported shape cast``).  An explicit
    ``interpret=False`` still asks for the in-kernel form and raises that
    error; it is never demoted."""
    return interpret is None and on_accelerator()


def hist_scan_traffic_bytes(num_candidates: int, num_features: int,
                            num_bins: int, quant: bool = False) -> int:
    """Per-level HBM bytes the fused kernel does NOT move vs staged.

    Staged, per round of K candidates: the split scan re-reads both
    children's histograms (2K·ch·F·B cells) and the sibling histograms
    are written+read through the cache (K·ch·F·B each way).  Fused scans
    in VMEM and derives siblings in-kernel, so exactly this term drops.
    The SHARDED seam keeps the same drop: the
    psum moves only the ``[K, ch, F, B]`` smaller-child arena the staged
    sharded arm already moves, while the scan re-read + sibling
    write/read still never touch HBM."""
    ch = 2 if quant else 3
    cell = ch * num_features * num_bins * 4
    return num_candidates * cell * 4          # 2K scan reads + K write + K read


def _derive_and_scan(small, sums_k, meta_rows, hp,
                     parent=None, s_is_left_vec=None, scales=None,
                     mono=None, bounds=None):
    """The megakernel epilogue body, shared VERBATIM by the combined
    kernel and ``fused_sibling_scan`` (the post-collective half of the
    sharded seam) so their tuples cannot diverge.

    ``small`` [K, ch, Ft, B]; ``parent`` None | [K, ch, Ft, B];
    ``s_is_left_vec`` None | [K] i32; ``sums_k`` [3, NC];
    ``meta_rows`` (num_bin, missing, default) [Ft] rows; ``mono``
    None | [Ft] i32; ``bounds`` None | ([NC], [NC]) per-child output
    clamp.  Returns ``NumericFeatureBest`` [NC, Ft]."""
    if parent is not None:
        s_is_left = (s_is_left_vec != 0)[:, None, None, None]
        h_left = jnp.where(s_is_left, small, parent - small)
        h_right = parent - h_left
        ch_hist = jnp.concatenate([h_left, h_right], axis=0)
    else:
        ch_hist = small
    sg, sh, cnt = sums_k[0], sums_k[1], sums_k[2]
    if scales is not None:
        # the SHARED rescale body (batched over children; its default
        # count factor reads the block's FIRST feature — any feature's
        # bins partition the child's rows, so the integer total equals
        # the staged feature-0 total bit-for-bit)
        hist3 = quant_rescale_hist(ch_hist, scales[0], scales[1], cnt)
    else:
        hist3 = ch_hist
    return numeric_feature_scan(
        hist3, sg, sh, cnt, meta_rows[0], meta_rows[1], meta_rows[2], hp,
        monotone_constraints=mono, leaf_output_bounds=bounds)


# dtypes of the six per-feature-best tuple planes the scan emits, in
# NumericFeatureBest order (gain, threshold, default_left, left sums)
_TUPLE_DTYPES = (jnp.float32, jnp.int32, jnp.int32,
                 jnp.float32, jnp.float32, jnp.float32)


def _feature_blocked(a: jax.Array, Ft: int) -> jax.Array:
    """[R, F_pad] -> [F_pad // Ft, R, Ft]: one feature block per leading
    index, so a kernel's per-block window ``(None, R, Ft)`` spans the
    whole of the two minor dims (the TPU lowering takes no (R, Ft) window
    of an [R, F_pad] array unless it is a whole (8, 128) tile)."""
    R, F_pad = a.shape
    return a.reshape(R, F_pad // Ft, Ft).transpose(1, 0, 2)


def _feature_unblocked(a: jax.Array) -> jax.Array:
    """Inverse of ``_feature_blocked``: [nf, R, Ft] -> [R, nf * Ft]."""
    nf, R, Ft = a.shape
    return a.transpose(1, 0, 2).reshape(R, nf * Ft)


def fused_blocked_bins(binned_t: jax.Array, feat_tile: int,
                       row_multiple: int) -> jax.Array:
    """[F, n] -> the accumulate kernel's operand ``[F_pad // Ft, Ft,
    n_pad]`` (features padded to whole blocks, rows to ``row_multiple``;
    padded cells are bin 0 of rows every caller drops).  A caller that
    runs several passes over one matrix builds it ONCE and hands it to
    each ``fused_frontier_accumulate`` — at F = 67 the pad is a copy of
    the whole matrix, which the kernel's own call would make again
    before every pass."""
    F, n = binned_t.shape
    Ft = max(1, min(int(feat_tile), F))
    F_pad, n_pad = _pad_rows(F, Ft), _pad_rows(n, int(row_multiple))
    if n_pad != n or F_pad != F:
        binned_t = jnp.pad(binned_t, ((0, F_pad - F), (0, n_pad - n)))
    # a free leading-dim split: the per-step window then spans the whole
    # Ft axis, which the TPU lowering takes at any feature tile — a bare
    # (Ft, C) window of [F, n] would need Ft % 8 == 0
    return binned_t.reshape(F_pad // Ft, Ft, n_pad)


def _row_tile(block_rows: int, tile_rows: Optional[int], n: int) -> int:
    """Rows per grid step: ``block_rows``, CAPPED by ``tile_rows`` (the
    planner's row-tile budget, like the staged family's _tile_block: peak
    per-step bytes track the tile), then halved while padding the ``n``
    rows to whole tiles would add more than a sixteenth to them (row
    counts off the planner's bucket ladder: small data, CPU runs)."""
    T = resolve_tile_rows(tile_rows, n)
    C = max(128, int(block_rows))
    if T is not None:
        C = min(C, _pad_rows(T, 128))
    while C % 256 == 0 and _pad_rows(n, C) - n > n // 16:
        C //= 2
    return C


def _arena_dims(K: int, B: int, Ft: int, quant: bool):
    """(K_pad, B_pad): the slot and bin axes padded so the VMEM arena
    ``[ch*K_pad, Ft*B_pad]`` and both one-hot operands sit on whole
    (sublane, lane) tiles — slots to the sublane count (16 keeps the
    int8 lhs ``[2*K_pad, C]`` on its 32-row tile), bins so one feature
    block spans whole 128-lane groups.  Padded bins match no row and
    padded slots only the dropped rows (slot == K); both are sliced off
    outside the kernel."""
    lane_groups = 128 // math.gcd(Ft, 128)
    return _pad_rows(K, 16 if quant else 8), _pad_rows(B, max(lane_groups, 8))


def _fused_call(
    binned_t: jax.Array,          # [F, n] uint8/uint16 feature-major, or
                                  # its fused_blocked_bins() [nf, Ft, n_pad]
    vals_t: jax.Array,            # f32 [3, n] (g,h,1)*w  |  int8 [2, n]
    slot: jax.Array,              # [n] i32 in [0, K]; K = dropped
    num_slots: int,
    num_bins: int,
    child_sums: Optional[jax.Array],  # [3, NC] f32 (sum_g, sum_h, count)
    meta_vecs: Optional[tuple],   # (num_bin, missing_type, default_bin) [F]
    hp: Optional[SplitHyperparams],
    small_left: Optional[jax.Array] = None,   # [K] bool (with parent)
    parent_hist: Optional[jax.Array] = None,  # [K, ch, F, B]
    quant_scales: Optional[tuple] = None,     # (g_scale, h_scale) traced
    monotone_constraints: Optional[jax.Array] = None,  # [F] i32
    child_bounds: Optional[tuple] = None,     # ([NC], [NC]) output clamp
    feat_tile: Optional[int] = None,
    block_rows: Optional[int] = None,
    tile_rows: Optional[int] = None,
    interpret: Optional[bool] = None,
    with_scan: bool = True,
    num_features: Optional[int] = None,   # F of a blocked ``binned_t``
    live_rows: Optional[jax.Array] = None,    # i32: tiles past it unread
):
    """One megakernel invocation; returns ``(slot_hist [K, ch, F, B],
    NumericFeatureBest [NC, F])`` with NC = 2K (parent mode: children are
    [left 0..K-1, right K..2K-1]) or K (leaf mode: the slot histograms
    themselves are scanned).  ``with_scan=False`` drops the epilogue and
    its inputs entirely — the accumulate half of the collective seam —
    and returns only the histogram.

    ``live_rows`` (a traced int32, or None): only the row tiles that hold
    a row before it are read, so the caller's ``slot`` drops every row at
    or past it in the last of them.  Their count rides in as a
    scalar-prefetch operand; a grid step past the last of them points its
    row blocks at that tile (no new DMA) and skips its accumulate, and the
    epilogue still runs on the grid's last step.  Without it the kernel is
    traced with no prefetch operand at all."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if with_scan and _scan_in_xla(interpret):
        with jax.named_scope("lgbm.hist"):
            hist = _fused_call(
                binned_t, vals_t, slot, num_slots, num_bins, None, None,
                None, feat_tile=feat_tile, block_rows=block_rows,
                tile_rows=tile_rows, with_scan=False,
                num_features=num_features, live_rows=live_rows)
        with jax.named_scope("lgbm.scan"):
            return hist, fused_sibling_scan(
                hist, child_sums, *meta_vecs, hp, small_left=small_left,
                parent_hist=parent_hist, quant_scales=quant_scales,
                monotone_constraints=monotone_constraints,
                child_bounds=child_bounds)

    quant = vals_t.dtype == jnp.int8
    ch = int(vals_t.shape[0])
    acc_dtype = jnp.int32 if quant else jnp.float32
    blocked = binned_t.ndim == 3
    n = int(vals_t.shape[1])
    F = int(num_features) if blocked else int(binned_t.shape[0])
    K = int(num_slots)
    B = int(num_bins)
    with_parent = parent_hist is not None
    NC = 2 * K if with_parent else K
    if with_scan and quant and quant_scales is None:
        raise ValueError("quantized fused kernel needs quant_scales")
    has_mono = with_scan and monotone_constraints is not None
    has_bounds = with_scan and child_bounds is not None

    if (feat_tile is None and not blocked) or block_rows is None:
        from .planner import plan_fused
        fp = plan_fused(K, B, quant, with_parent=with_parent,
                        feat_tile=int(binned_t.shape[1]) if blocked else None,
                        num_features=F)
        if feat_tile is None:
            feat_tile = fp["feat_tile"] if fp else 1
        if block_rows is None:
            block_rows = fp["block_rows"] if fp else 128
    C = _row_tile(block_rows, tile_rows, n)

    bt = binned_t if blocked else fused_blocked_bins(binned_t, feat_tile, C)
    nf_blocks, Ft, n_pad = (int(d) for d in bt.shape)
    if n_pad < n or n_pad % C:
        raise ValueError(f"blocked bins of {n_pad} rows do not hold {n} "
                         f"rows in whole tiles of {C}")
    F_pad = nf_blocks * Ft
    Kp, Bp = _arena_dims(K, B, Ft, quant)
    vt = jnp.pad(vals_t, ((0, 0), (0, n_pad - n))) if n_pad != n else vals_t
    st = jnp.pad(slot.astype(jnp.int32), (0, n_pad - n),
                 constant_values=K)[None, :]               # [1, n_pad]
    nt = n_pad // C
    bounded = live_rows is not None
    if bounded:
        # the row tiles that hold a live row (at least one: the grid's
        # first and last steps always run)
        live_tiles = jnp.clip((live_rows + C - 1) // C, 1, nt).astype(
            jnp.int32).reshape(1)

        def tile_of(i, lt):
            return jnp.minimum(i, lt[0] - 1)
    else:
        def tile_of(i):
            return i

    in_arrays = [bt, vt, st]
    in_specs = [
        pl.BlockSpec((None, Ft, C),
                     lambda j, i, *lt: (j, 0, tile_of(i, *lt))),
        pl.BlockSpec((ch, C), lambda j, i, *lt: (0, tile_of(i, *lt))),
        pl.BlockSpec((1, C), lambda j, i, *lt: (0, tile_of(i, *lt))),
    ]
    if with_parent:
        in_arrays.append(parent_hist.astype(acc_dtype))
        in_specs.append(pl.BlockSpec((K, ch, Ft, B),
                                     lambda j, i, *_: (0, 0, j, 0)))
        in_arrays.append(small_left.astype(jnp.int32)[None, :])  # [1, K]
        in_specs.append(pl.BlockSpec((1, K), lambda j, i, *_: (0, 0)))
    if with_scan:
        num_bin_v, missing_v, default_v = meta_vecs
        meta_rows = [jnp.asarray(num_bin_v, jnp.int32),
                     jnp.asarray(missing_v, jnp.int32),
                     jnp.asarray(default_v, jnp.int32)]
        if has_mono:
            meta_rows.append(jnp.asarray(monotone_constraints, jnp.int32))
        meta = jnp.stack(meta_rows)                        # [3|4, F]
        if F_pad != F:
            # padded features: num_bin 0 -> every bin invalid -> gain -inf
            meta = jnp.pad(meta, ((0, 0), (0, F_pad - F)))
        R = int(meta.shape[0])
        sums = jnp.asarray(child_sums, jnp.float32)        # [3, NC]
        in_arrays.append(sums)
        in_specs.append(pl.BlockSpec((3, NC), lambda j, i, *_: (0, 0)))
        in_arrays.append(_feature_blocked(meta, Ft))
        in_specs.append(pl.BlockSpec((None, R, Ft),
                                     lambda j, i, *_: (j, 0, 0)))
        if quant:
            in_arrays.append(
                jnp.stack([jnp.asarray(quant_scales[0], jnp.float32),
                           jnp.asarray(quant_scales[1],
                                       jnp.float32)])[None, :])
            in_specs.append(pl.BlockSpec((1, 2), lambda j, i, *_: (0, 0)))
        if has_bounds:
            in_arrays.append(jnp.stack(
                [jnp.asarray(child_bounds[0], jnp.float32),
                 jnp.asarray(child_bounds[1], jnp.float32)]))   # [2, NC]
            in_specs.append(pl.BlockSpec((2, NC), lambda j, i, *_: (0, 0)))

    def kernel(*refs):
        it = iter(refs)
        lt_ref = next(it) if bounded else None
        b_ref = next(it)
        v_ref = next(it)
        s_ref = next(it)
        p_ref = next(it) if with_parent else None
        sl_ref = next(it) if with_parent else None
        sum_ref = next(it) if with_scan else None
        m_ref = next(it) if with_scan else None
        sc_ref = next(it) if with_scan and quant else None
        bd_ref = next(it) if has_bounds else None
        hist_ref = next(it)
        if with_scan:
            gn_ref = next(it)
            th_ref = next(it)
            dl_ref = next(it)
            lg_ref = next(it)
            lh_ref = next(it)
            lc_ref = next(it)
        acc = next(it)

        i = pl.program_id(1)

        @pl.when(i == 0)
        def _init():
            acc[...] = jnp.zeros_like(acc)

        if bounded:
            pl.when(i < lt_ref[0])(lambda: _accumulate_tile(
                acc, b_ref, v_ref, s_ref, Kp, Ft, Bp, ch, quant))
        else:
            _accumulate_tile(acc, b_ref, v_ref, s_ref, Kp, Ft, Bp, ch, quant)

        # ---- epilogue after the last tile: emit the 2-D arena as it
        # sits in VMEM (the [K, ch, F, B] view is an XLA reshape outside
        # the kernel — Mosaic has no such shape cast), then derive +
        # scan in VMEM when the combined form is asked for
        @pl.when(i == nt - 1)
        def _epilogue():
            hist_ref[...] = acc[...]
            if with_scan:
                small = acc[...].reshape(ch, Kp, Ft, Bp)[
                    :, :K, :, :B].transpose(1, 0, 2, 3)
                res = _derive_and_scan(
                    small, sum_ref[...],
                    (m_ref[0, :], m_ref[1, :], m_ref[2, :]), hp,
                    parent=p_ref[...] if with_parent else None,
                    s_is_left_vec=sl_ref[0, :] if with_parent else None,
                    scales=(sc_ref[0, 0], sc_ref[0, 1]) if quant else None,
                    mono=m_ref[3, :] if has_mono else None,
                    bounds=(bd_ref[0, :], bd_ref[1, :]) if has_bounds
                    else None)
                gn_ref[...] = res.gain
                th_ref[...] = res.threshold
                dl_ref[...] = res.default_left.astype(jnp.int32)
                lg_ref[...] = res.left_sum_grad
                lh_ref[...] = res.left_sum_hess
                lc_ref[...] = res.left_count

    hist_spec = pl.BlockSpec((ch * Kp, Ft * Bp), lambda j, i, *_: (0, j))
    hist_shape = jax.ShapeDtypeStruct((ch * Kp, F_pad * Bp), acc_dtype)
    tuple_spec = pl.BlockSpec((None, NC, Ft), lambda j, i, *_: (j, 0, 0))
    if with_scan:
        out_specs = [hist_spec] + [tuple_spec] * 6
        out_shape = [hist_shape] + [
            jax.ShapeDtypeStruct((nf_blocks, NC, Ft), dt)
            for dt in _TUPLE_DTYPES]
    else:
        out_specs = [hist_spec]
        out_shape = [hist_shape]
    grid = dict(grid=(nf_blocks, nt), in_specs=in_specs,
                out_specs=out_specs,
                scratch_shapes=[pltpu.VMEM((ch * Kp, Ft * Bp), acc_dtype)])
    if bounded:
        grid = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, **grid))
        in_arrays = [live_tiles] + in_arrays
    out = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        interpret=_interp(interpret),
        name="lgbm_hist_accum",
        **grid,
    )(*in_arrays)
    hist = out[0].reshape(ch, Kp, F_pad, Bp)[
        :, :K, :F, :B].transpose(1, 0, 2, 3)               # [K, ch, F, B]
    if not with_scan:
        return hist
    gain, thr, dl, lgs, lhs_, lcs = (_feature_unblocked(o) for o in out[1:])
    best = NumericFeatureBest(
        gain=gain[:, :F], threshold=thr[:, :F],
        default_left=dl[:, :F].astype(bool),
        left_sum_grad=lgs[:, :F], left_sum_hess=lhs_[:, :F],
        left_count=lcs[:, :F])
    return hist, best


# the four bytes of a 32-bit word, each 1 / each with bit 7 alone (int32)
_BYTE_ONES = 0x01010101
_BYTE_TOPS = 0x80808080 - (1 << 32)


def packed_operands(quant: bool, bin_dtype, padded_bins: int) -> bool:
    """Whether ``_accumulate_tile`` builds its one-hot operands PACKED,
    four cells to a 32-bit word, and not by one int32 compare a cell:
    where the program can see the packed form is exact and aligned — the
    integer family (int8 operands), bins of one byte, every bin id below
    128 (a byte's bit 7 is the packed compare's borrow guard), and a
    feature's ``padded_bins`` (``_arena_dims``) whole int8 sublane tiles
    of 32.  Elsewhere (the f32 family, 2-byte bins, 16 or 256 padded bins)
    the compare form stays.  One predicate, fixed by the static shape;
    ``GBDT._note_trees`` counts passes by it (``hist_passes_*_total``,
    through ``pass_builds_packed``)."""
    return (bool(quant) and jnp.dtype(bin_dtype).itemsize == 1
            and padded_bins <= 128 and padded_bins % 32 == 0)


def _packed_ids(n: int, C: int) -> jax.Array:
    """int32 ``[n // 4, C]`` whose bytes are the row ids 0..n-1 of an
    int8 ``[n, C]`` array, in the bitcast's own packing order: a packed
    one-hot compared against it bitcasts back to int8 with row ``r`` at
    row ``r`` whatever that order is."""
    from jax.experimental.pallas import tpu as pltpu
    ids = lax.broadcasted_iota(jnp.int32, (n, C), 0).astype(jnp.int8)
    return pltpu.bitcast(ids, jnp.int32)


def _packed_onehot(row: jax.Array, ids: jax.Array) -> jax.Array:
    """Four one-hot cells to a word: ``row`` int32 ``[1, C]`` in [0, 128),
    ``ids`` ``_packed_ids``; returns int32 ``[n // 4, C]`` whose bytes are
    1 exactly where the byte of ``ids`` equals ``row``.  The XOR's bytes
    are 0 there and at most 0x7F elsewhere, so ``0x80 - byte`` keeps bit 7
    for the zero byte alone and no borrow crosses a byte: four int32 ops a
    word in place of a compare, a select and a share of a pack a cell."""
    x = (row * _BYTE_ONES) ^ ids
    return ((_BYTE_TOPS - x) >> 7) & _BYTE_ONES


def _accumulate_tile(acc, b_ref, v_ref, s_ref, K, Ft, B, ch, quant):
    """One row tile of the slot-expanded one-hot matmul, accumulated
    into the VMEM arena — the accumulate half of the megakernel, shared
    verbatim by the combined kernel and ``fused_frontier_accumulate``.

    Written in the forms the TPU compiler lowers: both one-hot operands
    are built as 2-D sublane concatenations (no 3-D compare, no
    minor-dim-merging reshape) and the bin one-hot stays TRANSPOSED
    ``[Ft*B, C]`` — the contraction runs over the lane axis of both
    operands (the q·kᵀ matmul form), so no in-kernel transpose is
    needed.  ``K`` and ``B`` arrive padded to the sublane/lane tiling
    (``_arena_dims``).

    The int8 operands come packed where ``packed_operands`` says so: the
    same bytes as the compare form's, hence the same int32 arena bit for
    bit, at a quarter of the VPU's words."""
    from jax.experimental.pallas import tpu as pltpu
    blk = b_ref[...].astype(jnp.int32)                 # [Ft, C]
    C = blk.shape[1]
    s = s_ref[...]                                     # [1, C]
    v = v_ref[...]                                     # [ch, C]
    nt = (((1,), (1,)), ((), ()))                      # lhs · rhsᵀ
    packed = packed_operands(quant, b_ref.dtype, B)
    if packed:
        bin_ids = _packed_ids(B, C)
        oh_bt = pltpu.bitcast(jnp.concatenate(
            [_packed_onehot(blk[f:f + 1, :], bin_ids)
             for f in range(Ft)], axis=0), jnp.int8)         # [Ft*B, C]
    else:
        iota_b = lax.broadcasted_iota(jnp.int32, (B, C), 0)
        cell = jnp.int32 if quant else jnp.float32
        oh_bt = jnp.concatenate(
            [(blk[f:f + 1, :] == iota_b).astype(cell)
             for f in range(Ft)], axis=0)                    # [Ft*B, C]
    if quant:
        # the slot operand packed too where a channel's slots are whole
        # int8 sublane tiles (at 16 slots the half-filled tiles cost more
        # than the compare: 45.7 against 44.7 ms a pass, root PERF.md
        # section 5) and a slot id fits a byte's seven bits
        lhs = (_packed_slot_operand(s, v, K, ch)
               if packed and K <= 128 and K % 32 == 0
               else _compared_slot_operand(s, v, K, ch))
        part = lax.dot_general(lhs, oh_bt.astype(jnp.int8), nt,
                               preferred_element_type=jnp.int32)
    else:
        oh_sf = (s == lax.broadcasted_iota(jnp.int32, (K, C), 0)
                 ).astype(jnp.float32)
        lhs = jnp.concatenate(
            [v[c:c + 1, :] * oh_sf for c in range(ch)], axis=0)
        part = lax.dot_general(lhs, oh_bt, nt,
                               precision=lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    acc[...] += part


def _compared_slot_operand(s, v, K, ch):
    """The int8 slot x value operand ``[ch*K, C]`` by one int32 compare
    and select a cell."""
    oh_s = s == lax.broadcasted_iota(jnp.int32, (K, s.shape[1]), 0)
    return jnp.concatenate(
        [jnp.where(oh_s, v[c:c + 1, :].astype(jnp.int32), 0)
         for c in range(ch)], axis=0).astype(jnp.int8)


def _packed_slot_operand(s, v, K, ch):
    """The same operand four slots to a word.  A dropped row (slot == K;
    128 would not fit the byte) takes slot 0 with value 0; 0/1 bytes
    times a value byte <= 255 carry nothing, so one int32 multiply writes
    four int8 cells."""
    from jax.experimental.pallas import tpu as pltpu
    live = s < K
    oh_s = _packed_onehot(jnp.where(live, s, 0), _packed_ids(K, s.shape[1]))
    vb = jnp.where(live, v.astype(jnp.int32) & 0xFF, 0)
    return pltpu.bitcast(jnp.concatenate(
        [oh_s * vb[c:c + 1, :] for c in range(ch)], axis=0), jnp.int8)


def fused_frontier_accumulate(
    binned_t: jax.Array,
    vals_t: jax.Array,
    slot: jax.Array,
    num_slots: int,
    num_bins: int,
    feat_tile: Optional[int] = None,
    block_rows: Optional[int] = None,
    tile_rows: Optional[int] = None,
    interpret: Optional[bool] = None,
    num_features: Optional[int] = None,
    live_rows: Optional[jax.Array] = None,
) -> jax.Array:
    """The accumulate HALF of the collective seam: build the K
    smaller-child (or slot) histograms in the VMEM arena and emit them —
    no scan, no parent.  Returns ``hist [K, ch, F, B]`` (int32 when
    ``vals_t`` is int8, f32 otherwise).  ``binned_t`` is the ``[F, n]``
    matrix, or its ``fused_blocked_bins`` operand with ``num_features``
    = F (``feat_tile`` is then the operand's own).  ``live_rows``: the
    pass reads only the row tiles that hold a row before it (a buffer
    whose live rows were gathered to the front; ``slot`` drops the rows
    past it).

    Sharded training runs THIS program per shard, reduces exactly its
    output over the data axes (``psum_int_tiered`` / tiered ``psum``),
    then hands the reduced arena to ``fused_sibling_scan`` — gains stay
    local, only hists cross the wire.  One program family also serves
    every frontier round AND the root (slot 0 = all member rows), one
    member a compiled slot width: ``frontier_accumulator`` (docs/PERF.md
    "shared frontier programs")."""
    return _fused_call(
        binned_t, vals_t, slot, num_slots, num_bins, None, None, None,
        feat_tile=feat_tile, block_rows=block_rows, tile_rows=tile_rows,
        interpret=interpret, with_scan=False, num_features=num_features,
        live_rows=live_rows)


# Slot widths the rounds grower compiles the accumulate pass at BELOW its
# round cap.  The one-hot matmul costs rows x F x (ch * slots) x B
# multiply-adds whatever the rows hold, so a round of k live candidates
# runs the narrowest of these that holds k.  Measured on one v5e at
# 25.2M x 67, 64 bins (root PERF.md section 5): int8 at 8192-row tiles
# 44.7 / 81.3 / 157.1 ms a pass at 16 / 64 / 128 slots with the packed
# operands (68.5 / 94.7 / 168.7 with the compared ones; 42.8 / 81.0 /
# 156.6 with both operands handed over for nothing, so building them is
# no longer what a pass waits for); f32 473 (16, 2048-row tiles), 1439
# (64, 512-row tiles), 2796 (128).  When 32 went, at 2048-row tiles and
# compared operands (79 / 87 / 106 / 180 ms at 16 / 32 / 64 / 128), a
# width stayed only where its pass was at least 20% faster than the next
# wider kept one; with the packed operands a 32-slot pass has not been
# measured, and whether the offer should have that rung is the grower's
# question (ROADMAP.md A3).
NARROW_SLOT_WIDTHS = (16, 64)


def slot_widths(kcap: int) -> tuple:
    """The slot widths a rounds grower of round cap ``kcap`` has, narrowest
    first: the rungs its passes run at and its offer moves on."""
    kcap = int(kcap)
    return tuple(w for w in NARROW_SLOT_WIDTHS if w < kcap) + (kcap,)


def pass_builds_packed(num_bins: int, feat_tile: int, num_features: int,
                       quant: bool, bin_dtype) -> bool:
    """``packed_operands`` for the accumulate passes a booster runs at
    these shapes (one answer for every slot width: the slot axis does not
    enter it): what its pass counters follow."""
    Ft = max(1, min(int(feat_tile), int(num_features)))
    return packed_operands(
        quant, bin_dtype, _arena_dims(1, int(num_bins), Ft, quant)[1])


def frontier_accumulator(
    binned_t: jax.Array,           # [F, n]
    vals_t: jax.Array,
    kcap: int,                     # the round cap: the arena's slot axis
    num_bins: int,
    feat_tile: Optional[int] = None,
    block_rows: Optional[int] = None,   # row tile AT ``kcap`` slots
    tile_rows: Optional[int] = None,
    live_rows: Optional[jax.Array] = None,
):
    """``accumulate(route, k) -> (hist [kcap, ch, F, B], width, rows,
    aux)``: one ``fused_frontier_accumulate`` pass at the narrowest
    compiled slot width ``W`` that holds the ``k`` live slots, zero-padded
    to ``kcap`` slots so whatever follows (the collective, the scan) keeps
    one shape; ``rows`` is how many of the ``n`` rows it read: ``n``, or
    with ``live_rows`` (a traced int32: the pass reads only the row tiles
    before it, ``fused_frontier_accumulate``) those of its live tiles.
    ``route(W) -> (slot, aux)`` makes the pass's ``slot`` ([n] i32 in
    [0, k) or >= k = dropped) inside the branch that runs at ``W``, so a
    router that works at the pass's width shares its switch; ``aux`` is
    whatever else it returns.  ``k`` is a Python int (the root: one slot)
    or a traced i32 (a round: the pass runs under ``lax.switch``);
    ``width`` is the slot width that ran.

    Built once a tree, outside the grower's loop: every width reads ONE
    feature-blocked operand (``fused_blocked_bins``), at the feature
    tile of the widest, with its own row tile from ``plan_fused``.
    Integer accumulation is associative, so the int8 arena is
    bit-identical at every width; the f32 arena differs by summation
    order where the row tiles differ."""
    from .planner import plan_fused
    quant = vals_t.dtype == jnp.int8
    F, n = binned_t.shape
    kcap = int(kcap)
    widths = slot_widths(kcap)
    if feat_tile is None:
        fp = plan_fused(kcap, num_bins, quant, num_features=F)
        feat_tile = fp["feat_tile"] if fp else 1
    feat_tile = max(1, min(int(feat_tile), F))

    def planned_rows(W):
        if W == kcap and block_rows is not None:
            return block_rows
        fp = plan_fused(W, num_bins, quant, feat_tile=feat_tile)
        return fp["block_rows"] if fp else 128

    tiles = tuple(_row_tile(planned_rows(W), tile_rows, n) for W in widths)
    bins = fused_blocked_bins(binned_t, feat_tile, math.lcm(*tiles))

    def rows_read(C):
        if live_rows is None:
            return jnp.int32(n)
        return jnp.minimum((live_rows + C - 1) // C * C, n).astype(jnp.int32)

    def at(W, C, route):
        def run():
            slot, aux = route(W)
            hist = fused_frontier_accumulate(
                bins, vals_t, jnp.minimum(slot, W), W, num_bins,
                block_rows=C, num_features=F, live_rows=live_rows)
            return jnp.pad(hist, ((0, kcap - W),) + ((0, 0),) * 3), aux
        return run

    def accumulate(route, k):
        branches = [at(W, C, route) for W, C in zip(widths, tiles)]
        i = sum(k > W for W in widths[:-1])     # the narrowest width >= k
        if isinstance(i, int):                  # static k, or one width
            hist, aux = branches[i]()
            return hist, jnp.int32(widths[i]), rows_read(tiles[i]), aux
        hist, aux = lax.switch(i, branches)
        return (hist, jnp.asarray(widths, jnp.int32)[i],
                rows_read(jnp.asarray(tiles, jnp.int32)[i]), aux)

    return accumulate


def fused_sibling_scan(
    small_hist: jax.Array,         # [K, ch, F, B] REDUCED smaller-child hists
    child_sums: jax.Array,         # [3, NC] (NC = 2K parent mode, K leaf)
    num_bin: jax.Array,
    missing_type: jax.Array,
    default_bin: jax.Array,
    hp: SplitHyperparams,
    small_left: Optional[jax.Array] = None,   # [K] bool (parent mode)
    parent_hist: Optional[jax.Array] = None,  # [K, ch, F, B]
    quant_scales: Optional[tuple] = None,
    monotone_constraints: Optional[jax.Array] = None,  # [F] i32
    child_bounds: Optional[tuple] = None,     # ([NC], [NC]) output clamp
    feat_tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> NumericFeatureBest:
    """The scan HALF of the collective seam: sibling-derive + rescale +
    gain scan over the ALREADY-REDUCED arena, one feature block per grid
    step, all in VMEM.  The body is ``_derive_and_scan`` — the verbatim
    epilogue of the combined kernel — so seam-split tuples equal combined
    tuples bit-for-bit given equal histograms."""
    from jax.experimental import pallas as pl

    quant = jnp.issubdtype(small_hist.dtype, jnp.integer)
    if quant and quant_scales is None:
        raise ValueError("quantized fused sibling scan needs quant_scales")
    K, ch, F, B = (int(d) for d in small_hist.shape)
    with_parent = parent_hist is not None
    NC = 2 * K if with_parent else K
    has_mono = monotone_constraints is not None
    has_bounds = child_bounds is not None
    acc_dtype = jnp.int32 if quant else jnp.float32
    meta_rows = [jnp.asarray(num_bin, jnp.int32),
                 jnp.asarray(missing_type, jnp.int32),
                 jnp.asarray(default_bin, jnp.int32)]
    if has_mono:
        meta_rows.append(jnp.asarray(monotone_constraints, jnp.int32))
    if _scan_in_xla(interpret):
        def f32(pair):
            return tuple(jnp.asarray(x, jnp.float32) for x in pair)
        return _derive_and_scan(
            small_hist.astype(acc_dtype),
            jnp.asarray(child_sums, jnp.float32), meta_rows[:3], hp,
            parent=parent_hist.astype(acc_dtype) if with_parent else None,
            s_is_left_vec=(small_left.astype(jnp.int32) if with_parent
                           else None),
            scales=f32(quant_scales) if quant else None,
            mono=meta_rows[3] if has_mono else None,
            bounds=f32(child_bounds) if has_bounds else None)
    if feat_tile is None:
        from .planner import plan_fused
        fp = plan_fused(K, B, bool(quant), with_parent=with_parent)
        feat_tile = fp["feat_tile"] if fp else 1
    Ft = max(1, min(int(feat_tile), F))
    F_pad = _pad_rows(F, Ft)

    small = small_hist.astype(acc_dtype)
    if F_pad != F:
        small = jnp.pad(small, ((0, 0), (0, 0), (0, F_pad - F), (0, 0)))
    meta = jnp.stack(meta_rows)
    if F_pad != F:
        meta = jnp.pad(meta, ((0, 0), (0, F_pad - F)))
    R = int(meta.shape[0])

    in_arrays = [small]
    in_specs = [pl.BlockSpec((K, ch, Ft, B), lambda j: (0, 0, j, 0))]
    if with_parent:
        parent = parent_hist.astype(acc_dtype)
        if F_pad != F:
            parent = jnp.pad(parent,
                             ((0, 0), (0, 0), (0, F_pad - F), (0, 0)))
        in_arrays.append(parent)
        in_specs.append(pl.BlockSpec((K, ch, Ft, B), lambda j: (0, 0, j, 0)))
        in_arrays.append(small_left.astype(jnp.int32)[None, :])
        in_specs.append(pl.BlockSpec((1, K), lambda j: (0, 0)))
    in_arrays.append(jnp.asarray(child_sums, jnp.float32))
    in_specs.append(pl.BlockSpec((3, NC), lambda j: (0, 0)))
    in_arrays.append(_feature_blocked(meta, Ft))
    in_specs.append(pl.BlockSpec((None, R, Ft), lambda j: (j, 0, 0)))
    if quant:
        in_arrays.append(
            jnp.stack([jnp.asarray(quant_scales[0], jnp.float32),
                       jnp.asarray(quant_scales[1], jnp.float32)])[None, :])
        in_specs.append(pl.BlockSpec((1, 2), lambda j: (0, 0)))
    if has_bounds:
        in_arrays.append(jnp.stack(
            [jnp.asarray(child_bounds[0], jnp.float32),
             jnp.asarray(child_bounds[1], jnp.float32)]))
        in_specs.append(pl.BlockSpec((2, NC), lambda j: (0, 0)))

    def kernel(*refs):
        it = iter(refs)
        sm_ref = next(it)
        p_ref = next(it) if with_parent else None
        sl_ref = next(it) if with_parent else None
        sum_ref = next(it)
        m_ref = next(it)
        sc_ref = next(it) if quant else None
        bd_ref = next(it) if has_bounds else None
        gn_ref = next(it)
        th_ref = next(it)
        dl_ref = next(it)
        lg_ref = next(it)
        lh_ref = next(it)
        lc_ref = next(it)

        res = _derive_and_scan(
            sm_ref[...], sum_ref[...],
            (m_ref[0, :], m_ref[1, :], m_ref[2, :]), hp,
            parent=p_ref[...] if with_parent else None,
            s_is_left_vec=sl_ref[0, :] if with_parent else None,
            scales=(sc_ref[0, 0], sc_ref[0, 1]) if quant else None,
            mono=m_ref[3, :] if has_mono else None,
            bounds=(bd_ref[0, :], bd_ref[1, :]) if has_bounds else None)
        gn_ref[...] = res.gain
        th_ref[...] = res.threshold
        dl_ref[...] = res.default_left.astype(jnp.int32)
        lg_ref[...] = res.left_sum_grad
        lh_ref[...] = res.left_sum_hess
        lc_ref[...] = res.left_count

    nf_blocks = F_pad // Ft
    tuple_spec = pl.BlockSpec((None, NC, Ft), lambda j: (j, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(nf_blocks,),
        in_specs=in_specs,
        out_specs=[tuple_spec] * 6,
        out_shape=[jax.ShapeDtypeStruct((nf_blocks, NC, Ft), dt)
                   for dt in _TUPLE_DTYPES],
        interpret=_interp(interpret),
        name="lgbm_sibling_scan",
    )(*in_arrays)
    gain, thr, dl, lgs, lhs_, lcs = (_feature_unblocked(o) for o in out)
    return NumericFeatureBest(
        gain=gain[:, :F], threshold=thr[:, :F],
        default_left=dl[:, :F].astype(bool),
        left_sum_grad=lgs[:, :F], left_sum_hess=lhs_[:, :F],
        left_count=lcs[:, :F])


def fused_segment_splits(
    binned_t: jax.Array,
    vals_t: jax.Array,
    slot: jax.Array,
    num_slots: int,
    num_bins: int,
    slot_sums: jax.Array,          # [3, K] per-slot (sum_g, sum_h, count)
    num_bin: jax.Array,
    missing_type: jax.Array,
    default_bin: jax.Array,
    hp: SplitHyperparams,
    quant_scales: Optional[tuple] = None,
    monotone_constraints: Optional[jax.Array] = None,
    child_bounds: Optional[tuple] = None,
    feat_tile: Optional[int] = None,
    block_rows: Optional[int] = None,
    tile_rows: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Leaf mode: build K slot histograms AND their per-feature-best
    numeric splits in one pass.  Returns ``(hist [K, ch, F, B],
    NumericFeatureBest [K, F])`` — the staged equivalent is
    ``segment_histogram*`` + (rescale +) ``feature_best_splits`` with the
    full histogram round-tripping through HBM in between."""
    return _fused_call(
        binned_t, vals_t, slot, num_slots, num_bins, slot_sums,
        (num_bin, missing_type, default_bin), hp,
        quant_scales=quant_scales,
        monotone_constraints=monotone_constraints,
        child_bounds=child_bounds, feat_tile=feat_tile,
        block_rows=block_rows, tile_rows=tile_rows, interpret=interpret)


def fused_frontier_splits(
    binned_t: jax.Array,
    vals_t: jax.Array,
    slot: jax.Array,               # [n] i32: candidate rank of the row's
                                   # SMALLER child, K = dropped
    num_slots: int,                # K (the frontier width)
    num_bins: int,
    child_sums: jax.Array,         # [3, 2K] (left children, right children)
    small_left: jax.Array,         # [K] bool: smaller child is the LEFT one
    parent_hist: jax.Array,        # [K, ch, F, B] candidates' parent hists
    num_bin: jax.Array,
    missing_type: jax.Array,
    default_bin: jax.Array,
    hp: SplitHyperparams,
    quant_scales: Optional[tuple] = None,
    monotone_constraints: Optional[jax.Array] = None,
    child_bounds: Optional[tuple] = None,
    feat_tile: Optional[int] = None,
    block_rows: Optional[int] = None,
    tile_rows: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Frontier mode (the growers' per-level call): accumulate the K
    smaller-child histograms in VMEM, derive each sibling from the parent
    arena in-kernel, scan BOTH children, and write back the smaller-child
    histograms (the subtraction cache's input) plus ``[2K, F]``
    per-feature-best tuples — one streamed pass over the binned matrix
    per level."""
    return _fused_call(
        binned_t, vals_t, slot, num_slots, num_bins, child_sums,
        (num_bin, missing_type, default_bin), hp,
        small_left=small_left, parent_hist=parent_hist,
        quant_scales=quant_scales,
        monotone_constraints=monotone_constraints,
        child_bounds=child_bounds, feat_tile=feat_tile,
        block_rows=block_rows, tile_rows=tile_rows, interpret=interpret)


def pick_fused_best(best: NumericFeatureBest, sum_grad, sum_hess, num_data,
                    feature_mask: Optional[jax.Array] = None,
                    cat_best: Optional[PerFeatureBest] = None,
                    cat_idx=None) -> SplitResult:
    """argmax over features of fused per-feature-best tuples — the
    numeric twin of ``ops.split.pick_best_feature`` (ties -> smaller
    feature index), vectorized over the leading children axis.  The
    feature mask applies here (outside the kernel): masking gains after
    the scan is exactly what ``feature_best_splits`` does inside.

    Categorical merge (the lifted gate): the kernel accumulates EVERY
    column — per-category stats are the same segment reduction — but its
    in-kernel NUMERIC scan is meaningless on categorical columns, so the
    growers run the shared ``feature_best_splits`` cat scan on just the
    categorical slice of the derived child histograms and pass it here as
    ``cat_best`` (fields [..., Fc]) with the static column indices
    ``cat_idx``.  Scattering those tuples over the numeric ones before
    the argmax reproduces ``feature_best_splits``' own
    ``jnp.where(is_categorical, cat, numeric)`` merge and
    ``pick_best_feature``'s tie order exactly."""
    gain = best.gain
    thr = best.threshold
    dl = best.default_left
    blg_f = best.left_sum_grad
    blh_f = best.left_sum_hess
    blc_f = best.left_count
    F = gain.shape[-1]
    is_cat = jnp.zeros(gain.shape, bool)
    bitset = jnp.zeros(gain.shape + (MAX_CAT_WORDS,), jnp.uint32)
    if cat_best is not None:
        ci = jnp.asarray(cat_idx, jnp.int32)
        gain = gain.at[..., ci].set(cat_best.gain)
        thr = thr.at[..., ci].set(cat_best.threshold.astype(thr.dtype))
        dl = dl.at[..., ci].set(cat_best.default_left.astype(dl.dtype))
        blg_f = blg_f.at[..., ci].set(cat_best.left_sum_grad)
        blh_f = blh_f.at[..., ci].set(cat_best.left_sum_hess)
        blc_f = blc_f.at[..., ci].set(cat_best.left_count)
        is_cat = is_cat.at[..., ci].set(cat_best.is_categorical)
        bitset = bitset.at[..., ci, :].set(cat_best.cat_bitset)
    if feature_mask is not None:
        gain = jnp.where(feature_mask.astype(bool), gain, K_MIN_SCORE)
    f = jnp.argmax(gain, axis=-1).astype(jnp.int32)

    def sel(a):
        return jnp.take_along_axis(a, f[..., None], -1)[..., 0]

    blg = sel(blg_f)
    blh = sel(blh_f)
    blc = sel(blc_f)
    return SplitResult(
        gain=sel(gain), feature=f,
        threshold=sel(thr),
        default_left=sel(dl),
        left_sum_grad=blg, left_sum_hess=blh, left_count=blc,
        right_sum_grad=jnp.asarray(sum_grad) - blg,
        right_sum_hess=jnp.asarray(sum_hess) - blh,
        right_count=jnp.asarray(num_data).astype(jnp.float32) - blc,
        is_categorical=sel(is_cat),
        cat_bitset=jnp.take_along_axis(
            bitset, f[..., None, None],
            -2)[..., 0, :] if cat_best is not None else
        jnp.zeros(f.shape + (MAX_CAT_WORDS,), jnp.uint32))


# one-time per-backend verdict: does the fused arm AGREE with the staged
# pipeline on this backend?  {backend_name: bool}
_FUSED_PROBE: dict = {}


def fused_kernel_verified() -> bool:
    """Run the fused arm at a tiny shape on the live accelerator and
    check its numbers against the staged pipeline.

    A NUMERIC probe only.  Whether the kernels compile is settled
    before a chip is touched — tests/test_chip_compile.py compiles them
    for a described v5e — so a compile or lowering error here is a
    defect and propagates; nothing is caught.  A numeric mismatch
    demotes ``hist_method=auto`` to the staged family, at warning
    level.  Off-accelerator (interpret mode) the kernels are plain jax
    — verified trivially."""
    backend = jax.default_backend()
    ok = _FUSED_PROBE.get(backend)
    if ok is not None:
        return ok
    if not on_accelerator():
        _FUSED_PROBE[backend] = True
        return True
    rng = np.random.RandomState(0)
    F, n, B, K = 4, 256, 8, 2
    binned_np = rng.randint(0, B - 1, (F, n))
    slot_np = rng.randint(0, K + 1, n)
    binned = jnp.asarray(binned_np, jnp.uint8)
    g = jnp.asarray(rng.randn(n), jnp.float32)
    h = jnp.abs(g) + 0.1
    vals = jnp.stack([g, h, jnp.ones_like(g)])
    slot = jnp.asarray(slot_np, jnp.int32)
    sums = []
    for k in range(K):
        m = slot_np == k
        sums.append([float(np.asarray(g)[m].sum()),
                     float(np.asarray(h)[m].sum()), float(m.sum())])
    sums = jnp.asarray(np.asarray(sums).T, jnp.float32)
    nb = jnp.full((F,), B, jnp.int32)
    zero = jnp.zeros((F,), jnp.int32)
    hp = SplitHyperparams(min_data_in_leaf=1)
    hist, best = jax.jit(
        lambda b, v, s, su: fused_segment_splits(
            b, v, s, K, B, su, nb, zero, zero, hp,
            feat_tile=2, block_rows=128))(binned, vals, slot, sums)
    # the accumulated histograms against the staged scatter segment
    # pass (a mis-lowered slot-expanded dot would be internally
    # consistent with the scan that follows it, so scan parity alone
    # cannot catch it), then the scan against the shared body
    from .histogram import segment_histogram
    ref_hist = segment_histogram(binned, g, h, jnp.ones_like(g),
                                 slot, K, B)
    ok = bool(np.allclose(np.asarray(hist), np.asarray(ref_hist),
                          rtol=1e-4, atol=1e-3))
    ref = numeric_feature_scan(hist.astype(jnp.float32), sums[0],
                               sums[1], sums[2], nb, zero, zero, hp)
    ok = ok and bool(np.allclose(np.asarray(best.gain),
                                 np.asarray(ref.gain), equal_nan=True))
    # the integer family: int8 operands, int32 accumulation — exact
    vq_np = rng.randint(-8, 8, (2, n)).astype(np.int8)
    got_q = np.asarray(jax.jit(
        lambda b, v, s: fused_frontier_accumulate(
            b, v, s, K, B, feat_tile=2, block_rows=128))(
                binned, jnp.asarray(vq_np), slot))
    ref_q = np.zeros((K + 1, 2, F, B), np.int32)
    for f in range(F):
        for c in range(2):
            np.add.at(ref_q[:, c, f, :], (slot_np, binned_np[f]),
                      vq_np[c].astype(np.int32))
    ok = ok and bool(np.array_equal(got_q, ref_q[:K]))
    _FUSED_PROBE[backend] = ok
    if not ok:
        from ..utils.log import log_warning
        log_warning(
            f"fused histogram kernel disagrees with the staged pipeline "
            f"on backend {backend!r}; hist_method=auto falls back to the "
            "staged kernel family")
    return ok


def fused_enabled_env() -> bool:
    """LGBM_TPU_FUSED=0 drops the fused arm (compile-cost bisect hook,
    mirroring LGBM_TPU_SEGHIST / LGBM_TPU_ROUTER)."""
    return os.environ.get("LGBM_TPU_FUSED") != "0"
