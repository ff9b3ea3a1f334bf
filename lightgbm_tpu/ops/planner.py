"""HBM budget planner: pick histogram execution parameters at trace time.

The r5 run died in compile with an HBM OOM — a lane-padded
``f32[308000000, 3]`` whole-dataset record arena (157.7 GB requested vs
17.2 GB HBM) — because every kernel materialized O(n*F) intermediates
and nothing MODELED whether they fit.  This module is the model: it
predicts per-variant peak HBM bytes for the histogram pipeline
(device binned matrix, carried scores/gradients, per-tree hist cache
including TPU lane padding, per-pass transients, pack/sort arenas,
cross-device psum payloads) against the device's reported HBM limit and
picks, at trace time:

- ``tile_rows`` — the row-tile size every kernel in ops/histogram.py
  streams through (power of two; 0 = untiled).  Peak transient HBM
  becomes O(tile), not O(n*F);
- whether the whole-dataset ``pack_cols_u32`` record arena may be
  hoisted (``use_pack``) or records must be assembled per tile inside
  the kernel loops;
- the psum payload width for quantized histograms (``narrow_int16`` —
  the record of ``ops.histogram.quant_psum_narrow``'s static bound).

The same plan governs serial and sharded training: the GBDT layer plans
with PER-SHARD rows and threads the result through ``GrowerConfig``
(tile_rows / hist_pack), so the serial grower, the batched-frontier
grower, the fused macro-chunk program and the data-/voting-parallel
learners all execute under one verdict.

Env overrides:
- ``LGBM_TPU_TILE_ROWS``: force a tile size (``0``/``off`` forces
  untiled; a positive integer forces that many rows per tile).
- ``LGBM_TPU_HBM_BYTES``: override the device HBM limit (useful off-TPU
  and in tests, which plan against a fake memory model).

Related work: bounding device memory by streaming row chunks through a
fixed-footprint histogram kernel is the GPU GBDT move (Wen et al.,
arXiv:1706.08359; Ou, arXiv:1806.11248 — gradient-based sketching to
bound device memory); here the bound is a *planner verdict* instead of
an operator-tuned chunk count.
"""

from __future__ import annotations

import os
import threading
from contextlib import contextmanager
from typing import NamedTuple, Optional

# the limit CPU planning runs against (tests; one v5e-class chip).  An
# accelerator's limit is read from the device, never assumed
DEFAULT_HBM_BYTES = 16 * (1 << 30)
# fraction of the limit a plan may claim: XLA needs slack for fusion
# temps, the program image, and collectives' staging buffers
HEADROOM = 0.85
# smallest tile the planner will degrade to (a histogram pass over fewer
# rows is dominated by fixed per-pass overhead)
MIN_TILE_ROWS = 1 << 16
_DEFAULT_BLOCK_ROWS = 4096

# on-chip vector memory per core (v5e-class ~16 MiB; LGBM_TPU_VMEM_BYTES
# overrides) and the fraction the fused megakernel's arena may claim —
# Mosaic needs slack for the pipeline's double-buffered tile windows and
# spills
DEFAULT_VMEM_BYTES = 16 << 20
VMEM_HEADROOM = 0.7

# host-RSS side of the two-level budget (out-of-core streaming,
# lightgbm_tpu/data/): fraction of the host limit training may claim —
# the OS, the Python runtime and JAX's own host allocations need the rest
HOST_HEADROOM = 0.8
DEFAULT_HOST_BYTES = 8 * (1 << 30)
# smallest streamed row block the stream planner will degrade to; a
# device_put + histogram pass over fewer rows is dominated by dispatch
# overhead (tests force smaller via LGBM_TPU_STREAM_BLOCK_ROWS)
MIN_STREAM_BLOCK_ROWS = 1 << 16
MAX_STREAM_BLOCK_ROWS = 1 << 24


def _pad(x: int, m: int) -> int:
    return -(-int(x) // m) * m


def _arr(minor: int, second: int, itemsize: int, accel: bool,
         leading: int = 1) -> int:
    """Bytes of an array whose two minor dims are (second, minor).

    On accelerators the two minor-most dims tile to (sublanes, 128) with
    sublanes scaling inversely with itemsize — (8, 128) for 4-byte,
    (16, 128) for 2-byte, (32, 128) for 1-byte (ops/histogram.py LAYOUT
    DOCTRINE).  Off-accelerator: dense.
    """
    if not accel:
        return leading * second * minor * itemsize
    sub = {4: 8, 2: 16, 1: 32}.get(itemsize, 8)
    return leading * _pad(second, sub) * _pad(minor, 128) * itemsize


class HistPlan(NamedTuple):
    """Trace-time histogram execution plan (see module docstring)."""

    tile_rows: int              # 0 = untiled
    use_pack: bool              # whole-dataset u32 record arena allowed
    variant: str                # resolved histogram kernel family
    quant: bool
    narrow_int16: bool          # quantized psum payload narrowed
    predicted_peak_bytes: int   # at the chosen tile
    untiled_peak_bytes: int     # what the unplanned pipeline would take
    budget_bytes: int           # limit * HEADROOM
    limit_bytes: int
    limit_source: str           # "memory_stats" | "env" | "default"
    feasible: bool              # predicted peak fits the budget
    degraded: bool              # tiling was forced by the budget
    fused: bool = False         # fused Pallas megakernel elected
    fused_feat_tile: int = 0    # features per VMEM arena block
    fused_block_rows: int = 0   # rows per double-buffered tile DMA
    fused_vmem_bytes: int = 0   # predicted VMEM arena bytes at that shape
    vmem_limit_bytes: int = 0   # VMEM limit the fused election ran against
    elected_by: str = "analytic"

    def summary(self) -> dict:
        """JSON-friendly form for telemetry."""
        return {
            "tile_rows": self.tile_rows,
            "use_pack": self.use_pack,
            "variant": self.variant,
            "quant": self.quant,
            "narrow_int16": self.narrow_int16,
            "predicted_peak_bytes": self.predicted_peak_bytes,
            "untiled_peak_bytes": self.untiled_peak_bytes,
            "budget_bytes": self.budget_bytes,
            "hbm_limit_bytes": self.limit_bytes,
            "limit_source": self.limit_source,
            "feasible": self.feasible,
            "degraded": self.degraded,
            "fused": self.fused,
            "fused_feat_tile": self.fused_feat_tile,
            "fused_block_rows": self.fused_block_rows,
            "fused_vmem_bytes": self.fused_vmem_bytes,
            "vmem_limit_bytes": self.vmem_limit_bytes,
            "elected_by": self.elected_by,
        }


def hbm_limit_bytes() -> tuple:
    """(limit_bytes, source) for the active device.

    Priority: ``LGBM_TPU_HBM_BYTES`` env (tests / fake memory models) >
    the device allocator's reported ``bytes_limit``.  On an accelerator
    a device that reports none is an error — a plan against a guessed
    limit is how a shape OOMs.  Off-accelerator (CPU planning in tests;
    the CPU allocator reports no limit) the conservative default
    stands, and ``source`` says so.
    """
    env = os.environ.get("LGBM_TPU_HBM_BYTES", "").strip()
    if env:
        try:
            return max(int(float(env)), 1), "env"
        except ValueError:
            pass
    from .histogram import on_accelerator
    if not on_accelerator():
        return DEFAULT_HBM_BYTES, "default"
    import jax
    dev = jax.local_devices()[0]
    limit = int((dev.memory_stats() or {}).get("bytes_limit", 0))
    if limit <= 0:
        raise RuntimeError(
            f"{dev} reports no memory_stats()['bytes_limit']; set "
            "LGBM_TPU_HBM_BYTES to plan against a stated limit")
    return limit, "memory_stats"


def vmem_limit_bytes() -> int:
    """VMEM per core for the fused megakernel's arena election
    (``LGBM_TPU_VMEM_BYTES`` overrides; tests plan against fakes)."""
    env = os.environ.get("LGBM_TPU_VMEM_BYTES", "").strip()
    if env:
        try:
            return max(int(float(env)), 1)
        except ValueError:
            pass
    return DEFAULT_VMEM_BYTES


def fused_vmem_bytes(num_slots: int, num_bins: int, feat_tile: int,
                     block_rows: int, quant: bool = False,
                     with_parent: bool = True) -> int:
    """Predicted VMEM bytes of one fused-megakernel step (ops/fused.py).

    Resident across the row loop: the [ch·K, Ft·B] accumulator arena at
    its PADDED dims (``fused._arena_dims``: an odd feature tile pads the
    bins to whole 128-lane groups), the parent block, the double-buffered
    input tile windows and the two one-hot operands the dot consumes
    (bins ``[Ft·B, C]`` and slots x values ``[ch·K, C]``: they grow with
    the row tile; a byte a cell in the integer family, which is all the
    packed form of ``fused.packed_operands`` ever holds of them — the
    compare form makes an int32 copy first, which the headroom has always
    carried); the epilogue additionally materializes the 2K children
    (+ their rescale/prefix transients) and the tiny tuple blocks.
    Deliberately simple — the right ORDER for the fits/doesn't verdict,
    like ``predict_peak_bytes`` — and held to the chip's compiler at every
    tile ``plan_fused`` elects by tests/test_chip_compile.py."""
    from .fused import _arena_dims
    Ft = max(int(feat_tile), 1)
    C = max(int(block_rows), 128)
    K, B = _arena_dims(max(int(num_slots), 1), max(int(num_bins), 2), Ft,
                       quant)
    ch = 2 if quant else 3
    nc = 2 * K if with_parent else K
    acc = ch * K * Ft * B * 4
    parent = K * ch * Ft * B * 4 if with_parent else 0
    small_out = K * ch * Ft * B * 4
    # epilogue: children + one prefix/rescale transient of the same shape
    children = 2 * nc * 3 * Ft * B * 4
    # double-buffered tile DMA windows: binned (1B), vals (<=4B), slot
    tiles = 2 * (Ft * C + ch * C * 4 + C * 4)
    onehot = C * (Ft * B + ch * K) * (1 if quant else 4)
    tuples = 6 * nc * Ft * 4
    return acc + parent + small_out + children + tiles + onehot + tuples


# row tiles plan_fused walks, longest first: the accumulate kernel pays
# ~0.2 us a grid step, 442,368 steps a pass at 25.2M x 67 in 512-row
# tiles.  Measured on one v5e at 16 / 64 / 128 int8 slots, ms a pass: 143 /
# 163 / 235 at 512 rows, 96 / 122 / 197 at 1024, 79 / 106 / 180 at 2048,
# 72 / 99 / 172 at 4096, 68 / 95 / 168 at 8192 (root PERF.md section 5;
# 45 / 81 / 157 at 8192 since the operands are built packed, ops/fused.py).
# The VMEM model stops the f32 family earlier (the chip's compiler refuses
# its 128-slot kernel at 4096 rows).
FUSED_BLOCK_ROWS = (8192, 4096, 2048, 1024, 512, 256, 128)


def plan_fused(num_slots: int, num_bins: int, quant: bool = False,
               with_parent: bool = True,
               vmem_bytes: Optional[int] = None,
               feat_tile: Optional[int] = None,
               num_features: Optional[int] = None) -> Optional[dict]:
    """Pick {feat_tile, block_rows} for the fused megakernel, or None
    when no shape fits the VMEM budget (the staged family then keeps the
    level).  Preference order: widest feature block first (fewer grid
    columns, better MXU occupancy), then the longer row tile.
    ``feat_tile`` pins the feature block (the rounds grower's narrower
    slot widths share one blocked operand with its widest);
    ``num_features`` caps it, as the kernel does (a matrix of 3 columns
    runs a 3-feature block, whose bins pad to whole lane groups)."""
    limit = int(vmem_bytes if vmem_bytes is not None else vmem_limit_bytes())
    budget = int(limit * VMEM_HEADROOM)
    fts = (8, 4, 2, 1) if feat_tile is None else (int(feat_tile),)
    if num_features is not None:
        fts = tuple(dict.fromkeys(min(ft, int(num_features)) for ft in fts))
    for ft in fts:
        for c in FUSED_BLOCK_ROWS:
            need = fused_vmem_bytes(num_slots, num_bins, ft, c, quant,
                                    with_parent)
            if need <= budget:
                return {"feat_tile": ft, "block_rows": c,
                        "vmem_bytes": need, "vmem_limit_bytes": limit}
    return None


def predict_peak_bytes(
    rows: int,                  # per-shard row count the kernels see
    features: int,              # device column count (groups under EFB)
    num_bins: int,              # padded bin axis B
    num_leaves: int = 31,
    num_class: int = 1,
    quant: bool = False,
    variant: str = "scatter",   # resolved kernel family name
    tile_rows: int = 0,         # 0 = untiled
    use_pack: bool = True,
    round_width: int = 128,
    machines: int = 1,
    accel: Optional[bool] = None,
    block_rows: int = _DEFAULT_BLOCK_ROWS,
) -> tuple:
    """(peak_bytes, breakdown dict) for one training step's histogram
    pipeline on one device.

    A deliberately simple sum of the dominant allocations — resident
    state plus the largest per-pass transient — NOT an XLA simulator.
    Accuracy target: the right ORDER for the feasibility verdict (the
    r5 failure was off by 9x, not 10%).
    """
    if accel is None:
        from .histogram import on_accelerator
        accel = on_accelerator()
    n = max(int(rows), 1)
    F = max(int(features), 1)
    B = max(int(num_bins), 2)
    L = max(int(num_leaves), 2)
    K = max(int(num_class), 1)
    S = max(int(round_width), 1)
    T = n if tile_rows <= 0 else min(int(tile_rows), n)
    C = min(block_rows, _pad(T, 128))
    ch = 2 if quant else 3          # histogram channels
    hitem = 4                       # i32 / f32 cells

    b = {}
    bin_item = 1 if B <= 256 else 2
    # resident: the device binned matrix (feature-major [F, n]) and one
    # transformation copy (pad / compaction gather of the same shape)
    b["binned"] = _arr(n, F, bin_item, accel) * 2
    # carried scores (donated in+out) + per-class grad/hess f32 rows
    b["scores"] = 2 * K * _arr(n, 1, 4, accel)
    b["grads"] = 2 * K * _arr(n, 1, 4, accel)
    if quant:
        b["grads"] += 2 * K * _arr(n, 1, 1, accel)      # int8 gq/hq
    # per-tree histogram cache [L, ch, F, B] + the round's segment
    # output [S, ch, F, B]
    b["hist_cache"] = L * ch * _arr(B, F, hitem, accel)
    b["seg_hist"] = (S + 1) * ch * _arr(B, F, hitem, accel)
    # sorted-arena fixed state: u32 sort keys (key + sorted + order)
    if variant in ("sorted", "matmul", "matmul_int8"):
        b["sort_keys"] = 3 * _arr(n, 1, 4, accel)
    # whole-dataset fused record arena (pack_cols_u32): Wb+3 u32 words
    # per row (Wb+1 quantized)
    if use_pack:
        wb = (F + 3) // 4
        b["pack_arena"] = _arr(n, wb + (1 if quant else 3), 4, accel)

    # dominant per-pass transient, by kernel family
    if variant.startswith("scatter"):
        # the r5 OOM shape: [T*F, ch] update buffer (lane-padded on
        # accel) + [T, F] i32 flat indices
        b["scatter_updates"] = _arr(ch, T * F, hitem, accel)
        b["scatter_index"] = _arr(F, T, 4, accel)
    elif variant == "pallas":
        # VPU kernel: the accumulator and tile windows live in VMEM; HBM
        # transients are just the padded vals copy and the (small)
        # blocked output already counted in seg_hist/hist_cache
        b["vals_pad"] = _arr(n, ch, 4, accel)
    elif variant == "fused":
        # fused megakernel (ops/fused.py): the arena and one-hot operands
        # are VMEM-resident (modeled by fused_vmem_bytes, a SEPARATE
        # budget); HBM sees the streamed tiles, the smaller-child hist
        # writeback (seg_hist above) and the tiny tuple outputs — the
        # [L,ch,F,B] scan round-trip term is exactly what this variant
        # deletes
        b["vals_pad"] = _arr(n, ch, 4, accel)
        b["fused_tuples"] = 6 * _arr(F, 2 * S, 4, accel)
    elif variant.startswith("matmul"):
        onehot_item = 1 if (quant or variant == "matmul") else 4
        if variant == "matmul" and not quant:
            onehot_item = 2                      # bf16 one-hot
        b["onehot"] = _arr(B * F, C, onehot_item, accel)
        b["vals_pad"] = _arr(n, ch, 4, accel)    # padded vals copy
    else:                                        # sorted / expanded
        b["onehot"] = _arr(B * F, C, 1 if quant else 2, accel)
        if tile_rows <= 0:
            # hoisted whole-arena record gather
            wb = (F + 3) // 4
            width = (wb + (1 if quant else 3)) if use_pack else (F + 3)
            b["arena_gather"] = _arr(n, width, 4, accel)
        else:
            wb = (F + 3) // 4
            width = (wb + (1 if quant else 3)) if use_pack else (F + 3)
            b["arena_gather"] = _arr(C, width, 4, accel)
    # cross-device histogram reduction staging
    if machines > 1:
        from .histogram import hist_payload_bytes
        b["psum"] = 2 * hist_payload_bytes(
            F, B, rows_global=n * machines,
            quant_bins=None if not quant else 64) * S

    return sum(b.values()), b


def _resolved_variant(method: str, quant: bool) -> str:
    from .histogram import resolve_hist_method, use_sorted_seghist
    # "fused" models at the staged family here; fused election is a
    # separate verdict in plan_histograms (VMEM budget, plan_fused)
    m = resolve_hist_method("auto" if method == "fused" else method,
                            quantized=quant)
    # the segment passes dominate peak; their dispatch follows
    # use_sorted_seghist, not the point-histogram method — a forced
    # "pallas" POINT kernel still runs sorted-arena segment passes on
    # accelerators, so the peak model must keep those terms
    if use_sorted_seghist():
        return "sorted"
    return m


def _tile_override():
    """LGBM_TPU_TILE_ROWS: None = unset, 0 = force untiled, >0 = force."""
    v = os.environ.get("LGBM_TPU_TILE_ROWS", "").strip().lower()
    if not v:
        return None
    if v in ("0", "off", "none", "false"):
        return 0
    try:
        return max(int(v), 1)
    except ValueError:
        return None


# ======================================================================
# Compile-time war: shape-bucket ladders.  Every distinct row
# count is a distinct XLA program, so a pipeline of nearby dataset sizes
# recompiles everything from scratch each time.  Padding training rows
# up to a coarse ladder rung (the serving-bucket trick from predict,
# applied to training) makes nearby sizes share ONE compiled program;
# padded rows ride the existing row_mask machinery (mask 0, zero
# grad/hess) so sums and counts are untouched.
# ======================================================================

# smallest ladder rung: below this, compile time dwarfs any pad waste,
# so every tiny fit shares a single program shape
MIN_BUCKET_ROWS = 4096


def shape_buckets_enabled() -> bool:
    """LGBM_TPU_SHAPE_BUCKETS: "0" off, "1" on, unset = accelerators
    only.  CPU defaults OFF so golden-model tests keep exact row counts
    (f32 reduction trees change with padding; quantized paths do not)."""
    v = os.environ.get("LGBM_TPU_SHAPE_BUCKETS", "").strip().lower()
    if v in ("0", "off", "false", "no"):
        return False
    if v in ("1", "on", "true", "yes"):
        return True
    from .histogram import on_accelerator
    return on_accelerator()


def bucket_rows(n: int) -> int:
    """Smallest ladder rung >= n; rungs are {2^k, 1.5 * 2^k}.

    Two rungs per octave bounds pad waste at 50% (just past a power of
    two) while keeping the distinct-program count logarithmic in the
    row-count range.
    """
    n = max(int(n), 1)
    if n <= MIN_BUCKET_ROWS:
        return MIN_BUCKET_ROWS
    base = 1 << (n.bit_length() - 1)        # 2^k <= n
    for rung in (base, base + (base >> 1), base << 1):
        if rung >= n:
            return rung
    return base << 1                        # unreachable


def plan_histograms(
    rows: int,
    features: int,
    num_bins: int,
    num_leaves: int = 31,
    num_class: int = 1,
    quant: bool = False,
    quant_bins: int = 4,
    method: str = "auto",
    round_width: int = 128,
    machines: int = 1,
    budget_bytes: Optional[int] = None,   # tests: fake memory model
    accel: Optional[bool] = None,
    fused_ok: bool = False,               # caller-verified fused context
    vmem_bytes: Optional[int] = None,     # tests: fake VMEM model
    ledger: Optional["ResidencyLedger"] = None,   # co-resident budget
) -> HistPlan:
    """Choose {tile_rows, use_pack, psum narrowing} for a training shape.

    Search: untiled first (fastest dispatch); if its predicted peak
    exceeds the budget, walk tile_rows down through powers of two until
    the prediction fits (records un-hoisted — ``use_pack=False`` — the
    moment tiling engages, so no whole-dataset record arena is ever
    materialized in tiled mode).  ``feasible=False`` means even
    MIN_TILE_ROWS does not fit: the caller should refuse to launch the
    shape rather than hand XLA a guaranteed OOM.

    ``fused_ok=True`` (the caller proved the semantic context applies:
    numeric features, no bundles/monotone/per-node randomness, unsharded
    axes — GBDT._build_jit_fns) lets ``method`` "auto"/"fused" elect the
    fused Pallas histogram→split megakernel (ops/fused.py): elected ONLY
    when ``plan_fused`` proves its VMEM arena fits, so the staged family
    remains the fallback arm and an explicit ``hist_method=fused`` that
    does not fit degrades to staged instead of OOMing VMEM.
    """
    from .fused import fused_enabled_env
    from .histogram import quant_psum_narrow

    if budget_bytes is not None:
        limit, source = int(budget_bytes), "caller"
        budget = int(limit * HEADROOM)
    elif ledger is not None:
        # co-resident planning: the budget is what the ledger has LEFT
        # (already post-HEADROOM — the ledger applied it once to the
        # device limit; re-applying here would double-charge)
        limit, source = int(ledger.limit_bytes), "ledger"
        budget = int(ledger.available_bytes())
    else:
        limit, source = hbm_limit_bytes()
        # HEADROOM applies to EVERY limit source (caller-supplied fake
        # memory models included) so tests exercise the shipped rule
        budget = int(limit * HEADROOM)
    fp = None
    if fused_ok and method in ("auto", "fused") and fused_enabled_env():
        # the frontier never exceeds num_leaves - 1 candidates, so the
        # arena is sized by the EFFECTIVE round width (grower KCAP)
        kcap = max(min(int(round_width), int(num_leaves) - 1), 1)
        fp = plan_fused(kcap, num_bins, quant, with_parent=True,
                        vmem_bytes=vmem_bytes, num_features=features)
    variant = "fused" if fp is not None else _resolved_variant(method, quant)
    narrow = bool(quant and quant_psum_narrow(rows * machines, quant_bins))
    # the fused grower never hoists the pack_cols_u32 record arena (it
    # gathers nothing), so its plan must not charge — or report — it
    pack_cap = variant != "fused"

    def peak(tile, pack):
        return predict_peak_bytes(
            rows, features, num_bins, num_leaves, num_class, quant,
            variant, tile, pack and pack_cap, round_width, machines,
            accel)[0]

    untiled_peak = peak(0, True)
    forced = _tile_override()

    def mk(tile, pack, degraded):
        pack = pack and pack_cap
        p = peak(tile, pack)
        return HistPlan(
            tile_rows=tile, use_pack=pack, variant=variant, quant=quant,
            narrow_int16=narrow, predicted_peak_bytes=p,
            untiled_peak_bytes=untiled_peak, budget_bytes=budget,
            limit_bytes=limit, limit_source=source,
            feasible=p <= budget, degraded=degraded,
            fused=fp is not None,
            fused_feat_tile=fp["feat_tile"] if fp else 0,
            fused_block_rows=fp["block_rows"] if fp else 0,
            fused_vmem_bytes=fp["vmem_bytes"] if fp else 0,
            vmem_limit_bytes=fp["vmem_limit_bytes"] if fp else 0)

    if forced is not None:
        if forced == 0 or forced >= rows:
            return mk(0, True, False)
        return mk(int(forced), False, False)

    if untiled_peak <= budget:
        return mk(0, True, False)

    # degrade: largest power-of-two tile whose prediction fits
    tile = 1 << max(int(rows - 1).bit_length() - 1, 0)
    tile = max(tile, MIN_TILE_ROWS)
    while tile > MIN_TILE_ROWS and peak(tile, False) > budget:
        tile //= 2
    return mk(tile, False, True)


def apply_plan(cfg, rows: int, features: int, accel: Optional[bool] = None,
               fused_ok: bool = False):
    """Thread a plan into a ``GrowerConfig``; returns (cfg, plan).

    Shared by the GBDT layer (per-shard rows) and the standalone
    parallel learners so every path trains under the same verdict.
    ``fused_ok`` carries the caller's semantic-applicability verdict for
    the fused megakernel; when the plan elects it, ``hist_method`` flips
    to "fused" and the kernel's {feat_tile, block_rows} ride along — and
    when an EXPLICIT hist_method="fused" fails the VMEM election, the
    config degrades to the staged auto family instead of OOMing.
    """
    plan = plan_histograms(
        rows=rows, features=features, num_bins=cfg.num_bins,
        num_leaves=cfg.num_leaves, quant=cfg.quant,
        quant_bins=cfg.quant_bins, method=cfg.hist_method,
        round_width=cfg.round_width, machines=max(cfg.num_machines, 1),
        accel=accel, fused_ok=fused_ok)
    # first-class predicted-peak event (docs/OBSERVABILITY.md): beside
    # the allocator's peak it shows memory-model drift per run
    from ..obs.trace import instant
    instant("planner.plan", rows=rows, features=features, **plan.summary())
    cfg = cfg._replace(tile_rows=plan.tile_rows,
                       hist_pack=cfg.hist_pack and plan.use_pack)
    if plan.fused:
        cfg = cfg._replace(hist_method="fused",
                           fused_feat_tile=plan.fused_feat_tile,
                           fused_block_rows=plan.fused_block_rows)
    elif cfg.hist_method == "fused":
        from .fused import fused_enabled_env
        if fused_ok and fused_enabled_env():
            # the VMEM election actually ran and declined; the env-gate
            # (LGBM_TPU_FUSED=0) and context rejections are explained by
            # their own channels (the bisect operator / GBDT's gate
            # warning / make_sharded_grower's note)
            from ..utils.log import log_warning
            log_warning(
                "hist_method=fused: the fused megakernel's VMEM arena "
                f"does not fit at round_width={cfg.round_width}, "
                f"num_bins={cfg.num_bins} "
                f"(limit {vmem_limit_bytes()} bytes; LGBM_TPU_VMEM_BYTES "
                "overrides); falling back to the staged kernel family")
        cfg = cfg._replace(hist_method="auto")
    return cfg, plan


# ======================================================================
# Model-axis (batched multi-booster) memory model: lightgbm_tpu/multi/
# trains B boosters in ONE vmapped chunk program.  Per-lane state — the
# carried scores, gradients, per-tree hist cache, per-pass transients —
# scales ×B; the binned matrix does NOT in shared-data mode (every lane
# indexes one device matrix, in_axes=None) and DOES in stacked-data mode
# (CV folds upload per-lane matrices along the lane axis).  plan_model_batch
# elects the largest lane-chunk Bc <= B whose predicted peak fits the
# budget; the driver degrades to ceil(B / Bc) sequential dispatch groups
# when HBM says no.  LGBM_TPU_MODEL_BATCH: "" = planner-elected, "0"/"off"
# = force sequential (Bc=1), N = cap Bc.
# ======================================================================


def _model_batch_override():
    """LGBM_TPU_MODEL_BATCH: None = planner-elected, 1 = batching off,
    N = cap the elected lane chunk."""
    v = os.environ.get("LGBM_TPU_MODEL_BATCH", "").strip().lower()
    if not v:
        return None
    if v in ("0", "off", "false", "none", "no"):
        return 1
    try:
        return max(int(v), 1)
    except ValueError:
        return None


class ModelBatchPlan(NamedTuple):
    """Lane-chunk verdict for one batched multi-booster group."""

    b_total: int                # boosters in the group
    b_chunk: int                # lanes per device dispatch
    num_dispatch_groups: int    # ceil(b_total / b_chunk)
    stacked: bool               # binned matrix scales with Bc
    per_lane_bytes: int         # what ONE extra lane costs
    shared_bytes: int           # lane-independent residency (shared binned)
    predicted_peak_bytes: int   # at the elected b_chunk
    budget_bytes: int
    limit_bytes: int
    limit_source: str           # "memory_stats" | "env" | "default" | "caller"
    feasible: bool              # even Bc=1 fits the budget
    degraded: bool              # budget forced Bc < b_total
    forced: bool                # LGBM_TPU_MODEL_BATCH capped the election

    def summary(self) -> dict:
        """JSON-friendly form for telemetry."""
        return {
            "b_total": self.b_total,
            "b_chunk": self.b_chunk,
            "num_dispatch_groups": self.num_dispatch_groups,
            "stacked": self.stacked,
            "per_lane_bytes": self.per_lane_bytes,
            "shared_bytes": self.shared_bytes,
            "predicted_peak_bytes": self.predicted_peak_bytes,
            "budget_bytes": self.budget_bytes,
            "hbm_limit_bytes": self.limit_bytes,
            "limit_source": self.limit_source,
            "feasible": self.feasible,
            "degraded": self.degraded,
            "forced": self.forced,
        }


def plan_model_batch(
    b_total: int,
    rows: int,
    features: int,
    num_bins: int,
    num_leaves: int = 31,
    num_class: int = 1,
    quant: bool = False,
    method: str = "auto",
    round_width: int = 128,
    machines: int = 1,
    stacked: bool = False,
    tile_rows: int = 0,
    use_pack: bool = True,
    budget_bytes: Optional[int] = None,   # tests: fake memory model
    accel: Optional[bool] = None,
    ledger: Optional["ResidencyLedger"] = None,   # co-resident budget
) -> ModelBatchPlan:
    """Elect the lane chunk for a B-booster batched training group.

    Memory model: ``total(Bc) = shared + Bc * per_lane`` where ``shared``
    is the binned matrix (plus its transformation copy) in shared-data
    mode and zero in stacked mode, and ``per_lane`` is everything else in
    ``predict_peak_bytes``'s breakdown (scores, gradients, hist cache,
    per-pass transients — all of which vmap replicates along the lane
    axis) plus, in stacked mode, the lane's own binned matrix.  Walk Bc
    down from B until the prediction fits; ``feasible=False`` means even
    one lane does not fit (same contract as ``plan_histograms``: refuse,
    don't OOM).
    """
    B = max(int(b_total), 1)
    if budget_bytes is not None:
        limit, source = int(budget_bytes), "caller"
        budget = int(limit * HEADROOM)
    elif ledger is not None:
        limit, source = int(ledger.limit_bytes), "ledger"
        budget = int(ledger.available_bytes())   # already post-HEADROOM
    else:
        limit, source = hbm_limit_bytes()
        budget = int(limit * HEADROOM)
    variant = _resolved_variant(method, quant)
    solo_peak, bd = predict_peak_bytes(
        rows, features, num_bins, num_leaves, num_class, quant, variant,
        tile_rows, use_pack, round_width, machines, accel)
    binned = bd["binned"]
    shared = 0 if stacked else binned
    per_lane = solo_peak - binned + (binned if stacked else 0)
    forced_cap = _model_batch_override()
    cap = B if forced_cap is None else min(B, forced_cap)

    def total(bc):
        return shared + bc * per_lane

    bc = cap
    while bc > 1 and total(bc) > budget:
        bc -= 1
    plan = ModelBatchPlan(
        b_total=B, b_chunk=bc, num_dispatch_groups=-(-B // bc),
        stacked=bool(stacked), per_lane_bytes=int(per_lane),
        shared_bytes=int(shared), predicted_peak_bytes=int(total(bc)),
        budget_bytes=budget, limit_bytes=limit, limit_source=source,
        feasible=total(1) <= budget,
        degraded=bc < B and (forced_cap is None or bc < cap),
        forced=forced_cap is not None)
    from ..obs.trace import instant
    instant("planner.model_batch", rows=rows, features=features,
            **plan.summary())
    return plan


# ======================================================================
# Per-tier collective link model: the hybrid ("dcn", "ici") mesh's
# reduction-schedule election (parallel/collectives.py).
#
# A TPU pod moves histogram payloads over TWO transports with a ~10-50x
# bandwidth gap: the intra-slice ICI torus and the cross-host DCN
# (PAPER.md §2.6).  ``plan_collectives`` models one histogram reduction
# under each schedule — flat (one psum over every data axis; the full
# payload effectively crosses the slow tier once per PARTICIPATING
# DEVICE, un-preaggregated), hierarchical (psum over ICI first, so DCN
# runs between num_slices pre-reduced participants), and voting
# (PV-Tree: only the top-k elected features' columns ever cross DCN) —
# and elects the cheapest.  Deliberately simple, like every model in
# this module: the right ORDER for the schedule verdict, not an XLA
# collective simulator.
# ======================================================================

# per-tier link bandwidths the election runs against (GB/s); order-of-
# magnitude figures for a v5e-class slice (ICI torus per-chip) vs a
# 50 Gbps-class host NIC.  LGBM_TPU_ICI_GBPS / LGBM_TPU_DCN_GBPS override
# (tests plan against fakes; operators against their fabric)
DEFAULT_ICI_GBPS = 100.0
DEFAULT_DCN_GBPS = 6.25


def _env_gbps(name: str, default: float) -> float:
    v = os.environ.get(name, "").strip()
    if v:
        try:
            return max(float(v), 1e-6)
        except ValueError:
            pass
    return default


def _hier_override():
    """LGBM_TPU_HIER_REDUCE: None = planner-elected, True/False forced."""
    v = os.environ.get("LGBM_TPU_HIER_REDUCE", "").strip().lower()
    if v in ("1", "on", "true", "yes", "force"):
        return True
    if v in ("0", "off", "false", "no"):
        return False
    return None


def pinned_reduce_env() -> bool:
    """LGBM_TPU_PINNED_REDUCE=1: deterministic tier-ordered f32 sums
    (parallel/collectives.py pinned mode) — the determinism knob behind
    the f32 flat==hierarchical model-text parity claim."""
    return os.environ.get("LGBM_TPU_PINNED_REDUCE", "").strip().lower() \
        in ("1", "on", "true", "yes")


class CollectivePlan(NamedTuple):
    """Reduction-schedule verdict for one histogram psum (see section
    docstring).  Byte fields are PER REDUCTION: what one [ch, F, B]
    histogram sync moves across each tier."""

    num_slices: int             # DCN participants (1 = single tier)
    devices_per_slice: int      # ICI participants per slice
    total_shards: int
    hierarchical: bool          # ICI-first tiered schedule elected
    pinned: bool                # deterministic tier-ordered f32 sums
    voting_k: int               # >0: only k elected features cross DCN
    payload_bytes: int          # one full-histogram psum payload
    ici_bytes: int              # bytes crossing the fast tier / device
    dcn_bytes: int              # bytes crossing the slow tier / slice
    flat_dcn_bytes: int         # what the FLAT schedule would move there
    est_flat_us: float          # modeled reduction time per schedule
    est_hier_us: float
    ici_gbps: float
    dcn_gbps: float
    elected: str                # "single" | "flat" | "hierarchical"
    #                             | "hierarchical+voting"

    def summary(self) -> dict:
        """JSON-friendly form for telemetry / checkpoint manifests."""
        return {
            "mesh_shape": [self.num_slices, self.devices_per_slice],
            "num_slices": self.num_slices,
            "total_shards": self.total_shards,
            "hierarchy_elected": self.hierarchical,
            "pinned": self.pinned,
            "voting_k": self.voting_k,
            "payload_bytes": self.payload_bytes,
            "ici_bytes": self.ici_bytes,
            "dcn_bytes": self.dcn_bytes,
            "flat_dcn_bytes": self.flat_dcn_bytes,
            "est_flat_us": round(self.est_flat_us, 3),
            "est_hier_us": round(self.est_hier_us, 3),
            "ici_gbps": self.ici_gbps,
            "dcn_gbps": self.dcn_gbps,
            "elected": self.elected,
        }


def plan_collectives(
    features: int,
    num_bins: int,
    rows_global: int,
    quant: bool = False,
    quant_bins: int = 4,
    num_slices: int = 1,
    devices_per_slice: int = 1,
    voting_k: int = 0,
    pinned: Optional[bool] = None,
    ici_gbps: Optional[float] = None,     # tests: fake link model
    dcn_gbps: Optional[float] = None,
) -> CollectivePlan:
    """Elect the reduction schedule for a (possibly hybrid) data mesh.

    ``features == 0`` plans shape-free (a nominal unit payload): the
    standalone learners elect a schedule before the traced shapes are
    known, and only the byte ACCOUNTING needs the real feature count.
    ``voting_k`` caps at ``features`` when both are known.  The verdict
    is journaled as a ``planner.plan_collectives`` trace instant, the
    twin of ``planner.plan`` (docs/OBSERVABILITY.md).
    """
    from .histogram import hist_payload_bytes

    s = max(int(num_slices), 1)
    d = max(int(devices_per_slice), 1)
    F = max(int(features), 0)
    k = min(int(voting_k), F) if (voting_k and F) else int(voting_k or 0)
    ici_bw = ici_gbps if ici_gbps is not None else _env_gbps(
        "LGBM_TPU_ICI_GBPS", DEFAULT_ICI_GBPS)
    dcn_bw = dcn_gbps if dcn_gbps is not None else _env_gbps(
        "LGBM_TPU_DCN_GBPS", DEFAULT_DCN_GBPS)
    payload = hist_payload_bytes(
        F or 1, max(int(num_bins), 2), rows_global=rows_global,
        quant_bins=(quant_bins if quant else None))
    # what crosses the slow tier per reduction: pre-aggregated full
    # payload (hierarchical data-parallel), the elected columns only
    # (voting), or the payload from every device of a slice (flat — no
    # pre-aggregation before the slow hop)
    # unknown feature count (shape-free planning) models NO voting
    # saving — a conservative ratio of 1.0 keeps the election and the
    # journaled DCN bytes honest until the real F is known
    vote_ratio = (k / F) if (k and F) else 1.0
    dcn_hier = int(payload * (vote_ratio if k else 1.0))
    if k:
        # the vote itself: [k] gains f32 + [k] indices i32, gathered
        # across slices — tiny next to histogram columns, but accounted
        dcn_hier += 8 * max(k, 1) * s
    flat_dcn = payload * d if s > 1 else 0
    us = 1e6 / 1e9   # bytes/GBps -> microseconds
    est_flat = (flat_dcn / dcn_bw + payload / ici_bw) * us if s > 1 \
        else (payload / ici_bw) * us
    est_hier = (payload / ici_bw + dcn_hier / dcn_bw) * us
    forced = _hier_override()
    if s <= 1:
        hier = False
        elected = "single" if d <= 1 else "flat"
    elif forced is not None:
        hier = forced
        elected = ("hierarchical+voting" if (hier and k) else
                   "hierarchical" if hier else "flat")
    else:
        hier = est_hier <= est_flat
        elected = ("hierarchical+voting" if (hier and k) else
                   "hierarchical" if hier else "flat")
    pin = pinned_reduce_env() if pinned is None else bool(pinned)
    plan = CollectivePlan(
        num_slices=s, devices_per_slice=d, total_shards=s * d,
        hierarchical=hier, pinned=pin, voting_k=k,
        payload_bytes=int(payload),
        ici_bytes=int(payload) if s * d > 1 else 0,
        dcn_bytes=int(dcn_hier if hier else flat_dcn) if s > 1 else 0,
        flat_dcn_bytes=int(flat_dcn),
        est_flat_us=float(est_flat), est_hier_us=float(est_hier),
        ici_gbps=float(ici_bw), dcn_gbps=float(dcn_bw), elected=elected)
    from ..obs.trace import instant
    instant("planner.plan_collectives", features=F, **plan.summary())
    return plan


# ======================================================================
# Two-level (device HBM + host RSS) budget: out-of-core streaming verdict
#
# PR 5's plan above made the *transients* O(tile); the binned matrix
# itself was still fully resident on BOTH memories, so dataset scale was
# capped by whichever is smaller.  ``plan_stream`` generalizes the model:
# it predicts the resident peaks on each memory, and when either budget
# is blown it elects ROW-BLOCK STREAMING (lightgbm_tpu/data/): the
# binned matrix lives in a checksummed spill store on disk, the host
# holds O(block) windows, and the device sees one double-buffered block
# at a time while the per-row vectors (scores/gradients/leaf routing)
# stay device-resident.  External-memory execution with block-compressed
# feature pages is the XGBoost external-memory lineage (arXiv
# 1806.11248); the one-pass-per-level feature-block access pattern is
# arXiv 1706.08359's.
# ======================================================================


def host_limit_bytes() -> tuple:
    """(limit_bytes, source) for the host-RSS side of the budget.

    Priority: ``LGBM_TPU_HOST_BYTES`` env (tests / fake memory models) >
    /proc/meminfo MemAvailable (what this process may still claim) > the
    conservative default.  Never raises.
    """
    env = os.environ.get("LGBM_TPU_HOST_BYTES", "").strip()
    if env:
        try:
            return max(int(float(env)), 1), "env"
        except ValueError:
            pass
    try:
        with open("/proc/meminfo") as fh:
            for line in fh:
                if line.startswith("MemAvailable:"):
                    kb = int(line.split()[1])
                    if kb > 0:
                        return kb * 1024, "meminfo"
    except OSError:
        pass
    return DEFAULT_HOST_BYTES, "default"


def predict_host_peak_bytes(rows: int, groups: int, bin_item: int = 1,
                            block_rows: int = 0) -> tuple:
    """(peak_bytes, breakdown) of the HOST side of one training run.

    ``block_rows == 0`` models the resident loader: the full [n, G]
    binned matrix plus one chunk of float64 binning scratch and the
    per-row metadata.  ``block_rows > 0`` models the streaming loader:
    three block windows (the spill writer's buffer + the pump's two
    double-buffered read windows) replace the matrix.  Deliberately
    simple — the right ORDER for the fits/doesn't verdict, like
    ``predict_peak_bytes``.
    """
    n = max(int(rows), 1)
    G = max(int(groups), 1)
    b = {}
    # label f32 + weight f32 + score fetches f32 + leaf routing i32 hosted
    # transiently by checkpoints: ~16 bytes/row of per-row metadata
    b["row_meta"] = 16 * n
    if block_rows <= 0:
        b["binned"] = n * G * bin_item
        # one float64 column of binning scratch per worker (dataset.py
        # _bin_block: 8 workers max)
        b["bin_scratch"] = 8 * 8 * n
    else:
        C = int(block_rows)
        b["block_windows"] = 3 * C * G * bin_item
        b["bin_scratch"] = 8 * 8 * C
    return sum(b.values()), b


class StreamPlan(NamedTuple):
    """Two-level budget verdict (see module section docstring)."""

    stream: bool                       # row-block streaming elected
    block_rows: int                    # rows per streamed block (0 = resident)
    num_blocks: int
    resident_device_ok: bool           # full residency fits the HBM budget
    resident_host_ok: bool             # full residency fits the RSS budget
    predicted_device_peak_bytes: int   # for the chosen mode
    predicted_host_peak_bytes: int     # for the chosen mode
    device_budget_bytes: int
    host_budget_bytes: int
    host_limit_bytes: int
    host_limit_source: str             # "env" | "meminfo" | "default"
    feasible: bool                     # the chosen mode fits BOTH budgets
    reason: str                        # why streaming was/wasn't elected

    def summary(self) -> dict:
        """JSON-friendly form for telemetry / checkpoint provenance."""
        return {
            "stream": self.stream,
            "block_rows": self.block_rows,
            "num_blocks": self.num_blocks,
            "resident_device_ok": self.resident_device_ok,
            "resident_host_ok": self.resident_host_ok,
            "predicted_device_peak_bytes": self.predicted_device_peak_bytes,
            "predicted_host_peak_bytes": self.predicted_host_peak_bytes,
            "device_budget_bytes": self.device_budget_bytes,
            "host_budget_bytes": self.host_budget_bytes,
            "host_limit_bytes": self.host_limit_bytes,
            "host_limit_source": self.host_limit_source,
            "feasible": self.feasible,
            "reason": self.reason,
        }


def _stream_override():
    """LGBM_TPU_STREAM: None = auto (budget-elected), True = force
    streaming, False = never stream."""
    v = os.environ.get("LGBM_TPU_STREAM", "").strip().lower()
    if v in ("1", "on", "force", "true", "yes"):
        return True
    if v in ("0", "off", "false", "no", "none"):
        return False
    return None


def _stream_block_override():
    v = os.environ.get("LGBM_TPU_STREAM_BLOCK_ROWS", "").strip()
    if not v:
        return None
    try:
        return max(int(float(v)), 128)
    except ValueError:
        return None


def predict_stream_device_peak_bytes(
        rows: int, features: int, num_bins: int, block_rows: int,
        num_leaves: int = 31, num_class: int = 1, quant: bool = False,
        variant: str = "scatter", tile_rows: int = 0,
        round_width: int = 128, accel: Optional[bool] = None) -> int:
    """Device peak of one STREAMED training step: the resident model with
    the whole-matrix terms replaced by two device block windows plus the
    per-row routing vectors the streamed grower keeps resident."""
    if accel is None:
        from .histogram import on_accelerator
        accel = on_accelerator()
    n = max(int(rows), 1)
    C = min(max(int(block_rows), 1), n)
    bin_item = 1 if num_bins <= 256 else 2
    # model the per-pass transients at block scale: the kernels only ever
    # see C rows at a time
    peak, b = predict_peak_bytes(
        C, features, num_bins, num_leaves, num_class, quant, variant,
        min(tile_rows, C) if tile_rows else 0, False, round_width,
        1, accel)
    peak -= b["binned"]                      # no resident matrix
    peak -= b["scores"] + b["grads"]         # re-added at full n below
    dev = peak
    dev += 2 * _arr(C, max(int(features), 1), bin_item, accel)  # 2 windows
    K = max(int(num_class), 1)
    dev += 2 * K * _arr(n, 1, 4, accel)      # scores (donated in+out)
    dev += 2 * K * _arr(n, 1, 4, accel)      # grad/hess rows
    if quant:
        dev += 2 * K * _arr(n, 1, 1, accel)
    # leaf_id i32 + goes-left bool + candidate-rank i32 + row mask f32
    dev += _arr(n, 1, 4, accel) * 3 + _arr(n, 1, 1, accel)
    return int(dev)


# ======================================================================
# Serving-fleet residency budget: multi-model shared-HBM election
#
# The serving tier (lightgbm_tpu/fleet/) keeps N models' device routing
# arrays (DeviceForest) plus their per-bucket compiled programs resident
# in the SAME HBM the training plans above budget.  ``plan_fleet``
# applies the training planner's discipline to the fleet: model the
# per-model resident bytes, elect which models (and which of their
# ladder buckets) stay device-resident under the budget, and mark the
# rest EVICTED — an evicted model keeps serving through the bit-identical
# host path instead of OOMing the chip.  Low-precision models
# (bf16/int8 thresholds, host-gathered leaves — the fixed-point GBDT
# accelerator direction of arXiv 2011.02022) charge proportionally less,
# so opting a model into low precision buys residency for its neighbors.
# ======================================================================


def predict_forest_bytes(num_trees: int, nodes_dim: int, leaves_dim: int,
                         precision: str = "f32", cat_words: int = 0,
                         accel: Optional[bool] = None,
                         routing_only: bool = False) -> int:
    """Resident device bytes of ONE model's DeviceForest arrays.

    ``nodes_dim``/``leaves_dim`` are the padded [T, I]/[T, L] axes of the
    stacked forest (predict.py).  ``precision`` prices the threshold
    array (f32 = 4, bf16 = 2, int8 = 1 byte + a per-tree f32 dequant
    scale); ``routing_only`` drops the leaf-value array (low-precision
    serving gathers leaves on the host, so it never uploads them).
    Deliberately simple — the right ORDER for the residency election,
    like ``predict_peak_bytes``.
    """
    if accel is None:
        from .histogram import on_accelerator
        accel = on_accelerator()
    T = max(int(num_trees), 1)
    I = max(int(nodes_dim), 1)
    L = max(int(leaves_dim), 1)
    thr_item = {"f32": 4, "bf16": 2, "int8": 1}.get(precision, 4)
    b = 3 * _arr(I, T, 4, accel)            # split_feature, left, right i32
    b += _arr(I, T, thr_item, accel)        # thresholds
    b += 2 * _arr(I, T, 1, accel)           # is_cat, default_left bool
    b += _arr(I, T, 4, accel)               # missing_type i32
    if precision == "int8":
        b += _arr(1, T, 4, accel)           # per-tree dequant scale f32
    if not routing_only:
        b += _arr(L, T, 4, accel)           # leaf_value f32
    if cat_words > 0:
        b += 2 * _arr(I, T, 8, accel) + _arr(int(cat_words), 1, 4, accel)
    return int(b)


def predict_program_bytes(num_trees: int, bucket_rows: int, features: int,
                          accel: Optional[bool] = None) -> int:
    """Transient device bytes of one bucket-shaped serving program
    invocation: the padded [bucket, F] f32 input, the [T, bucket]
    traversal state (node + gathered attrs live across the while-loop
    step) and the leaf-index output.  This is what the residency
    election charges per WARMED bucket — the executable itself is small
    next to its activations."""
    if accel is None:
        from .histogram import on_accelerator
        accel = on_accelerator()
    T = max(int(num_trees), 1)
    C = max(int(bucket_rows), 1)
    F = max(int(features), 1)
    b = _arr(F, C, 4, accel)                # input batch f32
    b += 4 * _arr(C, T, 4, accel)           # node/next/fval/threshold state
    b += _arr(C, T, 4, accel)               # leaves out i32
    return int(b)


def fleet_replica_bytes(m: "FleetModelShape",
                        accel: Optional[bool] = None):
    """Device cost of ONE replica of ``m``: ``(forest_bytes,
    {bucket: program_bytes})`` — the unit the single-device residency
    election (``plan_fleet``) and the multi-device placement planner
    (``fleet/topology.plan_topology``) both charge, so a topology's
    per-device loads and each device's own residency verdicts can never
    disagree about what a replica costs."""
    fb = predict_forest_bytes(
        m.num_trees, m.nodes_dim, m.leaves_dim, m.precision,
        m.cat_words, accel, routing_only=m.precision != "f32")
    ladder = sorted(set(int(b) for b in m.buckets)) or [8]
    prog = {b: predict_program_bytes(m.num_trees, b, m.features, accel)
            for b in ladder}
    return fb, prog


class FleetModelShape(NamedTuple):
    """One serving model's shape as the fleet election sees it."""

    name: str
    num_trees: int
    nodes_dim: int              # padded internal-node axis I
    leaves_dim: int             # padded leaf axis L
    features: int
    num_class: int = 1
    buckets: tuple = ()         # the model's bucket ladder (row counts)
    weight: float = 1.0         # admission weight (fleet config)
    age_s: float = 0.0          # seconds since last request (0 = hot)
    precision: str = "f32"      # "f32" | "bf16" | "int8"
    cat_words: int = 0


class FleetModelPlan(NamedTuple):
    """Residency verdict for one model."""

    name: str
    resident: bool              # device forest stays in HBM
    resident_buckets: tuple     # buckets whose programs stay warm
    forest_bytes: int           # charged when resident
    program_bytes: int          # charged for the resident buckets
    priority: float             # weight / (1 + age): the election key


class FleetPlan(NamedTuple):
    """Shared-HBM residency plan for a serving fleet (see section
    docstring).  Always servable: eviction falls back to the host path,
    so ``feasible`` is about DEVICE residency, not about serving."""

    models: tuple               # FleetModelPlan per input model, input order
    total_resident_bytes: int
    budget_bytes: int
    limit_bytes: int
    limit_source: str           # "memory_stats" | "env" | "default" | "caller"
    evicted: tuple              # names of non-resident models
    pressure: float             # wanted-resident bytes / budget
    feasible: bool              # every model got device residency

    def summary(self) -> dict:
        """JSON-friendly form for telemetry."""
        return {
            "models": [
                {"name": m.name, "resident": m.resident,
                 "resident_buckets": list(m.resident_buckets),
                 "forest_bytes": m.forest_bytes,
                 "program_bytes": m.program_bytes,
                 "priority": round(m.priority, 6)}
                for m in self.models
            ],
            "total_resident_bytes": self.total_resident_bytes,
            "budget_bytes": self.budget_bytes,
            "hbm_limit_bytes": self.limit_bytes,
            "limit_source": self.limit_source,
            "evicted": list(self.evicted),
            "pressure": round(self.pressure, 4),
            "feasible": self.feasible,
        }


def plan_fleet(models, budget_bytes: Optional[int] = None,
               accel: Optional[bool] = None,
               ledger: Optional["ResidencyLedger"] = None) -> FleetPlan:
    """Elect per-model device residency for a serving fleet.

    Greedy by priority ``weight / (1 + age_s)`` — hot, heavily-weighted
    models first.  A model is admitted when its forest plus at least its
    smallest bucket's program fit the remaining budget; further buckets
    are admitted smallest-first (the cheapest warm shapes give the most
    service per byte).  Models that do not fit are EVICTED: their device
    arrays and compiled programs are released and they serve through the
    bit-identical host path until a replan readmits them.  ``HEADROOM``
    applies to every limit source, exactly like ``plan_histograms``.
    """
    if budget_bytes is not None:
        limit, source = int(budget_bytes), "caller"
        budget = int(limit * HEADROOM)
    elif ledger is not None:
        # serving election against the ledger's REMAINING budget: bytes
        # already leased (e.g. by an in-flight training refresh) are not
        # available for model residency
        limit, source = int(ledger.limit_bytes), "ledger"
        budget = int(ledger.available_bytes())
    else:
        limit, source = hbm_limit_bytes()
        budget = int(limit * HEADROOM)
    models = list(models)
    order = sorted(
        range(len(models)),
        key=lambda i: (-(models[i].weight / (1.0 + max(models[i].age_s, 0.0))),
                       i))
    plans: dict = {}
    used = 0
    wanted = 0
    for i in order:
        m = models[i]
        prio = m.weight / (1.0 + max(m.age_s, 0.0))
        fb, prog = fleet_replica_bytes(m, accel)
        ladder = sorted(prog)
        wanted += fb + sum(prog.values())
        if used + fb + prog[ladder[0]] > budget:
            plans[i] = FleetModelPlan(m.name, False, (), fb, 0, prio)
            continue
        used += fb
        taken, pb = [], 0
        for b in ladder:
            if used + prog[b] <= budget:
                taken.append(b)
                used += prog[b]
                pb += prog[b]
        plans[i] = FleetModelPlan(m.name, True, tuple(taken), fb, pb, prio)
    ordered = tuple(plans[i] for i in range(len(models)))
    evicted = tuple(p.name for p in ordered if not p.resident)
    return FleetPlan(
        models=ordered, total_resident_bytes=used, budget_bytes=budget,
        limit_bytes=limit, limit_source=source, evicted=evicted,
        pressure=(wanted / budget) if budget > 0 else float("inf"),
        feasible=not evicted)


def plan_stream(
    rows: int,
    features: int,               # device column count (groups under EFB)
    num_bins: int,
    num_leaves: int = 31,
    num_class: int = 1,
    quant: bool = False,
    method: str = "auto",
    round_width: int = 128,
    tile_rows: int = 0,          # the hist plan's tile (block aligns to it)
    device_budget_bytes: Optional[int] = None,   # tests: fake memory model
    host_budget_bytes: Optional[int] = None,     # tests: fake memory model
    accel: Optional[bool] = None,
    ledger: Optional["ResidencyLedger"] = None,  # co-resident budget
) -> StreamPlan:
    """Choose resident vs row-block-streamed execution for a shape.

    Streaming is elected when full residency blows EITHER budget (device
    HBM via ``predict_peak_bytes``'s model, host RSS via
    ``predict_host_peak_bytes``) and a block size exists whose streamed
    peaks fit BOTH.  Block search: largest power of two first (fewer
    dispatches), aligned up to a multiple of the hist plan's ``tile_rows``
    so the streamed fold partitions rows exactly like the resident tiled
    kernels (the f32 matmul family's bit-parity needs the alignment; the
    scatter family is partition-free).  ``feasible=False`` means even
    MIN_STREAM_BLOCK_ROWS does not fit — refuse to launch rather than
    OOM either memory.

    Env: ``LGBM_TPU_STREAM`` (1 = force streaming, 0 = never),
    ``LGBM_TPU_STREAM_BLOCK_ROWS`` (force the block size),
    ``LGBM_TPU_HOST_BYTES`` (host limit override).
    """
    n = max(int(rows), 1)
    variant = _resolved_variant(method, quant)
    if device_budget_bytes is not None:
        dev_budget = int(device_budget_bytes * HEADROOM)
    elif ledger is not None:
        dev_budget = int(ledger.available_bytes())   # already post-HEADROOM
    else:
        dev_budget = int(hbm_limit_bytes()[0] * HEADROOM)
    if host_budget_bytes is not None:
        host_limit, host_src = int(host_budget_bytes), "caller"
    else:
        host_limit, host_src = host_limit_bytes()
    host_budget = int(host_limit * HOST_HEADROOM)
    bin_item = 1 if num_bins <= 256 else 2

    resident_dev = predict_peak_bytes(
        n, features, num_bins, num_leaves, num_class, quant, variant,
        tile_rows, tile_rows <= 0, round_width, 1, accel)[0]
    resident_host = predict_host_peak_bytes(n, features, bin_item)[0]
    dev_ok = resident_dev <= dev_budget
    host_ok = resident_host <= host_budget

    forced = _stream_override()
    want = forced if forced is not None else not (dev_ok and host_ok)

    def mk(stream, block, reason, dev_peak, host_peak):
        nb = 0 if block <= 0 else -(-n // block)
        return StreamPlan(
            stream=stream, block_rows=block, num_blocks=nb,
            resident_device_ok=dev_ok, resident_host_ok=host_ok,
            predicted_device_peak_bytes=int(dev_peak),
            predicted_host_peak_bytes=int(host_peak),
            device_budget_bytes=dev_budget, host_budget_bytes=host_budget,
            host_limit_bytes=host_limit, host_limit_source=host_src,
            feasible=(dev_peak <= dev_budget and host_peak <= host_budget),
            reason=reason)

    if not want:
        reason = ("disabled by LGBM_TPU_STREAM=0" if forced is False
                  else "resident fits both budgets")
        return mk(False, 0, reason, resident_dev, resident_host)

    def peaks(block):
        return (predict_stream_device_peak_bytes(
                    n, features, num_bins, block, num_leaves, num_class,
                    quant, variant, tile_rows, round_width, accel),
                predict_host_peak_bytes(n, features, bin_item, block)[0])

    def align(block):
        if tile_rows > 0 and block > tile_rows:
            return block // tile_rows * tile_rows
        return block

    reason = ("forced by LGBM_TPU_STREAM=1" if forced else
              ("device+host" if not dev_ok and not host_ok else
               "device" if not dev_ok else "host") + " budget exceeded")
    b_forced = _stream_block_override()
    if b_forced is not None:
        block = min(b_forced, n)
        dp, hp = peaks(block)
        return mk(True, block, reason + " (block forced)", dp, hp)
    block = MAX_STREAM_BLOCK_ROWS
    while block > MIN_STREAM_BLOCK_ROWS:
        if align(block) < n:        # a single-block "stream" is resident
            dp, hp = peaks(align(block))
            if dp <= dev_budget and hp <= host_budget:
                return mk(True, align(block), reason, dp, hp)
        block //= 2
    block = align(min(MIN_STREAM_BLOCK_ROWS, n))
    dp, hp = peaks(block)
    return mk(True, block, reason, dp, hp)


# ======================================================================
# Residency ledger: ONE per-device HBM budget both planes lease from.
#
# Every planner above models its OWN plane's peak against a budget it
# assumes it owns — which is exactly how co-resident train+serve on one
# pod over-commits and dies as a compile-OOM.  ``ResidencyLedger`` is
# the arbitration layer: one post-HEADROOM budget per device, explicit
# leases (who, which plane, how many bytes, preemptible?), and a
# ``ledger=`` seam on ``plan_histograms`` / ``plan_model_batch`` /
# ``plan_stream`` / ``plan_fleet`` (and ``fleet.topology.plan_topology``)
# that makes each planner elect against the ledger's REMAINING bytes.
# The degradation order falls out of the existing planners: a training
# refresh planned against the remainder degrades its tile size first
# (plan_histograms' tile walk), and only an explicit ``preempt`` ever
# touches serving residency.  Infeasible co-residency is a loud
# ``LedgerError`` carrying the lease table — never an XLA OOM.  Every
# ledger event is journaled as a ``planner.ledger`` trace instant and
# mirrored to ``ledger_*`` gauges (docs/OBSERVABILITY.md).
# ======================================================================


class LedgerError(RuntimeError):
    """A lease request exceeds the ledger's remaining budget — the loud
    co-residency verdict (refuse, don't OOM).  The message carries the
    full lease table so the operator sees WHO holds the HBM."""


class Lease(NamedTuple):
    """One admitted residency claim."""

    lease_id: int
    owner: str                  # e.g. "fleet:ranker" / "refresh:ranker"
    plane: str                  # "serving" | "train"
    nbytes: int
    preemptible: bool           # preempt() may evict it


class ResidencyLedger:
    """Per-device HBM budget shared by the serving and training planes.

    Thread-safe: the serving fleet's replan thread and the co-resident
    training scheduler lease/release concurrently.  The ledger applies
    ``HEADROOM`` ONCE to the device limit; planners handed a ledger use
    ``available_bytes()`` directly (already post-HEADROOM), so the slack
    is never double-charged.
    """

    def __init__(self, limit_bytes: Optional[int] = None):
        if limit_bytes is not None:
            limit, source = max(int(limit_bytes), 1), "caller"
        else:
            limit, source = hbm_limit_bytes()
        self.limit_bytes = limit
        self.limit_source = source
        self.budget_bytes = int(limit * HEADROOM)
        self._lock = threading.Lock()
        self._leases = {}       # guarded-by: _lock
        self._next_id = 1       # guarded-by: _lock

    # -- accounting --------------------------------------------------

    def leased_bytes(self, plane: Optional[str] = None) -> int:
        with self._lock:
            return sum(l.nbytes for l in self._leases.values()
                       if plane is None or l.plane == plane)

    def available_bytes(self) -> int:
        """Remaining post-HEADROOM budget — what a co-resident planner
        may claim without over-committing the device."""
        return max(self.budget_bytes - self.leased_bytes(), 0)

    def train_limit_bytes(self, lease: Optional[Lease] = None) -> int:
        """The remainder expressed as a LIMIT (pre-HEADROOM), for code
        paths that re-apply HEADROOM themselves (``LGBM_TPU_HBM_BYTES``
        consumers).  Int-floored so re-applying HEADROOM lands <= the
        actual remainder.  ``lease`` adds a held training lease back in:
        the training plane's envelope is its own lease plus the slack."""
        grant = self.available_bytes()
        if lease is not None:
            with self._lock:
                if lease.lease_id in self._leases:
                    grant += lease.nbytes
        return max(int(grant / HEADROOM), 1)

    def table(self) -> list:
        """The lease table, JSON-friendly (flight bundles / doctor
        evidence / LedgerError messages)."""
        with self._lock:
            leases = sorted(self._leases.values())
        return [{"lease_id": l.lease_id, "owner": l.owner,
                 "plane": l.plane, "bytes": l.nbytes,
                 "preemptible": l.preemptible} for l in leases]

    def summary(self) -> dict:
        """JSON-friendly totals for journals / telemetry."""
        with self._lock:
            leased = sum(l.nbytes for l in self._leases.values())
            by_plane: dict = {}
            for l in self._leases.values():
                by_plane[l.plane] = by_plane.get(l.plane, 0) + l.nbytes
            count = len(self._leases)
        return {"limit_bytes": self.limit_bytes,
                "limit_source": self.limit_source,
                "budget_bytes": self.budget_bytes,
                "leased_bytes": leased,
                "available_bytes": max(self.budget_bytes - leased, 0),
                "num_leases": count,
                "leased_by_plane": by_plane}

    # -- lease lifecycle ---------------------------------------------

    def lease(self, owner: str, nbytes: int, plane: str = "train",
              preemptible: bool = False) -> Lease:
        """Admit a residency claim or raise ``LedgerError`` loudly."""
        need = max(int(nbytes), 0)
        with self._lock:
            leased = sum(l.nbytes for l in self._leases.values())
            if leased + need > self.budget_bytes:
                denied = True
                granted = None
            else:
                denied = False
                granted = Lease(self._next_id, str(owner), str(plane),
                                need, bool(preemptible))
                self._leases[granted.lease_id] = granted
                self._next_id += 1
        if denied:
            self._emit("deny", owner=str(owner), plane=str(plane),
                       bytes=need)
            raise LedgerError(
                f"residency ledger: lease '{owner}' ({plane}) wants "
                f"{need} bytes but only {self.available_bytes()} of the "
                f"{self.budget_bytes}-byte budget remain "
                f"(limit {self.limit_bytes}, source "
                f"{self.limit_source}); held leases: {self.table()}")
        self._emit("lease", owner=granted.owner, plane=granted.plane,
                   bytes=granted.nbytes, lease_id=granted.lease_id)
        return granted

    def try_lease(self, owner: str, nbytes: int, plane: str = "train",
                  preemptible: bool = False) -> Optional[Lease]:
        """``lease`` that returns None instead of raising."""
        try:
            return self.lease(owner, nbytes, plane, preemptible)
        except LedgerError:
            return None

    def release(self, lease) -> None:
        """Return a lease's bytes to the budget (idempotent)."""
        lid = getattr(lease, "lease_id", lease)
        with self._lock:
            gone = self._leases.pop(lid, None)
        if gone is not None:
            self._emit("release", owner=gone.owner, plane=gone.plane,
                       bytes=gone.nbytes, lease_id=gone.lease_id)

    def preempt(self, plane: str = "train") -> int:
        """Evict every preemptible lease of ``plane``; returns the bytes
        freed.  The co-resident scheduler marks training leases
        preemptible, so a serving-side replan under pressure preempts
        training residency — never the other way around (degrade tile
        before degrading serving residency)."""
        with self._lock:
            victims = [l for l in self._leases.values()
                       if l.plane == plane and l.preemptible]
            for v in victims:
                del self._leases[v.lease_id]
        freed = sum(v.nbytes for v in victims)
        if victims:
            self._emit("preempt", plane=plane, freed_bytes=freed,
                       victims=[v.owner for v in victims])
        return freed

    @contextmanager
    def train_env(self, lease: Optional[Lease] = None):
        """Pin ``LGBM_TPU_HBM_BYTES`` to the training plane's envelope
        so every planner reached INSIDE ``engine.train`` (hist, stream,
        model-batch) plans against remaining-HBM-plus-own-lease instead
        of the whole device."""
        key = "LGBM_TPU_HBM_BYTES"
        prev = os.environ.get(key)
        os.environ[key] = str(self.train_limit_bytes(lease))
        try:
            yield self
        finally:
            if prev is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = prev

    # -- telemetry ---------------------------------------------------

    def _emit(self, event: str, **extra) -> None:
        s = self.summary()
        from ..obs.trace import instant
        instant("planner.ledger", event=event, **extra, **s)
        from ..obs.metrics import global_registry
        global_registry.gauge("ledger_budget_bytes").set(s["budget_bytes"])
        global_registry.gauge("ledger_available_bytes").set(
            s["available_bytes"])
        for plane in ("serving", "train"):
            global_registry.gauge(
                "ledger_leased_bytes", labels={"plane": plane}).set(
                    s["leased_by_plane"].get(plane, 0))


# the process's co-residency ledger, when a coresident.Scheduler (or an
# operator) installed one — the diagnose layer reads it for the
# contention verdict's lease-table evidence
_active_ledger: Optional[ResidencyLedger] = None
_active_ledger_lock = threading.Lock()


def set_active_ledger(ledger: Optional[ResidencyLedger]):
    """Install ``ledger`` as the process's co-residency ledger; returns
    the previous one (restore it when tearing down a scheduler)."""
    global _active_ledger
    with _active_ledger_lock:
        prev = _active_ledger
        _active_ledger = ledger
    return prev


def active_ledger() -> Optional[ResidencyLedger]:
    return _active_ledger


# ======================================================================
# Inference kernel + chunk election: plan_predict.  The predict path's
# analogue of plan_histograms — byte models answer "does it fit", and
# LGBM_TPU_PREDICT_KERNEL is the bisect gate over the whole election.
# ======================================================================

PREDICT_VARIANTS = ("while", "fori", "fused")
# largest device chunk the election will reach for (a ladder rung; the
# per-call chunk still shrinks to bucket_rows(n) for small batches)
MAX_PREDICT_CHUNK_ROWS = 1 << 20
# fused-traversal row-tile ladder (widest VMEM-resident tile first)
FUSED_PREDICT_TILES = (2048, 1024, 512, 256, 128)


def _predict_kernel_override():
    """LGBM_TPU_PREDICT_KERNEL: pin the traversal variant, bypassing
    the analytic election (the bisect gate)."""
    v = os.environ.get("LGBM_TPU_PREDICT_KERNEL", "").strip().lower()
    return v if v in PREDICT_VARIANTS else None


def _predict_chunk_override():
    """LGBM_TPU_PREDICT_CHUNK: pin the predict chunk size."""
    v = os.environ.get("LGBM_TPU_PREDICT_CHUNK", "").strip()
    if not v:
        return None
    try:
        n = int(float(v))
    except ValueError:
        return None
    return max(n, 8) if n > 0 else None


def predict_fused_vmem_bytes(num_trees: int, nodes_dim: int, features: int,
                             tile_rows: int, cat_words: int = 0,
                             leaves_dim: int = 0, num_class: int = 1,
                             emit_scores: bool = False) -> int:
    """Predicted VMEM bytes of one fused-traversal grid step
    (ops/predict_kernels.py): the nine resident [T, I] forest planes +
    bitset words, the double-buffered [tile, F] input window, the
    [T, tile] node state with its gather transients, and the output
    block (leaf plane, or the [K, tile] score block plus the resident
    leaf-value plane in score mode).  Deliberately simple — the right
    ORDER for the fits/doesn't verdict, like ``fused_vmem_bytes``."""
    T = max(int(num_trees), 1)
    I = max(int(nodes_dim), 1)
    F = max(int(features), 1)
    C = max(int(tile_rows), 8)
    K = max(int(num_class), 1)
    planes = 9 * T * I * 4 + max(int(cat_words), 1) * 4
    x = 2 * C * F * 4
    state = 6 * T * C * 4
    if emit_scores:
        out = K * C * 4 + T * max(int(leaves_dim), 1) * 4
    else:
        out = T * C * 4
    return planes + x + state + out


def plan_predict_fused_tile(num_trees, nodes_dim, features, cat_words=0,
                            leaves_dim=0, num_class=1, emit_scores=False,
                            vmem_bytes=None):
    """Largest fused row tile whose VMEM prediction fits, or None when
    no ladder rung does (the election then stays on ``fori``)."""
    limit = int(vmem_bytes if vmem_bytes is not None else vmem_limit_bytes())
    budget = int(limit * VMEM_HEADROOM)
    for c in FUSED_PREDICT_TILES:
        need = predict_fused_vmem_bytes(num_trees, nodes_dim, features, c,
                                        cat_words, leaves_dim, num_class,
                                        emit_scores)
        if need <= budget:
            return {"tile_rows": c, "vmem_bytes": need,
                    "vmem_limit_bytes": limit}
    return None


def elect_predict_chunk(num_trees, nodes_dim, leaves_dim, features,
                        precision="f32", cat_words=0, routing_only=False,
                        accel=None, budget=None) -> int:
    """Largest ladder rung whose forest + per-chunk activation bytes fit
    the HBM budget, replacing ``DeviceForest``'s historical hard-coded
    ``1 << 16``.  ``LGBM_TPU_PREDICT_CHUNK`` pins it outright."""
    o = _predict_chunk_override()
    if o:
        return o
    if budget is None:
        limit, _ = hbm_limit_bytes()
        budget = int(limit * HEADROOM)
    fb = predict_forest_bytes(num_trees, nodes_dim, leaves_dim, precision,
                              cat_words, accel, routing_only)
    best = MIN_BUCKET_ROWS
    c = MIN_BUCKET_ROWS
    while c <= MAX_PREDICT_CHUNK_ROWS:
        if fb + predict_program_bytes(num_trees, c, features,
                                      accel) > budget:
            break
        best = c
        c = bucket_rows(c + 1)
    return int(best)


def elect_csr_chunk(features: int) -> int:
    """Host-memory-aware CSR densification chunk for
    ``predict.predict_csr_chunked``: the dense f64 chunk (plus its
    densify + result transients, ~3x) may claim a quarter of the host
    budget.  ``LGBM_TPU_PREDICT_CHUNK`` pins it outright."""
    o = _predict_chunk_override()
    if o:
        return o
    limit, _ = host_limit_bytes()
    budget = int(limit * HOST_HEADROOM) // 4
    per_row = max(int(features), 1) * 8 * 3
    return int(min(max(budget // per_row, 1 << 12), 1 << 20))


class PredictPlan(NamedTuple):
    """plan_predict's verdict: traversal variant, fused row tile, device
    chunk, and the byte story the election ran under."""

    variant: str                # "while" | "fori" | "fused"
    tile_rows: int              # fused VMEM row tile (0 = not fused)
    chunk_rows: int             # elected device chunk (a ladder rung)
    forest_bytes: int
    program_bytes: int          # activations at chunk_rows
    predicted_peak_bytes: int
    budget_bytes: int
    limit_bytes: int
    limit_source: str
    feasible: bool
    elected_by: str             # "env" | "analytic"

    def summary(self) -> dict:
        """JSON-friendly form for telemetry."""
        return {
            "variant": self.variant,
            "tile_rows": self.tile_rows,
            "chunk_rows": self.chunk_rows,
            "forest_bytes": self.forest_bytes,
            "program_bytes": self.program_bytes,
            "predicted_peak_bytes": self.predicted_peak_bytes,
            "budget_bytes": self.budget_bytes,
            "hbm_limit_bytes": self.limit_bytes,
            "limit_source": self.limit_source,
            "feasible": self.feasible,
            "elected_by": self.elected_by,
        }


def plan_predict(num_trees: int, nodes_dim: int, leaves_dim: int,
                 features: int, rows: int = 0, num_class: int = 1,
                 precision: str = "f32", cat_words: int = 0,
                 routing_only: bool = False, ledger=None,
                 accel: Optional[bool] = None,
                 vmem_bytes: Optional[int] = None) -> PredictPlan:
    """Elect {variant, tile_rows, chunk_rows} for one model's predict
    path.

    Budget: the ledger's remaining bytes when one is leased against
    (serving co-residency, PR 17), else HEADROOM x the device limit.
    Variant: ``LGBM_TPU_PREDICT_KERNEL`` > analytic (``fori`` — the
    while arm and the fused Pallas traversal are never elected, only
    pinned).  The chip's compiler refuses the fused traversal's
    in-kernel table gathers (an ``AssertionError`` in Mosaic's gather
    lowering rule — docs/PERF.md "what compiles on the chip"), so there
    the pin raises the compiler's error; where Pallas interprets, the
    pin runs it.
    """
    if accel is None:
        from .histogram import on_accelerator
        accel = on_accelerator()
    limit, source = hbm_limit_bytes()
    if ledger is not None:
        # ledger budgets are already post-HEADROOM (applied once at the
        # ledger's limit — see plan_histograms' co-resident arm)
        limit, source = int(ledger.limit_bytes), "ledger"
        budget = int(ledger.available_bytes())
    else:
        budget = int(limit * HEADROOM)
    chunk = elect_predict_chunk(num_trees, nodes_dim, leaves_dim, features,
                                precision, cat_words, routing_only,
                                accel=accel, budget=budget)
    if rows:
        chunk = min(chunk, bucket_rows(rows))
    fb = predict_forest_bytes(num_trees, nodes_dim, leaves_dim, precision,
                              cat_words, accel, routing_only)
    pb = predict_program_bytes(num_trees, chunk, features, accel)
    ft = plan_predict_fused_tile(num_trees, nodes_dim, features, cat_words,
                                 leaves_dim, num_class,
                                 emit_scores=not routing_only,
                                 vmem_bytes=vmem_bytes)
    variant, elected_by = "fori", "analytic"
    o = _predict_kernel_override()
    if o is not None:
        variant, elected_by = o, "env"
    tile = (ft["tile_rows"] if ft is not None else FUSED_PREDICT_TILES[-1]) \
        if variant == "fused" else 0
    peak = fb + pb
    return PredictPlan(
        variant=variant, tile_rows=tile, chunk_rows=chunk,
        forest_bytes=fb, program_bytes=pb, predicted_peak_bytes=peak,
        budget_bytes=budget, limit_bytes=limit, limit_source=source,
        feasible=peak <= budget, elected_by=elected_by)


# ======================================================================
# Ingest kernel + chunk election: plan_ingest.  The binning pass's
# analogue of plan_predict — byte models answer "what chunk fits the
# ledger remainder" and "kernel or host", and LGBM_TPU_INGEST_KERNEL is
# the bisect gate over the election.
# ======================================================================

INGEST_VARIANTS = ("kernel", "host")
# largest device ingest chunk the election reaches for (a ladder rung)
MAX_INGEST_CHUNK_ROWS = 1 << 21
# bucketize+pack row-tile ladder (widest VMEM-resident tile first)
INGEST_TILES = (2048, 1024, 512, 256, 128)
# past this width the unrolled per-feature kernel stops being the
# analytic default (compile time grows with the feature loop); the env
# pin can still elect it
MAX_INGEST_KERNEL_FEATURES = 1024


def _ingest_kernel_override():
    """LGBM_TPU_INGEST_KERNEL: pin the binning arm ("kernel" | "host"),
    bypassing the analytic election (the bisect gate)."""
    v = os.environ.get("LGBM_TPU_INGEST_KERNEL", "").strip().lower()
    return v if v in INGEST_VARIANTS else None


def _ingest_chunk_override():
    """LGBM_TPU_INGEST_CHUNK: pin the device ingest chunk size."""
    v = os.environ.get("LGBM_TPU_INGEST_CHUNK", "").strip()
    if not v:
        return None
    try:
        n = int(float(v))
    except ValueError:
        return None
    return max(n, 8) if n > 0 else None


def ingest_vmem_bytes(features: int, tile_rows: int, bounds_width: int,
                      cats_width: int, num_groups: int) -> int:
    """Predicted scoped-VMEM bytes of one bucketize+pack grid step
    (ops/ingest.py), in the chip's layout: every [tile, x] f32/i32
    plane pads its minor dim to 128 lanes, so the unit is one padded
    row of 512 bytes.  Per row: the double-buffered input and output
    windows (4 units), the per-feature column extractions and the
    per-group fold columns the unrolled feature loop keeps live
    (0.8 units each), and the broadcast compare plane with its i32
    cast (2 units per 128 table columns); the resident tables ride on
    top.  The per-column factor is a fit to what the TPU compiler
    itself reports as the kernel's scoped allocation (25.74 MB at tile
    1024, F = G = 28; 94 units/row at F = G = 56 — docs/PERF.md "what
    compiles on the chip"); the earlier dense-bytes model was 4x short
    and elected a tile the compiler refused."""
    F = max(int(features), 1)
    C = max(int(tile_rows), 8)
    G = max(int(num_groups), 1)
    W = max(int(bounds_width), int(cats_width), 1)
    row_units = 5 + 0.8 * (F + G) + 2 * (_pad(W, 128) // 128)
    tables = _pad(F, 8) * (_pad(max(int(bounds_width), 1), 128)
                           + _pad(max(int(cats_width), 1), 128)) * 4
    return int(C * row_units * 512) + tables


def plan_ingest_tile(features, bounds_width, cats_width, num_groups,
                     vmem_bytes=None):
    """Largest ingest row tile whose VMEM prediction fits, or None when
    no ladder rung does (the election then stays on host)."""
    limit = int(vmem_bytes if vmem_bytes is not None else vmem_limit_bytes())
    budget = int(limit * VMEM_HEADROOM)
    for c in INGEST_TILES:
        need = ingest_vmem_bytes(features, c, bounds_width, cats_width,
                                 num_groups)
        if need <= budget:
            return {"tile_rows": c, "vmem_bytes": need,
                    "vmem_limit_bytes": limit}
    return None


def ingest_chunk_bytes(chunk_rows: int, features: int, num_groups: int,
                       item_bytes: int) -> int:
    """Device bytes of one in-flight ingest chunk: the double-buffered
    raw f32 block (the pump keeps chunk t+1 in flight while t bins),
    the i32 kernel output, and its cast to the group dtype."""
    c = max(int(chunk_rows), 1)
    return c * (2 * max(int(features), 1) * 4
                + max(int(num_groups), 1) * (4 + max(int(item_bytes), 1)))


def elect_ingest_chunk(features: int, num_groups: int, item_bytes: int,
                       budget: Optional[int] = None) -> int:
    """Largest ladder rung whose in-flight chunk bytes fit the budget —
    how 11M rows bin without a single 157 GB device_put.
    ``LGBM_TPU_INGEST_CHUNK`` pins it outright."""
    o = _ingest_chunk_override()
    if o:
        return o
    if budget is None:
        limit, _ = hbm_limit_bytes()
        budget = int(limit * HEADROOM)
    best = MIN_BUCKET_ROWS
    c = MIN_BUCKET_ROWS
    while c <= MAX_INGEST_CHUNK_ROWS:
        if ingest_chunk_bytes(c, features, num_groups, item_bytes) > budget:
            break
        best = c
        c = bucket_rows(c + 1)
    return int(best)


class IngestPlan(NamedTuple):
    """plan_ingest's verdict: binning arm, VMEM row tile, device chunk,
    and the byte story the election ran under."""

    variant: str                # "kernel" | "host"
    tile_rows: int              # kernel VMEM row tile (0 = host)
    chunk_rows: int             # elected device chunk (a ladder rung)
    chunk_bytes: int            # in-flight bytes at chunk_rows
    budget_bytes: int
    limit_bytes: int
    limit_source: str
    feasible: bool
    elected_by: str             # "env" | "analytic"

    def summary(self) -> dict:
        """JSON-friendly form for telemetry."""
        return {
            "variant": self.variant,
            "tile_rows": self.tile_rows,
            "chunk_rows": self.chunk_rows,
            "chunk_bytes": self.chunk_bytes,
            "budget_bytes": self.budget_bytes,
            "hbm_limit_bytes": self.limit_bytes,
            "limit_source": self.limit_source,
            "feasible": self.feasible,
            "elected_by": self.elected_by,
        }


def plan_ingest(rows: int, features: int, num_groups: int,
                item_bytes: int = 1, bounds_width: int = 1,
                cats_width: int = 1, ledger=None,
                accel: Optional[bool] = None,
                vmem_bytes: Optional[int] = None) -> IngestPlan:
    """Elect {variant, tile_rows, chunk_rows} for one dataset's binning
    pass.

    Budget: the ledger's remaining bytes when one is leased against
    (co-residency, PR 17), else HEADROOM x the device limit.  Variant:
    ``LGBM_TPU_INGEST_KERNEL`` > analytic (kernel on accelerators when
    its VMEM tile fits and the feature width is kernel-sized, host
    everywhere else).
    """
    if accel is None:
        from .histogram import on_accelerator
        accel = on_accelerator()
    limit, source = hbm_limit_bytes()
    if ledger is not None:
        # ledger budgets are already post-HEADROOM (plan_predict's rule)
        limit, source = int(ledger.limit_bytes), "ledger"
        budget = int(ledger.available_bytes())
    else:
        budget = int(limit * HEADROOM)
    chunk = elect_ingest_chunk(features, num_groups, item_bytes,
                               budget=budget)
    if rows:
        chunk = min(chunk, bucket_rows(rows))
    tile = plan_ingest_tile(features, bounds_width, cats_width, num_groups,
                            vmem_bytes=vmem_bytes)
    analytic = "kernel" if (accel and tile is not None
                            and features <= MAX_INGEST_KERNEL_FEATURES) \
        else "host"
    variant, elected_by = analytic, "analytic"
    o = _ingest_kernel_override()
    if o is not None:
        variant, elected_by = o, "env"
    cb = ingest_chunk_bytes(chunk, features, num_groups, item_bytes)
    return IngestPlan(
        variant=variant,
        tile_rows=(tile["tile_rows"] if tile is not None
                   else INGEST_TILES[-1]) if variant == "kernel" else 0,
        chunk_rows=chunk, chunk_bytes=cb,
        budget_bytes=budget, limit_bytes=limit, limit_source=source,
        feasible=cb <= budget, elected_by=elected_by)
