"""On-device gradient/hessian histogram construction.

TPU-native replacement for LightGBM's histogram kernels
(reference: src/io/dense_bin.hpp:97 ConstructHistogramInner — CPU scatter-add;
src/treelearner/ocl/histogram256.cl:317 — GPU atomic scatter).

Design inversion for the MXU: instead of scatter-add (random-access, serializes
on TPU), the histogram is a **one-hot matmul**: for a block of rows build the
0/1 matrix ``onehot[C, F*B]`` (row r has a 1 at column f*B + bin(r, f)) in
bfloat16 (exact for 0/1) and compute ``vals @ onehot`` with
``vals = mask * [grad, hess, 1]`` — a [3, C] x [C, F*B] matmul accumulated in
float32 over row blocks.  This keeps the hot loop on the systolic array at
~100% HBM streaming rate instead of scalar scatter.  Leaf membership is folded
into ``mask``, which replaces the reference's ordered-gradient gather
(src/io/dataset.cpp:1318-1333) with a branch-free masked pass.

LAYOUT DOCTRINE (round 5, measured): TPU tiles the two minor-most dims to
(8, 128) — f32 [n, 3] pads 42x, u8 [n, 28] pads 4.6x, u32 [n, 13] pads 10x
(the OOM at 11M rows was exactly a lane-padded [n*F, 3]).  Therefore:

- the binned matrix lives on device FEATURE-MAJOR: ``binned_t`` [F, n]
  (minor dim n — unpadded), and every kernel here consumes that layout;
- histograms are ``[3, F, B]`` / ``[S, 3, F, B]`` with the tiny component
  axis LEADING (minor dims (F, B) pad ~2x instead of 128/3 = 42x);
- per-row values ride as separate [n] vectors or [3, n] / [W, n] blocks,
  never as [n, small] matrices.

A scatter-based variant is kept for CPU testing / tiny inputs; `auto` probes
are selected at trace time by platform.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# rows per block of the one-hot matmul; 8 sublanes * 128 lanes friendly
_DEFAULT_BLOCK_ROWS = 4096

# backends where the MXU/one-hot formulations win; everywhere this set is
# consulted it must stay in sync with the bf16/f32 precision pairing
ACCEL_BACKENDS = ("tpu",)


def on_accelerator() -> bool:
    return jax.default_backend() in ACCEL_BACKENDS


def use_sorted_seghist() -> bool:
    """Whether the segment histogram takes the sorted-arena path (ONE
    shared predicate for the kernel dispatch and the grower's decision to
    pre-pack column records).  LGBM_TPU_SEGHIST=sorted|scatter overrides."""
    forced = os.environ.get("LGBM_TPU_SEGHIST")
    if forced in ("sorted", "scatter"):
        return forced == "sorted"
    return on_accelerator()


def resolve_hist_method(method: str, quantized: bool = False) -> str:
    """The concrete kernel ``method='auto'`` resolves to on this backend.

    Kept in ONE place so the grower's segment-histogram precision choice
    (bf16 one-hot vs f32-exact) can never disagree with the parent
    histogram kernel it subtracts from.

    ``quantized=True`` resolves within the INTEGER kernel family
    (use_quantized_grad): int8 one-hot matmul with int32 accumulation on
    accelerators, packed scatter on CPU.  A forced f32-family name maps
    to its integer analogue so ``tpu_hist_method`` keeps steering the
    matmul-vs-scatter axis in either mode.

    ``method="fused"`` (the Pallas histogram→split megakernel,
    ops/fused.py) resolves to itself in BOTH families — the growers gate
    where it actually applies and this module's plain-histogram entry
    points (``build_histogram*``) map it to the staged auto kernel,
    since a bare histogram has no split scan to fuse.  The growers'
    refusal set has shrunk: categorical features, monotone constraints
    and data-parallel sharding now run fused (the collective seam);
    only EFB bundles, per-node randomness and feature/voting sharding
    still force the staged family.
    """
    if method == "fused":
        return "fused"
    if quantized:
        if method in ("matmul_int8", "scatter_int"):
            return method
        if method == "auto":
            return "matmul_int8" if on_accelerator() else "scatter_int"
        if method in ("matmul", "matmul_f32", "pallas"):
            return "matmul_int8"
        if method == "scatter":
            return "scatter_int"
        raise ValueError(f"unknown histogram method {method!r}")
    if method == "auto":
        return "matmul" if on_accelerator() else "scatter"
    return method


def _pad_rows(n: int, block: int) -> int:
    return (n + block - 1) // block * block


def _vals_t(grad, hess, mask):
    """[3, n] f32 value block (g, h, 1) * mask — minor dim n, unpadded."""
    return jnp.stack([grad, hess, jnp.ones_like(grad)]) * mask[None, :]


def resolve_tile_rows(tile_rows, n: int):
    """Normalize a ``tile_rows`` request: None/0/>=n means untiled."""
    if tile_rows is None or tile_rows <= 0 or tile_rows >= n:
        return None
    return int(tile_rows)


def _tile_block(block_rows: int, tile_rows, lane: int = 128) -> int:
    """Streaming block size under a tile budget.

    The matmul-family kernels were ALWAYS streamed (a ``lax.scan`` over
    ``block_rows``-row blocks with an O(block) one-hot transient), so for
    them ``tile_rows`` simply CAPS the block: peak transient bytes track
    min(block, tile).  Rounded to the lane width so the one-hot stays
    tile-aligned.  TILE-MAJOR ORDER PIN: blocks accumulate into one shared
    f32 accumulator in ascending row order at every block size, so any
    ``tile_rows >= block_rows`` is bit-identical to untiled (the block
    partition is unchanged); a smaller tile refines the partition — still
    deterministic, exact for the int family (associative), and within
    f32 reassociation for the bf16/f32 matmuls."""
    if tile_rows is None:
        return block_rows
    return max(lane, min(block_rows, _pad_rows(tile_rows, lane)))


def histogram_matmul(
    binned_t: jax.Array,  # [F, n] uint8/uint16/int32 (feature-major)
    vals_t: jax.Array,    # [3, n] f32 rows already masked: (g, h, 1)*mask
    num_bins: int,        # padded bin axis B (static)
    block_rows: int = _DEFAULT_BLOCK_ROWS,
    onehot_dtype=jnp.bfloat16,
    tile_rows: Optional[int] = None,
    init: Optional[jax.Array] = None,
) -> jax.Array:
    """Histogram via one-hot matmul over row blocks. Returns [3, F, B] f32.

    ``init`` is the carry-in accumulator for the out-of-core streaming
    fold (lightgbm_tpu/data/stream.py): a block pass that STARTS from the
    running histogram continues the same block-ascending accumulation
    sequence the one-shot kernel runs internally, so folding row blocks
    through carried calls is bit-identical to one resident call — the
    invariant behind streamed == resident f32 parity (the tile partition
    must align across the two runs for the matmul family; scatter is
    partition-free).
    """
    F, n = binned_t.shape
    B = num_bins
    block_rows = _tile_block(block_rows, resolve_tile_rows(tile_rows, n))
    nb = max(1, _pad_rows(n, block_rows) // block_rows)
    n_pad = nb * block_rows
    if n_pad != n:
        binned_t = jnp.pad(binned_t, ((0, 0), (0, n_pad - n)))
        vals_t = jnp.pad(vals_t, ((0, 0), (0, n_pad - n)))
    iota = jnp.arange(B, dtype=binned_t.dtype)
    C = block_rows
    prec = (lax.Precision.HIGHEST if onehot_dtype == jnp.float32
            else lax.Precision.DEFAULT)

    def body(acc, i):
        b = lax.dynamic_slice(binned_t, (0, i * C), (F, C))   # [F, C]
        v = lax.dynamic_slice(vals_t, (0, i * C), (3, C))     # [3, C]
        onehot = (b.T[:, :, None] == iota).astype(onehot_dtype)
        onehot2d = onehot.reshape(C, F * B)
        part = lax.dot(v.astype(onehot_dtype), onehot2d, precision=prec,
                       preferred_element_type=jnp.float32)
        return acc + part, None

    acc0 = (jnp.zeros((3, F * B), dtype=jnp.float32) if init is None
            else init.reshape(3, F * B))
    acc, _ = lax.scan(body, acc0, jnp.arange(nb, dtype=jnp.int32))
    return acc.reshape(3, F, B)


def histogram_matmul_f32(
    binned_t: jax.Array, vals_t: jax.Array, num_bins: int,
    block_rows: int = _DEFAULT_BLOCK_ROWS,
    tile_rows: Optional[int] = None,
    init: Optional[jax.Array] = None,
) -> jax.Array:
    """Like histogram_matmul but f32 one-hot (exact grads; ~2x slower MXU)."""
    return histogram_matmul(binned_t, vals_t, num_bins, block_rows,
                            onehot_dtype=jnp.float32, tile_rows=tile_rows,
                            init=init)


def histogram_pallas(
    binned_t: jax.Array,  # [F, n] uint8/uint16 (feature-major)
    vals_t: jax.Array,    # [3, n] f32 rows already masked: (g, h, 1)*mask
    num_bins: int,
    block_rows: int = 512,
    feat_tile: int = 8,
    interpret: Optional[bool] = None,
    tile_rows: Optional[int] = None,
) -> jax.Array:
    """Histogram via a Pallas VPU kernel accumulating in VMEM.

    Why not the MXU: the one-hot matmul formulation has M=3 output rows
    (grad/hess/count), so the 128x128 systolic array runs at <3% utilization
    AND materializes a [rows, F*B] one-hot intermediate in HBM.  This kernel
    instead streams `binned_t` once ([F, n] — its resident layout) and does
    the compare-select-accumulate on the VPU with the [3, F, B] accumulator
    resident in VMEM across row blocks — HBM traffic is exactly one read of
    the binned matrix + the vals block per pass, the memory-optimal floor.

    reference analogue: dense_bin.hpp:97 ConstructHistogramInner (CPU
    scatter) / ocl/histogram256.cl:317 (GPU atomic scatter); this is the
    TPU-shaped third answer.  Grid = (feature tiles, row blocks); the row
    axis iterates fastest so each feature tile's accumulator initializes
    once (@pl.when i==0) and revisits its output block across row blocks.

    ``tile_rows`` (the ops/planner.py row-tile budget) CAPS the VMEM row
    block like the matmul family's ``_tile_block``: the kernel was always
    streamed with an O(block) transient, so under a tile budget the block
    simply shrinks to min(block, tile) — this brings the one previously
    unbudgeted kernel in the family under the same planner accounting
    (``predict_peak_bytes`` variant "pallas"), so ``auto`` can elect it
    safely.  Off-accelerator the kernel runs ``interpret=True`` so the
    tier-1 CPU pytest run executes it rather than skipping.
    """
    from jax.experimental import pallas as pl

    F, n = binned_t.shape
    B = num_bins
    C = _tile_block(block_rows, resolve_tile_rows(tile_rows, n))
    Ft = min(feat_tile, F)
    if interpret is None:
        interpret = not on_accelerator()

    n_pad = _pad_rows(n, C)
    F_pad = _pad_rows(F, Ft)
    bt = binned_t
    # widened to i32 PER BLOCK inside the kernel so the HBM copy stays at
    # the narrow dtype (a .astype here would materialize a 4x intermediate)
    if n_pad != n or F_pad != F:
        # padded features get bin 0 with weight 0 (vals rows padded to 0)
        bt = jnp.pad(bt, ((0, F_pad - F), (0, n_pad - n)))
    vt = vals_t.astype(jnp.float32)
    if n_pad != n:
        vt = jnp.pad(vt, ((0, 0), (0, n_pad - n)))

    nb = n_pad // C
    nf = F_pad // Ft

    def kernel(b_ref, v_ref, out_ref):
        i = pl.program_id(1)

        @pl.when(i == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        blk = b_ref[...].astype(jnp.int32)              # [Ft, C]
        g = v_ref[0, :]                                 # [C]
        h = v_ref[1, :]
        w = v_ref[2, :]
        iota = lax.broadcasted_iota(jnp.int32, (B, C), 0)
        for f in range(Ft):                             # static unroll
            oh = blk[f, :][None, :] == iota             # [B, C]
            out_ref[f, 0, :] += jnp.sum(
                jnp.where(oh, g[None, :], 0.0), axis=1)
            out_ref[f, 1, :] += jnp.sum(
                jnp.where(oh, h[None, :], 0.0), axis=1)
            out_ref[f, 2, :] += jnp.sum(
                jnp.where(oh, w[None, :], 0.0), axis=1)

    out = pl.pallas_call(
        kernel,
        grid=(nf, nb),
        in_specs=[
            pl.BlockSpec((Ft, C), lambda j, i: (j, i)),
            pl.BlockSpec((3, C), lambda j, i: (0, i)),
        ],
        out_specs=pl.BlockSpec((Ft, 3, B), lambda j, i: (j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((F_pad, 3, B), jnp.float32),
        interpret=interpret,
        name="lgbm_hist_staged",
    )(bt, vt)
    return out[:F].transpose(1, 0, 2)                   # [3, F, B]


def histogram_scatter(
    binned_t: jax.Array, vals_t: jax.Array, num_bins: int,
    tile_rows: Optional[int] = None,
    init: Optional[jax.Array] = None,
) -> jax.Array:
    """Scatter-add histogram (XLA scatter). Reference semantics check path
    (CPU-oriented: the [n, F, 3] update buffer lane-pads on TPU).

    ``tile_rows`` streams row tiles through a ``fori_loop``: the update
    buffer shrinks from [n, F, 3] to [tile, F, 3] — THE r5 OOM class
    (f32[n*F, 3] lane-padded 42x at 11M rows).  Tiles accumulate into one
    shared histogram in ascending row order, so per-bin adds happen in
    the same sequence as the untiled scatter: tiled == untiled
    bit-identical (padded tail rows carry +0 values into bin 0).

    ``init`` carries a running [3, F, B] accumulator in for the
    out-of-core block fold (data/stream.py): per-bin adds always land in
    ascending row order, so a carried fold over row blocks is
    bit-identical to one resident pass regardless of the block
    partition."""
    F, n = binned_t.shape
    B = num_bins
    offsets = (jnp.arange(F, dtype=jnp.int32) * B)[None, :]
    hist0 = (jnp.zeros((F * B, 3), dtype=jnp.float32) if init is None
             else init.transpose(1, 2, 0).reshape(F * B, 3))
    T = resolve_tile_rows(tile_rows, n)
    if T is None:
        binned = binned_t.T                                # [n, F]
        vals = vals_t.T                                    # [n, 3]
        flat_idx = binned.astype(jnp.int32) + offsets      # [n, F]
        # vals broadcast across features: updates [n, F, 3]
        updates = jnp.broadcast_to(vals[:, None, :], (n, F, 3))
        hist = hist0.at[flat_idx.reshape(-1)].add(updates.reshape(-1, 3))
        return hist.reshape(F, B, 3).transpose(2, 0, 1)    # [3, F, B]
    nt = _pad_rows(n, T) // T
    n_pad = nt * T
    bt = jnp.pad(binned_t, ((0, 0), (0, n_pad - n)))
    vt = jnp.pad(vals_t, ((0, 0), (0, n_pad - n)))

    def body(t, hist):
        b = lax.dynamic_slice(bt, (0, t * T), (F, T)).T    # [T, F]
        v = lax.dynamic_slice(vt, (0, t * T), (3, T)).T    # [T, 3]
        flat = b.astype(jnp.int32) + offsets               # [T, F]
        upd = jnp.broadcast_to(v[:, None, :], (T, F, 3))
        return hist.at[flat.reshape(-1)].add(upd.reshape(-1, 3))

    hist = lax.fori_loop(0, nt, body, hist0)
    return hist.reshape(F, B, 3).transpose(2, 0, 1)


def build_histogram(
    binned_t: jax.Array,   # [F, n] feature-major
    grad: jax.Array,
    hess: jax.Array,
    mask: jax.Array,
    num_bins: int,
    method: str = "auto",
    block_rows: int = _DEFAULT_BLOCK_ROWS,
    tile_rows: Optional[int] = None,
    init: Optional[jax.Array] = None,
) -> jax.Array:
    """Masked histogram [3, F, B] = sum over rows with mask of (g, h, 1).

    ``mask`` is f32 and may carry bagging weights; leaf membership is encoded
    by zeroing non-member rows.  ``tile_rows`` streams the pass through
    row tiles so peak transient HBM is O(tile), not O(n) (planner-selected;
    see ops/planner.py).  ``init`` is the streaming block fold's carry-in
    accumulator (scatter/matmul families only — the pallas kernel
    initializes its VMEM accumulator in-grid).
    """
    vals_t = _vals_t(grad, hess, mask)
    # "fused" is a grower-level arm (ops/fused.py pairs the histogram
    # with its split scan); a bare histogram maps to a staged kernel.
    # PRECISION PAIRING (same invariant as the growers' seg_f32): the
    # fused kernel accumulates f32-exact (HIGHEST one-hot dot), and its
    # in-kernel sibling subtraction consumes THIS kernel's output as the
    # parent — so the root/parent pass must be f32-exact too, never the
    # bf16 one-hot (a bf16 parent minus an exact child could go negative
    # in derived sibling bins).  matmul_f32 on accelerators, auto
    # (scatter, exact) on CPU.
    if method == "fused":
        method = "matmul_f32" if on_accelerator() else "auto"
    method = resolve_hist_method(method)
    if method == "matmul":
        return histogram_matmul(binned_t, vals_t, num_bins, block_rows,
                                tile_rows=tile_rows, init=init)
    if method == "matmul_f32":
        return histogram_matmul_f32(binned_t, vals_t, num_bins, block_rows,
                                    tile_rows=tile_rows, init=init)
    if method == "scatter":
        return histogram_scatter(binned_t, vals_t, num_bins,
                                 tile_rows=tile_rows, init=init)
    if method == "pallas":
        if init is not None:
            raise ValueError("histogram_pallas does not take a carry-in "
                             "accumulator; stream folds use scatter/matmul")
        return histogram_pallas(binned_t, vals_t, num_bins,
                                tile_rows=tile_rows)
    raise ValueError(f"unknown histogram method {method!r}")


_probe_cache: dict = {}


def measured_best_method(n: int, num_features: int, num_bins: int,
                         candidates=("matmul", "scatter", "pallas"),
                         reps: int = 8) -> str:
    """Pick the histogram kernel by TIMING it on the live backend.

    reference: Dataset::GetShareStates times col-wise vs row-wise histogram
    construction at startup and keeps the winner (src/io/dataset.cpp:589-684)
    — the same idea applied to this module's kernel variants.  The probe
    runs once per (backend, F, B, n-bucket) per process (~seconds) on
    synthetic data of the training shape; CPU skips straight to "scatter".
    Every candidate is a variant the chip's compiler is known to accept
    (tests/test_chip_compile.py), so nothing is caught here: a variant
    that fails to compile or run is a defect, not a loser.
    """
    import time

    if not on_accelerator():
        return "scatter"
    backend = jax.default_backend()
    n_probe = int(min(n, 1_000_000))
    key = (backend, num_features, num_bins, n_probe)
    if key in _probe_cache:
        return _probe_cache[key]
    rng = np.random.RandomState(0)
    host_dtype = np.uint8 if num_bins <= 256 else np.uint16
    binned_t = jnp.asarray(rng.randint(0, max(num_bins - 1, 1),
                                       (num_features, n_probe),
                                       dtype=host_dtype))
    grad = jnp.asarray(rng.randn(n_probe), jnp.float32)
    hess = jnp.abs(grad) + 0.1
    mask = jnp.ones((n_probe,), jnp.float32)

    timings = {}
    for method in candidates:
        fn = jax.jit(functools.partial(build_histogram, num_bins=num_bins,
                                       method=method))
        fn(binned_t, grad, hess, mask).block_until_ready()   # compile
        t0 = time.perf_counter()
        out = None
        for _ in range(reps):
            out = fn(binned_t, grad, hess, mask)
        out.block_until_ready()
        timings[method] = (time.perf_counter() - t0) / reps
    winner = min(timings, key=timings.get)
    from ..utils.log import log_info
    log_info("histogram kernel probe "
             f"({n_probe}x{num_features}, B={num_bins}): "
             + ", ".join(f"{k}={v * 1e3:.1f}ms" for k, v in timings.items())
             + f" -> {winner}")
    _probe_cache[key] = winner
    return winner


def capacity_schedule(n: int, min_cap: int = _DEFAULT_BLOCK_ROWS,
                      step: int = 4) -> list:
    """Descending capacities n, n/step, ... >= min_cap.

    Trace-time constants for the bucketed compaction below.  The smaller
    child of a split never exceeds n/2 rows, and leaf sizes shrink roughly
    geometrically in leaf-wise growth, so per-tree histogram work drops from
    O(n * num_leaves) (full masked pass per split) to ~O(n * log(num_leaves))
    — the same asymptotic the reference gets from per-leaf ordered gradients
    (src/io/dataset.cpp:1318-1333) without data-dependent shapes.

    The ladder stops at ``max(min_cap, n/256)``: every rung is a compiled
    branch of a ``lax.switch`` (XLA compile time — and the remote compile
    service's appetite — scales with them), and a histogram pass over
    n/256 rows is already noise next to the per-loop-step overhead the
    compaction exists to avoid.  ``step=4`` (default) keeps the rung
    count at ~4 for 11M rows: a rung overshoots the live set by at most
    4x, a bounded waste the slot-expanded pass has made cheap, while the
    branch count stays compile-friendly.
    """
    step = max(int(step), 2)
    min_cap = max(min_cap, _pad_rows(max(n, 1), min_cap) // 256)
    caps = []
    c = _pad_rows(n, min_cap)
    while c >= min_cap:
        caps.append(c)
        if c == min_cap:
            break
        c = _pad_rows((c + step - 1) // step, min_cap)
        if caps and c == caps[-1]:
            break
    if not caps:
        caps = [_pad_rows(max(n, 1), min_cap)]
    return caps


def compacted_histogram(
    binned_t: jax.Array,     # [F, n] feature-major
    grad: jax.Array,         # [n]
    hess: jax.Array,         # [n]
    weights: jax.Array,      # [n] f32 bagging/GOSS weights
    member: jax.Array,       # [n] bool leaf membership
    num_bins: int,
    caps: list,              # static descending capacities from capacity_schedule
    method: str = "auto",
    tile_rows: Optional[int] = None,
) -> jax.Array:
    """Masked histogram restricted to `member` rows via gather compaction.

    The member row-ids are compacted into the smallest static capacity that
    fits (lax.switch over precompiled bucket sizes); the histogram kernel
    then runs over `cap` rows instead of n.  Returns [3, F, B] f32.
    """
    F, n = binned_t.shape
    # zero-weight rows (bagged-out / GOSS-dropped) contribute nothing, so
    # exclude them from compaction too — same result, tighter capacity
    member = member & (weights > 0)
    count = jnp.sum(member)

    def branch(cap: int):
        def run():
            idx = jnp.nonzero(member, size=cap, fill_value=n)[0]
            valid = idx < n
            idxc = jnp.minimum(idx, n - 1)
            cols = jnp.take(binned_t, idxc, axis=1)        # [F, cap]
            w = jnp.where(valid, jnp.take(weights, idxc), 0.0)
            g = jnp.take(grad, idxc)
            h = jnp.take(hess, idxc)
            return build_histogram(cols, g, h, w, num_bins, method=method,
                                   tile_rows=tile_rows)
        return run

    if len(caps) == 1:
        return build_histogram(binned_t, grad, hess,
                               weights * member, num_bins, method=method,
                               tile_rows=tile_rows)
    caps_arr = jnp.asarray(caps, jnp.int32)
    # smallest capacity >= count (caps[0] >= n covers everything)
    bucket = jnp.sum(caps_arr >= count) - 1
    return lax.switch(bucket, [branch(c) for c in caps])


def segment_histogram(
    binned_t: jax.Array,     # [F, n] feature-major
    grad: jax.Array,         # [n]
    hess: jax.Array,         # [n]
    weights: jax.Array,      # [n] f32 bagging/GOSS weights
    slot: jax.Array,         # [n] i32 in [0, num_slots]; num_slots = dropped
    num_slots: int,
    num_bins: int,
    tile_rows: Optional[int] = None,
    init: Optional[jax.Array] = None,
) -> jax.Array:
    """Per-slot masked histogram: [S, 3, F, B] where row r contributes its
    (g, h, 1)*w to slot[r]'s histogram.  Rows with slot == num_slots are
    dropped (the dummy slot).

    ``init`` carries a running [S, 3, F, B] accumulator in for the
    out-of-core block fold (data/stream.py): the dummy slot restarts at
    zero each block (it is dropped from the output anyway) while the S
    real slots continue the global ascending-row add sequence —
    bit-identical to one resident pass over the concatenated rows.

    This is the batched-frontier generalization of ``build_histogram``: one
    pass over the data builds the histograms of EVERY smaller child of a
    round's splits (reference equivalent: one ConstructHistograms call per
    leaf, serial_tree_learner.cpp:380-388 — here a whole frontier per call).
    Scatter-add formulation (CPU semantics-reference path): the work is
    O(n*F) independent of S, unlike a one-hot matmul over (slot, bin) which
    would cost O(n*F*B*S).

    ``tile_rows`` streams the [n, F, 3] update buffer — the EXACT
    f32[n*F, 3] allocation that OOM'd the r5 >=10M-row stage — through
    [tile, F, 3] pieces; tiles scatter sequentially in ascending row
    order, so tiled == untiled bit-identical (tail rows pad into the
    dummy slot with +0 values).
    """
    F, n = binned_t.shape
    B = num_bins
    S = num_slots
    offsets = (jnp.arange(F, dtype=jnp.int32) * B)[None, :]
    if init is None:
        hist0 = jnp.zeros(((S + 1) * F * B, 3), dtype=jnp.float32)
    else:
        hist0 = jnp.concatenate(
            [init.transpose(0, 2, 3, 1).reshape(S * F * B, 3),
             jnp.zeros((F * B, 3), jnp.float32)])
    T = resolve_tile_rows(tile_rows, n)
    if T is None:
        binned = binned_t.T
        vals = _vals_t(grad, hess, weights).T              # [n, 3]
        flat = (slot[:, None].astype(jnp.int32) * (F * B)
                + binned.astype(jnp.int32) + offsets)      # [n, F]
        updates = jnp.broadcast_to(vals[:, None, :], (n, F, 3))
        hist = hist0.at[flat.reshape(-1)].add(updates.reshape(-1, 3))
        return hist.reshape(S + 1, F, B, 3)[:S].transpose(0, 3, 1, 2)
    nt = _pad_rows(n, T) // T
    n_pad = nt * T
    bt = jnp.pad(binned_t, ((0, 0), (0, n_pad - n)))
    vt = jnp.pad(_vals_t(grad, hess, weights), ((0, 0), (0, n_pad - n)))
    st = jnp.pad(slot.astype(jnp.int32), (0, n_pad - n), constant_values=S)

    def body(t, hist):
        b = lax.dynamic_slice(bt, (0, t * T), (F, T)).T    # [T, F]
        v = lax.dynamic_slice(vt, (0, t * T), (3, T)).T    # [T, 3]
        s = lax.dynamic_slice(st, (t * T,), (T,))
        flat = (s[:, None] * (F * B) + b.astype(jnp.int32) + offsets)
        upd = jnp.broadcast_to(v[:, None, :], (T, F, 3))
        return hist.at[flat.reshape(-1)].add(upd.reshape(-1, 3))

    hist = lax.fori_loop(0, nt, body, hist0)
    return hist.reshape(S + 1, F, B, 3)[:S].transpose(0, 3, 1, 2)


# one-time per-backend verdict of the table-matmul exactness probe:
# {backend_name: bool}.  Populated lazily by _table_matmul_verified.
_TABLE_MATMUL_PROBE: dict = {}


def _table_matmul_probe() -> bool:
    """Run the one-hot table matmul ON THE LIVE BACKEND and compare it
    bitwise against a host-side plain gather.

    The matmul path's exactness claim (one nonzero per one-hot row, so
    each output is a single f32 product that precision=HIGHEST must
    round-trip) is only TESTED on CPU (test_histogram.py monkeypatches
    on_accelerator); leaf values ride this kernel into train scores and
    predictions, so an accelerator where HIGHEST is not bit-exact would
    silently perturb every prediction (ADVICE.md round 5).  The probe
    covers both the single-block and the lax.scan-blocked variant (via a
    shrunken block size) and every table entry, with awkward magnitudes
    across the full NORMAL f32 range (tiny, huge, negatives, zeros).
    Subnormals are deliberately excluded: XLA's dot kernels flush them to
    zero on every backend (measured here even on CPU), and table entries
    — leaf values, per-leaf stat rows — are normal-range by construction,
    so failing the probe on an irrelevant domain would cost the MXU path
    for nothing.  Any mismatch demotes the backend to the plain gather,
    equivalent to LGBM_TPU_TABLE_MATMUL=0; an exception is a defect and
    propagates.
    """
    rng = np.random.RandomState(7)
    vals = np.concatenate([
        rng.standard_normal(40),
        10.0 ** rng.uniform(-37, 38, 20),
        -(10.0 ** rng.uniform(-37, 38, 20)),
        np.array([0.0, -0.0, 1.2e-38, -1.2e-38, np.float32(np.pi), 3e38]),
    ]).astype(np.float32)
    L = len(vals)
    idx = np.concatenate([np.arange(L), rng.randint(0, L, 4 * L)]) \
        .astype(np.int32)
    want = vals[idx]
    got1 = np.asarray(_take_matmul(jnp.asarray(vals), jnp.asarray(idx),
                                   leading=False))
    got2 = np.asarray(_take_matmul(jnp.asarray(vals), jnp.asarray(idx),
                                   leading=False, block=64))
    return np.array_equal(got1, want) and np.array_equal(got2, want)


def _table_matmul_verified() -> bool:
    """True iff the one-hot table matmul is bit-exact on this backend
    (probed once per backend name, at first accelerator use)."""
    backend = jax.default_backend()
    ok = _TABLE_MATMUL_PROBE.get(backend)
    if ok is None:
        # the first call usually comes from inside a jit trace, where
        # plain jnp calls would be staged into the caller's program:
        # force the probe to run eagerly, on concrete arrays
        with jax.ensure_compile_time_eval():
            ok = _table_matmul_probe()
        _TABLE_MATMUL_PROBE[backend] = ok
        if not ok:
            from ..utils.log import log_warning
            log_warning(
                f"take_from_table: one-hot matmul is NOT bit-exact on "
                f"backend {backend!r}; falling back to plain gather "
                "(equivalent to LGBM_TPU_TABLE_MATMUL=0)")
    return ok


def take_from_table(table: jax.Array, idx: jax.Array,
                    leading: bool = False) -> jax.Array:
    """``table[idx]`` for a SMALL table and a huge ``idx`` vector.

    On a TPU an [n]-sized gather from even a tiny table lowers to
    serialized-gather territory (a builder's r5 probe read ~130 ms at
    11M rows — older than the current code, not re-measured);
    reformulated as a one-hot matmul it rides the MXU instead.  The
    one-hot has exactly one nonzero per row, so each output is a single
    product — numerically EXACT in f32 under precision=HIGHEST (XLA's
    bf16x3 expansion round-trips f32 multiplicands exactly; there is no
    accumulation ordering to worry about).  That claim is VERIFIED on the
    live backend by a one-time probe at first use
    (``_table_matmul_verified``); a backend that fails it serves plain
    gathers instead of silently perturbing predictions.

    ``table`` may be [L] or [L, k]; returns idx.shape (+ [k]) in
    table.dtype — or, with ``leading=True`` (and a 2-D table), [k] +
    idx.shape: the component-leading layout that avoids the [n, k]
    lane-padding tax for huge idx (see LAYOUT DOCTRINE).  Falls back to a
    plain gather off-accelerator, when ``LGBM_TPU_TABLE_MATMUL=0``, or
    when the probe failed.
    """
    if (not on_accelerator()
            or os.environ.get("LGBM_TPU_TABLE_MATMUL") == "0"
            or not jnp.issubdtype(table.dtype, jnp.floating)
            or not _table_matmul_verified()):
        out = table[idx]
        if leading and table.ndim == 2:
            return jnp.moveaxis(out, -1, 0)
        return out
    return _take_matmul(table, idx, leading)


def _take_matmul(table: jax.Array, idx: jax.Array, leading: bool = False,
                 block: int = 65536) -> jax.Array:
    """The MXU one-hot formulation of ``take_from_table`` (no dispatch)."""
    L = table.shape[0]
    squeeze = table.ndim == 1
    t2 = (table[:, None] if squeeze else table).astype(jnp.float32)
    flat = idx.reshape(-1)
    n = flat.shape[0]
    iota_L = jnp.arange(L, dtype=flat.dtype)
    # blocked like histogram_matmul's body: a single [n, L] f32 one-hot
    # would materialize ~11 GB at the 11M-row x 255-leaf headline shape
    # (dot operands are not producer-fused) — exactly the lane-padded-HBM
    # class of failure this module's layout doctrine exists to avoid
    k = t2.shape[1]
    C = block
    if n <= C:
        # [k, L] @ [L, n] keeps every intermediate k-leading (minor dim n)
        oh = (iota_L[:, None] == flat[None, :]).astype(jnp.float32)
        out_t = lax.dot(t2.T, oh, precision=lax.Precision.HIGHEST)  # [k, n]
    else:
        nb = _pad_rows(n, C) // C
        fpad = jnp.pad(flat, (0, nb * C - n), constant_values=-1)

        def body(_, blk):
            oh = (iota_L[:, None] == blk[None, :]).astype(jnp.float32)
            return _, lax.dot(t2.T, oh,
                              precision=lax.Precision.HIGHEST)   # [k, C]

        _, chunks = lax.scan(body, None, fpad.reshape(nb, C))
        out_t = jnp.moveaxis(chunks, 1, 0).reshape(k, nb * C)[:, :n]
    out_t = out_t.astype(table.dtype)
    if squeeze:
        return out_t[0].reshape(idx.shape)
    if leading:
        return out_t.reshape((k,) + idx.shape)
    return out_t.T.reshape(idx.shape + (k,))


def pack_cols_u32(binned_t: jax.Array, grad: jax.Array, hess: jax.Array,
                  weights: jax.Array):
    """Fuse a u8 feature-major matrix and the (g, h, 1)*w value triple into
    ONE u32 word-matrix [Wb + 3, n] (minor dim n — unpadded).

    Motivation (a builder's r5 primitive probe, superseded and not
    re-measured): XLA gather cost on the TPU scales
    with gathered ELEMENT count — packing 4 bins per u32 word and fusing
    the three f32 value rows into the same record turns the arena's four
    gathers into one with ~3x fewer elements.  Words are built
    arithmetically (b0 | b1<<8 | ...) so no [.., 4]-minor bitcast
    intermediate ever exists.  Returns (words_t, Wb) with Wb = bin words.
    """
    F, n = binned_t.shape
    if binned_t.dtype != jnp.uint8:
        return None, 0          # u16 bins (max_bin > 256): no packing
    Wb = (F + 3) // 4
    pad = Wb * 4 - F
    bt = jnp.pad(binned_t, ((0, pad), (0, 0))) if pad else binned_t
    b32 = bt.astype(jnp.uint32).reshape(Wb, 4, n)
    bin_words = (b32[:, 0] | (b32[:, 1] << 8)
                 | (b32[:, 2] << 16) | (b32[:, 3] << 24))   # [Wb, n]
    vals_t = _vals_t(grad, hess, weights)                   # [3, n] f32
    val_words = lax.bitcast_convert_type(vals_t, jnp.uint32)
    return jnp.concatenate([bin_words, val_words], axis=0), Wb


def segment_histogram_sorted(
    binned_t: jax.Array,     # [F, n] uint8/16 feature-major
    grad: jax.Array,         # [n]
    hess: jax.Array,         # [n]
    weights: jax.Array,      # [n] f32 bagging/GOSS weights
    slot: jax.Array,         # [n] i32 in [0, num_slots]; num_slots = dropped
    num_slots: int,
    num_bins: int,
    block_rows: int = 1024,
    f32_vals: bool = False,
    caps: Optional[list] = None,   # static descending arena capacities
    packed: Optional[tuple] = None,   # (words_t [Wb+3, n] u32, Wb) from
                                      # pack_cols_u32 — hoisted per tree
    tile_rows: Optional[int] = None,
) -> jax.Array:
    """TPU-native segment histogram: sort-by-slot + block-aligned matmuls.

    The scatter formulation (``segment_histogram``) serializes on TPU and
    materializes an [n*F, 3] update buffer that XLA lane-pads to 128 (157 GB
    at HIGGS scale) — so here the problem is reshaped for the MXU instead:

      1. sort row ids by slot via ONE u32 combined key
         ``slot << 24 | row_id`` (stable by construction; falls back to a
         two-array stable sort when n >= 2^24);
      2. per-slot counts/starts come free from the sorted keys via
         ``searchsorted`` (a scatter-free bincount);
      3. lay the sorted rows into a block-aligned arena where every slot's
         segment starts on a ``block_rows`` boundary — so each C-row block
         belongs to exactly ONE slot.  The destination->source map is
         elementwise (no inverse permutation / scatter needed): destination
         q in block j holds the (q - C*blk_start[s])-th sorted row of slot
         s = blk_slot[j].  The arena size is the ladder's smallest static
         capacity that fits the slotted-row count (``lax.switch`` over
         ``caps``), so the gather+matmul cost tracks the live frontier,
         not n.  All gathers run in the TRANSPOSED layout ([W, n] ->
         [W, arena]: minor dim = arena, unpadded);
      4. one-hot matmul per block ([3, C] @ [C, F*B], the histogram_matmul
         body) producing per-block partials;
      5. reduce partials into slots with a tiny [S, NB] one-hot matmul
         (blocks of a slot are contiguous by construction).

    Every step is a gather, sort, or matmul — nothing scatters.  Returns
    [S, 3, F, B] f32.  reference analogue: ordered-gradient per-leaf
    histograms (src/io/dataset.cpp:1318-1333) built from a DataPartition
    that keeps leaves contiguous (src/treelearner/data_partition.hpp).

    Accumulation-order pin (tiling discipline): per-block partials fold
    into their slot INSIDE the block scan, in ascending block order — the
    same order whether the arena records were gathered up front (untiled:
    one big [W, cap] gather, fastest dispatch when it fits HBM) or
    per block inside the loop (``tile_rows`` set: O(block) transients, no
    whole-arena record materialization — the planner's O(tile) mode).
    Both modes therefore produce BIT-IDENTICAL histograms; the sort
    (n u32 words) is the only O(n) device state either way.

    DELIBERATE f32 reassociation vs the pre-tiling code: the old fold
    was one HIGHEST-precision ``slot_onehot @ parts`` dot; pinning the
    in-scan order (required for tiled == untiled parity) reassociates
    the per-slot f32 sums, so multi-block slots can differ from the
    previous release in the last bit.  Same class of difference as the
    reference's CPU-vs-GPU histograms (module docstring of
    grower_rounds.py); the int kernel's fold is associative and exact
    either way.  CPU defaults (scatter) and the golden guard are
    untouched — this kernel only runs on accelerators or when
    LGBM_TPU_SEGHIST=sorted forces it.
    """
    F, n = binned_t.shape
    B = num_bins
    S = num_slots
    if caps is None:
        caps = [n]

    if n < (1 << 24) and num_slots < 256:
        # single-array sort: the combined UNSIGNED key carries the payload
        # (u32 so slot values up to 255 — including the dummy num_slots —
        # never touch the sign bit; an i32 key would wrap for slot >= 128
        # and silently drop those slots' mass)
        key = ((slot.astype(jnp.uint32) << 24)
               | jnp.arange(n, dtype=jnp.uint32))
        skey = lax.sort(key)
        sorted_slot = (skey >> 24).astype(jnp.int32)
        order = (skey & jnp.uint32(0x00FFFFFF)).astype(jnp.int32)
    else:
        row_ids = jnp.arange(n, dtype=jnp.int32)
        sorted_slot, order = lax.sort((slot, row_ids), is_stable=True,
                                      num_keys=1)
    # counts without a scatter: positions of slot boundaries in sorted keys
    bounds = jnp.searchsorted(sorted_slot,
                              jnp.arange(S + 1, dtype=sorted_slot.dtype))
    row_start = bounds[:S].astype(jnp.int32)
    counts = (bounds[1:] - bounds[:S]).astype(jnp.int32)

    iota = jnp.arange(B, dtype=binned_t.dtype)
    acc_t = jnp.float32 if f32_vals else jnp.bfloat16
    prec = lax.Precision.HIGHEST if f32_vals else lax.Precision.DEFAULT

    def arena(cap: int):
        """Histogram over a cap-row block-aligned arena.

        The block size shrinks with the capacity rung so the worst-case
        per-slot padding (S partial blocks) stays a small multiple of the
        live rows instead of a fixed S*block_rows floor."""
        C = max(128, min(block_rows,
                         1 << max(0, (max(cap, 1) // (4 * max(S, 1))
                                      ).bit_length() - 1)))
        NB = _pad_rows(max(cap, 1), C) // C + S     # every slot may pad

        def run():
            nblk = (counts + C - 1) // C            # blocks per slot
            blk_end = jnp.cumsum(nblk)
            blk_start = (blk_end - nblk).astype(jnp.int32)
            # block j -> slot: first slot whose block range extends past j
            j_idx = jnp.arange(NB, dtype=blk_end.dtype)
            blk_slot = jnp.searchsorted(blk_end, j_idx,
                                        side="right").astype(jnp.int32)
            blk_slot = jnp.minimum(blk_slot, S)     # beyond last: dummy

            # destination -> source (elementwise over the arena)
            q = jnp.arange(NB * C, dtype=jnp.int32)
            s_of = blk_slot[q // C]
            s_c = jnp.minimum(s_of, S - 1)
            o = q - blk_start[s_c] * C
            valid = (s_of < S) & (o < counts[s_c])
            src_sorted = jnp.minimum(row_start[s_c] + o, n - 1)
            src = order[src_sorted]

            def block_partial(rows, vals):
                """Shared per-block one-hot matmul: [F, C] bins x [3, C]
                vals -> [3, F*B] partial (both gather branches feed this
                one body so dtype/precision tweaks can never diverge)."""
                onehot2d = (rows.T[:, :, None] == iota.astype(rows.dtype)
                            ).astype(acc_t).reshape(C, F * B)
                return lax.dot(vals.astype(acc_t), onehot2d,
                               precision=prec,
                               preferred_element_type=jnp.float32)

            use_packed = packed is not None and packed[0] is not None

            def part_from_packed(blk_rec, vm):
                """[Wb+3, C] u32 fused record block -> [3, F*B] partial."""
                Wb = packed[1]
                bw = blk_rec[:Wb]                       # [Wb, C] u32
                rows = jnp.concatenate(
                    [((bw >> (8 * j)) & 0xFF) for j in range(4)],
                    axis=0).reshape(4, Wb, C).transpose(
                        1, 0, 2).reshape(Wb * 4, C)[:F]   # [F, C]
                vals = lax.bitcast_convert_type(blk_rec[Wb:], jnp.float32)
                vals = jnp.where(vm, vals, 0.0)         # [3, C]
                return block_partial(rows.astype(jnp.int32), vals)

            def part_from_raw(cols, g, h, w, vm):
                vt = (jnp.stack([g, h, jnp.ones_like(g)])
                      * jnp.where(vm, w, 0.0)[None, :])
                return block_partial(cols, vt)

            # the block -> slot fold happens INSIDE the scan (ascending
            # block order, one shared f32 accumulator): the pinned order
            # that makes the hoisted and in-loop gather modes — and hence
            # tiled vs untiled — bit-identical
            acc0 = jnp.zeros((S + 1, 3 * F * B), jnp.float32)
            j_arange = jnp.arange(NB, dtype=jnp.int32)

            if resolve_tile_rows(tile_rows, n) is None:
                # untiled: ONE whole-arena gather up front (fastest
                # dispatch; O(cap) transient the planner must afford)
                if use_packed:
                    words_t, Wb = packed
                    rec = jnp.take(words_t, src, axis=1)  # [Wb+3, NBC] u32
                    recb = rec.reshape(Wb + 3, NB, C).transpose(1, 0, 2)
                    vmask = valid.reshape(NB, 1, C)

                    def body(acc, xs):
                        j, blk_rec, vm = xs
                        return acc.at[blk_slot[j]].add(
                            part_from_packed(blk_rec, vm).reshape(-1)), None

                    acc, _ = lax.scan(body, acc0, (j_arange, recb, vmask))
                else:
                    cols = jnp.take(binned_t, src, axis=1)  # [F, NBC]
                    w = jnp.take(weights, src)
                    g = jnp.take(grad, src)
                    h = jnp.take(hess, src)
                    colsb = cols.reshape(F, NB, C).transpose(1, 0, 2)
                    gb = g.reshape(NB, C)
                    hb = h.reshape(NB, C)
                    wb = w.reshape(NB, C)
                    vmask = valid.reshape(NB, C)

                    def body(acc, xs):
                        j, b, gg, hh, ww, vm = xs
                        return acc.at[blk_slot[j]].add(
                            part_from_raw(b, gg, hh, ww, vm).reshape(-1)), \
                            None

                    acc, _ = lax.scan(body, acc0,
                                      (j_arange, colsb, gb, hb, wb, vmask))
            else:
                # tiled: records are gathered/assembled PER BLOCK inside
                # the loop — no whole-arena (or whole-dataset) record
                # materialization; peak transient is O(block)
                def body(acc, j):
                    sb = lax.dynamic_slice(src, (j * C,), (C,))
                    vm = lax.dynamic_slice(valid, (j * C,), (C,))
                    if use_packed:
                        rec = jnp.take(packed[0], sb, axis=1)  # [Wb+3, C]
                        part = part_from_packed(rec, vm[None, :])
                    else:
                        cols = jnp.take(binned_t, sb, axis=1)  # [F, C]
                        part = part_from_raw(cols, jnp.take(grad, sb),
                                             jnp.take(hess, sb),
                                             jnp.take(weights, sb), vm)
                    return acc.at[blk_slot[j]].add(part.reshape(-1)), None

                acc, _ = lax.scan(body, acc0, j_arange)
            return acc[:S].reshape(S, 3, F, B)
        return run

    if len(caps) == 1:
        return arena(caps[0])()
    total = bounds[S].astype(jnp.int32)             # slotted-row count
    caps_arr = jnp.asarray(caps, jnp.int32)
    bucket = jnp.sum(caps_arr >= total) - 1
    return lax.switch(bucket, [arena(c) for c in caps])


_SMALL_ROUND_SLOTS = 4
# slot-expanded LHS rows: 3 * 42 = 126 <= the MXU's 128-row tile, so a
# 42-slot segment histogram costs the SAME matmul cycles as a 1-slot one
_EXPAND_SLOTS = 42


def segment_histogram_expanded(
    binned_t: jax.Array,     # [F, n] feature-major
    grad: jax.Array,
    hess: jax.Array,
    weights: jax.Array,      # [n] f32
    slot: jax.Array,         # [n] i32; values >= live_cap contribute nothing
    num_bins: int,
    live_cap: int = _EXPAND_SLOTS,
    block_rows: int = _DEFAULT_BLOCK_ROWS,
    f32_vals: bool = False,
    tile_rows: Optional[int] = None,
) -> jax.Array:
    """Histograms of slots [0, live_cap) in ONE streamed full-matrix pass.

    The plain histogram matmul uses M=3 of the MXU's 128 output rows
    (grad/hess/count); expanding the LHS to ``[3*live_cap, C]`` — row
    (j*live_cap + s) carrying ``vals[j] * (slot == s)`` — fills the tile and
    computes every live slot's histogram in the SAME pass: no sort, no
    gather, no arena.  One systolic tile (3*live_cap <= 128) costs the
    same cycles as M=3, so this replaces the sorted arena for every
    round with <= ``live_cap`` candidates — i.e. all but the widest
    rounds of a 255-leaf tree (reference equivalent: one
    ConstructHistograms call per leaf, serial_tree_learner.cpp:380-388;
    here a frontier per PASS).  Returns [live_cap, 3, F, B] f32.
    """
    F, n = binned_t.shape
    B = num_bins
    SE = live_cap
    block_rows = _tile_block(block_rows, resolve_tile_rows(tile_rows, n))
    nb = max(1, _pad_rows(n, block_rows) // block_rows)
    n_pad = nb * block_rows
    vals_t = _vals_t(grad, hess, weights)
    slot_i = slot.astype(jnp.int32)
    if n_pad != n:
        binned_t = jnp.pad(binned_t, ((0, 0), (0, n_pad - n)))
        vals_t = jnp.pad(vals_t, ((0, 0), (0, n_pad - n)))
        slot_i = jnp.pad(slot_i, (0, n_pad - n), constant_values=SE)
    iota_b = jnp.arange(B, dtype=binned_t.dtype)
    iota_s = jnp.arange(SE, dtype=jnp.int32)
    C = block_rows
    acc_t = jnp.float32 if f32_vals else jnp.bfloat16
    prec = lax.Precision.HIGHEST if f32_vals else lax.Precision.DEFAULT

    def body(acc, i):
        b = lax.dynamic_slice(binned_t, (0, i * C), (F, C))   # [F, C]
        v = lax.dynamic_slice(vals_t, (0, i * C), (3, C))     # [3, C]
        sl = lax.dynamic_slice(slot_i, (i * C,), (C,))        # [C]
        oh_s = (sl[None, :] == iota_s[:, None]).astype(acc_t)   # [SE, C]
        lhs = (v.astype(acc_t)[:, None, :] * oh_s[None, :, :]
               ).reshape(3 * SE, C)
        onehot2d = (b.T[:, :, None] == iota_b).astype(acc_t).reshape(
            C, F * B)
        part = lax.dot(lhs, onehot2d, precision=prec,
                       preferred_element_type=jnp.float32)
        return acc + part, None

    init = jnp.zeros((3 * SE, F * B), dtype=jnp.float32)
    acc, _ = lax.scan(body, init, jnp.arange(nb, dtype=jnp.int32))
    return acc.reshape(3, SE, F, B).transpose(1, 0, 2, 3)


def compacted_segment_histogram(
    binned_t: jax.Array,     # [F, n] feature-major
    grad: jax.Array,
    hess: jax.Array,
    weights: jax.Array,      # [n] f32
    slot: jax.Array,         # [n] i32 in [0, num_slots]; num_slots = dropped
    num_slots: int,
    num_bins: int,
    caps: list,              # static descending capacities
    f32_vals: bool = False,
    num_live: Optional[jax.Array] = None,   # traced count of live slots
    packed: Optional[tuple] = None,         # pack_cols_u32 output, hoisted
    tile_rows: Optional[int] = None,
) -> jax.Array:
    """Segment histogram over only the rows with a real slot, with the
    work bounded by the smallest static capacity that fits (see
    ``compacted_histogram``).  Returns [S, 3, F, B] f32.

    Backend dispatch: sorted block-matmul arena on accelerators (the
    scatter formulation both OOMs — its [n*F, 3] update buffer lane-pads
    to 128 — and serializes there); XLA scatter with nonzero-compaction
    on CPU (measured fastest there in every earlier round).
    When ``num_live`` (the round's live-slot count) is given and at most
    ``_EXPAND_SLOTS``, accelerators take ONE slot-expanded full-matrix
    pass instead (``segment_histogram_expanded``): a streamed matmul
    pass costs ~17 ms at 11M rows vs ~90 ms for sort+gather+arena
    (same r5 probe), and the expanded LHS computes up to 42 slots
    for the cycles of one.  ``LGBM_TPU_SEGHIST=sorted|scatter``
    overrides (testing hook).
    """
    F, n = binned_t.shape
    if use_sorted_seghist():
        # zero-weight rows are dropped by reslotting (cheaper than compact)
        slot_w = jnp.where(weights > 0, slot, num_slots)

        def arena_path(_):
            return segment_histogram_sorted(
                binned_t, grad, hess, weights, slot_w, num_slots, num_bins,
                f32_vals=f32_vals, caps=caps, packed=packed,
                tile_rows=tile_rows)

        # LGBM_TPU_SMALL_ROUNDS=0 drops the expanded-pass branch (and its
        # lax.cond program duplication) — compile-cost bisect hook
        small_enabled = os.environ.get("LGBM_TPU_SMALL_ROUNDS") != "0"
        if num_live is None or num_slots <= _SMALL_ROUND_SLOTS \
                or not small_enabled:
            return arena_path(None)
        se = min(_EXPAND_SLOTS, num_slots)

        def expanded_path(_):
            hist = segment_histogram_expanded(
                binned_t, grad, hess, weights, slot_w, num_bins,
                live_cap=se, f32_vals=f32_vals, tile_rows=tile_rows)
            if num_slots > se:
                hist = jnp.concatenate(
                    [hist, jnp.zeros((num_slots - se, 3, F, num_bins),
                                     jnp.float32)], axis=0)
            return hist

        return lax.cond(num_live <= se, expanded_path, arena_path, None)

    member = (slot < num_slots) & (weights > 0)
    count = jnp.sum(member)

    def branch(cap: int):
        def run():
            idx = jnp.nonzero(member, size=cap, fill_value=n)[0]
            valid = idx < n
            idxc = jnp.minimum(idx, n - 1)
            cols = jnp.take(binned_t, idxc, axis=1)
            w = jnp.where(valid, jnp.take(weights, idxc), 0.0)
            g = jnp.take(grad, idxc)
            h = jnp.take(hess, idxc)
            s = jnp.where(valid, jnp.take(slot, idxc), num_slots)
            return segment_histogram(cols, g, h, w, s, num_slots, num_bins,
                                     tile_rows=tile_rows)
        return run

    if len(caps) == 1:
        return segment_histogram(binned_t, grad, hess, weights,
                                 jnp.where(member, slot, num_slots),
                                 num_slots, num_bins, tile_rows=tile_rows)
    caps_arr = jnp.asarray(caps, jnp.int32)
    bucket = jnp.sum(caps_arr >= count) - 1
    return lax.switch(bucket, [branch(c) for c in caps])


def subtract_histogram(parent: jax.Array, child: jax.Array) -> jax.Array:
    """The subtraction trick: sibling = parent - child.

    reference: FeatureHistogram::Subtract (feature_histogram.hpp:79-84).

    Works unchanged on the quantized integer histograms below — and there
    it is EXACT: int32 subtraction has no rounding, so the sibling
    histogram carries no accumulated float error (the quantized-training
    selling point the reference's gradient_discretizer.hpp exploits).
    """
    return parent - child


# ======================================================================
# Quantized-gradient integer histogram family (use_quantized_grad)
#
# LightGBM 4.x lineage (src/treelearner/gradient_discretizer.{hpp,cpp}):
# per-round discretization of grad/hess to a few signed integer levels
# with stochastic rounding, integer histogram accumulation, and split
# gains computed from the integer sums rescaled in high precision.  On
# this backend the wins compound:
#
# - the one-hot matmul runs int8 x int8 -> int32 on the MXU
#   (``preferred_element_type=int32``), halving the one-hot operand
#   bytes vs bf16 and producing EXACT integer sums — no
#   accumulation-order nondeterminism, so parent - child subtraction
#   (``subtract_histogram``) is exact;
# - histograms shrink to TWO channels ([2, F, B] i32: grad, hess) —
#   per-bin COUNTS are estimated from the hessian channel at split time
#   exactly like the reference's main path
#   (``Common::RoundInt(sum_hess * cnt_factor)``,
#   feature_histogram.hpp:813), which is what lets the data-parallel
#   psum payload drop from 12 bytes/cell (3 x f32) to 8 (2 x i32), and
#   to 4 (2 x i16) when the static row x level bound allows
#   (``psum_quant_hist``);
# - per-row values ride as int8 [2, n] blocks (LAYOUT DOCTRINE: tiny
#   component axis leading, minor dim n unpadded).
#
# Accumulator width: per-cell |sum| <= n * level_bound; with
# num_grad_quant_bins <= 64 (config-validated) that stays inside int32
# up to ~34M rows — above every shape this repo targets (11M HIGGS).
# ======================================================================


def quant_levels(num_bins: int):
    """(grad level bound, hess level bound) for ``num_grad_quant_bins``.

    reference: gradient_discretizer.cpp — gradients take signed levels in
    [-bins/2 + 1, bins/2 - 1], hessians (non-negative) [0, bins - 1]."""
    return max(num_bins // 2 - 1, 1), max(num_bins - 1, 1)


def quantize_gradients(grad: jax.Array, hess: jax.Array, weights: jax.Array,
                       num_bins: int, key: jax.Array,
                       stochastic: bool = True,
                       axis_name: Optional[str] = None):
    """Discretize one class's grad/hess to signed integer levels.

    Bagging/GOSS weights are FOLDED INTO the values before discretization
    (the reference amplifies sampled gradients before discretizing,
    goss.hpp:94-98 + gradient_discretizer); the histogram mask is then
    binary membership, which is what keeps the histogram updates integer.
    Scales are the per-round max-abs over the GLOBAL rows (``lax.pmax``
    under data sharding) divided by the level bound; stochastic rounding
    is ``floor(x + u)`` (unbiased), round-to-nearest otherwise.

    Returns ``(gq int8 [n], hq int8 [n], g_scale f32, h_scale f32)`` with
    ``value ~= q * scale``.  Zero-weight rows quantize to exactly 0.
    """
    qg, qh = quant_levels(num_bins)
    gw = grad * weights
    hw = hess * weights
    gmax = jnp.max(jnp.abs(gw))
    hmax = jnp.max(jnp.abs(hw))
    if axis_name is not None:
        # pmax is exact under any association, so one fused collective
        # serves flat AND hierarchical meshes (tuple axis names OK)
        from ..parallel.collectives import pmax_tiered
        gmax = pmax_tiered(gmax, axis_name)
        hmax = pmax_tiered(hmax, axis_name)
    g_scale = (jnp.maximum(gmax, 1e-30) / qg).astype(jnp.float32)
    h_scale = (jnp.maximum(hmax, 1e-30) / qh).astype(jnp.float32)
    if stochastic:
        u = jax.random.uniform(key, (2,) + gw.shape)
        gq = jnp.floor(gw / g_scale + u[0])
        hq = jnp.floor(hw / h_scale + u[1])
    else:
        gq = jnp.round(gw / g_scale)
        hq = jnp.round(hw / h_scale)
    gq = jnp.clip(gq, -qg, qg).astype(jnp.int8)
    hq = jnp.clip(hq, 0, qh).astype(jnp.int8)
    return gq, hq, g_scale, h_scale


def quant_psum_narrow(rows_global: int, num_bins: int) -> bool:
    """True when the STATIC bound rows * hess_levels fits int16, so the
    cross-device histogram psum can ride a half-width payload.  The bound
    covers every partial AND the global sum, so no reduction order can
    overflow.  This is the "payload shrinks with the quantization width"
    lever: fewer levels => smaller bound => narrower psum."""
    _, qh = quant_levels(num_bins)
    return rows_global * qh < (1 << 15)


def psum_quant_hist(hist: jax.Array, axis_name,
                    rows_global: int, num_bins: int,
                    hierarchical: bool = False) -> jax.Array:
    """psum an integer histogram across the data axis (a single mesh axis
    or the hybrid ``("dcn", "ici")`` tuple), narrowed to int16 when
    ``quant_psum_narrow`` proves it safe.  ``hierarchical`` reduces the
    fast tier first (parallel/collectives.py); the narrowing bound covers
    every partial sum, so each stage rides the same narrowed payload.
    The ICI payload is 2 channels x {2,4} bytes vs the f32 path's 3 x 4
    (``hist_payload_bytes`` is the accounting twin)."""
    if axis_name is None:
        return hist
    from ..parallel.collectives import psum_int_tiered
    narrow = jnp.int16 if quant_psum_narrow(rows_global, num_bins) else None
    return psum_int_tiered(hist, axis_name, hierarchical=hierarchical,
                           narrow=narrow)


def hist_payload_bytes(num_features: int, num_bins: int,
                       rows_global: int = 0,
                       quant_bins: Optional[int] = None) -> int:
    """Per-psum histogram payload bytes for one [*, F, B] histogram.

    ``quant_bins=None`` = the f32 pipeline (3 channels x f32); otherwise
    the integer pipeline (2 channels, int16 when the static bound
    narrows, else int32).  Pure accounting — shared by the planner,
    the telemetry gauges and tests so the claimed payload can never
    drift from the psum'd dtypes."""
    if quant_bins is None:
        return 3 * num_features * num_bins * 4
    item = 2 if quant_psum_narrow(rows_global, quant_bins) else 4
    return 2 * num_features * num_bins * item


def _vals_t_int(gq, hq, member):
    """[2, n] int8 value block (g, h) * member — the integer twin of
    ``_vals_t`` (no count row: counts are hessian-estimated at split
    time, reference feature_histogram.hpp:813 cnt_factor)."""
    return jnp.stack([gq, hq]) * member.astype(jnp.int8)


def histogram_matmul_int(
    binned_t: jax.Array,   # [F, n] uint8/uint16 feature-major
    vals_t: jax.Array,     # [2, n] int8 (g, h) * member
    num_bins: int,
    block_rows: int = _DEFAULT_BLOCK_ROWS,
    tile_rows: Optional[int] = None,
) -> jax.Array:
    """Integer histogram via int8 one-hot matmul. Returns [2, F, B] i32.

    The MXU's s8 x s8 -> s32 path: one-hot operands are int8 (half the
    bytes of the bf16 f32-path one-hot) and accumulation is exact int32
    (``preferred_element_type``), so there is no bf16 mantissa loss and
    no accumulation-order wobble to re-verify per backend.  ``tile_rows``
    caps the streaming block — int32 accumulation is associative, so
    EVERY tile size is exactly equal to untiled."""
    F, n = binned_t.shape
    B = num_bins
    block_rows = _tile_block(block_rows, resolve_tile_rows(tile_rows, n))
    nb = max(1, _pad_rows(n, block_rows) // block_rows)
    n_pad = nb * block_rows
    if n_pad != n:
        binned_t = jnp.pad(binned_t, ((0, 0), (0, n_pad - n)))
        vals_t = jnp.pad(vals_t, ((0, 0), (0, n_pad - n)))
    iota = jnp.arange(B, dtype=binned_t.dtype)
    C = block_rows

    def body(acc, i):
        b = lax.dynamic_slice(binned_t, (0, i * C), (F, C))   # [F, C]
        v = lax.dynamic_slice(vals_t, (0, i * C), (2, C))     # [2, C]
        onehot2d = (b.T[:, :, None] == iota).astype(jnp.int8).reshape(
            C, F * B)
        part = lax.dot(v, onehot2d, preferred_element_type=jnp.int32)
        return acc + part, None

    init = jnp.zeros((2, F * B), dtype=jnp.int32)
    acc, _ = lax.scan(body, init, jnp.arange(nb, dtype=jnp.int32))
    return acc.reshape(2, F, B)


def _pack_modulus(n: int, levels) -> int:
    """Static modulus for the packed-scatter trick, or 0 when unsafe.

    Per-bin field bounds: hess sum in [0, n*qh], grad sum in
    [-n*qg, n*qg].  Packing word = g * M + h with M > n*qh keeps the two
    sums separable after accumulation (h never borrows into g because it
    is non-negative and < M); the whole packed value must stay inside
    int32."""
    if levels is None:
        return 0
    qg, qh = levels
    bound_h = n * qh
    M = 1
    while M <= bound_h:
        M <<= 1
    if n * qg * M + M < (1 << 31):
        return M
    return 0


def histogram_scatter_int(
    binned_t: jax.Array, vals_t: jax.Array, num_bins: int,
    levels: Optional[tuple] = None,
    tile_rows: Optional[int] = None,
) -> jax.Array:
    """Integer scatter-add histogram (CPU semantics path) — [2, F, B] i32.

    When the static bound allows, the two channels are PACKED into one
    i32 word per row (``g * M + h``), halving the scatter update traffic;
    the fields are split back apart arithmetically after accumulation.
    ``tile_rows`` streams the update buffer in [tile, F] pieces
    (exact under any tiling: int32 adds are associative)."""
    F, n = binned_t.shape
    B = num_bins
    offsets = (jnp.arange(F, dtype=jnp.int32) * B)[None, :]
    M = _pack_modulus(n, levels)
    T = resolve_tile_rows(tile_rows, n)
    if T is not None:
        nt = _pad_rows(n, T) // T
        n_pad = nt * T
        bt = jnp.pad(binned_t, ((0, 0), (0, n_pad - n)))
        vt = jnp.pad(vals_t, ((0, 0), (0, n_pad - n)))
        if M:
            word_all = (vt[0].astype(jnp.int32) * M
                        + vt[1].astype(jnp.int32))         # [n_pad]

            def body(t, hist):
                b = lax.dynamic_slice(bt, (0, t * T), (F, T)).T  # [T, F]
                wd = lax.dynamic_slice(word_all, (t * T,), (T,))
                flat = b.astype(jnp.int32) + offsets
                return hist.at[flat.reshape(-1)].add(
                    jnp.broadcast_to(wd[:, None], (T, F)).reshape(-1))

            hist = lax.fori_loop(0, nt, body, jnp.zeros((F * B,), jnp.int32))
            h = jnp.mod(hist, M)
            g = (hist - h) // M
            return jnp.stack([g, h]).reshape(2, F, B)

        def body(t, hist):
            b = lax.dynamic_slice(bt, (0, t * T), (F, T)).T      # [T, F]
            v = lax.dynamic_slice(vt, (0, t * T), (2, T)).T.astype(jnp.int32)
            flat = b.astype(jnp.int32) + offsets
            upd = jnp.broadcast_to(v[:, None, :], (T, F, 2))
            return hist.at[flat.reshape(-1)].add(upd.reshape(-1, 2))

        hist = lax.fori_loop(0, nt, body, jnp.zeros((F * B, 2), jnp.int32))
        return hist.reshape(F, B, 2).transpose(2, 0, 1)
    binned = binned_t.T                                    # [n, F]
    flat_idx = binned.astype(jnp.int32) + offsets          # [n, F]
    if M:
        word = (vals_t[0].astype(jnp.int32) * M
                + vals_t[1].astype(jnp.int32))             # [n]
        hist = jnp.zeros((F * B,), jnp.int32)
        hist = hist.at[flat_idx.reshape(-1)].add(
            jnp.broadcast_to(word[:, None], (n, F)).reshape(-1))
        h = jnp.mod(hist, M)
        g = (hist - h) // M
        return jnp.stack([g, h]).reshape(2, F, B)
    vals = vals_t.T.astype(jnp.int32)                      # [n, 2]
    hist = jnp.zeros((F * B, 2), jnp.int32)
    updates = jnp.broadcast_to(vals[:, None, :], (n, F, 2))
    hist = hist.at[flat_idx.reshape(-1)].add(updates.reshape(-1, 2))
    return hist.reshape(F, B, 2).transpose(2, 0, 1)


def build_histogram_int(
    binned_t: jax.Array,   # [F, n] feature-major
    gq: jax.Array,         # [n] int8 quantized grad (weights folded)
    hq: jax.Array,         # [n] int8 quantized hess
    member: jax.Array,     # [n] bool leaf membership
    num_bins: int,
    method: str = "auto",
    block_rows: int = _DEFAULT_BLOCK_ROWS,
    levels: Optional[tuple] = None,
    tile_rows: Optional[int] = None,
) -> jax.Array:
    """Masked integer histogram [2, F, B] i32 = per-bin (sum gq, sum hq)
    over ``member`` rows — the quantized twin of ``build_histogram``,
    dispatched through the same ``resolve_hist_method`` seam."""
    vals_t = _vals_t_int(gq, hq, member)
    method = resolve_hist_method("auto" if method == "fused" else method,
                                 quantized=True)
    if method == "matmul_int8":
        return histogram_matmul_int(binned_t, vals_t, num_bins, block_rows,
                                    tile_rows=tile_rows)
    if method == "scatter_int":
        return histogram_scatter_int(binned_t, vals_t, num_bins, levels,
                                     tile_rows=tile_rows)
    raise ValueError(f"unknown quantized histogram method {method!r}")


def compacted_histogram_int(
    binned_t: jax.Array, gq: jax.Array, hq: jax.Array,
    weights: jax.Array,    # [n] f32 bagging/GOSS weights (0 = excluded)
    member: jax.Array,     # [n] bool leaf membership
    num_bins: int,
    caps: list,
    method: str = "auto",
    levels: Optional[tuple] = None,
    tile_rows: Optional[int] = None,
) -> jax.Array:
    """Integer twin of ``compacted_histogram``: gather the member rows
    into the smallest static capacity that fits, then run the integer
    kernel over ``cap`` rows instead of n."""
    F, n = binned_t.shape
    member = member & (weights > 0)
    count = jnp.sum(member)

    def branch(cap: int):
        def run():
            idx = jnp.nonzero(member, size=cap, fill_value=n)[0]
            valid = idx < n
            idxc = jnp.minimum(idx, n - 1)
            cols = jnp.take(binned_t, idxc, axis=1)        # [F, cap]
            g = jnp.take(gq, idxc)
            h = jnp.take(hq, idxc)
            return build_histogram_int(cols, g, h, valid, num_bins,
                                       method=method, levels=levels,
                                       tile_rows=tile_rows)
        return run

    if len(caps) == 1:
        return build_histogram_int(binned_t, gq, hq, member, num_bins,
                                   method=method, levels=levels,
                                   tile_rows=tile_rows)
    caps_arr = jnp.asarray(caps, jnp.int32)
    bucket = jnp.sum(caps_arr >= count) - 1
    return lax.switch(bucket, [branch(c) for c in caps])


def segment_histogram_int(
    binned_t: jax.Array, gq: jax.Array, hq: jax.Array,
    member: jax.Array,     # [n] bool; non-members land in the dummy slot
    slot: jax.Array,       # [n] i32 in [0, num_slots]
    num_slots: int,
    num_bins: int,
    levels: Optional[tuple] = None,
    tile_rows: Optional[int] = None,
) -> jax.Array:
    """Per-slot integer histogram [S, 2, F, B] i32 (scatter formulation,
    CPU semantics path) — the quantized twin of ``segment_histogram``,
    with the same packed-word shrink as ``histogram_scatter_int`` and the
    same [tile, F] update-buffer streaming under ``tile_rows`` (exact:
    integer adds are associative)."""
    F, n = binned_t.shape
    B = num_bins
    S = num_slots
    slot_m = jnp.where(member, slot.astype(jnp.int32), S)
    offsets = (jnp.arange(F, dtype=jnp.int32) * B)[None, :]
    M = _pack_modulus(n, levels)
    T = resolve_tile_rows(tile_rows, n)
    if T is not None:
        nt = _pad_rows(n, T) // T
        n_pad = nt * T
        bt = jnp.pad(binned_t, ((0, 0), (0, n_pad - n)))
        st = jnp.pad(slot_m, (0, n_pad - n), constant_values=S)
        if M:
            word_all = jnp.pad(
                (gq.astype(jnp.int32) * M + hq.astype(jnp.int32))
                * member.astype(jnp.int32), (0, n_pad - n))

            def body(t, hist):
                b = lax.dynamic_slice(bt, (0, t * T), (F, T)).T  # [T, F]
                s = lax.dynamic_slice(st, (t * T,), (T,))
                wd = lax.dynamic_slice(word_all, (t * T,), (T,))
                flat = (s[:, None] * (F * B) + b.astype(jnp.int32)
                        + offsets)
                return hist.at[flat.reshape(-1)].add(
                    jnp.broadcast_to(wd[:, None], (T, F)).reshape(-1))

            hist = lax.fori_loop(0, nt, body,
                                 jnp.zeros(((S + 1) * F * B,), jnp.int32))
            h = jnp.mod(hist, M)
            g = (hist - h) // M
            return jnp.stack([g, h]).reshape(2, S + 1, F, B).transpose(
                1, 0, 2, 3)[:S]
        vt = jnp.pad(_vals_t_int(gq, hq, member), ((0, 0), (0, n_pad - n)))

        def body(t, hist):
            b = lax.dynamic_slice(bt, (0, t * T), (F, T)).T      # [T, F]
            s = lax.dynamic_slice(st, (t * T,), (T,))
            v = lax.dynamic_slice(vt, (0, t * T), (2, T)).T.astype(jnp.int32)
            flat = s[:, None] * (F * B) + b.astype(jnp.int32) + offsets
            upd = jnp.broadcast_to(v[:, None, :], (T, F, 2))
            return hist.at[flat.reshape(-1)].add(upd.reshape(-1, 2))

        hist = lax.fori_loop(0, nt, body,
                             jnp.zeros(((S + 1) * F * B, 2), jnp.int32))
        return hist.reshape(S + 1, F, B, 2)[:S].transpose(0, 3, 1, 2)
    binned = binned_t.T
    flat = (slot_m[:, None] * (F * B)
            + binned.astype(jnp.int32) + offsets)          # [n, F]
    if M:
        word = (gq.astype(jnp.int32) * M + hq.astype(jnp.int32)) \
            * member.astype(jnp.int32)
        hist = jnp.zeros(((S + 1) * F * B,), jnp.int32)
        hist = hist.at[flat.reshape(-1)].add(
            jnp.broadcast_to(word[:, None], (n, F)).reshape(-1))
        h = jnp.mod(hist, M)
        g = (hist - h) // M
        return jnp.stack([g, h]).reshape(2, S + 1, F, B).transpose(
            1, 0, 2, 3)[:S]
    vals = _vals_t_int(gq, hq, member).T.astype(jnp.int32)  # [n, 2]
    hist = jnp.zeros(((S + 1) * F * B, 2), jnp.int32)
    updates = jnp.broadcast_to(vals[:, None, :], (n, F, 2))
    hist = hist.at[flat.reshape(-1)].add(updates.reshape(-1, 2))
    return hist.reshape(S + 1, F, B, 2)[:S].transpose(0, 3, 1, 2)


def pack_cols_u32_quant(binned_t: jax.Array, gq: jax.Array, hq: jax.Array,
                        member: jax.Array):
    """Quantized twin of ``pack_cols_u32``: bins pack 4-per-u32 as before,
    and the THREE f32 value words collapse into ONE
    (``(gq+128) | hq<<8 | member<<16``) — the arena's single fused gather
    moves Wb+1 words per row instead of Wb+3."""
    F, n = binned_t.shape
    if binned_t.dtype != jnp.uint8:
        return None, 0          # u16 bins (max_bin > 256): no packing
    Wb = (F + 3) // 4
    pad = Wb * 4 - F
    bt = jnp.pad(binned_t, ((0, pad), (0, 0))) if pad else binned_t
    b32 = bt.astype(jnp.uint32).reshape(Wb, 4, n)
    bin_words = (b32[:, 0] | (b32[:, 1] << 8)
                 | (b32[:, 2] << 16) | (b32[:, 3] << 24))   # [Wb, n]
    val_word = ((gq.astype(jnp.int32) + 128).astype(jnp.uint32)
                | (hq.astype(jnp.uint32) << 8)
                | (member.astype(jnp.uint32) << 16))        # [1, n]
    return jnp.concatenate([bin_words, val_word[None, :]], axis=0), Wb


def segment_histogram_sorted_int(
    binned_t: jax.Array,   # [F, n] uint8/16 feature-major
    gq: jax.Array,         # [n] int8
    hq: jax.Array,         # [n] int8
    slot: jax.Array,       # [n] i32 in [0, num_slots]; dummies pre-slotted
    num_slots: int,
    num_bins: int,
    block_rows: int = 1024,
    caps: Optional[list] = None,
    packed: Optional[tuple] = None,    # pack_cols_u32_quant output
    tile_rows: Optional[int] = None,
) -> jax.Array:
    """Integer sorted-arena segment histogram: same sort + block-aligned
    arena as ``segment_histogram_sorted`` but the per-block one-hot
    matmul runs int8 -> int32 and the block->slot fold accumulates exact
    int32 inside the block scan (a slot-fold matmul would lose integer
    exactness past 2^24).  ``tile_rows`` switches the record gathers
    from one hoisted whole-arena gather to per-block in-loop gathers —
    O(block) transients, identical values.  Returns [S, 2, F, B] i32."""
    F, n = binned_t.shape
    B = num_bins
    S = num_slots
    if caps is None:
        caps = [n]

    if n < (1 << 24) and num_slots < 256:
        key = ((slot.astype(jnp.uint32) << 24)
               | jnp.arange(n, dtype=jnp.uint32))
        skey = lax.sort(key)
        sorted_slot = (skey >> 24).astype(jnp.int32)
        order = (skey & jnp.uint32(0x00FFFFFF)).astype(jnp.int32)
    else:
        row_ids = jnp.arange(n, dtype=jnp.int32)
        sorted_slot, order = lax.sort((slot, row_ids), is_stable=True,
                                      num_keys=1)
    bounds = jnp.searchsorted(sorted_slot,
                              jnp.arange(S + 1, dtype=sorted_slot.dtype))
    row_start = bounds[:S].astype(jnp.int32)
    counts = (bounds[1:] - bounds[:S]).astype(jnp.int32)

    iota = jnp.arange(B, dtype=binned_t.dtype)

    def arena(cap: int):
        C = max(128, min(block_rows,
                         1 << max(0, (max(cap, 1) // (4 * max(S, 1))
                                      ).bit_length() - 1)))
        NB = _pad_rows(max(cap, 1), C) // C + S

        def run():
            nblk = (counts + C - 1) // C
            blk_end = jnp.cumsum(nblk)
            blk_start = (blk_end - nblk).astype(jnp.int32)
            j_idx = jnp.arange(NB, dtype=blk_end.dtype)
            blk_slot = jnp.searchsorted(blk_end, j_idx,
                                        side="right").astype(jnp.int32)
            blk_slot = jnp.minimum(blk_slot, S)

            q = jnp.arange(NB * C, dtype=jnp.int32)
            s_of = blk_slot[q // C]
            s_c = jnp.minimum(s_of, S - 1)
            o = q - blk_start[s_c] * C
            valid = (s_of < S) & (o < counts[s_c])
            src_sorted = jnp.minimum(row_start[s_c] + o, n - 1)
            src = order[src_sorted]

            def block_partial(rows, vals):
                """[F, C] bins x [2, C] int8 vals -> [2, F*B] i32."""
                onehot2d = (rows.T[:, :, None] == iota.astype(rows.dtype)
                            ).astype(jnp.int8).reshape(C, F * B)
                return lax.dot(vals, onehot2d,
                               preferred_element_type=jnp.int32)

            use_packed = packed is not None and packed[0] is not None

            def part_from_packed(blk_rec, vm):
                Wb = packed[1]
                bw = blk_rec[:Wb]                       # [Wb, C] u32
                rows = jnp.concatenate(
                    [((bw >> (8 * j)) & 0xFF) for j in range(4)],
                    axis=0).reshape(4, Wb, C).transpose(
                        1, 0, 2).reshape(Wb * 4, C)[:F]   # [F, C]
                vw = blk_rec[Wb]                        # [C] u32
                g = (vw & 0xFF).astype(jnp.int32) - 128
                h = ((vw >> 8) & 0xFF).astype(jnp.int32)
                m = ((vw >> 16) & 1).astype(jnp.int32)
                sel = vm[0] & (m == 1)
                vals = jnp.where(sel, jnp.stack([g, h]), 0).astype(jnp.int8)
                return block_partial(rows.astype(jnp.int32), vals)

            def part_from_raw(cols, g, h, vm):
                vt = jnp.stack([jnp.where(vm, g, 0),
                                jnp.where(vm, h, 0)]).astype(jnp.int8)
                return block_partial(cols, vt)

            # blocks -> slots: exact int32 accumulation inside the scan
            # (shared by the hoisted and in-loop gather modes)
            acc0 = jnp.zeros((S + 1, 2 * F * B), jnp.int32)
            j_arange = jnp.arange(NB, dtype=jnp.int32)

            if resolve_tile_rows(tile_rows, n) is None:
                if use_packed:
                    words_t, Wb = packed
                    rec = jnp.take(words_t, src, axis=1)  # [Wb+1, NBC] u32
                    recb = rec.reshape(Wb + 1, NB, C).transpose(1, 0, 2)
                    vmask = valid.reshape(NB, 1, C)

                    def body(acc, xs):
                        j, blk_rec, vm = xs
                        return acc.at[blk_slot[j]].add(
                            part_from_packed(blk_rec, vm).reshape(-1)), None

                    acc, _ = lax.scan(body, acc0, (j_arange, recb, vmask))
                else:
                    cols = jnp.take(binned_t, src, axis=1)  # [F, NBC]
                    g = jnp.take(gq, src)
                    h = jnp.take(hq, src)
                    colsb = cols.reshape(F, NB, C).transpose(1, 0, 2)
                    gb = g.reshape(NB, C)
                    hb = h.reshape(NB, C)
                    vmask = valid.reshape(NB, C)

                    def body(acc, xs):
                        j, b, gg, hh, vm = xs
                        return acc.at[blk_slot[j]].add(
                            part_from_raw(b, gg, hh, vm).reshape(-1)), None

                    acc, _ = lax.scan(body, acc0,
                                      (j_arange, colsb, gb, hb, vmask))
            else:
                def body(acc, j):
                    sb = lax.dynamic_slice(src, (j * C,), (C,))
                    vm = lax.dynamic_slice(valid, (j * C,), (C,))
                    if use_packed:
                        rec = jnp.take(packed[0], sb, axis=1)  # [Wb+1, C]
                        part = part_from_packed(rec, vm[None, :])
                    else:
                        cols = jnp.take(binned_t, sb, axis=1)  # [F, C]
                        part = part_from_raw(cols, jnp.take(gq, sb),
                                             jnp.take(hq, sb), vm)
                    return acc.at[blk_slot[j]].add(part.reshape(-1)), None

                acc, _ = lax.scan(body, acc0, j_arange)
            return acc[:S].reshape(S, 2, F, B)
        return run

    if len(caps) == 1:
        return arena(caps[0])()
    total = bounds[S].astype(jnp.int32)
    caps_arr = jnp.asarray(caps, jnp.int32)
    bucket = jnp.sum(caps_arr >= total) - 1
    return lax.switch(bucket, [arena(c) for c in caps])


# 2 int channels instead of 3 f32: 2 * 64 = 128 rows fill the MXU tile,
# so the quantized expanded pass covers 64 live slots for the cycles the
# f32 path spends on 42
_EXPAND_SLOTS_QUANT = 64


def segment_histogram_expanded_int(
    binned_t: jax.Array,   # [F, n] feature-major
    gq: jax.Array,
    hq: jax.Array,
    member: jax.Array,     # [n] bool
    slot: jax.Array,       # [n] i32; values >= live_cap contribute nothing
    num_bins: int,
    live_cap: int = _EXPAND_SLOTS_QUANT,
    block_rows: int = _DEFAULT_BLOCK_ROWS,
    tile_rows: Optional[int] = None,
) -> jax.Array:
    """Integer slot-expanded full-matrix pass: LHS [2*live_cap, C] int8
    (row j*cap+s carries vals[j] where slot == s), one s8 MXU tile per
    block.  Returns [live_cap, 2, F, B] i32."""
    F, n = binned_t.shape
    B = num_bins
    SE = live_cap
    block_rows = _tile_block(block_rows, resolve_tile_rows(tile_rows, n))
    nb = max(1, _pad_rows(n, block_rows) // block_rows)
    n_pad = nb * block_rows
    vals_t = _vals_t_int(gq, hq, member)
    slot_i = slot.astype(jnp.int32)
    if n_pad != n:
        binned_t = jnp.pad(binned_t, ((0, 0), (0, n_pad - n)))
        vals_t = jnp.pad(vals_t, ((0, 0), (0, n_pad - n)))
        slot_i = jnp.pad(slot_i, (0, n_pad - n), constant_values=SE)
    iota_b = jnp.arange(B, dtype=binned_t.dtype)
    iota_s = jnp.arange(SE, dtype=jnp.int32)
    C = block_rows

    def body(acc, i):
        b = lax.dynamic_slice(binned_t, (0, i * C), (F, C))   # [F, C]
        v = lax.dynamic_slice(vals_t, (0, i * C), (2, C))     # [2, C]
        sl = lax.dynamic_slice(slot_i, (i * C,), (C,))        # [C]
        oh_s = (sl[None, :] == iota_s[:, None]).astype(jnp.int8)  # [SE, C]
        lhs = (v[:, None, :] * oh_s[None, :, :]).reshape(2 * SE, C)
        onehot2d = (b.T[:, :, None] == iota_b).astype(jnp.int8).reshape(
            C, F * B)
        part = lax.dot(lhs, onehot2d, preferred_element_type=jnp.int32)
        return acc + part, None

    init = jnp.zeros((2 * SE, F * B), dtype=jnp.int32)
    acc, _ = lax.scan(body, init, jnp.arange(nb, dtype=jnp.int32))
    return acc.reshape(2, SE, F, B).transpose(1, 0, 2, 3)


def compacted_segment_histogram_int(
    binned_t: jax.Array,   # [F, n] feature-major
    gq: jax.Array,
    hq: jax.Array,
    weights: jax.Array,    # [n] f32 (0 = excluded)
    slot: jax.Array,       # [n] i32 in [0, num_slots]
    num_slots: int,
    num_bins: int,
    caps: list,
    num_live: Optional[jax.Array] = None,
    packed: Optional[tuple] = None,     # pack_cols_u32_quant output
    levels: Optional[tuple] = None,
    tile_rows: Optional[int] = None,
) -> jax.Array:
    """Integer twin of ``compacted_segment_histogram`` with the same
    backend dispatch: sorted int arena / expanded int pass on
    accelerators (LGBM_TPU_SEGHIST overrides), packed scatter with
    nonzero compaction on CPU.  Returns [S, 2, F, B] i32."""
    F, n = binned_t.shape
    member = weights > 0
    if use_sorted_seghist():
        slot_w = jnp.where(member, slot, num_slots)

        def arena_path(_):
            return segment_histogram_sorted_int(
                binned_t, gq, hq, slot_w, num_slots, num_bins,
                caps=caps, packed=packed, tile_rows=tile_rows)

        small_enabled = os.environ.get("LGBM_TPU_SMALL_ROUNDS") != "0"
        if num_live is None or num_slots <= _SMALL_ROUND_SLOTS \
                or not small_enabled:
            return arena_path(None)
        se = min(_EXPAND_SLOTS_QUANT, num_slots)

        def expanded_path(_):
            hist = segment_histogram_expanded_int(
                binned_t, gq, hq, member, slot_w, num_bins, live_cap=se,
                tile_rows=tile_rows)
            if num_slots > se:
                hist = jnp.concatenate(
                    [hist, jnp.zeros((num_slots - se, 2, F, num_bins),
                                     jnp.int32)], axis=0)
            return hist

        return lax.cond(num_live <= se, expanded_path, arena_path, None)

    in_play = (slot < num_slots) & member
    count = jnp.sum(in_play)

    def branch(cap: int):
        def run():
            idx = jnp.nonzero(in_play, size=cap, fill_value=n)[0]
            valid = idx < n
            idxc = jnp.minimum(idx, n - 1)
            cols = jnp.take(binned_t, idxc, axis=1)
            g = jnp.take(gq, idxc)
            h = jnp.take(hq, idxc)
            s = jnp.where(valid, jnp.take(slot, idxc), num_slots)
            return segment_histogram_int(cols, g, h, valid, s, num_slots,
                                         num_bins, levels=levels,
                                         tile_rows=tile_rows)
        return run

    if len(caps) == 1:
        return segment_histogram_int(binned_t, gq, hq, in_play, slot,
                                     num_slots, num_bins, levels=levels,
                                     tile_rows=tile_rows)
    caps_arr = jnp.asarray(caps, jnp.int32)
    bucket = jnp.sum(caps_arr >= count) - 1
    return lax.switch(bucket, [branch(c) for c in caps])
