"""Inference traversal kernels: the predict path's kernel family.

Training got a kernel war (histogram families, the fused megakernel);
this module gives the inference hot path the same treatment.  Three
variants share ONE decision-step expression so routing parity across
them is by construction, not by test luck:

``while``   the legacy ``lax.while_loop`` node chase (predict.py) — a
            per-step ``jnp.any`` convergence sync and a dynamic trip
            count that AOT export cannot serialize.  Kept as the
            fallback arm.
``fori``    the same [T, nc] depth-stepping state advanced for a STATIC
            ``forest.max_depth`` trips.  Rows that reach a leaf freeze
            (the step is idempotent on negative node ids), so the extra
            trips are no-ops — and the fixed trip count drops the
            convergence sync and AOT-exports cleanly (fleet/aot.py).
``fused``   a Pallas kernel that streams rows tile-by-tile (the PR 5
            ``tile_rows`` regime): the [T, tile] node state lives in
            VMEM for the whole descent, the forest arrays stay resident
            across grid steps (their block index never moves, so Pallas
            skips the re-DMA), and — when leaf values are on device —
            per-class raw scores are accumulated in-kernel in a pinned
            iteration-major order so only a [K, tile] block leaves HBM.

All three carry the full routing contract: categorical bitsets, the
three missing-value types, and every threshold precision
(f32/bf16/int8 via fleet/lowprec.py) — the fused kernel consumes a
precomputed full [T, I] f32 threshold plane that is elementwise
identical to ``DeviceForest._thr_at``'s per-gather dequantization.

Off accelerators the Pallas kernel runs in interpret mode (the
ops/fused.py convention), which executes the very jnp expressions the
other variants use — so CPU tier-1 parity tests are meaningful.  On the
chip the fused kernel does not compile — Mosaic's gather lowering rule
refuses the in-kernel ``table[tid, node]`` gathers with an
``AssertionError`` — so ``plan_predict`` never elects it there; pinning
it with ``LGBM_TPU_PREDICT_KERNEL=fused`` raises that error.  Where it
does run, a one-time per-backend probe compares fused leaf indices
against the while_loop arm and demotes to ``fori`` on a mismatch.
"""

from __future__ import annotations

import numpy as np

PREDICT_VARIANTS = ("while", "fori", "fused")

# matches predict.py's kZeroThreshold (feature_group.h)
_K_ZERO = 1e-35

# row-tile ladder the planner's VMEM model elects from
FUSED_TILE_LADDER = (2048, 1024, 512, 256, 128)


def _interp(interpret):
    """Pallas interpret-mode default: real kernel on accelerators,
    interpreted everywhere else (the ops/fused.py convention)."""
    if interpret is None:
        from .histogram import on_accelerator
        return not on_accelerator()
    return bool(interpret)


# ----------------------------------------------------------------------
# the shared decision step
# ----------------------------------------------------------------------

def decide_step(node, Xc, sf, thr, left, right, mt, dl, has_cat,
                ic=None, co=None, cn=None, cw=None):
    """One depth step of the [T', nc] node chase, written once.

    ``node`` < 0 marks a frozen row (two's-complement leaf id); the
    returned state keeps frozen entries untouched, so the step is
    idempotent and any trip count >= the true depth is exact.  All
    operand planes are FULL [T', I] arrays (thresholds already in f32)
    — the jnp variants pass the DeviceForest arrays through unchanged
    and the Pallas kernel passes its VMEM-resident blocks, so every
    variant evaluates literally this expression.
    """
    import jax.numpy as jnp

    T, nc = node.shape
    from jax import lax
    rows = lax.broadcasted_iota(jnp.int32, (T, nc), 1)
    tid2 = lax.broadcasted_iota(jnp.int32, (T, nc), 0)
    nd = jnp.maximum(node, 0)
    fval = Xc[rows, sf[tid2, nd]]
    th = thr[tid2, nd]
    m = mt[tid2, nd]
    nan = jnp.isnan(fval)
    fz = jnp.where(nan & (m != 2), 0.0, fval)
    is_missing = ((m == 1) & (jnp.abs(fz) <= _K_ZERO)) | ((m == 2) & nan)
    gl = jnp.where(is_missing, dl[tid2, nd] != 0, fz <= th)
    if has_cat:
        # truncate toward zero (reference static_cast<int> semantics)
        iv = jnp.fix(jnp.where(nan, -1.0, fval)).astype(jnp.int32)
        nw = cn[tid2, nd]
        valid = (iv >= 0) & (iv < nw * 32)
        ivc = jnp.clip(iv, 0, None)
        widx = co[tid2, nd] + jnp.minimum(ivc // 32, jnp.maximum(nw - 1, 0))
        inset = (cw[0, widx] >> (ivc % 32).astype(jnp.uint32)) & 1
        gl = jnp.where(ic[tid2, nd] != 0, valid & (inset == 1), gl)
    nxt = jnp.where(gl, left[tid2, nd], right[tid2, nd])
    return jnp.where(node < 0, node, nxt)


def full_threshold_f32(dev) -> "np.ndarray":
    """The complete [T, I] f32 threshold plane for ``dev``, elementwise
    identical to what ``DeviceForest._thr_at`` gathers: bf16 widens,
    int8 dequantizes (q * per-tree scale) with the sparse fix-mask
    correction for non-quantized nodes.  Dequantization is elementwise,
    so precomputing the plane cannot change a single routing bit."""
    import jax.numpy as jnp
    if dev.precision == "bf16":
        return dev.threshold.astype(jnp.float32)
    if dev.precision == "int8":
        thr = dev.threshold.astype(jnp.float32) * dev._thr_scale
        return jnp.where(dev._thr_fix_mask, dev._thr_fix, thr)
    return dev.threshold


def kernel_args(dev) -> dict:
    """The fused kernel's operand planes for ``dev``, cached on the
    instance: int32 copies of the routing arrays (Mosaic has no i64 or
    1-bit lanes), the precomputed f32 threshold plane, and the bitset
    words lifted to a 2D [1, W] block."""
    cached = dev.__dict__.get("_fused_kernel_args")
    if cached is not None:
        return cached
    import jax
    import jax.numpy as jnp
    # never build (and cache!) these under an active trace — the planes
    # must be concrete device arrays, not leaked tracers
    with jax.ensure_compile_time_eval():
        args = {
            "sf": dev.split_feature.astype(jnp.int32),
            "thr": full_threshold_f32(dev),
            "left": dev.left.astype(jnp.int32),
            "right": dev.right.astype(jnp.int32),
            "mt": dev.missing_type.astype(jnp.int32),
            "dl": dev.default_left.astype(jnp.int32),
            "ic": dev.is_cat.astype(jnp.int32),
            "co": dev.cat_offset.astype(jnp.int32),
            "cn": dev.cat_nwords.astype(jnp.int32),
            "cw": dev.cat_words.reshape(1, -1),
        }
    dev.__dict__["_fused_kernel_args"] = args
    return args


# ----------------------------------------------------------------------
# jnp variants
# ----------------------------------------------------------------------

def _dev_planes(dev):
    import jax.numpy as jnp
    return dict(sf=dev.split_feature, thr=full_threshold_f32(dev),
                left=dev.left, right=dev.right, mt=dev.missing_type,
                dl=dev.default_left.astype(jnp.int32),
                has_cat=dev.forest.has_cat,
                ic=dev.is_cat.astype(jnp.int32),
                co=dev.cat_offset.astype(jnp.int32), cn=dev.cat_nwords,
                cw=dev.cat_words.reshape(1, -1))


def leaves_while(dev, Xc):
    """[nc, F] f32 -> leaf index [T, nc] under ``lax.while_loop`` —
    the legacy arm, one shared step expression."""
    import jax.numpy as jnp
    from jax import lax
    planes = _dev_planes(dev)
    T = dev.forest.num_trees
    node = lax.while_loop(
        lambda nd: jnp.any(nd >= 0),
        lambda nd: decide_step(nd, Xc, **planes),
        jnp.zeros((T, Xc.shape[0]), jnp.int32))
    return ~node


def leaves_fori(dev, Xc):
    """[nc, F] f32 -> leaf index [T, nc] in exactly ``max_depth`` fixed
    trips — no convergence sync, AOT-export-clean (the trip count is a
    trace-time constant; ``StackedForest.max_depth`` counts decisions on
    the deepest root-to-leaf path, so it is exactly sufficient)."""
    import jax.numpy as jnp
    from jax import lax
    planes = _dev_planes(dev)
    T = dev.forest.num_trees
    node = lax.fori_loop(
        0, max(int(dev.forest.max_depth), 1),
        lambda _, nd: decide_step(nd, Xc, **planes),
        jnp.zeros((T, Xc.shape[0]), jnp.int32))
    return ~node


# ----------------------------------------------------------------------
# the fused Pallas kernel
# ----------------------------------------------------------------------

def _traverse_kernel(depth, has_cat, num_class, emit_scores):
    """Kernel body factory.  One grid step owns one row tile: descend
    all trees to their leaves with the node state held in VMEM, then
    either write the [T, tile] leaf ids or gather+accumulate the
    [K, tile] raw scores in pinned iteration-major order."""
    import jax.numpy as jnp
    from jax import lax

    def kernel(x_ref, sf_ref, thr_ref, left_ref, right_ref, mt_ref,
               dl_ref, ic_ref, co_ref, cn_ref, cw_ref, *rest):
        lv_ref, out_ref = rest if emit_scores else (None, rest[0])
        X = x_ref[...]
        T = sf_ref.shape[0]
        tile = X.shape[0]
        planes = dict(
            sf=sf_ref[...], thr=thr_ref[...], left=left_ref[...],
            right=right_ref[...], mt=mt_ref[...], dl=dl_ref[...],
            has_cat=has_cat, ic=ic_ref[...], co=co_ref[...],
            cn=cn_ref[...], cw=cw_ref[...])
        node = lax.fori_loop(
            0, depth, lambda _, nd: decide_step(nd, X, **planes),
            jnp.zeros((T, tile), jnp.int32))
        leaves = ~node
        if not emit_scores:
            out_ref[...] = leaves
            return
        tid2 = lax.broadcasted_iota(jnp.int32, (T, tile), 0)
        lv = lv_ref[...][tid2, leaves]                   # [T, tile] f32
        K = max(num_class, 1)
        lv3 = lv.reshape(T // K, K, tile)
        # pinned tree order: sequential iteration-major accumulation,
        # bit-stable run to run (jnp.sum may re-associate)
        out_ref[...] = lax.fori_loop(
            0, T // K, lambda i, acc: acc + lv3[i],
            jnp.zeros((K, tile), jnp.float32))

    return kernel


def fused_traverse(dev, Xpad, tile_rows: int = 512, num_class: int = 1,
                   emit_scores: bool = False, interpret=None):
    """Fused tile-streaming traversal of ``Xpad`` [n, F] f32.

    Returns leaf indices [T, n] i32, or raw scores [K, n] f32 when
    ``emit_scores`` (requires device leaf values).  Rows are padded up
    to a whole number of tiles and the pad columns sliced off; a padded
    all-zero row routes like any ordinary row, it just gets discarded.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if emit_scores and dev.leaf_value is None:
        raise ValueError("fused score accumulation needs device leaf "
                         "values (routing_only forest)")
    args = kernel_args(dev)
    n, F = Xpad.shape
    T = dev.forest.num_trees
    K = max(num_class, 1)
    tile = max(min(int(tile_rows), max(n, 1)), 8)
    ntiles = max(-(-n // tile), 1)
    npad = ntiles * tile
    X = jnp.asarray(Xpad, jnp.float32)
    if npad != n:
        X = jnp.pad(X, ((0, npad - n), (0, 0)))

    def _full(a):
        return pl.BlockSpec(a.shape, lambda i: (0,) * a.ndim)

    operands = [X] + [args[k] for k in
                      ("sf", "thr", "left", "right", "mt", "dl",
                       "ic", "co", "cn", "cw")]
    in_specs = [pl.BlockSpec((tile, F), lambda i: (i, 0))] + \
        [_full(a) for a in operands[1:]]
    if emit_scores:
        operands.append(dev.leaf_value)
        in_specs.append(_full(dev.leaf_value))
        out_shape = jax.ShapeDtypeStruct((K, npad), jnp.float32)
        out_specs = pl.BlockSpec((K, tile), lambda i: (0, i))
    else:
        out_shape = jax.ShapeDtypeStruct((T, npad), jnp.int32)
        out_specs = pl.BlockSpec((T, tile), lambda i: (0, i))
    kernel = _traverse_kernel(max(int(dev.forest.max_depth), 1),
                              dev.forest.has_cat, K, emit_scores)
    out = pl.pallas_call(
        kernel, grid=(ntiles,), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=_interp(interpret),
        name="lgbm_traverse")(*operands)
    return out[:, :n]


# ----------------------------------------------------------------------
# per-backend verification probe (the take_from_table precedent)
# ----------------------------------------------------------------------

_FUSED_PREDICT_PROBE: dict = {}


def fused_predict_verified(dev) -> bool:
    """One-time per (backend, precision, cat) verdict: the fused kernel
    must reproduce the while_loop arm's leaf indices BIT-exactly on a
    probe batch covering zeros, NaNs and sign extremes, or it is demoted
    (the caller falls back to ``fori``).  A numeric probe only: a
    compile or lowering error propagates.  Off accelerators the kernel
    interprets as the same jnp math, so the answer is trivially yes."""
    import jax

    from .histogram import on_accelerator
    if not on_accelerator():
        return True
    backend = jax.default_backend()
    key = (backend, dev.precision, bool(dev.forest.has_cat))
    ok = _FUSED_PREDICT_PROBE.get(key)
    if ok is None:
        F = int(np.asarray(dev.split_feature).max(initial=0)) + 1
        rng = np.random.RandomState(7)
        X = rng.standard_normal((16, F)).astype(np.float32) * 10.0
        X[0] = 0.0
        X[1] = np.nan
        X[2] = -1e30
        X[3] = 1e30
        X[4, ::2] = np.nan
        ref = np.asarray(jax.jit(dev._leaves)(X))
        got = np.asarray(fused_traverse(dev, X, tile_rows=8))
        ok = bool(np.array_equal(ref, got))
        if not ok:
            from ..utils.log import log_warning
            log_warning(
                "fused predict kernel demoted: leaf indices diverged "
                f"from the while_loop arm on backend {backend!r}")
        _FUSED_PREDICT_PROBE[key] = ok
    return bool(ok)
