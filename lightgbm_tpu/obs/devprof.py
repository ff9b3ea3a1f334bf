"""Measured device profiling: per-program FLOPs/bytes from XLA's
``Compiled.cost_analysis()`` -> measured MFU and HBM-bandwidth
utilization.

``bench.py``'s ``mfu_histogram_lower_bound`` hand-counts only the
histogram-matmul FLOPs and divides by a wall-clock that smears compile
and host time in — a lower bound good for trendlines, useless for
finding where the other 99.9% of the chip went.  This module asks the
compiler instead: ``jit(f).lower(*args).compile().cost_analysis()``
reports the FLOPs and bytes the COMPILED program actually executes
(post-fusion, post-DCE), so

    mfu      = flops / seconds / peak_flops
    hbm_util = bytes_accessed / seconds / peak_hbm_bandwidth

are measured per program variant, not estimated per formula.  Caveats
(docs/OBSERVABILITY.md): under async dispatch ``seconds`` must come from
a host-blocking sync (``block_until_ready``), ``cost_analysis`` is the
compiler's estimate and its availability varies by backend, and the
utilization figures exist only on a device the peak tables know: a CPU
run reports seconds and the cost model's counts, never an MFU.

A device timeline is ``jax.profiler.start_trace`` around ``lgb.train``:
the program's ``lgbm.*`` seams (obs/trace.py) and named scopes show there.
jax imports are lazy: importing this module never initializes a backend.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

# peak dense bf16 compute per chip, FLOP/s, keyed by a substring of
# ``device_kind`` (Google Cloud TPU documentation, per-generation pages)
PEAK_FLOPS = {
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v4": 275e12,
    "v5p": 459e12,
    "v6": 918e12,
}

# peak HBM bandwidth per chip, bytes/s (same source)
PEAK_HBM_BW = {
    "v5 lite": 819e9,
    "v5e": 819e9,
    "v4": 1228e9,
    "v5p": 2765e9,
    "v6": 1640e9,
}


def _peak_for(table: dict, device) -> float:
    """The table entry for ``device`` (default: the first device).  A
    device the table does not know is an error, not a default — a
    utilization against somebody else's peak is a wrong number."""
    if device is None:
        import jax
        device = jax.devices()[0]
    kind = str(device.device_kind).lower()
    for key, val in table.items():
        if key in kind:
            return val
    raise ValueError(
        f"no peak figure for device kind {device.device_kind!r}; add it "
        "to the tables in lightgbm_tpu/obs/devprof.py with its source")


def peak_flops_for(device=None) -> float:
    return _peak_for(PEAK_FLOPS, device)


def peak_hbm_bw_for(device=None) -> float:
    return _peak_for(PEAK_HBM_BW, device)


def normalize_cost(ca) -> dict:
    """Flatten a ``cost_analysis()`` result (dict, or list-of-dict on
    older jax) into {str: float}; {} when unavailable."""
    if ca is None:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    out = {}
    try:
        items = dict(ca).items()
    except Exception:
        return {}
    for k, v in items:
        try:
            out[str(k)] = float(v)
        except (TypeError, ValueError):
            continue
    return out


def program_cost(fn: Callable, *args) -> dict:
    """{"flops", "bytes_accessed"} of the compiled program for ``fn`` at
    ``args``'s shapes ({} when the backend reports no cost model).

    ``fn`` may be a plain callable or an already-``jax.jit``-wrapped one;
    the AOT path (``lower().compile()``) hits the persistent compile
    cache, so asking for the cost of an already-trained program is cheap.
    """
    try:
        import jax
        jfn = fn if hasattr(fn, "lower") else jax.jit(fn)
        compiled = jfn.lower(*args).compile()
        ca = normalize_cost(compiled.cost_analysis())
    except Exception:
        return {}
    if not ca:
        return {}
    out = {}
    if "flops" in ca:
        out["flops"] = ca["flops"]
    ba = ca.get("bytes accessed", ca.get("bytes_accessed"))
    if ba is not None:
        out["bytes_accessed"] = ba
    return out


def _default_sync(out) -> None:
    """Block until device work behind ``out`` is done."""
    import jax
    jax.block_until_ready(out)


def measure_program(fn: Callable, args: tuple, reps: int = 3,
                    sync: Optional[Callable] = None,
                    device=None) -> dict:
    """Compile ``fn(*args)``, read its cost analysis, time ``reps``
    executions, and report measured utilization::

        {"flops", "bytes_accessed",            # from cost_analysis
         "seconds_per_call", "mfu", "hbm_gbps", "hbm_util",
         "peak_flops", "peak_hbm_bw"}

    Cost keys are absent when the backend has no cost model, and the
    utilization and peak keys on a CPU run (no device to be a share
    of); ``seconds_per_call`` is always present.  ``sync`` defaults to
    ``jax.block_until_ready``.
    """
    import jax
    if device is None:
        device = jax.devices()[0]
    jfn = fn if hasattr(fn, "lower") else jax.jit(fn)
    sync = sync or _default_sync
    # ONE compile: the AOT executable serves both the cost analysis and
    # the timed runs (jit'ing again would pay a second, discarded compile
    # for every variant — compile time dominates bench stages)
    out = {}
    runner = jfn
    try:
        compiled = jfn.lower(*args).compile()
        ca = normalize_cost(compiled.cost_analysis())
        if "flops" in ca:
            out["flops"] = ca["flops"]
        ba = ca.get("bytes accessed", ca.get("bytes_accessed"))
        if ba is not None:
            out["bytes_accessed"] = ba
        compiled(*args)                  # callable-executable probe
        runner = compiled
    except Exception:
        runner = jfn                     # backend without AOT/cost model
    sync(runner(*args))                  # warm outside the clock
    t0 = time.perf_counter()
    for _ in range(max(reps, 1)):
        sync(runner(*args))
    sec = (time.perf_counter() - t0) / max(reps, 1)
    out["seconds_per_call"] = sec
    if device.platform == "cpu":
        return out
    pf = peak_flops_for(device)
    pb = peak_hbm_bw_for(device)
    out["peak_flops"] = pf
    out["peak_hbm_bw"] = pb
    if "flops" in out and sec > 0:
        out["mfu"] = out["flops"] / sec / pf
    if "bytes_accessed" in out and sec > 0:
        out["hbm_gbps"] = out["bytes_accessed"] / sec / 1e9
        out["hbm_util"] = out["bytes_accessed"] / sec / pb
    return out


def histogram_utilization_table(rows: int = 200_000, features: int = 28,
                                num_bins: int = 64, slots: int = 8,
                                reps: int = 2, tile_rows: Optional[int] = None,
                                seed: int = 0, quant: bool = True) -> dict:
    """Measured per-kernel-variant utilization table for the histogram
    family: {matmul, matmul_f32, scatter, pallas, sorted, expanded,
    fused} x {f32, quant} x {untiled, tiled} -> ``measure_program``
    dicts.

    This replaces the bench's hand-derived MFU lower bound with the
    compiler's own FLOP/byte counts per compiled variant — the numbers
    the Pallas-megakernel work (ROADMAP item 2) is steered by; the
    ``*/fused`` rows are that megakernel itself (ops/fused.py: histogram
    build + in-VMEM split scan in one program — the acceptance figure is
    its MFU against the staged rows at the same shape); the
    ``*/fused_sharded_{flat,hier}`` rows are its collective-seam form —
    accumulate-only kernel, data-axis psum (identity off-mesh), sibling
    derive + scan kernel — the program pair the data-parallel growers
    actually run.  The
    ``f32/scatter_batched8`` row is the model-axis plane
    (lightgbm_tpu/multi/): the same scatter build vmapped over 8
    lane-stacked gradient vectors against ONE shared binned matrix —
    its MFU against ``f32/scatter`` at the same shape is the per-kernel
    evidence behind the batched sweep stage (tools/sweep_probe.py).  A
    variant unsupported on the backend reports ``{"error": ...}``
    instead of failing the table.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..ops import fused as FU
    from ..ops import histogram as H
    from ..ops.split import SplitHyperparams
    from ..parallel import collectives as PC

    rng = np.random.RandomState(seed)
    n, F, B = int(rows), int(features), int(num_bins)
    binned = jnp.asarray(
        rng.randint(0, B, (F, n), dtype=np.int64), jnp.uint8)
    grad = jnp.asarray(rng.randn(n), jnp.float32)
    hess = jnp.abs(grad) + 0.1
    mask = jnp.ones((n,), jnp.float32)
    slot = jnp.asarray(rng.randint(0, slots, n, dtype=np.int64), jnp.int32)
    gq = jnp.asarray(rng.randint(-8, 8, n, dtype=np.int64), jnp.int8)
    hq = jnp.asarray(rng.randint(0, 8, n, dtype=np.int64), jnp.int8)
    member = jnp.ones((n,), bool)
    # model-axis fixtures: 8 heterogeneous gradient lanes over the ONE
    # shared binned matrix (lane scaling defeats cross-lane CSE)
    lanes = 8
    gradB = jnp.stack([grad * (1.0 + 0.01 * i) for i in range(lanes)])
    hessB = jnp.stack([hess * (1.0 + 0.01 * i) for i in range(lanes)])

    if tile_rows is None:
        tile_rows = 1 << max((n // 4).bit_length() - 1, 10)
    tile_rows = max(min(int(tile_rows), n), 1)

    # fused-megakernel fixtures: per-slot totals + trivially-valid meta
    hp = SplitHyperparams(min_data_in_leaf=1)
    nb_v = jnp.full((F,), B, jnp.int32)
    z_v = jnp.zeros((F,), jnp.int32)
    oh_slot = (slot[None, :] == jnp.arange(slots)[:, None])
    slot_sums = jnp.stack([
        jnp.sum(jnp.where(oh_slot, grad[None, :], 0.0), axis=1),
        jnp.sum(jnp.where(oh_slot, hess[None, :], 0.0), axis=1),
        jnp.sum(oh_slot.astype(jnp.float32), axis=1)])

    def fam(tile):
        ms = {
            "f32/matmul": lambda b, g, h, m: H.build_histogram(
                b, g, h, m, B, method="matmul", tile_rows=tile),
            "f32/matmul_f32": lambda b, g, h, m: H.build_histogram(
                b, g, h, m, B, method="matmul_f32", tile_rows=tile),
            "f32/scatter": lambda b, g, h, m: H.build_histogram(
                b, g, h, m, B, method="scatter", tile_rows=tile),
            "f32/scatter_batched8": lambda b, g, h, m: jax.vmap(
                lambda gg, hh: H.build_histogram(
                    b, gg, hh, m, B, method="scatter", tile_rows=tile)
            )(gradB, hessB),
            "f32/pallas": lambda b, g, h, m: H.build_histogram(
                b, g, h, m, B, method="pallas", tile_rows=tile),
            "f32/sorted": lambda b, g, h, m: H.segment_histogram_sorted(
                b, g, h, m, slot, slots, B, tile_rows=tile),
            "f32/expanded": lambda b, g, h, m: H.segment_histogram_expanded(
                b, g, h, m, slot, B, tile_rows=tile),
            "f32/fused": lambda b, g, h, m: FU.fused_segment_splits(
                b, H._vals_t(g, h, m), slot, slots, B, slot_sums,
                nb_v, z_v, z_v, hp, tile_rows=tile),
            # sharded-seam rows (ops/fused.py collective seam): fused
            # accumulate -> data-axis psum -> fused sibling scan.  Off a
            # mesh the psum is identity, so these measure the two kernel
            # halves the sharded path actually runs; flat vs hierarchical
            # differ only in the reduction routing a real mesh would take
            # (parallel/collectives.py), kept as separate rows so on-mesh
            # captures land in distinct keys.
            "f32/fused_sharded_flat": lambda b, g, h, m:
                FU.fused_sibling_scan(
                    PC.psum_tiered(FU.fused_frontier_accumulate(
                        b, H._vals_t(g, h, m), slot, slots, B,
                        tile_rows=tile), None),
                    slot_sums, nb_v, z_v, z_v, hp),
            "f32/fused_sharded_hier": lambda b, g, h, m:
                FU.fused_sibling_scan(
                    PC.psum_tiered(FU.fused_frontier_accumulate(
                        b, H._vals_t(g, h, m), slot, slots, B,
                        tile_rows=tile), None, hierarchical=True),
                    slot_sums, nb_v, z_v, z_v, hp),
        }
        if quant:
            ms.update({
                "quant/matmul_int8": lambda b, g, h, m: H.build_histogram_int(
                    b, gq, hq, member, B, method="matmul_int8",
                    tile_rows=tile),
                "quant/scatter_int": lambda b, g, h, m: H.build_histogram_int(
                    b, gq, hq, member, B, method="scatter_int",
                    tile_rows=tile),
                "quant/sorted": lambda b, g, h, m:
                    H.segment_histogram_sorted_int(
                        b, gq, hq, slot, slots, B, tile_rows=tile),
                "quant/expanded": lambda b, g, h, m:
                    H.segment_histogram_expanded_int(
                        b, gq, hq, member, slot, B, tile_rows=tile),
                "quant/fused": lambda b, g, h, m:
                    FU.fused_segment_splits(
                        b, H._vals_t_int(gq, hq, member), slot, slots, B,
                        slot_sums, nb_v, z_v, z_v, hp,
                        quant_scales=(jnp.float32(0.25), jnp.float32(0.5)),
                        tile_rows=tile),
                "quant/fused_sharded_flat": lambda b, g, h, m:
                    FU.fused_sibling_scan(
                        H.psum_quant_hist(FU.fused_frontier_accumulate(
                            b, H._vals_t_int(gq, hq, member), slot, slots,
                            B, tile_rows=tile), None, n, B),
                        slot_sums, nb_v, z_v, z_v, hp,
                        quant_scales=(jnp.float32(0.25), jnp.float32(0.5))),
                "quant/fused_sharded_hier": lambda b, g, h, m:
                    FU.fused_sibling_scan(
                        H.psum_quant_hist(FU.fused_frontier_accumulate(
                            b, H._vals_t_int(gq, hq, member), slot, slots,
                            B, tile_rows=tile), None, n, B,
                            hierarchical=True),
                        slot_sums, nb_v, z_v, z_v, hp,
                        quant_scales=(jnp.float32(0.25), jnp.float32(0.5))),
            })
        return ms

    device = None
    try:
        device = jax.devices()[0]
    except Exception:
        pass
    out = {"rows": n, "features": F, "num_bins": B, "slots": slots,
           "tile_rows": tile_rows}
    for tile_label, tile in (("untiled", None), ("tiled", tile_rows)):
        for name, fn in fam(tile).items():
            key = f"{name}/{tile_label}"
            try:
                out[key] = measure_program(
                    jax.jit(fn), (binned, grad, hess, mask),
                    reps=reps, device=device)
            except Exception as e:  # unsupported variant on this backend
                out[key] = {"error": str(e)[:160]}
    return out


def predict_utilization_table(device_forest, rows: int = 200_000,
                              reps: int = 2, num_class: int = 1,
                              seed: int = 0) -> dict:
    """Measured per-traversal-variant utilization table for the predict
    family (ops/predict_kernels.py): {while, fori, fused[, fused_scores]}
    -> ``measure_program`` dicts over one synthetic ``[rows, F]`` batch.

    The histogram table above steers the training-kernel war; this is
    its inference twin — the compiler-counted FLOPs/bytes behind the
    ``predict_probe`` bench stage's sec/Mrow trendline.  ``device_forest``
    is a ``predict.DeviceForest`` (any precision — the variants all read
    its quantized planes); ``fused_scores`` adds the in-kernel leaf-sum
    epilogue row when the forest carries leaf values and the tree count
    divides by ``num_class``.  A variant unsupported on the backend
    reports ``{"error": ...}`` instead of failing the table.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..ops import predict_kernels as PK

    f = device_forest.forest
    F = int(np.asarray(f.split_feature).max(initial=0)) + 1
    rng = np.random.RandomState(seed)
    X = jnp.asarray(rng.randn(int(rows), F), jnp.float32)
    tile = int(getattr(device_forest, "tile_rows", 512)) or 512
    K = max(int(num_class), 1)

    variants = {
        "while": lambda x: PK.leaves_while(device_forest, x),
        "fori": lambda x: PK.leaves_fori(device_forest, x),
        "fused": lambda x: PK.fused_traverse(device_forest, x, tile),
    }
    if (device_forest.leaf_value is not None
            and int(f.num_trees) % K == 0):
        variants["fused_scores"] = lambda x: PK.fused_traverse(
            device_forest, x, tile, K, emit_scores=True)

    device = None
    try:
        device = jax.devices()[0]
    except Exception:
        pass
    out = {"rows": int(rows), "features": F,
           "num_trees": int(f.num_trees), "tile_rows": tile,
           "elected_variant": getattr(device_forest, "variant", "while")}
    for name, fn in variants.items():
        try:
            out[name] = measure_program(jax.jit(fn), (X,), reps=reps,
                                        device=device)
        except Exception as e:  # unsupported variant on this backend
            out[name] = {"error": str(e)[:160]}
    return out


def ingest_utilization_table(dataset, raw: "np.ndarray", reps: int = 2,
                             tile_rows: Optional[int] = None) -> dict:
    """Measured utilization table for the ingest family (ops/ingest.py):
    the bucketize+pack kernel per tile-ladder rung -> ``measure_program``
    dicts over one real raw block, plus a wall-clock ``host`` row (the
    NumPy ``_bin_block`` oracle at the same shape) so the kernel-vs-host
    speedup is read straight off the table — the number behind the
    ``ingest_probe`` bench stage and the ``bin_rows_per_sec`` telemetry
    gauge.  ``dataset`` must be constructed (or sample-fitted) so its
    bin mappers and EFB layout exist; a rung unsupported on the backend
    reports ``{"error": ...}`` instead of failing the table.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..ops import ingest as ING
    from ..ops.planner import INGEST_TILES

    tables = ING.build_ingest_tables(dataset)
    X = np.ascontiguousarray(np.asarray(raw), dtype=np.float32)
    n = int(X.shape[0])
    device = None
    try:
        device = jax.devices()[0]
    except Exception:
        pass
    out = {"rows": n, "features": int(tables.num_features),
           "num_groups": int(tables.num_groups),
           "out_dtype": str(tables.out_dtype)}
    ladder = ((int(tile_rows),) if tile_rows else INGEST_TILES)
    Xd = jnp.asarray(X)
    for tile in ladder:
        binner = ING.DeviceBinner(tables, tile)
        try:
            out[f"kernel/t{tile}"] = measure_program(
                binner._call, (Xd,), reps=reps, device=device)
        except Exception as e:  # unsupported rung on this backend
            out[f"kernel/t{tile}"] = {"error": str(e)[:160]}
    # the host oracle at the same shape: wall clock only (no compiler
    # cost model exists for NumPy) — the denominator of the speedup
    ref = np.zeros((n, tables.num_groups), tables.out_dtype)
    dataset._bin_block(X.astype(np.float64), None, ref)   # warm caches
    t0 = time.perf_counter()
    for _ in range(max(reps, 1)):
        dataset._bin_block(X.astype(np.float64), None, ref)
    sec = (time.perf_counter() - t0) / max(reps, 1)
    out["host"] = {"seconds_per_call": sec}
    best = min((v["seconds_per_call"] for k, v in out.items()
                if k.startswith("kernel/") and isinstance(v, dict)
                and "seconds_per_call" in v), default=None)
    if best:
        out["best_kernel_seconds_per_call"] = best
        out["kernel_speedup_vs_host"] = round(sec / max(best, 1e-12), 3)
        out["bin_rows_per_sec"] = round(n / max(best, 1e-12), 1)
    return out
