#!/usr/bin/env python
"""Histogram-pipeline micro-bench: f32 vs quantized-gradient throughput
plus per-round psum payload accounting (use_quantized_grad).

Measures, on the live backend:

- ``f32``: the resolved f32 histogram kernel (matmul/bf16 on
  accelerators, scatter on CPU) over a synthetic [F, n] binned matrix;
- ``quant``: gradient discretization (``quantize_gradients``) + the
  resolved integer kernel (int8 one-hot matmul with int32 accumulation
  on accelerators — ``matmul_int8`` — packed scatter on CPU);
- payload accounting per histogram psum for both modes
  (``hist_payload_bytes``: 3 x f32 channels vs 2 integer channels,
  int16-narrowed when the static rows x level bound allows) and the
  per-tree estimate (one masked pass per frontier level,
  ~log2(leaves) levels);
- a rescale sanity check: the integer histogram rescaled by the
  quantization scales must track the f32 histogram within the
  discretization step.

Tile-sweep mode (``--tile-sweep``, or the default small sweep inside
``run_probe``): for each row-tile size, report the HBM planner's
PREDICTED peak bytes (ops/planner.py memory model) next to the MEASURED
per-pass time (and measured peak where the device allocator reports
``memory_stats``) — the predicted-vs-measured table that validates the
planner's model at bench time.

Fused column (``--fused``, default on; ``--no-fused`` skips): the
histogram→split megakernel (ops/fused.py) vs the staged pipeline
(``build_histogram`` + ``feature_best_splits``) at one frontier level —
sec/level, HBM ``bytes_accessed`` from the compiler's cost model
(``obs/devprof.measure_program``), measured MFU for both, and the
accounting drop (``hist_scan_traffic_bytes``: the [ch, F, B] scan
re-read + sibling write/read the fused kernel never performs).

Autotune column (``--autotune``, default on; ``--no-autotune`` skips;
needs the fused column): banks the measured staged/fused sec-per-level
into the planner's timing store (ops/planner.py autotuner), then runs
the kernel election cold and warm so the journal shows the
analytic-elected vs measured-elected variant side by side with the
sec/level backing each, names the winner, and reports
``autotune_{hits,misses,flips}`` for bench_diff's election-quality gate.
Reports ``skipped`` when the store is switched off
(``LGBM_TPU_AUTOTUNE_DIR=off``).

The LAST stdout line is a single JSON object so bench.py can
bank it as a stage (``stage: hist_probe``, wired next to
``dispatch_probe``; ``BENCH_SKIP_HIST_PROBE=1`` skips the stage).

Usage:
    JAX_PLATFORMS=cpu python tools/hist_probe.py \
        [--rows 1000000] [--features 28] [--max-bin 63] \
        [--quant-bins 4] [--leaves 255] [--reps 5] \
        [--tile-sweep 0,262144,65536]
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _measured_peak():
    """Allocator peak bytes, 0 when the backend reports none."""
    import jax
    try:
        stats = jax.local_devices()[0].memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0))
    except Exception:
        return 0


def tile_sweep(binned_t, grad, hess, ones, B, tiles, reps, sync,
               leaves=255) -> list:
    """Predicted-vs-measured table per row-tile size (see module doc).

    The allocator's ``peak_bytes_in_use`` is a process-lifetime
    HIGH-WATER mark that cannot be reset, so the sweep runs in ASCENDING
    predicted-peak order (smallest tile first, untiled last): each
    config's high-water then reflects its own pass rather than an
    earlier larger one's.  The field is named
    ``measured_peak_bytes_highwater`` to say exactly that.
    """
    import jax

    from lightgbm_tpu.ops import histogram as H
    from lightgbm_tpu.ops import planner as P

    F, n = binned_t.shape
    variant = H.resolve_hist_method("auto")

    def predicted(t):
        return P.predict_peak_bytes(n, F, B, num_leaves=leaves,
                                    variant=variant, tile_rows=t,
                                    use_pack=(t == 0))[0]

    out = []
    for t in sorted(set(tiles), key=predicted):
        fn = jax.jit(lambda b, g, h, m, _t=t: H.build_histogram(
            b, g, h, m, B, tile_rows=(_t or None)))
        sync(fn(binned_t, grad, hess, ones))            # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            sync(fn(binned_t, grad, hess, ones))
        ms = (time.perf_counter() - t0) / reps * 1e3
        row = {"tile_rows": t,
               "ms_per_pass": round(ms, 2),
               "iters_per_sec": round(1e3 / max(ms, 1e-9), 2),
               "predicted_peak_bytes": predicted(t)}
        measured = _measured_peak()
        if measured:
            row["measured_peak_bytes_highwater"] = measured
        out.append(row)
    return out


def fused_probe(binned_t, grad, hess, ones, B, reps, leaves=255,
                slots=None) -> dict:
    """Fused megakernel vs staged pipeline at one frontier level.

    Staged = per-slot segment histogram + per-slot
    ``feature_best_splits`` scan (TWO stages with the [S, ch, F, B]
    histogram materialized between them); fused = ONE
    ``fused_segment_splits`` program.  Reports measured sec/level and
    MFU for both (``obs/devprof.measure_program``) plus the compiler's
    ``bytes_accessed`` so the per-level HBM-traffic drop is a measured
    number next to the ``hist_scan_traffic_bytes`` accounting term.
    """
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.obs.devprof import measure_program
    from lightgbm_tpu.ops import fused as FU
    from lightgbm_tpu.ops import histogram as H
    from lightgbm_tpu.ops.split import SplitHyperparams, feature_best_splits

    F, n = binned_t.shape
    # frontier width: one level of a `leaves`-leaf tree, capped at the
    # 8-candidate slice that keeps the staged comparator cheap
    S = int(slots) if slots else max(1, min(8, int(leaves) - 1))
    hp = SplitHyperparams(min_data_in_leaf=1)
    nb = jnp.full((F,), B, jnp.int32)
    zz = jnp.zeros((F,), jnp.int32)
    slot = jnp.asarray(np.random.RandomState(5).randint(0, S, n), jnp.int32)
    oh = slot[None, :] == jnp.arange(S)[:, None]
    sums = jnp.stack([jnp.sum(jnp.where(oh, grad[None, :], 0.0), axis=1),
                      jnp.sum(jnp.where(oh, hess[None, :], 0.0), axis=1),
                      jnp.sum(oh.astype(jnp.float32), axis=1)])
    iscat = jnp.zeros((F,), bool)

    def staged(b, g, h, m):
        seg = H.segment_histogram_sorted(b, g, h, m, slot, S, B,
                                         f32_vals=True) \
            if H.use_sorted_seghist() else \
            H.segment_histogram(b, g, h, m, slot, S, B)
        return jax.vmap(
            lambda hs, sg, sh, cnt: feature_best_splits(
                hs, sg, sh, cnt, nb, zz, zz, iscat, hp).gain
        )(seg, sums[0], sums[1], sums[2])

    def fused(b, g, h, m):
        _, best = FU.fused_segment_splits(
            b, H._vals_t(g, h, m), slot, S, B, sums, nb, zz, zz, hp)
        return best.gain

    args = (binned_t, grad, hess, ones)
    out = {"slots": S}
    for name, fn in (("staged", staged), ("fused", fused)):
        try:
            m = measure_program(jax.jit(fn), args, reps=reps)
            out[name] = {
                "sec_per_level": round(m["seconds_per_call"], 5),
                "mfu_measured": round(m.get("mfu", 0.0), 6),
                "hbm_bytes_accessed": int(m.get("bytes_accessed", 0)),
                "hbm_util": round(m.get("hbm_util", 0.0), 6),
            }
        except Exception as e:      # a variant may not lower here
            out[name] = {"error": str(e)[:160]}
    if "error" not in out.get("staged", {}) and \
            "error" not in out.get("fused", {}):
        out["speedup_vs_staged"] = round(
            out["staged"]["sec_per_level"]
            / max(out["fused"]["sec_per_level"], 1e-12), 3)
        sb = out["staged"]["hbm_bytes_accessed"]
        fb = out["fused"]["hbm_bytes_accessed"]
        if sb and fb:
            out["hbm_bytes_dropped"] = sb - fb
    # accounting twin: the scan re-read + sibling write/read the fused
    # arm deletes per level of S candidates (tests pin this formula)
    out["hist_scan_traffic_bytes"] = FU.hist_scan_traffic_bytes(S, F, B)
    from lightgbm_tpu.parallel.learners import fused_best_payload_bytes
    out["best_tuple_payload_bytes"] = fused_best_payload_bytes(F)
    return out


def autotune_probe(fused_result, rows, features, B, leaves) -> dict:
    """--autotune column: analytic-elected vs measured-elected variant.

    Feeds the fused column's measured staged/fused sec-per-level into
    the planner's persistent timing store (``record_timing``), running
    the election BEFORE the write (cold start or a prior run's
    measurements) and AFTER it (guaranteed warm), so the probe reports
    what the analytic model picks, what the stopwatch picks, the
    sec/level behind each, and the hit/miss/flip counters the bench
    stage journals for ``bench_diff``'s election-quality gate.
    """
    from lightgbm_tpu.ops import planner as P

    out = {"enabled": P.autotune_enabled(), "store_dir": P.autotune_dir()}
    if not (P.autotune_enabled() and P.autotune_dir()):
        out["skipped"] = ("the autotune store is switched off "
                          "(LGBM_TPU_AUTOTUNE / LGBM_TPU_AUTOTUNE_DIR)")
        return out
    staged = fused_result.get("staged", {})
    fus = fused_result.get("fused", {})
    if "error" in staged or "error" in fus:
        out["skipped"] = "staged or fused arm did not run"
        return out
    P.autotune_counters(reset=True)
    cold = P.plan_histograms(rows, features, B, num_leaves=leaves,
                             method="auto", fused_ok=True)
    P.record_timing(rows, features, B, False, 128, "staged",
                    staged["sec_per_level"])
    P.record_timing(rows, features, B, False, 128, "fused",
                    fus["sec_per_level"],
                    params={"feat_tile": cold.fused_feat_tile,
                            "block_rows": cold.fused_block_rows}
                    if cold.fused else None)
    warm = P.plan_histograms(rows, features, B, num_leaves=leaves,
                             method="auto", fused_ok=True)
    last = P.autotune_last()
    counters = P.autotune_counters()
    sec = {"staged": staged["sec_per_level"], "fused": fus["sec_per_level"]}
    out.update({
        "shape_bucket": warm.autotune_key,
        "analytic_variant": last.get("analytic_variant"),
        "measured_variant": last.get("measured_variant"),
        "elected_by": warm.elected_by,
        "elected_variant": last.get("elected_variant"),
        "winner": min(sec, key=sec.get),
        "sec_per_level": sec,
        "autotune_hits": counters["hits"],
        "autotune_misses": counters["misses"],
        "autotune_flips": counters["flips"],
    })
    return out


def run_probe(rows=1_000_000, features=28, max_bin=63, quant_bins=4,
              leaves=255, reps=5, tiles=None, fused=True,
              autotune=True) -> dict:
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops import histogram as H

    B = max_bin + 1
    rng = np.random.RandomState(0)
    binned_t = jnp.asarray(
        rng.randint(0, max_bin, (features, rows), dtype=np.int64), jnp.uint8)
    grad = jnp.asarray(rng.randn(rows), jnp.float32)
    hess = jnp.abs(grad) + 0.1
    ones = jnp.ones((rows,), jnp.float32)
    member = jnp.ones((rows,), bool)

    def sync(x):
        x.block_until_ready()

    out = {
        "rows": rows, "features": features, "max_bin": max_bin,
        "quant_bins": quant_bins,
        "platform": jax.devices()[0].platform,
        "f32_method": H.resolve_hist_method("auto"),
        "quant_method": H.resolve_hist_method("auto", quantized=True),
    }

    # ---- f32 pipeline -------------------------------------------------
    f32_fn = jax.jit(lambda b, g, h, m: H.build_histogram(b, g, h, m, B))
    sync(f32_fn(binned_t, grad, hess, ones))            # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        sync(f32_fn(binned_t, grad, hess, ones))
    f32_ms = (time.perf_counter() - t0) / reps * 1e3

    # ---- quantized pipeline (discretize + integer histogram) ----------
    levels = H.quant_levels(quant_bins)
    key = jax.random.PRNGKey(0)

    def quant_pass(b, g, h, w):
        gq, hq, gs, hs = H.quantize_gradients(g, h, w, quant_bins, key)
        hist = H.build_histogram_int(b, gq, hq, w > 0, B, levels=levels)
        return hist, gs, hs

    q_fn = jax.jit(quant_pass)
    hist_i, gs, hs = q_fn(binned_t, grad, hess, ones)
    sync(hist_i)                                        # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        sync(q_fn(binned_t, grad, hess, ones)[0])
    quant_ms = (time.perf_counter() - t0) / reps * 1e3

    # ---- rescale sanity: int sums * scale tracks the f32 sums ---------
    ref = np.asarray(f32_fn(binned_t, grad, hess, ones))
    hi = np.asarray(hist_i)
    g_err = np.abs(hi[0] * float(gs) - ref[0]).max()
    h_err = np.abs(hi[1] * float(hs) - ref[1]).max()
    # stochastic rounding error per row is < 1 level; per bin it grows
    # ~sqrt(rows_in_bin) — bound loosely by a few levels * sqrt(n/B)
    tol = 8.0 * max(float(gs), float(hs)) * max((rows / B) ** 0.5, 1.0)

    # ---- payload accounting -------------------------------------------
    f32_payload = H.hist_payload_bytes(features, B)
    quant_payload = H.hist_payload_bytes(features, B, rows, quant_bins)
    levels_per_tree = max(1.0, float(np.log2(leaves)))
    # ---- tile sweep: planner predicted-vs-measured per tile size ------
    if tiles is None:
        # default small sweep: untiled plus two power-of-two tiles
        p2 = 1 << max((rows // 4).bit_length() - 1, 10)
        tiles = [0, p2, max(p2 // 4, 1024)]
    sweep = tile_sweep(binned_t, grad, hess, ones, B, tiles, reps, sync,
                       leaves=leaves)

    # ---- fused megakernel vs staged pipeline (--fused column) ---------
    if fused:
        # interpret-mode emulation off-accelerator is slow at probe
        # scale: cap the fused comparison shape there (the on-device
        # bench worker runs the full size)
        if H.on_accelerator() or rows <= 200_000:
            fb, fg, fh, fo = binned_t, grad, hess, ones
        else:
            fb = binned_t[:, :200_000]
            fg, fh, fo = grad[:200_000], hess[:200_000], ones[:200_000]
        out["fused"] = fused_probe(fb, fg, fh, fo, B, reps, leaves=leaves)
        # the autotune column keys the store by the shape the stopwatch
        # actually measured (the capped one off-accelerator)
        out["fused"]["rows_measured"] = int(fb.shape[1])
        if autotune:
            out["autotune"] = autotune_probe(
                out["fused"], int(fb.shape[1]), features, B, leaves)

    out.update({
        "reps": reps,
        "tile_sweep": sweep,
        "f32": {"ms_per_pass": round(f32_ms, 2),
                "psum_payload_bytes": f32_payload,
                "psum_payload_bytes_per_tree":
                    int(f32_payload * levels_per_tree)},
        "quant": {"ms_per_pass": round(quant_ms, 2),
                  "psum_payload_bytes": quant_payload,
                  "psum_payload_bytes_per_tree":
                      int(quant_payload * levels_per_tree),
                  "psum_narrowed_int16":
                      H.quant_psum_narrow(rows, quant_bins),
                  "g_scale": float(gs), "h_scale": float(hs)},
        "payload_shrink": round(f32_payload / max(quant_payload, 1), 3),
        "speedup_vs_f32": round(f32_ms / max(quant_ms, 1e-9), 3),
        "rescale_abs_err": {"grad": round(float(g_err), 6),
                            "hess": round(float(h_err), 6),
                            "tol": round(tol, 6),
                            "ok": bool(g_err <= tol and h_err <= tol)},
    })
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--features", type=int, default=28)
    ap.add_argument("--max-bin", type=int, default=63)
    ap.add_argument("--quant-bins", type=int, default=4)
    ap.add_argument("--leaves", type=int, default=255)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--tile-sweep", type=str, default=None,
                    help="comma-separated row-tile sizes (0 = untiled); "
                         "default: a small automatic sweep")
    ap.add_argument("--fused", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="fused megakernel vs staged column (default on; "
                         "--no-fused skips)")
    ap.add_argument("--autotune", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="measured-vs-analytic election column (default "
                         "on; needs --fused and a configured timing "
                         "store; --no-autotune skips)")
    args = ap.parse_args()
    tiles = None
    if args.tile_sweep:
        tiles = [max(int(v), 0) for v in args.tile_sweep.split(",") if v]
    out = run_probe(args.rows, args.features, args.max_bin, args.quant_bins,
                    args.leaves, args.reps, tiles=tiles, fused=args.fused,
                    autotune=args.autotune)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
