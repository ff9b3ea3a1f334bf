"""Model-axis sweep micro-bench: aggregate boosting throughput vs B.

One booster's macro-chunk program cannot fill the MXU at small-data
shapes; the batched multi-booster plane (lightgbm_tpu/multi/) stacks B
boosters along a vmapped lane axis of ONE program over ONE shared binned
matrix.  This probe measures exactly that claim: the SAME chunk body is
compiled solo (B=1) and vmapped at B in {2, 4, 8} over heterogeneous
per-lane inputs (learning rates, bagging masks), and the table reports
per-dispatch latency, aggregate boosting iterations/sec and the
compiler-measured MFU per batch width (obs/devprof.measure_program), next
to the planner's lane-chunk verdict (ops.planner.plan_model_batch).

Acceptance (enforced on accelerator backends only — a CPU host has no
idle MXU to fill, so there the table is informational): B=8 aggregate
iters/sec >= 4x B=1.  A missed bar raises, so failed sweep runs are
never journaled (bench.py run_stage contract).

Usage: python tools/sweep_probe.py [--rows N] [--features F] [--reps R]
Prints one JSON object; bench.py wires this as the journaled ``sweep``
stage (BENCH_SKIP_SWEEP=1 skips).
"""

import argparse
import json
import sys

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

BATCH_WIDTHS = (1, 2, 4, 8)


def run_probe(rows=200_000, features=28, max_bin=63, leaves=31,
              chunk=8, reps=3, widths=BATCH_WIDTHS) -> dict:
    import jax
    import jax.numpy as jnp

    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting.macro import chunk_host_inputs, make_chunk_fn
    from lightgbm_tpu.obs.devprof import measure_program
    from lightgbm_tpu.ops.histogram import on_accelerator
    from lightgbm_tpu.ops.planner import plan_model_batch

    rng = np.random.RandomState(0)
    n, F = int(rows), int(features)
    X = rng.randn(n, F)
    y = (X[:, 0] + 0.5 * X[:, 1] + 0.1 * rng.randn(n) > 0).astype(np.float64)
    ds = lgb.Dataset(X, label=y, params={"max_bin": int(max_bin)},
                     free_raw_data=False)
    ds.construct()

    device = None
    try:
        device = jax.devices()[0]
    except Exception:
        pass

    widths = tuple(sorted(int(w) for w in widths))
    out = {"rows": n, "features": F, "max_bin": int(max_bin),
           "leaves": int(leaves), "chunk": int(chunk),
           "batch_widths": list(widths)}
    c = int(chunk)
    for B in widths:
        # heterogeneous lanes: per-lane lr + bagging keep the dispatch
        # honest (identical lanes would let XLA CSE the whole batch)
        boosters = [lgb.Booster(
            {"objective": "binary", "num_leaves": int(leaves),
             "max_bin": int(max_bin), "verbosity": -1,
             "deterministic": True,
             "learning_rate": 0.05 + 0.02 * i,
             "bagging_fraction": 0.9 - 0.05 * (i % 4),
             "bagging_freq": 1, "bagging_seed": 7 + i},
            train_set=ds) for i in range(B)]
        bs = [b.boosting for b in boosters]
        for b in bs:
            b.boost_from_average()
        xs_l = [chunk_host_inputs(b, c)[0] for b in bs]
        xs_B = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *xs_l)
        score_B = jnp.stack([b.train_score for b in bs])
        cu_B = jnp.stack([b._cegb_state[0] for b in bs])
        cr_B = jnp.stack([b._cegb_state[1] for b in bs])
        gc, hc = bs[0]._macro_const_grads()
        # measurement twin of multi/batch.py's program, WITHOUT score
        # donation: measure_program re-invokes with the same buffers
        fn_B = jax.jit(jax.vmap(
            make_chunk_fn(bs[0]),
            in_axes=(None, 0, 0, 0, None, 0, None, None, None, None, None)))
        args = (bs[0].binned, score_B, cu_B, cr_B, np.int32(c), xs_B,
                bs[0]._macro_ctx["label"], bs[0]._macro_ctx["weight"],
                gc, hc, bs[0]._macro_ctx["obj_tables"])
        m = measure_program(fn_B, args, reps=reps, device=device)
        sec = m["seconds_per_call"]
        out[f"B{B}"] = {
            "seconds_per_dispatch": sec,
            "iters_per_sec": (B * c) / sec if sec > 0 else 0.0,
            "mfu_measured": m.get("mfu"),
            "flops": m.get("flops"),
            "bytes_accessed": m.get("bytes_accessed"),
        }

    cfg = bs[0].grower_cfg
    out["model_batch_plan"] = plan_model_batch(
        b_total=max(widths), rows=bs[0].num_data, features=F,
        num_bins=bs[0].num_bins, num_leaves=int(leaves),
        stacked=False, method=cfg.hist_method,
        round_width=cfg.round_width, tile_rows=cfg.tile_rows).summary()

    b1 = out[f"B{min(widths)}"]["iters_per_sec"]
    bmax = out[f"B{max(widths)}"]["iters_per_sec"]
    out["aggregate_speedup_vs_b1"] = (bmax / b1) if b1 > 0 else 0.0
    out["accel"] = bool(on_accelerator())
    if out["accel"] and 8 in widths and 1 in widths:
        speedup8 = out["B8"]["iters_per_sec"] / out["B1"]["iters_per_sec"]
        if speedup8 < 4.0:
            raise RuntimeError(
                "sweep probe: B=8 aggregate throughput "
                f"{speedup8:.2f}x B=1 — below the 4x acceptance bar; "
                "the model axis is not filling the chip")
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, default=200_000)
    ap.add_argument("--features", type=int, default=28)
    ap.add_argument("--max-bin", type=int, default=63)
    ap.add_argument("--leaves", type=int, default=31)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--reps", type=int, default=3)
    a = ap.parse_args()
    print(json.dumps(run_probe(rows=a.rows, features=a.features,
                               max_bin=a.max_bin, leaves=a.leaves,
                               chunk=a.chunk, reps=a.reps), indent=2))


if __name__ == "__main__":
    main()
