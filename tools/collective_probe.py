"""Collective micro-bench: per-tier psum payload bytes + reduction latency.

Measures one histogram reduction under each schedule the pod-scale plane
can elect (parallel/collectives.py) over a hybrid ("dcn", "ici") mesh —

- **flat**: one psum over both data axes (the XLA runtime schedules it);
- **hierarchical**: psum over the fast ICI tier, then the slow DCN tier;
- **voting**: ICI reduction of the full histogram, then only the top-k
  elected feature columns cross DCN (PV-Tree's bandwidth saver,
  grower.py ``leaf_best_voting``);

for the f32 AND quantized-integer payloads, next to the planner's
byte accounting (``ops.planner.plan_collectives`` — ici_bytes /
dcn_bytes per schedule).  Off-pod the latency numbers are virtual-mesh
relative figures; the BYTES are exact and are the acceptance signal:
voting's DCN bytes must sit strictly below data-parallel's at equal
trees on the same workload.

Usage: python tools/collective_probe.py [--rows N] [--features F]
       [--slices S] [--top-k K] [--reps R]
Prints one JSON object; bench.py wires this as the journaled
``collective_probe`` stage (BENCH_SKIP_COLLECTIVE_PROBE=1 skips).
"""

import argparse
import functools
import json
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def run_probe(rows=200_000, features=28, max_bin=63, quant_bins=4,
              leaves=255, trees=100, num_slices=2, top_k=8,
              reps=5) -> dict:
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from lightgbm_tpu.ops import histogram as H
    from lightgbm_tpu.ops.planner import plan_collectives
    from lightgbm_tpu.parallel.collectives import (DCN_AXIS, HYBRID_AXES,
                                                   ICI_AXIS)
    from lightgbm_tpu.parallel.learners import make_hybrid_mesh

    nd = jax.device_count()
    s = max(1, min(int(num_slices), nd))
    while nd % s != 0 and s > 1:
        s -= 1
    mesh = make_hybrid_mesh(nd - nd % s if s > 1 else nd, num_slices=s)
    d = int(mesh.shape[ICI_AXIS])
    B = max_bin + 1
    F = int(features)
    k = min(int(top_k), F)
    levels_per_tree = max(1.0, float(np.log2(max(leaves, 2))))
    rows_g = int(rows)

    rng = np.random.RandomState(0)
    hist_f = rng.randn(3, F, B).astype(np.float32)
    hist_i = rng.randint(-1000, 1000, (2, F, B)).astype(np.int32)

    def timed(fn, *args):
        r = fn(*args)
        jax.tree_util.tree_map(
            lambda x: np.asarray(x), r)                    # compile + sync
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.tree_util.tree_map(lambda x: np.asarray(x), fn(*args))
        return (time.perf_counter() - t0) / reps * 1e3

    def sched(body):
        return jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                                out_specs=P(), check_vma=False)

    def flat(h):
        return lax.psum(h, HYBRID_AXES)

    def hier(h):
        return lax.psum(lax.psum(h, ICI_AXIS), DCN_AXIS)

    def vote(h):
        local = lax.psum(h, ICI_AXIS)
        # slice-level election stand-in: the top-k gain columns by |grad|
        score = jnp.abs(local[0]).sum(axis=-1)
        _, elected = lax.top_k(score, k)
        sub = lax.psum(local[:, elected], DCN_AXIS)
        return local.at[:, elected].set(sub)

    measured = {}
    for name, arr in (("f32", jnp.asarray(hist_f)),
                      ("quant", jnp.asarray(hist_i))):
        measured[name] = {
            "flat_ms": round(timed(jax.jit(sched(flat)), arr), 4),
            "hier_ms": round(timed(jax.jit(sched(hier)), arr), 4),
            "voting_ms": round(timed(jax.jit(sched(vote)), arr), 4),
        }

    # ---- planner byte accounting (the acceptance signal) ---------------
    out = {
        "rows": rows_g, "features": F, "max_bin": max_bin,
        "leaves": leaves, "trees": trees, "top_k": k,
        "mesh_shape": [s, d], "platform": jax.devices()[0].platform,
        "reps": reps, "measured_ms": measured,
    }
    for name, quant in (("f32", False), ("quant", True)):
        data = plan_collectives(
            features=F, num_bins=B, rows_global=rows_g, quant=quant,
            quant_bins=quant_bins, num_slices=s, devices_per_slice=d,
            voting_k=0)
        voting = plan_collectives(
            features=F, num_bins=B, rows_global=rows_g, quant=quant,
            quant_bins=quant_bins, num_slices=s, devices_per_slice=d,
            voting_k=k)
        reductions = levels_per_tree * trees
        out[name] = {
            "payload_bytes": data.payload_bytes,
            "data_parallel": dict(
                data.summary(),
                dcn_bytes_per_tree=int(data.dcn_bytes * levels_per_tree),
                dcn_bytes_total=int(data.dcn_bytes * reductions)),
            "voting_parallel": dict(
                voting.summary(),
                dcn_bytes_per_tree=int(voting.dcn_bytes * levels_per_tree),
                dcn_bytes_total=int(voting.dcn_bytes * reductions)),
            "voting_dcn_below_data": bool(
                s <= 1 or voting.dcn_bytes < data.dcn_bytes),
        }
    out["hierarchy_elected"] = bool(out["f32"]["data_parallel"]
                                    ["hierarchy_elected"])
    out["ici_bytes"] = int(out["f32"]["data_parallel"]["ici_bytes"])
    out["dcn_bytes"] = int(out["f32"]["data_parallel"]["dcn_bytes"])
    out["voting_k"] = k
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=200_000)
    ap.add_argument("--features", type=int, default=28)
    ap.add_argument("--max-bin", type=int, default=63)
    ap.add_argument("--quant-bins", type=int, default=4)
    ap.add_argument("--leaves", type=int, default=255)
    ap.add_argument("--trees", type=int, default=100)
    ap.add_argument("--slices", type=int, default=2)
    ap.add_argument("--top-k", type=int, default=8)
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()
    out = run_probe(rows=args.rows, features=args.features,
                    max_bin=args.max_bin, quant_bins=args.quant_bins,
                    leaves=args.leaves, trees=args.trees,
                    num_slices=args.slices, top_k=args.top_k,
                    reps=args.reps)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
