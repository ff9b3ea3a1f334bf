"""Decompose per-split cost of grow_tree on the live backend.

Times standalone jitted sub-ops at bench shapes, then whole grow_tree at
several leaf budgets to extract the per-iteration (per-split) cost.

Usage: python tools/profile_grow.py [n_rows] [max_bin]
"""
import functools
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from lightgbm_tpu.utils.platform import enable_compile_cache
enable_compile_cache()

import jax
import jax.numpy as jnp

N = int(sys.argv[1]) if len(sys.argv) > 1 else 500_000
MAX_BIN = int(sys.argv[2]) if len(sys.argv) > 2 else 63
F = 28


def timeit(fn, *args, reps=5, warmup=1):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps


def main():
    print("backend:", jax.default_backend(), jax.devices())
    rng = np.random.RandomState(0)
    X = rng.rand(N, F).astype(np.float32)
    w = rng.randn(F).astype(np.float32)
    y = ((X @ w) > 0).astype(np.float32)

    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops import histogram as H
    from lightgbm_tpu.ops import split as S
    from lightgbm_tpu import grower as GR

    ds = lgb.Dataset(X, label=y, params={"max_bin": MAX_BIN})
    ds.construct()
    meta = ds.feature_meta()
    binned = jnp.asarray(np.ascontiguousarray(ds.binned.T))   # [G, n]
    G, n = binned.shape
    B = MAX_BIN + 1
    grad = jnp.asarray(rng.randn(n).astype(np.float32))
    hess = jnp.abs(grad) + 0.1
    mask = jnp.ones((n,), jnp.float32)
    member = jnp.asarray(rng.rand(n) < 0.25)

    print(f"n={n} G={G} B={B}")

    # -- sub-ops
    for method in ("matmul", "pallas", "scatter"):
        fn = jax.jit(functools.partial(H.build_histogram, num_bins=B,
                                       method=method))
        t = timeit(fn, binned, grad, hess, mask)
        print(f"hist[{method}] full-n: {t*1e3:.3f} ms")

    caps = H.capacity_schedule(n)
    print("caps:", caps)
    fn = jax.jit(functools.partial(H.compacted_histogram, num_bins=B,
                                   caps=caps, method="pallas"))
    t = timeit(fn, binned, grad, hess, mask, member)
    print(f"compacted hist (25% member): {t*1e3:.3f} ms")

    nz = jax.jit(lambda m: jnp.nonzero(m, size=caps[1], fill_value=n)[0])
    t = timeit(nz, member)
    print(f"nonzero(size={caps[1]}): {t*1e3:.3f} ms")

    hist = jax.jit(functools.partial(H.build_histogram, num_bins=B,
                                     method="pallas"))(
        binned, grad, hess, mask)
    m = meta.resolved()
    sg = jnp.sum(grad); sh = jnp.sum(hess); cnt = jnp.asarray(float(n))
    hp = S.SplitHyperparams()
    bs = jax.jit(lambda h: S.best_split_for_leaf(
        h, sg, sh, cnt, jnp.asarray(m.num_bin), jnp.asarray(m.missing_type),
        jnp.asarray(m.default_bin), jnp.asarray(m.is_categorical), hp))
    t = timeit(bs, hist)
    print(f"best_split_for_leaf: {t*1e3:.3f} ms")

    # partition update
    def part(leaf_id, thr):
        col = jnp.take(binned, 3, axis=0).astype(jnp.int32)
        gl = col <= thr
        in_leaf = leaf_id == 0
        return jnp.where(in_leaf & ~gl, 7, leaf_id)
    pj = jax.jit(part)
    t = timeit(pj, jnp.zeros(n, jnp.int32), jnp.asarray(30))
    print(f"partition update: {t*1e3:.3f} ms")

    # -- segment histogram (the rounds grower's hot op)
    from lightgbm_tpu.ops.histogram import compacted_segment_histogram
    L = 255
    slot = jnp.asarray(np.where(rng.rand(n) < 0.5,
                                rng.randint(0, 128, n), L).astype(np.int32))
    sh_fn = jax.jit(functools.partial(compacted_segment_histogram,
                                      num_slots=L, num_bins=B, caps=caps))
    t = timeit(sh_fn, binned, grad, hess, mask, slot)
    print(f"compacted segment hist (50% rows, 128 slots): {t*1e3:.3f} ms")

    # -- whole tree growth: rounds vs serial
    from lightgbm_tpu.grower import GrowerConfig, grow_tree
    from lightgbm_tpu.grower_rounds import grow_tree_rounds
    for name, fn_, leaves in (("rounds", grow_tree_rounds, 255),
                              ("rounds", grow_tree_rounds, 63),
                              ("serial", grow_tree, 255)):
        cfg = GrowerConfig(num_leaves=leaves, num_bins=B, hp=hp,
                           hist_method="pallas", compact=True)
        gt = jax.jit(functools.partial(fn_, meta=meta, cfg=cfg))
        t0 = time.perf_counter()
        out = gt(binned, grad, hess, mask)
        jax.block_until_ready(out)
        tc = time.perf_counter() - t0
        t = timeit(gt, binned, grad, hess, mask, reps=3, warmup=1)
        print(f"grow[{name}] leaves={leaves}: {t*1e3:.1f} ms "
              f"(compile {tc:.1f}s, num_leaves="
              f"{int(out[0].num_leaves)})", flush=True)

    print("done")


if __name__ == "__main__":
    main()
