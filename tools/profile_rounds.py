"""On-chip decomposition of the rounds grower's per-round cost.

Times every candidate bottleneck of a round individually, with a
device-to-host copy of a small reduction of the result as the sync
barrier (it cannot complete early; ``sync_check`` compares it with
``block_until_ready``), banking results to JSON after each stage.  A
host-clock decomposition: device busy/idle shares come from a profiler
trace, not from here.

Run ALONE (one process per chip):  python tools/profile_rounds.py out.json

Stages:
  sync_check        block_until_ready vs D2H-copy timing of one matmul pass
  hist_full         full-pass histogram variants at 1M x 28 x 64
  hist_seg_scatter  segment_histogram (XLA scatter) at cap 512k, S=128
  seg_matmul_s16    segment hist as combined-onehot matmul, S=16 (FLOP wall)
  nonzero_compact   jnp.nonzero(size=cap) + row gather at several n
  sort_i32          jnp.sort / argsort of i32 keys at several n
  while_overhead    lax.while_loop step cost vs body size
  fori_hist         fori_loop of k compacted pallas histograms (design B)
  scatter_slices    scatter-add of nb [F*B*3] slices (grouped-block commit)
"""
import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from lightgbm_tpu.utils.platform import enable_compile_cache  # noqa: E402

enable_compile_cache()

OUT = sys.argv[1] if len(sys.argv) > 1 else os.path.join(REPO, "profile_rounds.json")
T0 = time.time()
DATA = {"started_utc": time.strftime("%Y-%m-%d %H:%M:%S", time.gmtime()),
        "stages": []}


def bank(stage, **kw):
    kw["stage"] = stage
    kw["t_elapsed"] = round(time.time() - T0, 1)
    DATA["stages"].append(kw)
    tmp = OUT + ".tmp"
    # manual tmp+os.replace below; stdlib-only probe must stay
    # importable before jax/package init
    with open(tmp, "w") as f:  # tpulint: disable=atomic-write
        json.dump(DATA, f, indent=1, default=str)
    os.replace(tmp, OUT)
    print(f"[profile] {stage}: {json.dumps(kw, default=str)[:400]}", flush=True)


def guard(stage, fn, *a, **kw):
    if os.environ.get(f"PR_SKIP_{stage.upper()}") == "1":
        bank(stage, skipped=True)
        return None
    t1 = time.time()
    try:
        r = fn(*a, **kw)
        out = dict(r) if isinstance(r, dict) else {"result": r}
        out["stage_seconds"] = round(time.time() - t1, 1)
        bank(stage, **out)
        return r
    except Exception as e:
        bank(stage, error=str(e)[-400:], tb=traceback.format_exc()[-1200:])
        return None


def d2h_time(fn, *args, reps=5):
    """Median wall time of fn(*args) synced by a D2H copy of a reduction.

    jnp.sum(out) adds negligible work; np.asarray cannot return before the
    whole computation has finished, unlike a possibly-lazy
    block_until_ready on this experimental backend.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    red = jax.jit(lambda *a: jnp.sum(
        jax.tree_util.tree_reduce(lambda x, y: jnp.sum(x) + jnp.sum(y),
                                  fn(*a), jnp.float32(0.0))))
    float(np.asarray(red(*args)))          # compile + warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        float(np.asarray(red(*args)))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return round(ts[len(ts) // 2] * 1e3, 3)   # median ms


SMALL = os.environ.get("PR_SMALL") == "1"   # CPU smoke-test mode


def _scale(n):
    return max(4096, n // 64) if SMALL else n


def make_inputs(n, f=28, bins=64, seed=0):
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.RandomState(seed)
    binned = jnp.asarray(rng.randint(0, bins - 1, (f, n), dtype=np.int64),
                         jnp.uint8)
    grad = jnp.asarray(rng.randn(n), jnp.float32)
    hess = jnp.abs(grad) + 0.1
    mask = jnp.ones((n,), jnp.float32)
    return binned, grad, hess, mask


def stage_sync_check():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from lightgbm_tpu.ops import histogram as H
    binned, grad, hess, mask = make_inputs(_scale(1_000_000))
    fn = jax.jit(lambda b, g, h, m: H.build_histogram(b, g, h, m, 64,
                                                      method="matmul"))
    out = fn(binned, grad, hess, mask)
    out.block_until_ready()
    # block_until_ready timing (the round-4 probe protocol)
    t0 = time.perf_counter()
    for _ in range(3):
        fn(binned, grad, hess, mask).block_until_ready()
    bur_ms = (time.perf_counter() - t0) / 3 * 1e3
    # D2H-synced timing
    d2h_ms = d2h_time(lambda b, g, h, m: H.build_histogram(
        b, g, h, m, 64, method="matmul"), binned, grad, hess, mask)
    return {"block_until_ready_ms": round(bur_ms, 3), "d2h_ms": d2h_ms,
            "suspect_lazy_sync": bool(d2h_ms > 4 * bur_ms + 1)}


def stage_hist_full():
    from lightgbm_tpu.ops import histogram as H
    binned, grad, hess, mask = make_inputs(_scale(1_000_000))
    out = {}
    for method in ("matmul", "matmul_f32", "scatter", "pallas"):
        try:
            out[f"{method}_ms"] = d2h_time(
                lambda b, g, h, m, _m=method: H.build_histogram(
                    b, g, h, m, 64, method=_m), binned, grad, hess, mask)
        except Exception as e:
            out[f"{method}_ms"] = f"error: {str(e)[:120]}"
    return out


def stage_hist_seg_scatter():
    import jax.numpy as jnp
    from lightgbm_tpu.ops import histogram as H
    out = {}
    for n, S in ((_scale(512 * 1024), 128), (_scale(512 * 1024), 16),
                 (_scale(65536), 128)):
        binned, grad, hess, mask = make_inputs(n)
        slot = (jnp.arange(n, dtype=jnp.int32) % S)
        try:
            out[f"n{n}_S{S}_ms"] = d2h_time(
                lambda b, g, h, m, s, _S=S: H.segment_histogram(
                    b, g, h, m, s, _S, 64), binned, grad, hess, mask, slot)
        except Exception as e:
            out[f"n{n}_S{S}_ms"] = f"error: {str(e)[:120]}"
    return out


def stage_seg_matmul_s16():
    """Combined (slot,bin) one-hot matmul — viable only for small S."""
    import jax.numpy as jnp
    from jax import lax

    def seg_mm(binned, grad, hess, mask, slot, S, B):
        F, n = binned.shape
        binned = binned.T
        vals = jnp.stack([grad, hess, jnp.ones_like(grad)], 1) * mask[:, None]
        C = 4096
        nb = n // C
        bb = binned.reshape(nb, C, F)
        sb = slot.reshape(nb, C)
        vb = vals.reshape(nb, C, 3)
        iota = jnp.arange(S * B, dtype=jnp.int32)

        def body(acc, blk):
            b, s, v = blk
            comb = s[:, None].astype(jnp.int32) * B + b.astype(jnp.int32)
            oh = (comb[:, :, None] == iota).astype(jnp.bfloat16)
            oh2 = oh.reshape(C, F * S * B)
            part = lax.dot(v.astype(jnp.bfloat16).T, oh2,
                           preferred_element_type=jnp.float32)
            return acc + part, None

        acc, _ = lax.scan(body, jnp.zeros((3, F * S * B), jnp.float32),
                          (bb, sb, vb))
        return acc

    n, S, B = _scale(512 * 1024), 16, 64
    binned, grad, hess, mask = make_inputs(n)
    slot = (jnp.arange(n, dtype=jnp.int32) % S)
    return {"n512k_S16_ms": d2h_time(
        lambda b, g, h, m, s: seg_mm(b, g, h, m, s, S, B),
        binned, grad, hess, mask, slot)}


def stage_nonzero_compact():
    import jax.numpy as jnp
    out = {}
    for n in (_scale(1_000_000), _scale(5_500_000), _scale(11_000_000)):
        binned, grad, hess, mask = make_inputs(n, seed=1)
        member = (grad > 0)
        cap = n // 2 + 65536

        def compact(b, mem, _cap=cap, _n=n):
            idx = jnp.nonzero(mem, size=_cap, fill_value=_n)[0]
            idxc = jnp.minimum(idx, _n - 1)
            return jnp.take(b, idxc, axis=1)

        try:
            out[f"n{n}_ms"] = d2h_time(compact, binned, member)
        except Exception as e:
            out[f"n{n}_ms"] = f"error: {str(e)[:120]}"
    return out


def stage_sort_i32():
    import jax.numpy as jnp
    import numpy as np
    out = {}
    for n in (_scale(512 * 1024), _scale(5_500_000)):
        keys = jnp.asarray(np.random.RandomState(0).randint(0, 128, n),
                           jnp.int32)
        try:
            out[f"sort_n{n}_ms"] = d2h_time(jnp.sort, keys)
            out[f"argsort_n{n}_ms"] = d2h_time(jnp.argsort, keys)
        except Exception as e:
            out[f"n{n}_ms"] = f"error: {str(e)[:120]}"
    return out


def stage_while_overhead():
    import jax.numpy as jnp
    from jax import lax
    out = {}
    for nops in (8, 64, 512):
        def body(c, _k=nops):
            i, x = c
            for _ in range(_k):
                x = x * 1.000001 + 1e-7
            return i + 1, x

        def run(x0):
            return lax.while_loop(lambda c: c[0] < 254,
                                  body, (jnp.int32(0), x0))[1]

        ms = d2h_time(run, jnp.ones((8, 128), jnp.float32))
        out[f"body{nops}ops_254steps_ms"] = ms
        out[f"body{nops}ops_per_step_us"] = round(ms / 254 * 1e3, 1)
    return out


def stage_fori_hist():
    """Design B prototype: k sequential compacted pallas histograms."""
    import jax.numpy as jnp
    from jax import lax
    from lightgbm_tpu.ops import histogram as H

    n, S, B = _scale(1_000_000), 14, 64
    binned, grad, hess, mask = make_inputs(n)
    slot = (jnp.arange(n, dtype=jnp.int32) % 137) % (S + 3)  # ~n/17 per slot
    caps = [n, n // 2, n // 4, n // 8, n // 16, n // 32]
    caps = [(c + 4095) // 4096 * 4096 for c in caps]

    def one(b, g, h, m, s):
        def body(i, acc):
            mem = (s == i) & (m > 0)
            cnt = jnp.sum(mem)

            def branch(cap):
                def run():
                    idx = jnp.nonzero(mem, size=cap, fill_value=n)[0]
                    idxc = jnp.minimum(idx, n - 1)
                    rows = jnp.take(b, idxc, axis=1)
                    w = jnp.where(idx < n, jnp.take(m, idxc), 0.0)
                    return H.build_histogram(rows, jnp.take(g, idxc),
                                             jnp.take(h, idxc), w, B,
                                             method="pallas")
                return run
            bucket = jnp.sum(jnp.asarray(caps, jnp.int32) >= cnt) - 1
            hist = lax.switch(bucket, [branch(c) for c in caps])
            return acc.at[i].set(hist)

        return lax.fori_loop(0, S, body,
                             jnp.zeros((S, 28, B, 3), jnp.float32))

    return {"k14_seq_compact_pallas_ms": d2h_time(
        one, binned, grad, hess, mask, slot)}


def stage_scatter_slices():
    """Scatter-add nb [F*B*3]-slices into S slots (grouped-block commit)."""
    import jax.numpy as jnp
    import numpy as np
    nb, S = 1024, 128
    F, B = 28, 64
    parts = jnp.asarray(np.random.RandomState(0).rand(nb, F * B * 3),
                        jnp.float32)
    sl = jnp.asarray(np.random.RandomState(1).randint(0, S, nb), jnp.int32)

    def commit(p, s):
        return jnp.zeros((S, F * B * 3), jnp.float32).at[s].add(p)

    return {"nb1024_slices_ms": d2h_time(commit, parts, sl)}


def main():
    t = time.time()
    try:
        import jax
        devs = jax.devices()
        import jax.numpy as jnp
        jnp.ones((8, 8)).sum().block_until_ready()
    except Exception as e:
        bank("init", error=str(e)[-400:])
        return 3
    d = devs[0]
    bank("init", seconds=round(time.time() - t, 1), platform=d.platform,
         kind=getattr(d, "device_kind", ""))
    if d.platform == "cpu" and os.environ.get("PR_ALLOW_CPU") != "1":
        bank("abort", reason="backend resolved to cpu")
        return 3

    guard("sync_check", stage_sync_check)
    guard("hist_full", stage_hist_full)
    guard("hist_seg_scatter", stage_hist_seg_scatter)
    guard("seg_matmul_s16", stage_seg_matmul_s16)
    guard("nonzero_compact", stage_nonzero_compact)
    guard("sort_i32", stage_sort_i32)
    guard("while_overhead", stage_while_overhead)
    guard("fori_hist", stage_fori_hist)
    guard("scatter_slices", stage_scatter_slices)
    bank("done", total_seconds=round(time.time() - T0, 1))
    return 0


if __name__ == "__main__":
    import jax.numpy as jnp  # noqa: F401  (stages assume jnp importable)
    sys.exit(main())
