"""Fused multi-iteration boosting: ``lax.scan`` macro-steps.

The per-iteration training step (gbdt.py ``iter_body``) is one jitted
device program, but the engine still launches it once per boosting round
from Python, and the fixed per-dispatch cost is what a small problem
pays most of its time for.  This module wraps the SAME ``iter_body`` in a
``lax.scan`` over a chunk of ``c`` iterations inside one jitted,
score-donating program, so ``num_boost_round`` trees cost
``ceil(rounds/c)`` dispatches instead of ``rounds``.

Everything the scan needs is device-resident or precomputable per chunk:

- gradients recompute from the carried score (the booster's
  ``gradients_fn`` closure, traced INSIDE the scan body; an objective's
  ``device_tables`` ride in as a runtime argument, ``obj_tables``);
- bagging masks are host-RNG draws -> stacked ``[c, n_pad]`` input;
- per-tree feature masks -> stacked ``[c, K, F]`` input;
- learning-rate schedules (reset_parameter) -> ``[c]`` array;
- per-iteration node keys -> stacked PRNG keys;
- GOSS masks derive from the in-scan gradients + precomputed subkeys,
  and the last round's row weights ride out beside the trees;
- RF's running-mean renormalization rides on a ``[c]`` iteration-index
  array (``score*it`` pre / ``(score+init)/(it+1)`` post, as in rf.py).

The scan stacks per-iteration ``TreeArrays`` so the host fetches ONE
``[c, ...]`` tree bundle per chunk (feeding gbdt.py's deferred-host
drain).  Chunked training is bit-identical to per-iteration training —
the scanned program composes the same ``iter_body`` — which
tests/test_macro.py asserts byte-for-byte on saved model text.

Compile-time note: every shape in the chunk program is keyed by
``n_pad``, so with shape buckets on (``ops.planner.bucket_rows``;
docs/PERF.md "shape buckets") nearby dataset sizes land on the same
rung and REUSE one compiled chunk program instead of building a fresh
one per exact row count.

Memory: the chunk program composes ``iter_body`` over the booster's
``grower_cfg``, so the HBM budget plan (ops/planner.py ``tile_rows`` /
``hist_pack``, chosen at ``_build_jit_fns`` time with per-shard rows)
governs the fused program exactly as it governs per-iteration training —
histogram transients inside the scan stay O(tile), and tiled chunked
training is byte-identical to untiled per-iteration training
(tests/test_macro.py tiled parity rows).

Env gate: ``LGBM_TPU_CHUNK`` — unset/"on"/"auto" = default cap (32),
"0"/"off" disables, a positive integer sets the cap (1 disables fusion).
The chunk SCHEDULER (engine.py) picks the distance to the next boundary
that genuinely needs the host (eval per ``metric_freq``, snapshots,
end-of-training) and rounds down to a power of two so at most
``log2(cap)+1`` program shapes ever compile.
"""

from __future__ import annotations

import os
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..obs.metrics import global_registry as _obs_registry
from ..obs.trace import span as _span

DEFAULT_CHUNK_CAP = 32


def chunk_cap() -> int:
    """Resolve the LGBM_TPU_CHUNK env gate to a max chunk size (0 = off)."""
    env = os.environ.get("LGBM_TPU_CHUNK", "").strip().lower()
    if env in ("0", "off", "false", "no"):
        return 0
    if env in ("", "on", "true", "auto", "default"):
        return DEFAULT_CHUNK_CAP
    try:
        return max(0, int(env))
    except ValueError:
        return DEFAULT_CHUNK_CAP


def pow2_chunk(distance: int, cap: int) -> int:
    """Largest power of two <= min(distance, cap); bounds the number of
    distinct compiled chunk shapes to log2(cap)+1."""
    d = min(distance, cap)
    if d < 1:
        return 1
    c = 1
    while c * 2 <= d:
        c *= 2
    return c


def _ix(arr, j):
    return lax.dynamic_index_in_dim(arr, j, 0, keepdims=False)


def make_chunk_fn(b):
    """The UNJITTED chunk callable for booster ``b`` — the body shared by
    the solo jitted program (``build_chunk_program``) and the batched
    model-axis program (``lightgbm_tpu/multi/batch.py``), which wraps the
    SAME callable in ``jax.vmap`` over a leading booster axis.  Batched
    training composes this exact body, so batch-invariance inherits the
    chunk program's bit-parity discipline wherever the elected histogram
    variant accumulates order-invariantly (scatter / integer paths —
    docs/PERF.md "model axis").

    The loop is a ``fori_loop`` whose trip count ``n_steps`` is a RUNTIME
    scalar (always equal to the static chunk capacity ``c`` carried by the
    input shapes).  The runtime bound is load-bearing for bit-parity: with
    a static trip count XLA unrolls short loops into straight-line code,
    where XLA:CPU contracts the leaf-value-scale + gather + score-add of
    ``iter_body`` into an FMA (observed at num_class > 1; neither
    ``optimization_barrier`` nor ``--xla_allow_excess_precision=false``
    prevents it) — while loop bodies keep the two-rounding form.  A
    dynamic bound forces the SAME loop-body codegen at every chunk size,
    including c=1, which is why per-iteration training of supported modes
    also routes through this program (GBDT._chunk_single): training is
    then invariant to the chunk decomposition, the property the
    checkpoint/resume interop relies on.

    ``c`` rides in the input shapes: jax retraces per distinct chunk
    capacity, so one returned callable serves every chunk size the
    scheduler picks.
    """
    from ..grower import TreeArrays
    core = b._macro_core          # the SAME iter_body (serial or shard_map)
    grad_fn = b._macro_grad       # gradients-from-score closure (unjitted)
    kind = b.boosting_type
    goss_mask = getattr(b, "_macro_goss_mask", None)
    init_col = (jnp.asarray(b.init_scores, jnp.float32)[:, None]
                if kind == "rf" else None)
    K = b.num_tree_per_iteration
    L = b.grower_cfg.num_leaves

    def chunk(binned, score, cegb_used, cegb_rows, n_steps, xs,
              label_r, weight_r, grad_c, hess_c, obj_tables):
        masks, fmasks, lrs, keys, its, gkeys, gons = xs
        c = lrs.shape[0]
        tmpl = TreeArrays.empty(L)
        ys0 = jax.tree_util.tree_map(
            lambda a: jnp.zeros((c, K) + a.shape, a.dtype), tmpl)
        # per-iteration gradient-quantization scales (use_quantized_grad)
        # ride out as a stacked [c, K, 2] buffer alongside the trees —
        # the in-loop quantization recomputes them from the carried score
        # exactly as per-iteration training does (the stochastic-rounding
        # keys derive from the stacked per-round key stream `keys`)
        qss0 = jnp.zeros((c, K, 2), jnp.float32)
        # the grower loop's (rounds, offered, applied, slots, clipped,
        # lanes) per tree ride out the same way, beside the trees, with
        # GOSS's (kept, top) rows of the round (0 for an unsampled one)
        # and the rows the tree's histogram passes read
        gss0 = jnp.zeros((c, K, 9), jnp.int32)
        # GOSS: the weights the round's trees were grown on, one [n_pad]
        # buffer carried through the loop; the last round's ride out
        wl0 = _ix(masks, 0) if kind == "goss" else None

        def body(j, state):
            score, cu, cr, ys, qss, gss, wl = state
            mask = _ix(masks, j)
            it = _ix(its, j)
            if kind == "rf":
                # rf.py runs the shared step on it*mean so "+ tree" keeps
                # the sum, then renormalizes to the running mean
                g, h = grad_c, hess_c
                score_in = score * it.astype(jnp.float32)
            else:
                g, h = grad_fn(score, obj_tables)
                score_in = score
            if kind == "goss":
                on = _ix(gons, j)
                gm, gcount = goss_mask(g, h, _ix(gkeys, j), mask)
                mask = wl = jnp.where(on, gm, mask)
            new_score, stacked, _leaf_ids, cu, cr, qsc, gst = core(
                binned, score_in, mask, g, h, _ix(fmasks, j), _ix(lrs, j),
                _ix(keys, j), cu, cr, label_r, weight_r)
            if kind == "goss":
                gst = gst.at[:, 6:8].set(jnp.where(on, gcount, 0))
            if kind == "rf":
                new_score = (new_score + init_col) / (
                    it.astype(jnp.float32) + 1.0)
            ys = jax.tree_util.tree_map(
                lambda buf, v: lax.dynamic_update_index_in_dim(buf, v, j, 0),
                ys, stacked)
            qss = lax.dynamic_update_index_in_dim(qss, qsc, j, 0)
            gss = lax.dynamic_update_index_in_dim(gss, gst, j, 0)
            return new_score, cu, cr, ys, qss, gss, wl

        return lax.fori_loop(
            0, n_steps, body,
            (score, cegb_used, cegb_rows, ys0, qss0, gss0, wl0))

    return chunk


def build_chunk_program(b):
    """The solo jitted chunk program: ``make_chunk_fn`` under ``jax.jit``
    with the carried score buffer donated, like the per-iteration
    program."""
    return jax.jit(make_chunk_fn(b), donate_argnums=(1,))


def build_chunk_valid(b, forms=()):
    """Fused valid-score update: one program applies a whole ``[c, ...]``
    tree bundle to a valid set (vs. one dispatch per iteration).  Same
    runtime-trip-count loop as the chunk program so RF's running-mean
    renormalization keeps identical codegen at every chunk size.

    A row's leaf is found by ``grower.predict_leaf_index_binned``: its path
    form where ``b._leaf_routed`` (no categorical feature, on the
    accelerator), else its walk over tree levels; the scores are the same
    to the bit.  The jitted function keeps the name ``upd``: the benchmark
    finds the evaluation layer's device time by the program name
    ``jit_upd`` (``benchmark/metrics/_eval.py``).

    ``forms``: the set's metrics whose device form its evaluation takes
    (``metrics.py``).  With any, ``upd`` also takes the set's float32
    labels, a runtime argument and never a constant of the program, and
    returns ``(vscore, partials)``: the metrics' partials of the updated
    score.  The reduction runs at every update, so a chunk that ends
    between two evaluations (``metric_freq`` > 1 at a snapshot or pause
    boundary) pays it unused."""
    from ..grower import predict_tree_binned
    from ..metrics import device_partials
    K = b.num_tree_per_iteration
    routed = b._leaf_routed
    meta_args = b.meta.as_runtime_arrays()
    rf = b.boosting_type == "rf"
    init_col = (jnp.asarray(b.init_scores, jnp.float32)[:, None]
                if rf else None)
    objective = b.objective

    def upd(vscore, stacked_seq, binned, its, n_steps, label=None):
        def body(j, vs):
            st = jax.tree_util.tree_map(lambda a: _ix(a, j), stacked_seq)
            if rf:
                itf = _ix(its, j).astype(jnp.float32)
                vs = vs * itf
            for k in range(K):
                tree_k = jax.tree_util.tree_map(lambda a: a[k], st)
                vs = vs.at[k].add(predict_tree_binned(
                    tree_k, binned, None, meta_arrays=meta_args,
                    routed=routed))
            if rf:
                vs = (vs + init_col) / (itf + 1.0)
            return vs

        vs = lax.fori_loop(0, n_steps, body, vscore)
        if not forms:
            return vs
        return vs, device_partials(forms, vs[0], label, objective)

    return jax.jit(upd, donate_argnums=(0,))


def _stack_row_arrays(b, arrs: Sequence[jax.Array]) -> jax.Array:
    """Stack per-iteration row arrays to [c, n_pad]; under a data-sharded
    mesh the stacked input keeps the row sharding so the scan slices feed
    shard_map without a gather to one device."""
    out = jnp.stack(arrs)
    if b._mesh is not None and b._data_axis is not None:
        from ..parallel.learners import put_stacked_rows
        out = put_stacked_rows(b._mesh, b._data_axis, out)
    return out


def chunk_host_inputs(b, c: int, lrs: Optional[Sequence[float]] = None):
    """Draw booster ``b``'s per-iteration host inputs for a chunk of ``c``
    iterations starting at ``b.iter`` — bagging masks, feature masks,
    per-round node keys, the lr schedule, iteration indices and GOSS
    subkeys — in the EXACT per-iteration order, so the host RNG streams
    replay identically whether the chunk runs solo (``run_chunk``) or
    stacked along a model axis (multi/driver.py).  Returns ``(xs,
    lr_list)``; the caller is responsible for ``boost_from_average`` first
    (the draw order starts after init)."""
    it0 = b.iter
    masks: List[jax.Array] = []
    fmasks: List[jax.Array] = []
    keys: List[jax.Array] = []
    for j in range(c):
        masks.append(b._bagging_mask(it0 + j))
        fmasks.append(b._feature_masks())
        keys.append(jax.random.fold_in(b._node_key_base, it0 + j))
    if b.boosting_type == "rf":
        lr_list = [1.0] * c                   # rf.py passes literal 1.0
    elif lrs is not None:
        lr_list = [float(v) for v in lrs]
        if len(lr_list) != c:
            raise ValueError(f"got {len(lr_list)} learning rates for a "
                             f"chunk of {c} iterations")
    else:
        lr_list = [float(b.shrinkage_rate)] * c
    its = jnp.arange(it0, it0 + c, dtype=jnp.int32)
    gkeys, gon = b._macro_goss_inputs(c, it0, lr_list)
    xs = (_stack_row_arrays(b, masks), jnp.stack(fmasks),
          jnp.asarray(lr_list, jnp.float32), jnp.stack(keys), its,
          gkeys, gon)
    return xs, lr_list


def run_chunk(b, c: int, lrs: Optional[Sequence[float]] = None) -> bool:
    """Train ``c`` iterations of booster ``b`` in one fused dispatch.

    ``lrs``: per-iteration learning rates (a reset_parameter schedule
    precomputed by the engine); None = the booster's current shrinkage.
    Returns True when training stopped (no more splittable leaves, only
    detectable on the eager host path; the deferred path reports it at
    drain time exactly like per-iteration training).
    """
    if c < 1:
        raise ValueError(f"chunk size must be >= 1, got {c}")
    if not b.chunk_supported():
        raise RuntimeError(
            f"boosting={b.boosting_type!r} with this config needs "
            "per-iteration host logic; use train_one_iter (the engine's "
            "chunk scheduler falls back to c=1 automatically)")
    b.boost_from_average()
    it0 = b.iter
    # masks, keys and the stacked [c, n_pad] row arrays, built on the
    # host every round (their eager device ops are dispatches too)
    with _span("macro.host_inputs", ring=True, it=it0, c=c):
        xs, lr_list = chunk_host_inputs(b, c, lrs)
        grad_c, hess_c = b._macro_const_grads()

    if b._macro_chunk_jit is None:
        with _span("jit.build", ring=True, what="chunk_program"):
            b._macro_chunk_jit = build_chunk_program(b)
    cu, cr = b._cegb_state
    # chunk-size telemetry on the unified registry (obs_dump snapshots
    # it instead of scraping logs)
    _obs_registry.counter("train_chunk_dispatches").inc()
    _obs_registry.histogram(
        "train_chunk_size",
        buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0)).observe(c)
    # host time only: the dispatch returns before the device is done
    # (the first one of a shape also traces, lowers and compiles)
    with _span("macro.dispatch", ring=True, it=it0, c=c, it0=it0,
               timer="TreeLearner::Train(dispatch)"):
        (b.train_score, cu, cr, stacked_seq, qss, gss,
         wl) = b._macro_chunk_jit(
            b.binned, b.train_score, cu, cr, np.int32(c), xs,
            b._macro_ctx["label"], b._macro_ctx["weight"], grad_c, hess_c,
            b._macro_ctx["obj_tables"])
    b._cegb_state = (cu, cr)
    if wl is not None:
        b.last_row_weights = wl
    if getattr(b, "_quant_on", False):
        b._quant_scales = qss[c - 1]   # last round's per-class scales
    return b._finish_chunk(stacked_seq, c, lr_list, it0, gss)
