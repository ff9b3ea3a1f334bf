"""The batched (model-axis) chunk program: ``jax.vmap`` over the EXACT
solo chunk body.

``macro.make_chunk_fn`` returns the unjitted fused-chunk callable; the
solo program is ``jit(chunk_fn)`` and the batched program built here is
``jit(vmap(chunk_fn))`` over a leading lane axis — the same trace, so a
lane's math is the solo math.  Bit-parity of the extracted models
(tests/test_multi.py byte-compares model text) additionally needs the
device ops the body reaches to accumulate order-invariantly under
batching, which holds for the scatter-add and integer histogram paths
(the families elected on CPU and for quantized training) — measured, not
assumed: the parity matrix pins it per mode.  f32 matmul histogram
variants reassociate under a batch dimension and carry no bitwise claim
(docs/PERF.md "model axis").

Liveness: a finished lane (early stop, per-lane round budget) keeps its
slot — the driver feeds it inert zero inputs drawn from NO RNG stream
(`dead_inputs`) and discards its outputs, so the batch never retraces
when one booster finishes and the survivors' lanes stay bit-identical.
vmap lanes never mix data, so a dead lane's garbage cannot leak into a
live one.

Stacked-data groups (CV folds) additionally swap the objective's baked
per-dataset arrays (label, binary's label_sign, multiclass one-hots)
for traced lane-stacked arguments during the ONE vmap trace — the
rebind-at-trace trick below — because ``gradients_fn`` reads them off
the live objective instance as closure constants.
"""

from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..boosting.macro import chunk_host_inputs, make_chunk_fn
from .group import MultiGroup, objective_array_attrs


def _put_rows_last(b0, arr: jax.Array) -> jax.Array:
    """Re-place a lane-stacked array whose LAST axis is the row axis so
    rows keep the data sharding (the lane/model axis is replicated) —
    the batched twin of parallel.learners.put_stacked_rows."""
    if b0._mesh is None or b0._data_axis is None:
        return arr
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = P(*([None] * (arr.ndim - 1) + [b0._data_axis]))
    return jax.device_put(arr, NamedSharding(b0._mesh, spec))


def stack_lanes(b0, arrs: Sequence[jax.Array], rows_last: bool) -> jax.Array:
    """Stack per-lane arrays along a new leading model axis; arrays whose
    trailing axis is the (possibly sharded) row axis keep that sharding."""
    out = jnp.stack(list(arrs))
    return _put_rows_last(b0, out) if rows_last else out


class BatchedChunkProgram:
    """One group's vmapped chunk program + lane input assembly.

    ``dispatch(c, lanes, lr_lists)`` advances every live lane ``c``
    iterations in ONE device program and runs each live booster's
    ``_finish_chunk`` host bookkeeping (the same code path solo training
    uses, so model extraction, deferred-tree banking, valid-score
    updates and stop detection are inherited, not reimplemented).
    """

    def __init__(self, group: MultiGroup):
        self.group = group
        self.b0 = b0 = group.boosters[0]
        self.stacked = group.stacked
        self._obj_attrs = (objective_array_attrs(b0.objective)
                          if group.stacked else [])
        self._dead_xs_templates = {}    # chunk size c -> inert zero xs
        chunk_fn = make_chunk_fn(b0)
        obj = b0.objective

        def wrapped(binned, score, cu, cr, n_steps, xs, label_r, weight_r,
                    grad_c, hess_c, obj_tables, obj_arrs):
            # rebind-at-trace: vmap traces this body once with ``obj_arrs``
            # as lane-batched tracers; gradients_fn reads the objective's
            # arrays at trace time, so pointing them at the tracers makes
            # the ONE trace consume per-lane labels.  Restored immediately
            # — the live objective never holds tracers after tracing.
            saved = {k: getattr(obj, k) for k in obj_arrs}
            for k, v in obj_arrs.items():
                setattr(obj, k, v)
            try:
                return chunk_fn(binned, score, cu, cr, n_steps, xs,
                                label_r, weight_r, grad_c, hess_c,
                                obj_tables)
            finally:
                for k, v in saved.items():
                    setattr(obj, k, v)

        data_ax = 0 if self.stacked else None
        self._fn = jax.jit(
            jax.vmap(wrapped,
                     in_axes=(data_ax, 0, 0, 0, None, 0, data_ax, data_ax,
                              None, None, None, 0)),
            donate_argnums=(1,))
        if self.stacked:
            self._binned_B = stack_lanes(
                b0, [b.binned for b in group.boosters], rows_last=True)
            self._label_B = stack_lanes(
                b0, [b._macro_ctx["label"] for b in group.boosters],
                rows_last=True)
            self._weight_B = stack_lanes(
                b0, [b._macro_ctx["weight"] for b in group.boosters],
                rows_last=True)
            self._obj_arrs_B = {
                k: stack_lanes(
                    b0, [jnp.asarray(getattr(b.objective, k))
                         for b in group.boosters],
                    rows_last=False)
                for k in self._obj_attrs}
        else:
            self._binned_B = b0.binned
            self._label_B = b0._macro_ctx["label"]
            self._weight_B = b0._macro_ctx["weight"]
            self._obj_arrs_B = {}

    # ------------------------------------------------------------ inputs

    def _lane_inputs(self, b, live: bool, c: int, lrs):
        """One lane's per-chunk host inputs.  Live lanes draw from the
        booster's real RNG streams (exact solo order — chunk_host_inputs
        is the same helper run_chunk uses); dead lanes get inert zeros
        drawn from NO stream, so a finished booster's replayable state
        never advances."""
        if live:
            b.boost_from_average()
            xs, lr_list = chunk_host_inputs(b, c, lrs)
            # xs shapes carry the chunk size in their leading axis, so
            # the inert template is cached PER chunk size
            if c not in self._dead_xs_templates:
                self._dead_xs_templates[c] = jax.tree_util.tree_map(
                    lambda a: jnp.zeros(a.shape, a.dtype), xs)
            return xs, lr_list
        if c not in self._dead_xs_templates:
            raise RuntimeError("batched chunk dispatched with no live lane")
        return self._dead_xs_templates[c], [0.0] * c

    # ---------------------------------------------------------- dispatch

    def dispatch(self, c: int, live: List[bool],
                 lr_lists: Sequence) -> List[bool]:
        """Advance live lanes ``c`` iterations; returns per-lane
        ``stopped`` flags (True = no more splittable leaves, the solo
        ``run_chunk`` contract; dead lanes report False)."""
        bs = self.group.boosters
        b0 = self.b0
        n_lanes = len(bs)
        lane_xs = [None] * n_lanes
        lane_lrs = [None] * n_lanes
        it0s = [b.iter for b in bs]
        # live lanes first: they seed the inert template a dead lane
        # earlier in the list needs for this chunk size
        for i in range(n_lanes):
            if live[i]:
                lane_xs[i], lane_lrs[i] = self._lane_inputs(
                    bs[i], True, c, lr_lists[i])
        for i in range(n_lanes):
            if not live[i]:
                lane_xs[i], lane_lrs[i] = self._lane_inputs(
                    bs[i], False, c, None)
        xs_B = jax.tree_util.tree_map(
            lambda *a: stack_lanes(b0, a, rows_last=a[0].ndim == 2
                                   and a[0].shape[-1] == b0._n_pad),
            *lane_xs)
        score_B = stack_lanes(b0, [b.train_score for b in bs],
                              rows_last=True)
        cu_B = jnp.stack([b._cegb_state[0] for b in bs])
        cr_B = jnp.stack([b._cegb_state[1] for b in bs])
        grad_c, hess_c = b0._macro_const_grads()

        from ..obs.metrics import global_registry as _obs_registry
        from ..obs.trace import span as _span
        _obs_registry.counter("multi_chunk_dispatches").inc()
        _obs_registry.histogram(
            "multi_batch_lanes",
            buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0)).observe(len(bs))
        with _span("multi.dispatch", lanes=len(bs), c=c,
                   live=sum(map(bool, live)),
                   timer="TreeLearner::Train(dispatch)"):
            score_B, cu_B, cr_B, ys_B, qss_B, gss_B, wl_B = self._fn(
                self._binned_B, score_B, cu_B, cr_B, np.int32(c), xs_B,
                self._label_B, self._weight_B, grad_c, hess_c,
                self.b0._macro_ctx["obj_tables"], self._obj_arrs_B)

        stopped = [False] * len(bs)
        for i, (b, is_live) in enumerate(zip(bs, live)):
            if not is_live:
                continue
            b.train_score = score_B[i]
            b._cegb_state = (cu_B[i], cr_B[i])
            if getattr(b, "_quant_on", False):
                b._quant_scales = qss_B[i][c - 1]
            if wl_B is not None:
                b.last_row_weights = wl_B[i]
            seq_i = jax.tree_util.tree_map(lambda a, _i=i: a[_i], ys_B)
            stopped[i] = b._finish_chunk(seq_i, c, lane_lrs[i], it0s[i],
                                         gss_B[i])
        return stopped
