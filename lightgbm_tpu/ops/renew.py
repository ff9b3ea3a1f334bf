"""Per-leaf weighted residual percentiles, on device.

reference: SerialTreeLearner::RenewTreeOutput (serial_tree_learner.cpp:628)
+ RegressionL1loss::RenewTreeOutput (regression_objective.hpp:250) — for
L1-family objectives, leaf outputs are re-fit to the (weighted) alpha-
percentile of the residuals in each leaf rather than the Newton step.

TPU design: one global sort of (leaf_id, residual) pairs (lax.sort, runs on
device), then per-row segment-local cumulative weights; the percentile
crossing row of each segment is detected branch-free and scattered out.
O(n log n) on device, no host round-trip, fixed shapes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def leaf_percentile(
    leaf_id: jax.Array,    # [n] i32
    residual: jax.Array,   # [n] f32
    weight: jax.Array,     # [n] f32 (bagging mask times row weight; 0 = excluded)
    num_leaves: int,
    alpha: float,
) -> jax.Array:
    """Weighted alpha-percentile of residual per leaf. Returns [L] f32.

    Weighted definition matches reference Common::WeightedPercentile
    (utils/common.h): positions p_i = (cumsum(w)_i - w_i/2) / W; linear
    interpolation between the rows bracketing alpha.  Rows with zero weight
    are pushed out of their segment (leaf key = L) so they never contribute.
    """
    n = leaf_id.shape[0]
    L = num_leaves
    # exclude zero-weight rows from segments
    seg = jnp.where(weight > 0, leaf_id, L).astype(jnp.int32)
    seg_sorted, res_sorted, w_sorted = lax.sort(
        (seg, residual, weight), dimension=0, num_keys=2)

    # segment-local cumulative weight: global cumsum minus segment offset
    cw = jnp.cumsum(w_sorted)
    seg_total = jax.ops.segment_sum(w_sorted, seg_sorted, num_segments=L + 1)
    seg_start_w = jnp.concatenate([jnp.zeros(1), jnp.cumsum(seg_total)[:-1]])
    local_cw = cw - seg_start_w[seg_sorted]
    tot = seg_total[seg_sorted]
    p = jnp.where(tot > 0, (local_cw - w_sorted / 2.0) / tot, 0.0)

    # previous row's p within the same segment (else -inf)
    prev_same = jnp.concatenate([jnp.array([False]), seg_sorted[1:] == seg_sorted[:-1]])
    p_prev = jnp.concatenate([jnp.zeros(1), p[:-1]])
    p_prev = jnp.where(prev_same, p_prev, -jnp.inf)
    r_prev = jnp.concatenate([jnp.zeros(1), res_sorted[:-1]])

    # crossing row: first row in segment with p >= alpha
    crossing = (p >= alpha) & (p_prev < alpha)
    frac = jnp.where(p > p_prev, (alpha - p_prev) / jnp.maximum(p - p_prev, 1e-30), 0.0)
    frac = jnp.clip(frac, 0.0, 1.0)
    interp = jnp.where(jnp.isfinite(p_prev), r_prev * (1 - frac) + res_sorted * frac,
                       res_sorted)

    out = jnp.zeros(L + 1, jnp.float32)
    out = out.at[jnp.where(crossing, seg_sorted, L)].set(interp.astype(jnp.float32))
    # segments where alpha beyond last row (p_n < alpha): use last row's residual
    is_last = jnp.concatenate([seg_sorted[1:] != seg_sorted[:-1], jnp.array([True])])
    need_last = is_last & (p < alpha)
    out = out.at[jnp.where(need_last, seg_sorted, L)].set(
        jnp.where(need_last, res_sorted, 0.0).astype(jnp.float32), mode="drop")
    return out[:L]


def quant_train_renew_leaf(
    leaf_id: jax.Array,    # [n] i32 final leaf assignment
    grad: jax.Array,       # [n] f32 TRUE (un-quantized) gradients
    hess: jax.Array,       # [n] f32 TRUE hessians
    weight: jax.Array,     # [n] f32 bagging/GOSS weights (0 = excluded)
    num_leaves: int,
):
    """True-f32 per-leaf gradient/hessian sums for quantized training's
    leaf renewal (config ``quant_train_renew_leaf``).

    reference: CUDASingleGPUTreeLearner::RenewDiscretizedTreeLeaves /
    GradientDiscretizer::RenewIntGradTreeOutput — with
    ``use_quantized_grad`` the tree STRUCTURE comes from the integer
    histograms, but the committed leaf outputs are re-fit from the true
    float gradient sums, removing the discretization bias from the
    scores the next round boosts against.  Returns ``(sg [L], sh [L])``
    f32; the grower turns them into outputs via ``ops.split.leaf_output``
    (and psums them under data sharding).
    """
    w = weight
    with jax.named_scope("lgbm.renew"):
        sg = jax.ops.segment_sum(grad * w, leaf_id, num_segments=num_leaves)
        sh = jax.ops.segment_sum(hess * w, leaf_id, num_segments=num_leaves)
    return sg.astype(jnp.float32), sh.astype(jnp.float32)
