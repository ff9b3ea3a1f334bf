"""Opt-in low-precision inference: quantize a forest at hot-swap time.

The fixed-point GBDT accelerator literature ("Booster: An Accelerator
for Gradient Boosting Decision Trees", arXiv 2011.02022) shows tree
THRESHOLDS and LEAF VALUES tolerate aggressive narrowing: routing only
needs enough threshold precision to keep rows on the same side of each
split, and leaf sums average out rounding.  This module does the model
surgery: ``quantize_forest`` rounds a ``StackedForest``'s numeric
thresholds and leaf values onto a bf16 or per-tree-int8 grid, producing
a NEW forest the serving registry treats like any other model —
distinct digest, its own compiled programs, host path and device path
bit-identical to each other (every grid value is exactly
f32-representable, so DeviceForest's f32 round-down is the identity).

What low precision buys the fleet: the device threshold array shrinks
2x (bf16) / 4x (int8 codes + one f32 scale per tree), and the leaf
array never uploads at all (serving gathers leaves on the host), so the
shared-HBM residency election (ops/planner.plan_fleet) can keep more
models resident.  What it costs: raw scores drift from the
full-precision model — which is why the serving registry measures the
drift on a probe batch at admission/swap time against a caller-declared
``accuracy_budget`` and QUARANTINES the model when it exceeds it
(serving/registry.py, riding the PR 2 probe-batch machinery).  Raw-score
bit-parity with ``Booster.predict(raw_score=True)`` remains the DEFAULT:
nothing here runs unless a model opts in with ``precision=``.

Deliberately a leaf module: numpy + ml_dtypes only, no jax, no serving
imports — predict.py and serving/registry.py import it lazily.
"""

from __future__ import annotations

import copy

import numpy as np

PRECISIONS = ("f32", "bf16", "int8")


def bf16_round(a: np.ndarray) -> np.ndarray:
    """Round float64 values to the nearest bfloat16, returned as float64
    (every bf16 value is exactly f32- and f64-representable)."""
    import ml_dtypes
    return a.astype(ml_dtypes.bfloat16).astype(np.float64)


def int8_rows(a: np.ndarray, skip=None):
    """Per-row symmetric int8 quantization of a [T, N] float64 array.

    Returns ``(q, scale, deq)``: int8 codes, per-row f32 scale, and the
    dequantized float64 grid ``f32(q * scale)``.  Entries where ``skip``
    is True (non-finite padding, categorical bitset indices) get code 0
    and keep their original value in ``deq``.  The scale and the
    dequantization are computed in float32 so a device kernel doing
    ``q.astype(f32) * scale`` reproduces ``deq`` bit-exactly.
    """
    a = np.asarray(a, np.float64)
    if skip is None:
        skip = ~np.isfinite(a)
    else:
        skip = np.asarray(skip, bool) | ~np.isfinite(a)
    live = np.where(skip, 0.0, a)
    mag = np.abs(live).max(axis=1)                        # [T]
    scale = np.where(mag > 0, mag, 1.0).astype(np.float32) / np.float32(127)
    q = np.clip(np.round(live / scale[:, None].astype(np.float64)),
                -127, 127).astype(np.int8)
    q = np.where(skip, np.int8(0), q)
    deq = (q.astype(np.float32) * scale[:, None]).astype(np.float64)
    deq = np.where(skip, a, deq)
    return q, scale, deq


def quantize_forest(forest, precision: str):
    """Shallow-copy ``forest`` with thresholds + leaf values moved onto
    the ``precision`` grid ("bf16" | "int8").

    Categorical split nodes keep their thresholds verbatim — there the
    "threshold" is a bitset INDEX (predict.py), and rounding an index
    corrupts routing rather than merely perturbing it.  Non-finite
    entries (the +inf padding of unused node slots) are preserved too.
    int8 forests additionally carry ``threshold_q`` / ``threshold_scale``
    / ``threshold_skip`` so ``DeviceForest(precision="int8")`` can store
    the codes on device and dequantize in-kernel to the exact same grid.
    """
    if precision == "f32":
        return forest
    if precision not in PRECISIONS:
        raise ValueError(f"unknown serving precision {precision!r}; "
                         f"expected one of {PRECISIONS}")
    qf = copy.copy(forest)
    thr_skip = ~np.isfinite(forest.threshold) | forest.is_cat
    if precision == "bf16":
        qf.threshold = np.where(thr_skip, forest.threshold,
                                bf16_round(forest.threshold))
        qf.leaf_value = bf16_round(forest.leaf_value)
    else:
        q, scale, deq = int8_rows(forest.threshold, skip=thr_skip)
        qf.threshold = deq
        qf.threshold_q = q
        qf.threshold_scale = scale
        qf.threshold_skip = thr_skip
        _, _, qf.leaf_value = int8_rows(forest.leaf_value)
    return qf


def forest_precision_bytes(forest, precision: str) -> dict:
    """Rough host-side accounting of what the grid move saves on device:
    {threshold_bytes, leaf_bytes} at the given precision vs f32 — the
    planner's ``predict_forest_bytes`` is the authoritative (padded)
    model; this is the human-readable smoke twin."""
    T, I = forest.threshold.shape
    L = forest.leaf_value.shape[1]
    thr_item = {"f32": 4, "bf16": 2, "int8": 1}[precision]
    return {
        "threshold_bytes": T * I * thr_item + (T * 4 if precision == "int8"
                                               else 0),
        "threshold_bytes_f32": T * I * 4,
        # low-precision serving gathers leaves on the host: no device copy
        "leaf_bytes": 0 if precision != "f32" else T * L * 4,
        "leaf_bytes_f32": T * L * 4,
    }


def measure_accuracy_delta(full_forest, lp_forest, X: np.ndarray,
                           num_class: int = 1) -> float:
    """max |raw_lp - raw_full| over the probe rows ``X`` — the number the
    serving registry compares against ``accuracy_budget`` and journals
    as ``lowprec_accuracy_delta``.  Uses the host path on both forests:
    for f32-precision probes it is bit-identical to what the device
    serves, and it needs no compile."""
    X = np.asarray(X, np.float64)
    ref = full_forest.predict_raw(X, num_class=num_class)
    got = lp_forest.predict_raw(X, num_class=num_class)
    return float(np.max(np.abs(got - ref))) if ref.size else 0.0
