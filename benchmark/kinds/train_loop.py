"""``train_loop``: closed loop, one trainer.  Data from the seed, ingest,
``warm_rounds`` rounds, then round after round until the seconds are up;
the round in flight finishes and counts.  A round is ``Booster.update()``,
a ``block_until_ready`` on the train score, and the tree taken into the
host ``Booster`` (a tree counts once the host holds it; one pending tree is
a plain ``device_get``, where a pull of N at the close would stack N trees
in a program compiled for that N, inside the window).  Before every round
the harness keeps a device copy of the train score (one 4 B/row copy,
~0.3 ms), so that the comparison can follow the last tree the window
finished from the state it was grown on.
"""
import gc
import time

import numpy as np

from benchmark.lib import correct, faults
from benchmark.lib.traffic import (Run, _generator, generate, host_tree,
                                   objective_module, peak_bytes)

PRIMARY = "train_s_per_tree"
LIMITS = {"window_tree_missing": 0}
FAULTS = faults.TRAIN


def run(manifest, config, traffic, cell_file, seed, seconds,
        spans, compiles, devices, on_window=None, fault=None):
    import jax
    import jax.numpy as jnp
    import lightgbm_tpu as lgb
    gen, gen_args = _generator(manifest, config)
    rows, features = int(config["rows"]), int(config["features"])
    params = dict(config["params"])
    with spans.span("data"):
        X, y, fields = generate(gen, seed, rows, features, **gen_args)
    with spans.span("ingest"):
        ds = lgb.Dataset(X, label=y, params=params, **fields)
        ds.construct()
        bst = lgb.Booster(params, ds)
        jax.block_until_ready(bst.boosting.binned)
    if fault:
        fault.after_build(bst)

    before = None

    def step():
        nonlocal before
        token = fault.before_step(bst) if fault else None
        with spans.span("keep_score"):
            before = jnp.copy(bst.boosting.train_score)
        with spans.span("update"):
            bst.update()
        with spans.span("sync"):
            jax.block_until_ready(bst.boosting.train_score)
        if fault:
            fault.after_step(bst, token)
        with spans.span("pull_trees"):
            held = len(bst.models)
        if fault:
            fault.after_pull(bst)
        return held

    warm = int(traffic["warm_rounds"])
    follow = int(cell_file.get("reference_trees", warm))
    snaps = []
    for i in range(warm):
        with spans.span("warm_round"):
            step()
        if i < follow:
            # what the timed path made of the train score, kept for the
            # comparison (50 MB to the host; set-up, not window)
            snaps.append(np.asarray(bst.boosting.train_score)[0, :rows]
                         .astype(np.float64))
    if on_window:
        on_window("start")
    compiles.active = True
    t0 = time.perf_counter()
    trees = 0
    held = warm
    step_seconds = []
    while time.perf_counter() - t0 < seconds:
        ts = time.perf_counter()
        held = step()
        step_seconds.append(time.perf_counter() - ts)
        trees += 1
    t1 = time.perf_counter()
    compiles.active = False
    if on_window:
        on_window("stop")
    peak = peak_bytes(devices)
    answers = [host_tree(m) for m in bst.models[:follow]]
    # the last tree the window finished, with the train score it was grown
    # on and the one it left (to the host only now: the window is closed
    # and the peak is read)
    last = None
    if trees and held == warm + trees:
        def host(score):
            return np.asarray(score)[0, :rows].astype(np.float64)
        last = {"index": held - 1, "tree": host_tree(bst.models[held - 1]),
                "before": host(before), "after": host(bst.boosting.train_score)}
    before = None
    info = {"binned_shape": list(bst.boosting.binned.shape),
            "binned_dtype": str(bst.boosting.binned.dtype),
            "n_pad": int(bst.boosting._n_pad), "trees_held": held}

    def free():
        nonlocal bst, ds
        bst = ds = None
        gc.collect()

    return Run(kind="train_loop", attempted=trees, failed=warm + trees - held,
               window_s=t1 - t0, trees=trees, step_seconds=step_seconds,
               rows=rows, features=features,
               peak_bytes=peak, X=X, y=y, answers=answers, snaps=snaps,
               last=last, params=params,
               objective=objective_module(manifest, params), aux=fields,
               info=info, free=free)


def primary(run):
    return PRIMARY, run.window_s / max(run.trees, 1)


def numbers(run, detail=None):
    return correct.train_numbers(run, detail=detail)
