"""``train_call``: closed loop, one trainer, driven as users drive it: ONE
``lgb.train(params, train_set, num_boost_round, valid_sets=[valid],
valid_names=[name], callbacks=[early_stopping, record_evaluation, <the
harness's two>])``.  The configuration's parameters with the traffic
file's ``params`` laid over them (the metrics to evaluate); a validation
set of ``valid_rows`` rows from the same generator at ``seed +
valid_seed_offset``, its own ``lgb.Dataset(..., reference=train_set)``.
Every round the engine updates the trees, updates the validation score,
evaluates every metric on the host and runs the callbacks.

The harness's callbacks are the window.  The one before a round keeps a
device copy of the train score (as ``train_loop`` does); the one after a
round, which runs last, reads ``len(model.models)`` (a tree counts once
the host ``Booster`` holds it, so no stacked pull lands in the window),
keeps the host snapshots of the warm rounds, opens the window when it
returns from round ``warm_rounds`` and, at the first round that ends at
or after ``seconds``, closes it by raising ``EarlyStopException``: the
round in flight has finished, is evaluated, and counts.  The primary
metric is window start -> return of the last counted round's callback,
over the rounds counted: update, validation update, evaluation and
callbacks all inside.
"""
import gc
import time

from benchmark.lib import correct, faults
from benchmark.lib.traffic import (Run, _generator, generate, host_score,
                                   host_tree, objective_module, peak_bytes)

PRIMARY = "train_s_per_tree"
LIMITS = {"window_tree_missing": 0, "eval_rounds_missing": 0}
FAULTS = {**faults.TRAIN, **faults.EVAL}


def run(manifest, config, traffic, cell_file, seed, seconds,
        spans, compiles, devices, on_window=None, fault=None):
    import jax
    import jax.numpy as jnp
    import lightgbm_tpu as lgb
    from lightgbm_tpu.callback import EarlyStopException
    gen, gen_args = _generator(manifest, config)
    rows, features = int(config["rows"]), int(config["features"])
    valid_rows = int(traffic["valid_rows"])
    params = dict(config["params"], **traffic.get("params", {}))
    name = traffic["eval"]["set"]
    with spans.span("data"):
        X, y, fields = generate(gen, seed, rows, features, **gen_args)
        Xv, yv, vfields = generate(
            gen, seed + int(traffic["valid_seed_offset"]), valid_rows,
            features, **gen_args)
    with spans.span("ingest"):
        ds = lgb.Dataset(X, label=y, params=params, **fields)
        ds.construct()
        dv = lgb.Dataset(Xv, label=yv, reference=ds, **vfields)
        dv.construct()

    warm = int(traffic["warm_rounds"])
    follow = int(cell_file.get("reference_trees", warm))
    evals = {}
    snaps = []
    state = {"before": None, "token": None, "bst": None, "held": 0,
             "trees": 0, "t0": None, "t_last": None, "t_round": None}
    step_seconds = []
    t_call = time.perf_counter()

    def before_round(env):
        bst = env.model
        if state["bst"] is None:
            # the Booster that lgb.train built: the rest of the ingest
            state["bst"] = bst
            jax.block_until_ready(bst.boosting.binned)
            spans.add("ingest", time.perf_counter() - t_call)
            if fault:
                fault.after_build(bst)
            state["t_round"] = time.perf_counter()
        state["token"] = fault.before_step(bst) if fault else None
        with spans.span("keep_score"):
            state["before"] = jnp.copy(bst.boosting.train_score)
    before_round.before_iteration = True

    def after_round(env):
        bst = env.model
        if fault:
            fault.after_step(bst, state["token"])
        with spans.span("pull_trees"):
            state["held"] = len(bst.models)
        if fault:
            fault.after_pull(bst)
        done = env.iteration + 1
        now = time.perf_counter()
        if done <= warm:
            spans.add("warm_round", now - state["t_round"])
            if done <= follow:
                # 50 MB to the host; set-up, not window
                snaps.append(host_score(bst.boosting.train_score, rows))
            if done == warm:
                if on_window:
                    on_window("start")
                compiles.active = True
                state["t0"] = state["t_last"] = time.perf_counter()
            state["t_round"] = time.perf_counter()
            return
        state["trees"] += 1
        step_seconds.append(now - state["t_last"])
        state["t_last"] = now
        if now - state["t0"] >= seconds:
            raise EarlyStopException(env.iteration,
                                     env.evaluation_result_list)
    after_round.order = 40          # after record_evaluation, early_stopping

    bst = lgb.train(
        params, ds, num_boost_round=int(traffic["num_boost_round"]),
        valid_sets=[dv], valid_names=[name], verbose_eval=False,
        callbacks=[lgb.early_stopping(int(traffic["early_stopping_rounds"]),
                                      verbose=False),
                   lgb.record_evaluation(evals), before_round, after_round])
    compiles.active = False
    if on_window:
        on_window("stop")
    peak = peak_bytes(devices)
    trees, held = state["trees"], state["held"]
    window_s = (state["t_last"] - state["t0"]) if state["t0"] else 0.0
    answers = [host_tree(m) for m in bst.models[:follow]]
    last = None
    if trees and held == warm + trees:
        last = {"index": held - 1, "tree": host_tree(bst.models[held - 1]),
                "before": host_score(state["before"], rows),
                "after": host_score(bst.boosting.train_score, rows)}
    # what was recorded for the validation set, and every tree the host
    # holds at the close, for the reference to score the rows through
    evaluation = {
        "recorded": {k: list(v) for k, v in evals.get(name, {}).items()},
        "rounds": warm + trees, "X": Xv, "y": yv, "aux": vfields,
        "loss": traffic["eval"]["loss"], "auc": traffic["eval"]["auc"],
        "trees": [host_tree(m) for m in bst.models[:held]]}
    state["before"] = state["token"] = state["bst"] = None
    info = {"binned_shape": list(bst.boosting.binned.shape),
            "binned_dtype": str(bst.boosting.binned.dtype),
            "n_pad": int(bst.boosting._n_pad), "trees_held": held,
            "valid_binned_shape": list(bst.boosting.valid_binned[0].shape),
            "best_iteration": int(bst.best_iteration),
            "recorded": {k: v[-3:] for k, v in
                         evaluation["recorded"].items()}}

    def free():
        nonlocal bst, ds, dv
        bst = ds = dv = None
        gc.collect()

    return Run(kind="train_call", attempted=trees,
               failed=warm + trees - held, window_s=window_s, trees=trees,
               step_seconds=step_seconds, rows=rows, features=features,
               valid_rows=valid_rows, peak_bytes=peak, X=X, y=y,
               answers=answers, snaps=snaps, last=last, params=params,
               objective=objective_module(manifest, params), aux=fields,
               evaluation=evaluation, info=info, free=free)


def primary(run):
    return PRIMARY, run.window_s / max(run.trees, 1)


def numbers(run, detail=None):
    out = correct.train_numbers(run, detail=detail)
    out.update(correct.eval_numbers(run.evaluation, run.objective))
    return out
