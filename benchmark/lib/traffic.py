"""The one general traffic generator.  A traffic mix is a JSON file of
parameters with a ``kind``:

``train_loop``   closed loop, one trainer.  Data from the seed, ingest,
                 ``warm_rounds`` rounds, then round after round until the
                 seconds are up; the round in flight finishes and counts.
                 A round is ``Booster.update()``, a ``block_until_ready`` on
                 the train score, and the tree taken into the host
                 ``Booster`` (a tree counts once the host holds it; one
                 pending tree is a plain ``device_get``, where a pull of N
                 at the close would stack N trees in a program compiled for
                 that N, inside the window).  Before every round the harness keeps a device copy
                 of the train score (one 4 B/row copy, ~0.3 ms), so that the
                 comparison can follow the last tree the window finished
                 from the state it was grown on.

``score_loop``   closed loop, one caller.  A forest from the seed loaded
                 as model text, ``pool_blocks`` distinct host blocks of
                 ``request_rows`` rows, each request one
                 ``Booster.predict(block, **predict_kwargs)``.

Both return a ``Run``: counts, what the timed path produced for the
comparison, and a ``free()`` that drops the program's device state.
"""
import gc
import time
from types import SimpleNamespace as Run

import numpy as np

from . import forest as forest_lib
from .lookup import find, load_module

TREE_FIELDS = ("split_feature", "threshold", "left_child", "right_child",
               "split_gain", "leaf_value", "leaf_weight", "leaf_count",
               "internal_count")


def _generator(manifest, config):
    gen = load_module(find(manifest, f"datagen/{config['data']['generator']}.py"))
    return gen, dict(config["data"].get("args", {}))


def host_tree(model):
    """The program's answer for one tree, as plain arrays."""
    return {k: np.array(getattr(model, k)) for k in TREE_FIELDS}


def peak_bytes(devices):
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def run_train_loop(manifest, config, traffic, cell_file, seed, seconds,
                   spans, compiles, devices, on_window=None, fault=None):
    import jax
    import jax.numpy as jnp
    import lightgbm_tpu as lgb
    gen, gen_args = _generator(manifest, config)
    rows, features = int(config["rows"]), int(config["features"])
    params = dict(config["params"])
    with spans.span("data"):
        X, y = gen.generate(seed, rows, features, **gen_args)
    with spans.span("ingest"):
        ds = lgb.Dataset(X, label=y, params=params)
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
               last=last, params=params, info=info, free=free)


def run_score_loop(manifest, config, traffic, cell_file, seed, seconds,
                   spans, compiles, devices, on_window=None, fault=None):
    import lightgbm_tpu as lgb
    gen, gen_args = _generator(manifest, config)
    features = int(config["features"])
    request_rows = int(traffic["request_rows"])
    pool = int(traffic["pool_blocks"])
    kwargs = dict(traffic["predict_kwargs"])
    with spans.span("data"):
        forest = forest_lib.random_forest(
            seed, int(traffic["forest_trees"]),
            int(config["params"]["num_leaves"]), features)
        text = forest_lib.to_model_text(forest, features)
        blocks = [gen.generate(seed + 1000003 * (b + 1), request_rows,
                               features, **gen_args)[0] for b in range(pool)]
    with spans.span("model_load"):
        bst = lgb.Booster(model_str=text)
    if fault:
        fault.after_build(bst)
    sample = np.sort(np.random.RandomState(seed % (1 << 32)).choice(
        request_rows, min(int(cell_file.get("sample_rows", 4096)),
                          request_rows), replace=False))

    def request(i):
        with spans.span("predict"):
            return bst.predict(blocks[i % pool], **kwargs)

    for i in range(int(traffic["warm_requests"])):
        with spans.span("warm_request"):
            request(i)
    if on_window:
        on_window("start")
    compiles.active = True
    t0 = time.perf_counter()
    t_last = t0
    done = 0
    short = 0
    sampled = []
    step_seconds = []
    while time.perf_counter() - t0 < seconds:
        out = request(done)
        step_seconds.append(time.perf_counter() - t_last)
        t_last = time.perf_counter()
        short += int(out.shape[0] != request_rows)
        sampled.append((done % pool, np.asarray(out, np.float64)[sample]))
        done += 1
    compiles.active = False
    if on_window:
        on_window("stop")
    peak = peak_bytes(devices)

    def free():
        nonlocal bst
        bst = None
        gc.collect()

    return Run(kind="score_loop", attempted=done, failed=short,
               window_s=t_last - t0, requests=done, step_seconds=step_seconds,
               rows=done * request_rows, request_rows=request_rows,
               features=features, peak_bytes=peak, forest=forest,
               blocks=blocks, sample=sample, sampled=sampled,
               info={"trees": len(forest)}, free=free)


KINDS = {"train_loop": run_train_loop, "score_loop": run_score_loop}
