"""``score_loop``: closed loop, one caller.  A forest from the seed loaded
as model text, ``pool_blocks`` distinct host blocks of ``request_rows``
rows, each request one ``Booster.predict(block, **predict_kwargs)``.
"""
import gc
import time

import numpy as np

from benchmark.lib import correct, faults
from benchmark.lib import forest as forest_lib
from benchmark.lib.traffic import Run, _generator, peak_bytes

PRIMARY = "score_rows_per_s"
FAULTS = faults.SCORE


def run(manifest, config, traffic, cell_file, seed, seconds,
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


def primary(run):
    return PRIMARY, run.rows / max(run.window_s, 1e-9)


def numbers(run, detail=None):
    return correct.score_numbers(run)


def control_numbers(run):
    """The plain reference put in the program's place with every node's
    comparison made in bfloat16, read at the same sampled rows."""
    import ml_dtypes
    low = correct.score_reference(run, ml_dtypes.bfloat16)
    return {"score_gap": correct.score_gap(list(enumerate(low)),
                                           correct.score_reference(run))}
