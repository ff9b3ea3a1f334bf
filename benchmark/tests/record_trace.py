#!/usr/bin/env python3
"""Record the small trace kept as ``tests/data/trace_small.xplane.pb``.

Run on the chip (the builder does; no test and no benchmark run calls it):
a 200k x 28 training with the fused histogram kernel forced, two warm
rounds and then two traced rounds under the harness's own annotations.
Prints what the trace holds, plane by plane, for a reader of
``lib/trace_reduce.py``.

    chiprun -- python3 benchmark/tests/record_trace.py chiprun_out/trace_small
"""
import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))


def main(out_dir):
    import jax
    import lightgbm_tpu as lgb
    from benchmark.datagen import higgs_like
    from benchmark.lib import trace_reduce
    from benchmark.lib.spans import Spans

    X, y = higgs_like.generate(7, 200_000, 28)
    params = {"objective": "binary", "num_leaves": 31, "max_bin": 63,
              "learning_rate": 0.1, "tpu_hist_method": "fused",
              "verbosity": -1}
    bst = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
    spans = Spans()

    def step():
        with spans.span("update"):
            bst.update()
        with spans.span("sync"):
            jax.block_until_ready(bst.boosting.train_score)

    step(), step()
    len(bst.models)
    tmp = Path(out_dir) / "raw"
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    step(), step()
    with spans.span("pull_trees"):
        len(bst.models)
    jax.profiler.stop_trace()
    xplane = trace_reduce.find_xplane(tmp)
    kept = Path(out_dir) / "trace_small.xplane.pb"
    shutil.copy(xplane, kept)
    shutil.rmtree(tmp, ignore_errors=True)
    print("bytes", kept.stat().st_size)
    planes = trace_reduce.read_planes(kept)
    for pname, lines in planes.items():
        print("PLANE", pname)
        for lname, events in lines.items():
            names = {}
            for n, _, d in events:
                c = names.setdefault(n, [0, 0.0])
                c[0] += 1
                c[1] += d
            top = sorted(names.items(), key=lambda kv: -kv[1][1])[:12]
            print("  LINE", repr(lname), len(events), "events;",
                  [(n[:60], c, round(d / 1e6, 3)) for n, (c, d) in top])
    reduced = trace_reduce.reduce_planes(planes)
    for d in reduced["devices"]:
        d["ops"] = dict(sorted(d["ops"].items(), key=lambda kv: -kv[1])[:8])
    print(json.dumps(reduced, indent=1)[:6000])


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/trace_small")
