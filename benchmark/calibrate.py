#!/usr/bin/env python3
"""Read the numbers ``correct`` compares, for setting their limits: the
program as the configuration states it over many seeds (the lower
readings), the control one precision step down (the upper readings), and
planted faults, all in ONE process so the set-up's compile is paid once.

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 4,5,6] [--fault half_batch --fault-seeds 7,8,9] \
        [--rows N] [--seconds S] [--keep DIR]

Every ``--seed`` gives other rows of the same law.  ``--rows`` reads the
program at another size than the cell's.  ``--seconds`` is the window after
the warm rounds: long enough for one round, so that the tree followed from
the window is read too.

The builder runs it on the chip; no benchmark run and no test calls it at
the cell's size.  Each line of stdout is one reading as JSON:
``{"what": "program"|"control"|<fault>, "seed": n, "numbers": {...}}``.

Training: every reading drives the cell's own traffic loop (same entry,
same sizes) for its warm rounds and a short window, which are what the
reference follows.  The control is the program with the parameters of
``controls/<config>.json`` laid over the configuration's.  Scoring: a short window at the cell's own load; the
control is the plain reference with its comparisons made in bfloat16, read
at the same sampled rows.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark.lib import lookup  # noqa: E402


def ints(text):
    return [int(x) for x in text.split(",") if x]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=ints, default=[])
    ap.add_argument("--control-seeds", type=ints, default=[])
    ap.add_argument("--fault", action="append", default=[])
    ap.add_argument("--fault-seeds", type=ints, default=[])
    ap.add_argument("--rows", type=int,
                    help="another row count than the cell's: where a "
                         "fault of the program begins")
    ap.add_argument("--seconds", type=float, default=1.0,
                    help="window of the program's readings")
    ap.add_argument("--other-seconds", type=float, default=1.0,
                    help="window of the control's and the faults' readings")
    ap.add_argument("--manifest", default="BENCHMARK.json")
    ap.add_argument("--keep", help="directory for per-leaf arrays (.npz)")
    args = ap.parse_args(argv)

    manifest = lookup.load_manifest(args.manifest)
    cell, _centry, config, traffic, cell_file = lookup.cell_files(
        manifest, args.workload)
    if args.rows:
        config = dict(config, rows=args.rows)
    lookup.apply_env(config)
    import jax
    import ml_dtypes
    import numpy as np
    from lightgbm_tpu.utils.platform import enable_compile_cache
    from benchmark.lib import correct, faults, traffic as traffic_lib
    from benchmark.lib.spans import CompileCounter, Spans

    if jax.devices()[0].platform != "tpu" and not manifest.get("rehearsal"):
        print("calibrate.py: no TPU", file=sys.stderr)
        return 3
    enable_compile_cache()
    compiles = CompileCounter()
    kind = traffic["kind"]
    control_path = lookup.find_optional(
        manifest, f"controls/{cell['config']}.json")
    control = lookup.load_json(control_path) if control_path else {}

    def reading(what, seed, cfg, fault=None):
        t0 = time.perf_counter()
        spans = Spans()
        run = traffic_lib.KINDS[kind](
            manifest, cfg, traffic, cell_file, seed,
            args.seconds if what == "program" else args.other_seconds,
            spans, compiles, jax.devices(), None, fault)
        run.free()
        t1 = time.perf_counter()
        out = []
        if kind == "train_loop":
            detail = []
            numbers = correct.train_numbers(
                run, float(run.params["learning_rate"]),
                float(run.params.get("lambda_l2", 0.0)), detail=detail)
            numbers["failed"] = float(run.failed)
            if args.keep:
                os.makedirs(args.keep, exist_ok=True)
                np.savez_compressed(
                    os.path.join(args.keep, f"{what}_{seed}.npz"),
                    **{f"t{t}_{k}": v for t, d in enumerate(detail)
                       for k, v in d.items()})
            out.append((what, numbers))
        else:
            exact = correct.score_reference(run)
            numbers = correct.score_numbers(run)
            numbers["failed"] = float(run.failed)
            out.append((what, numbers))
            if what == "program" and seed in args.control_seeds:
                low = correct.score_reference(run, ml_dtypes.bfloat16)
                out.append(("control", {"score_gap": correct.score_gap(
                    list(enumerate(low)), exact)}))
        for w, numbers in out:
            print(json.dumps({
                "what": w, "seed": seed, "numbers": numbers,
                "run_s": t1 - t0, "compare_s": time.perf_counter() - t1,
                "attempted": run.attempted,
                "step_seconds": getattr(run, "step_seconds", None),
                "spans": spans.seconds,
                "peak_bytes": run.peak_bytes}), flush=True)

    table = faults.TRAIN if kind == "train_loop" else faults.SCORE
    for seed in args.seeds:
        reading("program", seed, config)
    if kind == "train_loop":
        if args.control_seeds and not control:
            print("calibrate.py: no controls file", file=sys.stderr)
            return 2
        low = dict(config, params=dict(config["params"],
                                       **control.get("params", {})))
        for seed in args.control_seeds:
            reading("control", seed, low)
    else:
        for seed in set(args.control_seeds) - set(args.seeds):
            reading("program", seed, config)
    for name in args.fault:
        for seed in args.fault_seeds:
            reading(name, seed, config, table[name]())
    return 0


if __name__ == "__main__":
    sys.exit(main())
