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

Every reading drives the cell's own kind of traffic (``kinds/<kind>.py``:
same entry, same sizes) for its warm-up and a short window, which are what
the reference compares.  The control is the program with the parameters of
``controls/<config>.json`` laid over the configuration's, or, for a kind
that has ``control_numbers``, the plain reference in the program's place
one precision step down, read from the program's own run.
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
    import numpy as np
    from lightgbm_tpu.utils.platform import enable_compile_cache
    from benchmark.lib import traffic as traffic_lib
    from benchmark.lib.spans import CompileCounter, Spans

    if jax.devices()[0].platform != "tpu" and not manifest.get("rehearsal"):
        print("calibrate.py: no TPU", file=sys.stderr)
        return 3
    enable_compile_cache()
    compiles = CompileCounter()
    kind = traffic_lib.kind_module(manifest, traffic)
    # a kind whose control is the reference put in the program's place
    # reads it from the program's own run; the others take the control as
    # parameters laid over the configuration's
    reference_control = hasattr(kind, "control_numbers")
    control_path = lookup.find_optional(
        manifest, f"controls/{cell['config']}.json")
    control = lookup.load_json(control_path) if control_path else {}

    def reading(what, seed, cfg, fault=None):
        t0 = time.perf_counter()
        spans = Spans()
        run = kind.run(
            manifest, cfg, traffic, cell_file, seed,
            args.seconds if what == "program" else args.other_seconds,
            spans, compiles, jax.devices(), None, fault)
        run.free()
        t1 = time.perf_counter()
        detail = []
        numbers = kind.numbers(run, detail=detail)
        numbers["failed"] = float(run.failed)
        if args.keep and detail:
            os.makedirs(args.keep, exist_ok=True)
            np.savez_compressed(
                os.path.join(args.keep, f"{what}_{seed}.npz"),
                **{f"t{t}_{k}": v for t, d in enumerate(detail)
                   for k, v in d.items()})
        out = [(what, numbers)]
        if reference_control and what == "program" \
                and seed in args.control_seeds:
            out.append(("control", kind.control_numbers(run)))
        for w, numbers in out:
            print(json.dumps({
                "what": w, "seed": seed, "numbers": numbers,
                "run_s": t1 - t0, "compare_s": time.perf_counter() - t1,
                "attempted": run.attempted,
                "step_seconds": getattr(run, "step_seconds", None),
                "spans": spans.seconds, "info": run.info,
                "peak_bytes": run.peak_bytes}), flush=True)

    table = getattr(kind, "FAULTS", {})
    for seed in args.seeds:
        reading("program", seed, config)
    if not reference_control:
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
