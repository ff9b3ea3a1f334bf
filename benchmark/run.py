#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, the only one that touches JAX.  It finds the cell's files by
the names in the manifest (``lib/lookup.py``), among them the kind of its
traffic (``kinds/<kind>.py``: the run up to the close of the window, the
end-to-end metric it reports, the comparison), makes the inputs from the
seed, sets up and warms the cell's own shapes (all of that is ``setup_s``),
measures for ``--seconds``, reads the peak memory, frees the program's
state, then runs the plain reference over what the timed path produced and
prints every number compared beside its limit, and the seconds of every
phase.  Earlier stdout lines are
JSON information (phases, elected variants, cache warmth); the last line is
the result.  Without a TPU, or with fewer chips than the cell asks for, it
says why on stderr and exits non-zero with no result: there is no CPU
number.  (``--manifest`` points the same lookup at the tiny twins under
``benchmark/tests/data``, whose manifest says ``"rehearsal": true``: those
run on the CPU to check control flow and ``correct``, and print no metric.)
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark.lib import lookup  # noqa: E402

TRACE_DIR = REPO / "bench_trace"


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def fail(code, why):
    print(f"benchmark/run.py: {why}", file=sys.stderr, flush=True)
    sys.exit(code)


def gauges():
    from lightgbm_tpu.obs.metrics import global_registry
    g = global_registry.to_dict().get("gauges", {})
    keep = ("train_hist_method", "train_hist_elected_by", "train_tile_rows",
            "train_rows_bucketed", "train_hist_predicted_peak_bytes",
            "ingest_variant", "ingest_elected_by", "predict_variant",
            "predict_elected_by", "train_psum_payload_bytes")
    return {k: g[k] for k in keep if k in g}


def read_per_layer(manifest, workload, ctx):
    out = {}
    for m in lookup.metrics_for(manifest, workload, "per_layer"):
        reader = lookup.load_module(
            lookup.find(manifest, f"metrics/{m['name']}.py"))
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None, fault=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default="BENCHMARK.json")
    args = ap.parse_args(argv)

    try:
        manifest = lookup.load_manifest(args.manifest)
        cell, centry, config, traffic, cell_file = lookup.cell_files(
            manifest, args.workload)
    except (FileNotFoundError, KeyError) as e:
        fail(2, str(e))
    rehearsal = bool(manifest.get("rehearsal"))
    from benchmark.lib import traffic as traffic_lib
    try:
        kind = traffic_lib.kind_module(manifest, traffic)
    except FileNotFoundError as e:
        fail(2, str(e))
    e2e_names = [m["name"] for m in lookup.metrics_for(
        manifest, args.workload, "end_to_end")]
    if kind.PRIMARY not in e2e_names:
        fail(2, f"traffic kind {traffic['kind']!r} reports {kind.PRIMARY!r}, "
                f"which is no end-to-end metric of {args.workload} in the "
                f"manifest (it has {e2e_names})")
    lookup.apply_env(config)
    try:
        import jax
        import lightgbm_tpu  # noqa: F401
        from lightgbm_tpu.utils.platform import (compile_cache_entries,
                                                 enable_compile_cache)
    except ImportError as e:
        fail(4, f"the system under test is not importable here: {e}")
    from benchmark.lib import correct
    from benchmark.lib.spans import CompileCounter, Spans

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not rehearsal:
        fail(3, f"JAX found no TPU (devices: {devices}); a CPU run gives "
                "no number")
    if len(devices) < int(cell["chips"]):
        fail(3, f"the cell asks for {cell['chips']} chips, JAX reports "
                f"{len(devices)}")
    cache_dir = enable_compile_cache()
    t_ready = time.perf_counter()
    entries_start = compile_cache_entries(cache_dir)
    peaks = None if rehearsal else lookup.peaks_for(
        manifest, devices[0].device_kind)
    emit("start", workload=args.workload, seed=args.seed,
         seconds=args.seconds, trace=args.trace, rehearsal=rehearsal,
         cache_dir=cache_dir, cache_entries_at_start=entries_start,
         cache_env_set=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")))

    spans = Spans()
    compiles = CompileCounter()
    tracing = bool(args.trace) and platform == "tpu"
    trace_dir = TRACE_DIR / args.workload
    marks = {}

    def on_window(what):
        marks[what] = time.perf_counter()
        if not tracing:
            return
        if what == "start":
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            marks["start"] = time.perf_counter()
        else:
            jax.profiler.stop_trace()

    run = kind.run(
        manifest, config, traffic, cell_file, args.seed, args.seconds,
        spans, compiles, devices, on_window, fault)
    setup_s = marks["start"] - T_PROCESS
    emit("window", attempted=run.attempted, failed=run.failed,
         window_s=run.window_s, setup_s=setup_s, spans=spans.seconds,
         step_seconds=getattr(run, "step_seconds", None),
         compiles_in_window=compiles.names, compiles_total=compiles.total,
         peak_bytes=run.peak_bytes, info=run.info, **gauges(),
         cache_entries_at_end=compile_cache_entries(cache_dir))
    run.free()

    # ---- metrics -----------------------------------------------------
    primary = kind.primary(run)
    e2e_values = {"setup_s": setup_s, primary[0]: primary[1]}
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": run.peak_bytes}
    result = {"correct": False, "attempted": run.attempted,
              "failed": run.failed, "metrics": {}, "device": device}
    t_reduce = time.perf_counter()
    if rehearsal:
        pass
    elif not args.trace:
        for m in lookup.metrics_for(manifest, args.workload, "end_to_end"):
            result["metrics"][m["name"]] = {
                "value": float(e2e_values[m["name"]]), "unit": m["unit"]}
    else:
        from benchmark.lib import trace_reduce
        t0 = time.perf_counter()
        xplane = trace_reduce.find_xplane(trace_dir)
        reduced = trace_reduce.reduce_trace(xplane)
        emit("trace", file_bytes=xplane.stat().st_size,
             reduce_s=time.perf_counter() - t0,
             annotations=reduced["annotations"],
             modules=reduced["modules"],
             kernel_calls=[d["kernel_calls"] for d in reduced["devices"]])
        keep = os.environ.get("BENCH_KEEP_TRACE")
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(xplane, os.path.join(
                keep, f"{args.workload}.xplane.pb"))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"trace": reduced, "spans": spans.seconds,
               "samples": spans.samples, "run": run, "config": config,
               "traffic": traffic, "peaks": peaks, "e2e": e2e_values,
               "chips": int(cell["chips"]),
               "roofline": lambda name: lookup.load_module(
                   lookup.find(manifest, f"rooflines/{name}.py"))}
        result["metrics"] = read_per_layer(manifest, args.workload, ctx)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}

    # ---- the comparison, once the window has closed ------------------
    t0 = time.perf_counter()
    numbers = kind.numbers(run)
    numbers["window_compiles"] = float(compiles.count)
    numbers["nothing_done"] = float(run.attempted == 0)
    limits = dict(cell_file.get("limits", {}))
    for name, limit in {"window_compiles": 0, "nothing_done": 0,
                        **getattr(kind, "LIMITS", {})}.items():
        limits.setdefault(name, limit)
    ok, compared = correct.judge(numbers, limits)
    result["correct"] = ok and run.failed == 0
    t_end = time.perf_counter()
    # every phase of the run on the host clock, end to end: they add up to
    # the process so far (PERF.md section 4 holds a cell's sum to 300 s)
    warm = sum(v for k, v in spans.seconds.items() if k.startswith("warm_"))
    phases = {"imports": t_ready - T_PROCESS,
              "data": spans.seconds.get("data", 0.0),
              "ingest": spans.seconds.get("ingest", 0.0),
              "warm": warm,
              "other_setup": (marks["start"] - t_ready)
              - spans.seconds.get("data", 0.0)
              - spans.seconds.get("ingest", 0.0) - warm,
              "window": marks["stop"] - marks["start"],
              "peak": t_reduce - marks["stop"],
              "reduce": t0 - t_reduce, "compare": t_end - t0}
    emit("compare", seconds=t_end - t0, numbers=numbers, phases=phases,
         phases_sum=sum(phases.values()), process_s=t_end - T_PROCESS)
    result["compared"] = compared
    print("compared (value, limit): " + json.dumps(compared),
          file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
