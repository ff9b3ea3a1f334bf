"""Central registry of every environment flag the project reads.

The codebase is steered by ``LGBM_TPU_*`` / ``LIGHTGBM_TPU_*`` env
gates (library behavior).  Before this module
they lived as string literals scattered over ~20 files with no single
place answering "what knobs exist, what do they default to, and where
are they documented".  Every flag must be declared here — ``tpulint``'s
``env-flag-registry`` rule (tools/lint/) fails any matching string
literal in the tree that this registry does not know, any registry
entry whose name is absent from its declared doc file, and any stale
entry no code reads anymore.

This module is declarative and import-cheap (stdlib only, no jax): the
reading call sites keep their existing ``os.environ.get(...)`` idiom —
rewiring ~70 call sites through one accessor would churn every module
for zero behavioral gain — but new flags MUST be registered here first
or lint fails the PR by name.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Iterable, Optional


@dataclasses.dataclass(frozen=True)
class EnvFlag:
    """One environment knob: its default (textual, '' = unset), the
    module that reads it, a one-line doc, and the docs/ file that must
    mention it by name (the lint docs anchor)."""

    name: str
    default: str
    consumer: str
    doc: str
    docfile: str


def _f(name: str, default: str, consumer: str, doc: str,
       docfile: str) -> EnvFlag:
    return EnvFlag(name, default, consumer, doc, docfile)


_PERF = "docs/PERF.md"
_PERFORMANCE = "docs/PERFORMANCE.md"
_OBS = "docs/OBSERVABILITY.md"
_LIFECYCLE = "docs/LIFECYCLE.md"

FLAGS: Dict[str, EnvFlag] = {f.name: f for f in [
    # ------------------------------------------------ kernel/planner gates
    _f("LGBM_TPU_FUSED", "1", "ops/fused.py",
       "fused histogram->split megakernel eligibility ('0' disables)", _PERF),
    _f("LGBM_TPU_SHAPE_BUCKETS", "", "ops/planner.py",
       "pad training rows to ladder rungs so nearby sizes share one "
       "compiled program ('1' on, '0' off; default: accelerators only)",
       _PERF),
    _f("LGBM_TPU_SEGHIST", "", "ops/histogram.py",
       "force a histogram kernel family, bypassing the planner", _PERF),
    _f("LGBM_TPU_TABLE_MATMUL", "", "ops/histogram.py",
       "'0' demotes take_from_table's matmul gather to plain gather",
       _PERFORMANCE),
    _f("LGBM_TPU_SMALL_ROUNDS", "1", "ops/histogram.py",
       "small-frontier rounds kernel election ('0' disables)", _PERFORMANCE),
    _f("LGBM_TPU_PACK", "1", "grower_rounds.py",
       "packed per-level rounds program ('0' disables)", _PERFORMANCE),
    _f("LGBM_TPU_ROUTER", "1", "grower_rounds.py",
       "in-program row router ('0' disables)", _PERFORMANCE),
    _f("LGBM_TPU_HBM_BYTES", "", "ops/planner.py",
       "override detected device HBM capacity (bytes)", _PERF),
    _f("LGBM_TPU_VMEM_BYTES", "", "ops/planner.py",
       "override the VMEM budget the fused-kernel model plans against",
       _PERF),
    _f("LGBM_TPU_HOST_BYTES", "", "ops/planner.py",
       "override the host-RSS budget for the streaming planner", _PERF),
    _f("LGBM_TPU_TILE_ROWS", "", "ops/planner.py",
       "force the histogram row-tile size ('0' = untiled)", _PERF),
    _f("LGBM_TPU_ICI_GBPS", "", "ops/planner.py",
       "per-link ICI bandwidth (GB/s) for the collective link model",
       _PERF),
    _f("LGBM_TPU_DCN_GBPS", "", "ops/planner.py",
       "DCN bandwidth (GB/s) for the collective link model", _PERF),
    _f("LGBM_TPU_HIER_REDUCE", "", "ops/planner.py",
       "force ('1') / forbid ('0') tiered ICIxDCN reductions", _PERF),
    _f("LGBM_TPU_PINNED_REDUCE", "", "ops/planner.py",
       "pin the tiered-reduction variant the planner would elect", _PERF),
    _f("LGBM_TPU_PREDICT_KERNEL", "", "ops/planner.py",
       "pin the predict traversal variant (while/fori/fused), bypassing "
       "the measured + analytic election", _PERF),
    _f("LGBM_TPU_PREDICT_CHUNK", "", "ops/planner.py",
       "force the predict device chunk / CSR densify chunk (rows)", _PERF),
    _f("LGBM_TPU_PREDICT_EPILOGUE", "", "predict.py",
       "'0' pins the host float64 leaf-sum epilogue (skips the device "
       "bit-exactness probe)", _PERF),
    _f("LGBM_TPU_INGEST_KERNEL", "", "ops/planner.py",
       "pin the device-ingest binning variant ('kernel'/'host'), "
       "bypassing the measured + analytic election", _PERF),
    _f("LGBM_TPU_INGEST_CHUNK", "", "ops/planner.py",
       "force the streamed-ingest chunk size (rows)", _PERF),
    # ------------------------------------------------------ data plane
    _f("LGBM_TPU_STREAM", "", "ops/planner.py",
       "force ('1') / forbid ('0') out-of-core row-block streaming", _PERF),
    _f("LGBM_TPU_STREAM_BLOCK_ROWS", "", "ops/planner.py",
       "force the streaming row-block size", _PERF),
    _f("LGBM_TPU_STREAM_DIR", "", "data/stream.py",
       "directory for the spill blockstore (default: a tmpdir)", _PERF),
    _f("LGBM_TPU_FREE_BINNED", "", "boosting/gbdt.py",
       "'1' frees the host binned matrix after device upload", _PERF),
    _f("LGBM_TPU_CHUNK", "", "boosting/macro.py",
       "macro-chunk size override ('0'/'off' disables chunking)", _PERF),
    _f("LGBM_TPU_MODEL_BATCH", "", "ops/planner.py",
       "cap the batched model-axis lane chunk ('0'/'off' forces "
       "sequential training)", _PERF),
    _f("LGBT_DEFER_HOST_TREES", "", "boosting/gbdt.py",
       "'1' defers host tree fetch to training end (legacy prefix)", _PERF),
    # ------------------------------------------------------ model lifecycle
    _f("LGBM_TPU_LIFECYCLE_DIR", "", "lifecycle/rollout.py",
       "bundle + rollout-journal directory for the model lifecycle",
       _LIFECYCLE),
    _f("LGBM_TPU_LIFECYCLE_DRIFT_BUDGET", "10.0", "lifecycle/rollout.py",
       "max candidate-vs-live raw-score drift a rollout tolerates",
       _LIFECYCLE),
    _f("LGBM_TPU_LIFECYCLE_P99_MS", "", "lifecycle/rollout.py",
       "candidate p99 latency ceiling (ms) for the rollout gates",
       _LIFECYCLE),
    _f("LGBM_TPU_LIFECYCLE_MIRROR", "0.25", "lifecycle/rollout.py",
       "fraction of live requests mirrored to the candidate", _LIFECYCLE),
    _f("LGBM_TPU_LIFECYCLE_RAMP", "0.05,0.25,0.5", "lifecycle/rollout.py",
       "comma list of staged canary traffic fractions", _LIFECYCLE),
    # ------------------------------------------------------ parallel plane
    _f("LGBM_TPU_NUM_SLICES", "", "parallel/learners.py",
       "slice count for the simulated/hybrid multi-host mesh", _PERF),
    _f("LGBM_TPU_SLICE_DEVICES", "", "parallel/network.py",
       "devices per slice for the hybrid mesh plan", _PERF),
    # ------------------------------------------------------ observability
    _f("LIGHTGBM_TPU_TIMETAG", "", "utils/timer.py",
       "'1' timer table at exit; 'json'/'json:<path>' machine form", _OBS),
    _f("LIGHTGBM_TPU_TRACE", "", "obs/trace.py",
       "'1' record spans; any other value also dumps Chrome JSON there",
       _OBS),
    _f("LIGHTGBM_TPU_TRACE_MAX_EVENTS", "1000000", "obs/trace.py",
       "cap on the in-process span list", _OBS),
    _f("LIGHTGBM_TPU_FLIGHT", "1", "obs/flight.py",
       "flight recorder armed (default on); '0' disarms", _OBS),
    _f("LIGHTGBM_TPU_FLIGHT_EVENTS", "2048", "obs/flight.py",
       "flight ring capacity", _OBS),
    _f("LIGHTGBM_TPU_FLIGHT_DIR", "", "obs/flight.py",
       "flight bundle directory (default cwd)", _OBS),
    _f("LIGHTGBM_TPU_FLIGHT_MAX_DUMPS", "8", "obs/flight.py",
       "per-process flight dump budget", _OBS),
    _f("LIGHTGBM_TPU_WATCHDOG", "", "obs/watchdog.py",
       "'1' starts the SLO sentry thread at engine/server init", _OBS),
    _f("LIGHTGBM_TPU_WATCHDOG_INTERVAL_S", "5", "obs/watchdog.py",
       "sentry check interval (seconds)", _OBS),
    _f("LIGHTGBM_TPU_SLO_TREES_PER_SEC", "", "obs/watchdog.py",
       "training throughput floor (trees/sec) the sentry enforces", _OBS),
    _f("LIGHTGBM_TPU_SLO_SERVING_P99_MS", "", "obs/watchdog.py",
       "serving p99 latency ceiling (ms)", _OBS),
    _f("LIGHTGBM_TPU_SLO_MODEL_AGE_S", "", "obs/watchdog.py",
       "deployed-model freshness ceiling (seconds since promotion)",
       _OBS),
    _f("LIGHTGBM_TPU_SLO_AVAILABILITY", "", "obs/watchdog.py",
       "per-model windowed availability floor (0..1) the sentry "
       "enforces; typed shed/expired excluded", _OBS),
    _f("LIGHTGBM_TPU_SLO_HEARTBEAT_S", "300", "obs/watchdog.py",
       "heartbeat staleness threshold (seconds)", _OBS),
    _f("LIGHTGBM_TPU_METRICS_PORT", "", "obs/http.py",
       "opt-in HTTP metrics port ('0' = ephemeral)", _OBS),
    _f("LIGHTGBM_TPU_METRICS_HOST", "127.0.0.1", "obs/http.py",
       "bind host for the HTTP metrics endpoint", _OBS),
    # ------------------------------------------------- co-resident train+serve
    _f("LGBM_TPU_CORESIDENT_CHUNK_CAP", "", "coresident/scheduler.py",
       "macro-chunk cap ceiling for co-resident refreshes (default: the "
       "LGBM_TPU_CHUNK cap)", _PERF),
    _f("LGBM_TPU_CORESIDENT_THROTTLE_S", "0.02", "coresident/scheduler.py",
       "host-side yield per engine consult while brownout-throttled "
       "(seconds)", _PERF),
    _f("LGBM_TPU_CORESIDENT_RECOVERY_S", "1.0", "coresident/scheduler.py",
       "quiet time after the last breach ping before throttled/paused "
       "training resumes at full cap (seconds)", _PERF),
]}


def lookup(name: str) -> Optional[EnvFlag]:
    """The registry entry for ``name``, or None for unknown flags."""
    return FLAGS.get(name)


def all_flags() -> Iterable[EnvFlag]:
    return FLAGS.values()


def get(name: str) -> str:
    """Read ``name`` from the environment with its REGISTERED default.
    Raises KeyError for unregistered names — the programmatic analogue
    of the lint rule, for new call sites that want registry-backed
    defaults instead of inline literals."""
    return os.environ.get(name, FLAGS[name].default)
