"""Production serving fleet: multi-model registry with planner-driven
shared-HBM eviction, AOT cold start, and opt-in low-precision inference
(docs/SERVING.md fleet section).

Quick start::

    fleet = lightgbm_tpu.Fleet(max_batch_rows=512)
    fleet.add_model("ranker", "ranker.txt", weight=3.0,
                    deadline_class="interactive")
    fleet.add_model("scorer", booster, precision="bf16",
                    accuracy_budget=1e-2)
    scores = fleet.predict("ranker", X)        # or .submit() -> Future
    fleet.export_aot()                         # compile-free replicas
    print(fleet.prometheus_text())             # model="..."-labelled
    fleet.close()

Pod scale (docs/SERVING.md multi-device section; docs/RESILIENCE.md
failover section)::

    pod = lightgbm_tpu.PodFleet(devices=4)
    pod.add_model("ranker", booster, weight=3.0,
                  deadline_class="interactive")
    scores = pod.predict("ranker", X)   # health-routed, hedged, replicated
    pod.kill_device(2)                  # a replan, not an outage

Module map: ``registry`` (Fleet front door: weighted admission, deadline
classes, residency replans), ``topology`` (multi-device placement
planner: replicate hot models, partition the cold tail), ``router``
(PodFleet: health-scored routing, hedged retries, brownout degradation,
device-loss failover), ``aot`` (jax.export serialize/restore of
bucket programs under the compile-cache dir's serving/), ``lowprec``
(bf16/int8 forest quantization + the accuracy-budget measurement).
The single-model building blocks stay in ``lightgbm_tpu.serving``.
"""

from .aot import AOTStore, aot_dir_from_env
from .lowprec import measure_accuracy_delta, quantize_forest
from .registry import (DEFAULT_DEADLINE_CLASSES, Fleet, FleetConfig,
                       FleetEntry)
from .router import PodFleet, RouterConfig
from .topology import (DeviceSpec, TopologyPlan, plan_devices,
                       plan_topology)

__all__ = [
    "Fleet", "FleetConfig", "FleetEntry", "DEFAULT_DEADLINE_CLASSES",
    "PodFleet", "RouterConfig", "DeviceSpec", "TopologyPlan",
    "plan_devices", "plan_topology",
    "AOTStore", "aot_dir_from_env", "quantize_forest",
    "measure_accuracy_delta",
]
