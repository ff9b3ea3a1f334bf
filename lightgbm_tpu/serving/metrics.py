"""Back-compat shim: the serving metrics registry was promoted to
``lightgbm_tpu.obs.metrics`` as the single process-wide instrument
registry (training, serving and resilience all report through
it — docs/OBSERVABILITY.md).

This module re-exports the full historical surface so every existing
import path (``from lightgbm_tpu.serving.metrics import MetricsRegistry``,
the tier-1 serving tests, ``tools/serve_smoke.py``) keeps working
unchanged, and ``MetricsRegistry.to_dict()`` keeps its exact key layout
(``counters``/``gauges``/``histograms`` — schema: docs/SERVING.md).
"""

from ..obs.metrics import (LATENCY_BUCKETS_MS, RATIO_BUCKETS, Counter, Gauge,
                           Histogram, MetricsRegistry)

__all__ = [
    "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "LATENCY_BUCKETS_MS", "RATIO_BUCKETS",
]
