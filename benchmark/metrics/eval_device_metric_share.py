"""Of the metric evaluations, the share that the device form computed
(``lightgbm_tpu/metrics.py``: the score reduced on the device to block
partials, inside the validation update ``jit_upd`` or alone, and finished on
the host) and not the host form (the score pulled, NumPy in float64), in
percent: 100 x ``eval_metrics_device_total`` / (device + host), the
program's two counters, bumped once a metric an evaluation by the form that
computed it.  Whole run, warm rounds included.  ``None`` where the program
made neither counter (a program without device forms, or a run that
evaluated nothing)."""
from benchmark.metrics._program import counter


def read(ctx):
    device = counter("eval_metrics_device_total") or 0
    total = device + (counter("eval_metrics_host_total") or 0)
    return 100.0 * device / total if total else None
