"""Device milliseconds per tree in every other op of the round programs
(gradients, split scan, routing, commit, score update: one number until
the program names its scopes).  Where the run also updates validation
scores, the seconds of those programs (``metrics/_eval.py``, which
``eval_device_ms_per_tree`` reports) are taken out: a program's seconds on
the line ``XLA Modules`` are its ops' plus the gaps between them, so this
reads low by those gaps (under 0.1% of the program on the trace looked
at)."""
from benchmark.metrics._eval import eval_modules


def read(ctx):
    dev = ctx["trace"]["devices"][0]
    if not ctx["run"].trees or not dev["ops"]:
        return None
    other = sum(dev["ops"].values()) - dev["kernel_s"] - dev["collective_s"]
    found = eval_modules(ctx)
    if found:
        other -= found[1]
    return 1e3 * other / ctx["run"].trees
