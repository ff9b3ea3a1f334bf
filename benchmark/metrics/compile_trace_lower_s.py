"""Seconds of set-up in Python tracing (outermost traces only) and in
lowering to MLIR: the program's counters ``compile_trace_seconds`` +
``compile_lower_seconds`` (its listener in ``utils/platform.py``)."""
from benchmark.metrics._program import counter


def read(ctx):
    trace = counter("compile_trace_seconds")
    lower = counter("compile_lower_seconds")
    if trace is None or lower is None:
        return None
    return trace + lower
