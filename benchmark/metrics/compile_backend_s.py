"""Seconds of set-up in real backend compiles: JAX's
``backend_compile_duration`` events less the cache reads inside them
(the program's listener in ``utils/platform.py``)."""
from benchmark.metrics._program import counter


def read(ctx):
    return counter("compile_backend_seconds")
