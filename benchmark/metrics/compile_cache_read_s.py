"""Seconds of set-up spent reading compiled programs from the persistent
cache (JAX's ``cache_retrieval_time_sec`` events, summed by the program's
listener in ``utils/platform.py``).  The window compiles nothing, so the
counter at the end of a run is set-up's."""
from benchmark.metrics._program import counter


def read(ctx):
    return counter("compile_cache_read_seconds")
