"""Programs the process compiled or read from the cache in set-up: the
program's counter ``compile_programs_total`` (one a
``backend_compile_duration`` event)."""
from benchmark.metrics._program import counter


def read(ctx):
    return counter("compile_programs_total")
