"""Seconds of ingest in the host's binning pass, where the planner elects it
in place of the binning kernel (no kernel tile fits at 128 columns or more):
the ``dur`` of the program's ``ingest.host_bin`` records, one a construct.
``None`` where the ring holds none (the kernel binned, a program that keeps
the span off the ring, or a ring that has pushed set-up's records out)."""
from benchmark.metrics._program import records, seconds


def read(ctx):
    recs = records("ingest.host_bin", whole_run=True)
    return seconds(recs) if recs else None
