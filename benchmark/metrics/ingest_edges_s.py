"""Seconds of ingest finding the bin boundaries and feature groups from the row
sample, on the host: the program's ``ingest.edges`` records.  ``None`` once
the flight ring has pushed a record out (set-up's go first)."""
from benchmark.metrics._program import records, seconds


def read(ctx):
    recs = records("ingest.edges", whole_run=True)
    return seconds(recs) if recs else None
