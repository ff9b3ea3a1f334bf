"""Seconds the ranking objective took to build its query tables on the host
(length buckets, slot and inverse maps, inverse max DCGs) and put them on
the device: the ``dur`` of the program's ``rank.init`` record."""
from benchmark.metrics._rank import init_record


def read(ctx):
    rec = init_record()
    return rec.get("dur", 0.0) / 1e6 if rec else None
