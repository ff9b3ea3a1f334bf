"""Seconds of ingest in the binning kernel and the pull of every binned chunk
back to the host (``bin_s`` of the program's ``ingest.device_bin`` records;
the pull blocks, so the host clock holds the device time).  ``None`` once
the flight ring has pushed a record out (set-up's go first)."""
from benchmark.metrics._program import records


def read(ctx):
    bins = [e.get("args", {}).get("bin_s")
            for e in records("ingest.device_bin", whole_run=True) or ()]
    if not bins or any(b is None for b in bins):
        return None
    return sum(bins)
