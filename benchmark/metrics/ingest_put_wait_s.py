"""Seconds of ingest the host spent on puts that nothing hid: the consumer's
waits for its next raw chunk (``wait_put_s`` of the program's
``ingest.device_bin`` records; the puts themselves run on the pump's reader
thread under the kernel) plus ``ingest.to_device``, the layout and put of
the binned matrix in the booster's set-up.  ``None`` once the flight ring
has pushed a record out (set-up's go first)."""
from benchmark.metrics._program import records, seconds


def read(ctx):
    binned = records("ingest.device_bin", whole_run=True)
    placed = records("ingest.to_device", whole_run=True)
    if not binned and not placed:
        return None
    waits = [e.get("args", {}).get("wait_put_s") for e in binned or ()]
    if any(w is None for w in waits):
        return None
    return sum(waits) + seconds(placed or ())
