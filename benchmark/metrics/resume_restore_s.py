"""Seconds of the program's ring span ``engine.resume``: the newest
verified bundle resolved (read, every member's sha256, unpickled), the
state put back into the new ``Booster`` and the callbacks.  The part of
``resume_s`` that is the restore itself; ``None`` on a program without the
span."""
from benchmark.metrics._program import records, seconds


def read(ctx):
    recs = records("engine.resume")
    return seconds(recs[-1:]) if recs else None
