"""Milliseconds a save spends encoding the bundle (the ring span
``checkpoint.encode``: the model text of the whole forest, the pickle of
the state, a sha256 a member, the zip container), mean over the window's
saves."""
from benchmark.metrics._checkpoint import part_ms_per_save


def read(ctx):
    return part_ms_per_save(ctx, "checkpoint.encode")
