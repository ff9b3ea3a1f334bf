"""Milliseconds a save spends capturing the training state
(``GBDT.capture_state`` under the ring span ``checkpoint.capture``: the
pending trees drained, the device-to-host copy of the train score and the
other device state, the host trees copied), mean over the window's saves."""
from benchmark.metrics._checkpoint import part_ms_per_save


def read(ctx):
    return part_ms_per_save(ctx, "checkpoint.capture")
