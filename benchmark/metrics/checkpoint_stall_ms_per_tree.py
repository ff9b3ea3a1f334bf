"""Milliseconds a tree that the trainer stands still for checkpoints: the
durations of the window's ``checkpoint.save`` ring records (capture of the
state, encoding, atomic write, index and retention: all synchronous in the
engine's loop, the device idle behind them) over the window's trees.
``train_s_per_tree`` x 1000 less this is what the rounds themselves take."""
from benchmark.metrics._checkpoint import window_saves
from benchmark.metrics._program import seconds


def read(ctx):
    saves = window_saves(ctx)
    trees = int(getattr(ctx["run"], "trees", 0) or 0)
    if saves is None or not trees:
        return None
    return seconds(saves) * 1e3 / trees
