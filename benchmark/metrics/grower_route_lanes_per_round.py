"""The lanes the rounds grower's route compared a row with, a round: sum
``lanes`` / sum ``rounds`` over the window's ``grower.tree`` records.  The
router decides a round's rows against as many lanes as that round's
accumulate pass is wide (16, 64 or the cap); the candidate scan against its
live candidates.  ``None`` where the records carry no ``lanes`` (a program
whose router compares every row with every leaf)."""
from benchmark.metrics._program import window_trees


def read(ctx):
    trees = window_trees(ctx)
    if not trees or any("lanes" not in t for t in trees):
        return None
    rounds = sum(t["rounds"] for t in trees)
    if not rounds:
        return None
    return sum(t["lanes"] for t in trees) / rounds
