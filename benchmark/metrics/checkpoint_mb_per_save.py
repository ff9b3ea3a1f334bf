"""Megabytes (1e6 B) a bundle takes on disk: the program's counters
``checkpoint_bytes_total`` over ``checkpoint_saves_total``, whole run (a
bundle grows by a tree's text a round; the train score is nearly all of
it).  ``None`` where the program made neither counter."""
from benchmark.metrics._program import counter


def read(ctx):
    saves = counter("checkpoint_saves_total")
    nbytes = counter("checkpoint_bytes_total")
    if not saves or nbytes is None:
        return None
    return nbytes / saves / 1e6
