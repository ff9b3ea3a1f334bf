"""Host milliseconds per tree of the engine's loop that no seam names:
for each of the window's turns (``metrics/_turns.py``), the ``engine.step``
record's ``dur`` less the union of the ring records that ran inside it on
its thread (``macro.*``, ``gbdt.*``, ``engine.eval``, ``checkpoint.*``,
...), summed over the turns and divided by the trees they train.  Callbacks
(the harness's own among them), gauges and the loop's bookkeeping are what
is left; a few ms a tree is the expected reading, and more names the next
seam to add.  ``None`` on a program whose ``engine.step`` is not the whole
turn (the parent of this reader) and in a cell that bypasses
``engine.train``."""
from benchmark.metrics._turns import (every_record, inside, union_us,
                                      whole_turns, window_turns)


def read(ctx):
    found = window_turns(ctx)
    if found is None or not whole_turns():
        return None
    turns, trees = found
    recs = every_record()
    unnamed = sum(t["dur"] - union_us(inside(t, recs)) for t in turns)
    return unnamed / 1e3 / trees
