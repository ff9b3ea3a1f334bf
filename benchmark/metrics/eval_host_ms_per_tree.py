"""Host milliseconds per tree in the engine's evaluation: the ``dur`` of
the ``engine.eval`` ring records inside the window's turns
(``metrics/_turns.py``) over the trees those turns train.  The seam holds
``gbdt.eval`` for every data set evaluated: the pull of its score
(``eval.pull``) and each metric on the host (``metric.<name>``), which
run with nothing queued on the device; the pull also waits for what is
(the validation update).  ``None`` on a program whose ring has no
``engine.eval`` record (the parent of the seam) and in a cell without a
validation set."""
from benchmark.metrics._program import records
from benchmark.metrics._turns import inside, whole_turns, window_turns


def read(ctx):
    found = window_turns(ctx)
    evals = records("engine.eval")
    if found is None or not evals or not whole_turns():
        return None
    turns, trees = found
    recs = [e for t in turns for e in inside(t, evals)]
    if not recs:
        return None
    return sum(e["dur"] for e in recs) / 1e3 / trees
