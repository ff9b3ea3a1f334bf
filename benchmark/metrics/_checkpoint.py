"""What the checkpoint readers share: the program's ``checkpoint.*`` ring
records of the window's saves.  A ``train_job`` window holds ``run.saves``
whole periods, each ending in one save, and the round past its close makes
none, so the window's saves are the ring's last ``run.saves`` records of
``checkpoint.save``; a child record belongs to the save whose span holds
its start.  ``None`` where the program keeps those spans off the ring (an
older program), where the ring holds fewer saves than the window made, or
in a run that saved nothing."""
from benchmark.metrics._program import records, seconds


def window_saves(ctx):
    saves = int(getattr(ctx["run"], "saves", 0) or 0)
    recs = records("checkpoint.save")
    if not saves or len(recs or ()) < saves:
        return None
    return recs[-saves:]


def window_children(ctx, name):
    """The ``name`` records that ran under the window's saves, or ``None``."""
    saves = window_saves(ctx)
    if saves is None:
        return None
    spans = [(e["ts"], e["ts"] + e["dur"]) for e in saves]
    kids = [e for e in records(name) or ()
            if any(t0 <= e["ts"] <= t1 for t0, t1 in spans)]
    return kids if len(kids) == len(saves) else None


def part_ms_per_save(ctx, name):
    """Mean milliseconds of the part ``name`` over the window's saves."""
    kids = window_children(ctx, name)
    return seconds(kids) * 1e3 / len(kids) if kids else None
