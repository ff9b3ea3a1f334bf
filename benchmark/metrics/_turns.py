"""The turns of ``engine.train``'s loop that the window holds, and what
the program's ring recorded inside each.  A turn is one ``engine.step``
record (``it``: its first iteration, ``c``: the trees it trains): the
before-round callbacks, the update, the evaluation, the after-round
callbacks and the snapshot save, with the round's own seams under it on
the same thread.  The window's turns are those of the last ``run.trees``
values of ``it``, as ``engine_host_ms_per_tree`` selects its rounds; a
turn counts for its ``c`` trees.  A ``train_job`` window (``run.saves``
whole periods) ends with the turn that holds its last save: the round
past its close is no turn of the window, and in a traced run it holds the
profiler's stop.  ``None`` where the program records no whole turn (an
older program's ``engine.step`` held the update alone and had no
``engine.eval`` record, and a train_loop cell bypasses the loop) or where
the ring holds fewer turns than the window."""
from benchmark.metrics._program import records


def whole_turns():
    """True where the program's ``engine.step`` is the whole turn: a seam
    that runs after the update (``engine.eval``, ``checkpoint.save``) is
    on the ring under it.  An older program ran them under
    ``engine.train``, beside a step that held the update alone."""
    return any(e.get("args", {}).get("parent") == "engine.step"
               for e in records("engine.eval", "checkpoint.save") or ())


def every_record():
    """Every complete record the ring holds, or ``None``."""
    try:
        from lightgbm_tpu.obs.flight import global_flight
    except ImportError:
        return None
    return records(*{e.get("name") for e in global_flight.ring_events()})


def window_turns(ctx):
    """(the window's ``engine.step`` records, the trees they train), or
    ``None``."""
    trees = int(getattr(ctx["run"], "trees", 0) or 0)
    # one record an ``it``: a resumed job's second call repeats the round
    # it resumed from, and the later record is its own
    by_it = {e["args"]["it"]: e for e in records("engine.step") or ()
             if e.get("args", {}).get("it") is not None}
    if getattr(ctx["run"], "saves", 0):
        saves = records("checkpoint.save")
        if not saves:
            return None
        by_it = {it: e for it, e in by_it.items()
                 if e["ts"] <= saves[-1]["ts"]}
    turns, held = [], 0
    for it in sorted(by_it, reverse=True):
        if held >= trees:
            break
        turns.append(by_it[it])
        held += int(by_it[it]["args"].get("c", 1) or 1)
    if not trees or held < trees:
        return None
    return turns[::-1], held


def inside(turn, recs):
    """The records of ``recs`` that ran inside ``turn``, on its thread."""
    lo, hi = turn["ts"], turn["ts"] + turn["dur"]
    return [e for e in recs if e is not turn
            and e.get("tid") == turn.get("tid")
            and lo <= e["ts"] and e["ts"] + e.get("dur", 0.0) <= hi]


def union_us(recs):
    """Microseconds covered by the union of the records' intervals."""
    total, end = 0.0, None
    for s, e in sorted((r["ts"], r["ts"] + r.get("dur", 0.0)) for r in recs):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total
