"""Host milliseconds per tree in the seams of a round that hold the host's
own work: ``macro.host_inputs`` (masks, keys, stacked row arrays),
``macro.dispatch`` / ``gbdt.dispatch`` (they return before the device is
done) and ``gbdt.drain_pending`` (the trees' transfer and conversion; in a
``train_loop`` cell the harness has synced before it pulls the tree, so
its ``device_get`` waits for nothing).  None of them runs under another,
so their durations add.  Over the window's rounds, which are the records
of the last ``run.trees`` values of ``it``; ``None`` when the ring holds
fewer rounds than that.

``macro.host_fetch`` / ``gbdt.finish_iter`` are NOT entered: on the
deferred path their eager device ops wait for the device (on the v5e one
of them holds the rest of the round, ~8 s), so their duration is
``train_s_per_tree`` again and not the engine's."""
from benchmark.metrics._program import records

SEAMS = ("macro.host_inputs", "macro.dispatch", "gbdt.dispatch",
         "gbdt.drain_pending")


def read(ctx):
    trees = int(getattr(ctx["run"], "trees", 0) or 0)
    recs = [e for e in records(*SEAMS) or ()
            if e.get("args", {}).get("it") is not None]
    its = sorted({e["args"]["it"] for e in recs})
    if not trees or len(its) < trees:
        return None
    window = set(its[-trees:])
    return sum(e["dur"] for e in recs
               if e["args"]["it"] in window) / 1e3 / trees
