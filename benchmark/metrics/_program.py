"""What the program records of itself, for the readers of this directory:
the complete records of its flight ring (``lightgbm_tpu/obs/flight.py``:
name, ``ts`` and ``dur`` in microseconds, ``args`` with ``it``, ``parent``
and what the seam attached) and the counters of its registry.  Both are
``None`` where the program has none of it (an older program, or a ring
disarmed by ``LIGHTGBM_TPU_FLIGHT=0``): the reader then returns ``None``
and the line leaves the metric out."""


def records(*names, whole_run=False):
    """The ring's complete records with one of ``names``, oldest first;
    ``None`` when the ring is disarmed or holds none of them.  The ring
    keeps the last 2048 records (about 5 a round), so a reader of set-up's
    records asks for the ``whole_run`` and gets ``None`` once the ring has
    pushed any record out."""
    try:
        from lightgbm_tpu.obs.flight import global_flight
    except ImportError:
        return None
    if not global_flight.enabled:
        return None
    if whole_run and getattr(global_flight, "dropped", 0):
        return None
    out = [e for e in global_flight.ring_events()
           if e.get("ph") == "X" and e.get("name") in names]
    return out or None


def counter(name):
    """The registry's counter ``name``, ``None`` when it was never made."""
    try:
        from lightgbm_tpu.obs.metrics import global_registry
    except ImportError:
        return None
    return global_registry.to_dict().get("counters", {}).get(name)


def window_trees(ctx):
    """The ``grower.tree`` records of the window's trees: the last
    ``run.trees`` of them (one a tree, in the order the host took them);
    ``None`` when the ring holds fewer than that."""
    trees = int(getattr(ctx["run"], "trees", 0) or 0)
    recs = records("grower.tree")
    if not trees or len(recs or ()) < trees:
        return None
    return [e.get("args", {}) for e in recs[-trees:]]


def seconds(recs):
    return sum(e.get("dur", 0.0) for e in recs) / 1e6
