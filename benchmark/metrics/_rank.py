"""The program's ``rank.init`` record (``lightgbm_tpu/objective_rank.py``:
one a ``Booster`` with a ranking objective, written as the query tables are
built): its ``dur`` and the counts it carries, ``queries``, ``rows``,
``slots``, ``pair_slots``, ``label_pairs``.  ``None`` where the ring holds
none (a program older than the record, another objective, or a ring that has
pushed set-up's records out)."""
from benchmark.metrics._program import records


def init_record():
    recs = records("rank.init", whole_run=True)
    return recs[-1] if recs else None


def init_args():
    rec = init_record()
    return rec.get("args", {}) if rec else None
